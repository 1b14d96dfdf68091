"""The Phi-4-mini-flash configuration, its cell and what reads them: the
file against the source's keys (nothing reduced), the manifest, the new
readers' arithmetic, and the cell's rehearsal with its planted faults."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import manifest
from benchmark.families import phi4_flash as family
from benchmark.harness import cell, scopes
from benchmark.readers import phi4_flash as reader
from benchmark.roofline import mamba1

ROOT = manifest.ROOT
CONFIG = "v55.phi-4-mini-flash-reasoning"
CELL = CONFIG + ".serve.think-sat"
NEW = "v55."       # this PR's metric files sort behind the manifest's
DATA = os.path.join(os.path.dirname(__file__), "data")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
WHATS = ("decode_step_roofline", "serve_mfu_rows", "mamba_share",
         "yoco_share", "mamba_step_roofline", "mamba_scan_roofline",
         "yoco_read_roofline")


@pytest.fixture(scope="module")
def source():
    """The catalog's row for the model (``architectures.jsonl`` beside
    the model-configs guide), copied here as data."""
    with open(os.path.join(
            DATA, "phi-4-mini-flash-reasoning.catalog.json")) as f:
        return json.load(f)


def _cfg():
    return family.program_config(cell.load_json("configs", CONFIG)["model"])


def test_the_file_holds_the_sources_keys_and_values_and_cuts_nothing(source):
    config = cell.load_json("configs", CONFIG)
    model = config["model"]
    assert config["source"] == source["source_url"]
    assert len(source["config"]) == 17
    # key for key: at the top level, where the driver's check against
    # the catalog row reads them, and under ``model``, where the harness
    # does; the two are one statement
    assert {k: config[k] for k in source["config"]} == source["config"]
    assert model == source["config"]
    assert config["reduced"] == [] and config["published"] == {}
    assert (model["num_hidden_layers"], model["hidden_size"],
            model["vocab_size"], model["sliding_window"]) \
        == (32, 2560, 200064, 512)
    assert config["family"] == "phi4_flash"
    for said in ("assumed", "stands_for", "reckoning"):
        assert config[said]
    for reading in ("pattern", "mamba", "state", "memory", "differential",
                    "window", "positions", "layout"):
        assert config["assumed"][reading]
    assert len(config["why"]) <= 200
    assert "TO BE SET" not in json.dumps(config)
    assert 0.9 <= config["serving"]["check_near_share"] <= 1.0


def test_the_program_is_the_whole_model():
    config = cell.load_json("configs", CONFIG)
    cfg = _cfg()
    from deepspeed_tpu.models import phi4_flash as program

    kinds = program.layer_kinds(cfg)
    assert kinds == ("mamba", "window") * 8 + ("mamba", "full") \
        + ("gmu", "cross") * 7
    assert (cfg.n_layers, cfg.n_mamba_layers, cfg.n_sliding_layers,
            cfg.n_cross_layers) == (32, 9, 8, 7)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (40, 20, 64)
    assert (cfg.d_inner, cfg.d_state, cfg.d_conv, cfg.dt_rank) \
        == (5120, 16, 4, 160)
    assert cfg.vocab_size == 200064 and cfg.max_seq_len == 262144
    # the closed forms of the issue: a Mamba-1 mixer 41.24 M, attention
    # with keys of its own 19.67 M, cross 13.11 M, a GMU 26.21 M, the
    # SwiGLU 78.64 M, two norms a layer; the embedding (tied, once)
    # 512.2 M: 3,852.6 M
    assert family._counts(cfg) == (41_241_600, 19_668_864, 13_112_704,
                                   26_214_400, 78_643_200)
    assert mamba1.mixer_params(cfg) == 41_241_600
    assert family.param_count(cfg) == config["parameters"] \
        == program.param_count(cfg) == 3_852_562_944
    assert cfg.vocab_size * cfg.dim == 512_163_840
    # a token leaves 5 KiB in the pool's one layer, which eight layers
    # read a step; a slot keeps 9 x (320 KiB + 30 KiB) of Mamba-1 state
    # and rows and 8 x 2.5 MiB of rings: 23.1 MiB
    assert family.kv_bytes_per_token(cfg) == 5 * 1024
    assert family.pool_reads(cfg) == 8
    assert mamba1.state_bytes(cfg) == 5120 * 16 * 4 + 3 * 5120 * 2
    assert family.slot_bytes(cfg) == (9 * 358_400, 8 * 2_621_440)
    assert family.state_bytes_per_slot(cfg) == 24_197_120
    # a prompt row pays the self-decoder, a generated one the rest too
    self_, tail = (family.self_flops_per_token(cfg, 0),
                   family.tail_flops_per_token(cfg, 0))
    assert family.serve_flops_per_token(cfg, 0) == self_
    assert self_ + tail == 2 * family.param_count(cfg) \
        + 9 * mamba1.rule_flops(cfg, 1)
    assert 0.49 < self_ / (self_ + tail) < 0.53
    # a key of the source that says another layer than the program builds
    # stops the run
    model = config["model"]
    with pytest.raises(SystemExit, match="mb_per_layer"):
        family.program_config(dict(model, mb_per_layer=4))
    with pytest.raises(SystemExit, match="tie_word_embeddings"):
        family.program_config(dict(model, tie_word_embeddings=False))
    toy = family.program_config(family.toy(model))
    assert program.layer_kinds(toy) == ("mamba", "window") * 3 \
        + ("mamba", "full") + ("gmu", "cross") * 2


def test_the_cell_is_the_issues_traffic():
    c = cell.load_json("workloads", CELL)
    mix = cell.load_json("traffic", c["traffic"])
    pangu = cell.load_json(
        "workloads", "openpangu-ultra-moe-718b-ep16-d5.serve.think-sat")
    assert c["chips"] == 1 and len(c["why"]) <= 200
    assert mix["kind"] == "serve_backlog_long" and "none" in mix["sharing"]
    engine = dict(c["engine"])
    assert engine.pop("num_pages") in (40961, 49153)
    assert engine == {"max_seq": 12288, "max_batch": 128,
                      "prefill_chunk": 1024, "prefill_bucket": 0}
    # the same traffic, slots and max_seq as the sibling think-sat cell
    assert {k: v for k, v in pangu["engine"].items() if k != "num_pages"} \
        == engine
    assert mix["prompt_tokens"]["hi"] + mix["output_tokens"]["hi"] \
        <= c["engine"]["max_seq"]
    assert set(c["end_to_end"]) == {"serve_tokens_per_s", "setup_s"}
    assert "note_build" in c["notes"]
    for m in ("decode_step_roofline.sat", "mamba_scan_roofline.sat",
              "mamba_share_of_device.sat", "mamba_step_roofline.sat",
              "serve_mfu_rows.sat", "yoco_read_roofline.sat",
              "yoco_read_share_of_device.sat"):
        assert NEW + m in c["per_layer"]
        file = cell.metric(NEW + m)
        assert (file["moves"], file["reader"]) == ("serve_tokens_per_s",
                                                  "phi4_flash")
        assert file["args"]["what"] in WHATS
    for m in ("serve_mfu", "compile_cache_hits", "compiles_steady",
              "v44.window_share_of_device.sat"):
        assert m in c["per_layer"]
    assert sum(m.startswith("v37.") for m in c["per_layer"]) == 7
    assert not [m for m in c["per_layer"] if "expert" in m or "ssm_" in m]


def test_the_manifest_is_the_files_and_the_parents_with_entries_appended():
    """``BENCHMARK.json`` is ``manifest.py --write``'s output, and
    against the parent's (``git show HEAD:BENCHMARK.json``, where the
    tree is a git checkout whose HEAD has not this cell yet) nothing
    that was there is edited, moved or removed."""
    assert manifest.main(["--check"]) == 0
    built = manifest.build()
    assert {"name": CONFIG,
            "source": cell.load_json("configs", CONFIG)["source"],
            "file": f"benchmark/configs/{CONFIG}.json", "reduced": [],
            "why": cell.load_json("configs", CONFIG)["why"]} \
        in built["configs"]
    assert [w["chips"] for w in built["workloads"]
            if w["name"] == CELL] == [1]
    assert sum(w["chips"] == 4 for w in built["workloads"]) == 1
    listed = [m["name"] for m in built["per_layer"]
              if CELL in m.get("workloads", ())]
    assert len([n for n in listed if n.startswith(NEW)]) == 7
    show = subprocess.run(["git", "show", "HEAD:BENCHMARK.json"], cwd=ROOT,
                          capture_output=True, text=True)
    if show.returncode:
        pytest.skip("not a git checkout")
    parent = json.loads(show.stdout)
    if any(w["name"] == CELL for w in parent["workloads"]):
        pytest.skip("HEAD has the cell already")
    assert len(built["workloads"]) == len(parent["workloads"]) + 1 == 11
    for key in ("command", "paths", "run_seconds"):
        assert built[key] == parent[key]
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len(built[key]) >= len(parent[key])
        for a, b in zip(parent[key], built[key]):
            rest = lambda m: {k: v for k, v in m.items() if k != "workloads"}
            assert rest(a) == rest(b)
            assert ("workloads" in a) == ("workloads" in b)
            if "workloads" in a:
                assert b["workloads"][:len(a["workloads"])] == a["workloads"]
                assert set(b["workloads"][len(a["workloads"]):]) <= {CELL}


def test_roofline_arithmetic_of_the_mamba1_layers():
    cfg = _cfg()
    assert mamba1.projection_params(cfg) == 2560 * 10240 + 5120 * 192 \
        + 160 * 5120 + 5120 * 2560
    assert mamba1.mixer_params(cfg) - mamba1.projection_params(cfg) \
        == 5 * 5120 + 2 * 5120 + 5120 * 16
    assert mamba1.rule_flops(cfg, 1) == 2 * 4 * 5120 + 6 * 5120 * 16
    # a chunk of 1,024 tokens: 84.9 GFLOP a layer, 0.43 ms at the peak
    # (the weights and the state stream in 0.10 ms)
    assert mamba1.scan_floor_seconds(cfg, 1024, PEAKS) \
        == pytest.approx(mamba1.flops(cfg, 1024) / 197e12)
    assert 0.42e-3 < mamba1.scan_floor_seconds(cfg, 1024, PEAKS) < 0.44e-3
    # a decode step of 128 live slots: 2 x 128 x 350 KiB of state and
    # rows and 82 MB of weights a layer, bound by the memory: 0.21 ms
    assert mamba1.step_floor_seconds(cfg, 128, PEAKS) == pytest.approx(
        (2 * 128 * 358_400 + 2 * 41_241_600) / 819e9)


def _run(cfg, steps, **window):
    return types.SimpleNamespace(
        window=dict({"kind": "serve", "t_open": -1.0, "t_end": 9.0,
                     "first_step": 0, "pool_pages": 49152, "page_size": 16,
                     "program_config": cfg,
                     "ledger": types.SimpleNamespace(steps=steps)},
                    **window),
        family=family, peaks=PEAKS, chips=1, traced=None,
        traffic={"trace_seconds": 4.0},
        trace_dir="/nonexistent",
        config={"serving": {"engine": {"page_size": 16}}},
        cell={"engine": {"max_batch": 128, "prefill_chunk": 1024}})


def test_eight_readers_and_two_slot_kinds_count_in_a_decode_steps_floor():
    cfg = _cfg()
    steps = [(0.0, 0.060, 0, 0, 1.0, 0.5, 0)] * 3
    rows = 0.5 * 49152 * 16
    least = (family.weight_bytes(cfg) + 8 * rows * 5120
             + 128 * (2 * 9 * 358_400 + 8 * 2_621_440)) / 819e9
    assert reader.read(_run(cfg, steps), "decode_step_roofline") \
        == pytest.approx(100 * least / 0.060)
    # with half the pool live: 27 GB a step, 16 of them the pool's rows
    # read eight times, 7.7 the weights, 3.5 the slots' states and rings
    assert 8 * rows * 5120 == pytest.approx(16.1e9, rel=0.01)
    assert 27e9 < least * 819e9 < 28e9


def test_every_row_is_charged_what_it_paid():
    cfg = _cfg()
    led = types.SimpleNamespace(
        steps=[], requests={1: types.SimpleNamespace(prompt_len=2048)},
        stamps={1: [0.0] * 1536})
    run = _run(cfg, [], ledger=led, completed=[1])
    want = (2048 * family.self_flops_per_token(cfg, 1024)
            + 1536 * family.self_flops_per_token(cfg, 2048 + 768)
            + family.tail_flops_per_token(cfg, 2048)
            + 1536 * family.tail_flops_per_token(cfg, 2048 + 768))
    assert reader.read(run, "serve_mfu_rows") == pytest.approx(
        100 * want / 10.0 / 197e12)
    # the accepted reader's count (every token the self-decoder alone)
    # is the lower one
    lower = 3584 * family.serve_flops_per_token(cfg, 1792)
    assert lower < want < 2 * lower


@pytest.mark.parametrize("what", WHATS)
def test_the_reader_reads_nothing_where_there_is_nothing_to_read(
        monkeypatch, what):
    """A run that was not traced, a trace of a program that has no such
    scope (a recorded piece of a GPT-2 capture, as any parent of this PR
    gives), a configuration of another family: None, and no exception."""
    run = _run(_cfg(), [], completed=[])
    if what not in ("decode_step_roofline", "serve_mfu_rows"):
        assert reader.read(run, what) is None
        with open(os.path.join(DATA, "v5e_scoped.xplane.txt")) as f:
            recorded = scopes.from_text_proto(f.read())
        assert recorded.ops
        monkeypatch.setattr(scopes, "of_run", lambda run: recorded)
        assert reader.read(run, what) is None
    elif what == "decode_step_roofline":
        assert reader.read(run, what) is None       # no decode-only step
    run.window["program_config"] = types.SimpleNamespace()
    assert reader.read(run, what) is None


# ---------------------------------------------------------- the rehearsal
def _rehearse(plant="", trace=0):
    """The cell's rehearsal in a process of its own; ``plant`` is code
    run before the benchmark's entry point.  One test alone runs it
    traced: two traced runs of a cell at once share its trace
    directory."""
    code = plant + (
        "import sys\nfrom benchmark import run\n"
        f"sys.exit(run.main(['--workload', '{CELL}', '--seed', "
        f"'{2 ** 31 + 42}', '--trace', '{trace}', '--rehearse']))\n")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT),
        capture_output=True, text=True, timeout=900)
    lines = [json.loads(l) for l in out.stdout.splitlines()
             if l.startswith("{")]
    return out, lines


# the faults the configuration's ``check_why`` names, as code run before
# the benchmark's entry point (``{window}``: the model's, where a plant
# needs it); the chip runs of PR 55 planted these same strings
_COMBINE = """
import jax, jax.numpy as jnp
from deepspeed_tpu.models import phi4_flash as pf

def combine(cfg, attn, lp):
    B, T, _ = attn.shape
    f32 = jnp.float32
    a = attn.astype(f32).reshape(B, T, cfg.n_heads // 2, 2, 2 * cfg.head_dim)
    dot = lambda q, k: jnp.exp(jnp.sum(lp[q].astype(f32) * lp[k].astype(f32)))
    lam0 = lp["lam0"].astype(f32)
    lam = dot("lq1", "lk1") - dot("lq2", "lk2") + lam0
    o = a[..., 0, :] - LAMBDA * a[..., 1, :]
    o = o * NORM * lp["subln"].astype(f32) * (1.0 - lam0)
    return o.reshape(B, T, -1)

pf.diff_combine = combine
"""
_RMS = "jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.norm_eps)"
PLANTS = {
    "bf16_state": """
import jax.numpy as jnp
from deepspeed_tpu.inference import kernels, serving
kernels.STATE_DTYPE = serving.STATE_DTYPE = jnp.bfloat16
""",
    "a_averaged": """
from deepspeed_tpu.models import phi4_flash as pf
rule = pf.mamba_rule
pf.mamba_rule = lambda S, A, *v: rule(
    S, A.mean(-2, keepdims=True) + 0.0 * A, *v)
""",
    "no_lambda_p2": _COMBINE.replace("LAMBDA", "0.0").replace("NORM", _RMS),
    "no_subln": _COMBINE.replace("LAMBDA", "lam").replace("NORM", "1.0"),
    "memory_after_the_gate": """
import dataclasses
import jax, jax.numpy as jnp
from deepspeed_tpu.models import phi4_flash as pf
mix = pf.mamba_mix

def gated(cfg, x, lp, state, valid, start=None, ctx=()):
    (y, m), state = mix(cfg, x, lp, state, valid, start, ctx)
    a = pf.layer_norm(x, lp["attn_norm_g"], lp["attn_norm_b"], cfg.norm_eps)
    z = (a @ lp["w_in"])[..., cfg.d_inner:].astype(jnp.float32)
    return (y, (m.astype(jnp.float32) * jax.nn.silu(z)).astype(m.dtype)), \\
        state

pf.FAMILY = dataclasses.replace(pf.FAMILY, recurrent=dataclasses.replace(
    pf.FAMILY.recurrent, mix=gated))
""",
    "full_layer_windowed": """
import jax.numpy as jnp
from deepspeed_tpu.inference import paged_forward as pfw
step = pfw.paged_attention_step

def windowed(q, k, v, kp, vp, layer, table, start, **kw):
    if q.shape[1] == 1:         # a decode step's reads: the last rows alone
        ps, pages = kp.shape[-2], table.shape[1]
        after = start + (0 if k is None else 1)
        skip = jnp.maximum(after - {window}, 0) // ps
        at = jnp.minimum(skip[:, None] + jnp.arange(pages)[None], pages - 1)
        table = jnp.take_along_axis(table, at, axis=1)
        start = start - skip * ps
    return step(q, k, v, kp, vp, layer, table, start, **kw)

pfw.paged_attention_step = windowed
""",
    "unmasked_steps": """
from deepspeed_tpu.inference import serving
programs = serving.serving_programs

def no_mask(*a, **kw):
    prefill, chunk, boundary, sweep, _ = programs(*a, **kw)
    _, _, _, _, decode_all = programs(*a, **dict(kw, state=False))
    return prefill, chunk, boundary, sweep, decode_all

serving.serving_programs = no_mask
""",
    "cross_blind_to_the_new_row": """
import jax.numpy as jnp
from deepspeed_tpu.inference import paged_forward as pfw
read = pfw._paged_read_block
pfw._paged_read_block = lambda rd, cfg, x, lp, ctx, kp, vp, table, \\
    lens, **kw: read(rd, cfg, x, lp, ctx, kp, vp, table,
                     jnp.maximum(lens - 1, 0), **kw)
""",
}


def _check(lines):
    return next(l["note_check"] for l in lines if "note_check" in l)


@pytest.mark.parametrize("plant", [p for p in PLANTS if p != "bf16_state"])
def test_a_planted_fault_is_not_correct(plant):
    """Each fault the configuration's ``check_why`` names, planted in the
    programs the harness serves and checks, fails the run at the
    rehearsal's sizes: by the tokens (a layer that computes another
    thing), by the state probe (a state another slot's steps moved), or
    both.  The rehearsal serves float32, so what a fault moves stands
    far over the limits (set on the chip, where bfloat16 feeds both)."""
    out, lines = _rehearse(PLANTS[plant].replace("{window}", "16"))
    assert out.returncode == 1, out.stdout[-2000:] + out.stderr[-2000:]
    assert lines[-1]["rehearsal"] == "failed"
    check = _check(lines)
    state = check["router_probe"]["state"]
    by_state = family.state_failed(state)
    by_tokens = check["near"] < check["near_share_asked"] * check["tokens"]
    assert by_state or by_tokens
    if plant in ("a_averaged", "unmasked_steps"):
        assert by_state and state["first"]["error_mean"] \
            > 3 * state["first"]["limit"]
    elif plant == "cross_blind_to_the_new_row":
        # one row of a context's dozens: the short probe's to see
        assert state["short"]["error"] > state["short"]["limit"]
        assert state["first"]["error_mean"] < 1e-4
    else:
        assert by_tokens


def test_a_bfloat16_state_reaches_the_probe_and_reads_higher():
    """State kept in bfloat16.  The probe's limits are set at the cell's
    widths on the chip; at the rehearsal's toy widths in float32 the
    plant is seen to reach the probe's cache and to read a thousand times
    the same run without it, and the run to fail exactly where a reading
    is over a limit."""
    _, clean = _rehearse()
    out, lines = _rehearse(PLANTS["bf16_state"])
    state = _check(lines)["router_probe"]["state"]
    was = _check(clean)["router_probe"]["state"]
    assert (state["state_dtype"], was["state_dtype"]) \
        == ("bfloat16", "float32")
    assert state["first"]["error_mean"] > 1000 * was["first"]["error_mean"]
    assert state["first"]["error_mean"] > 0.001
    assert out.returncode == int(family.state_failed(state))


def test_the_cell_rehearses_on_the_cpu():
    out, lines = _rehearse(trace=1)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    last = lines[-1]
    assert last["rehearsal"] == "passed"
    assert last["metrics"]["v37.build_lower_s"]["value"] > 0
    assert "correct" not in last
    check = _check(lines)
    assert check["near"] == check["tokens"] > 0
    probe = check["router_probe"]
    assert probe["differ"] == 0 and probe["by"] == [4, 32]
    assert not family.state_failed(probe["state"])
    assert probe["state"]["first"]["error_mean"] < 1e-4
    assert probe["state"]["short"]["error"] < 1e-4
    assert probe["state"]["tokens"] == 3 * 32 + 2 + 32

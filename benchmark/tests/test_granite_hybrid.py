"""The Granite-4.0-H configuration, its cell and what reads them: the
file against the source's keys (nothing reduced), the manifest, the new
reader's arithmetic, and the cell's rehearsal with its planted faults."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import manifest
from benchmark.families import granite_hybrid as family
from benchmark.harness import cell, scopes
from benchmark.readers import decode_step_roofline_state
from benchmark.readers import ssm as ssm_reader
from benchmark.roofline import ssm

ROOT = manifest.ROOT
CONFIG = "v42.granite-4.0-h-micro"
CELL = CONFIG + ".serve.assist-sat"
NEW = "v42."       # this PR's metric files sort behind the manifest's
DATA = os.path.join(os.path.dirname(__file__), "data")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def source():
    """The catalog's row for the model (``architectures.jsonl`` beside
    the model-configs guide), copied here as data."""
    with open(os.path.join(DATA, "granite-4.0-h-micro.catalog.json")) as f:
        return json.load(f)


def _cfg():
    return family.program_config(cell.load_json("configs", CONFIG)["model"])


def test_the_file_holds_the_sources_keys_and_values_and_cuts_nothing(source):
    config = cell.load_json("configs", CONFIG)
    model = config["model"]
    assert config["source"] == source["source_url"]
    assert len(source["config"]) == 33
    # key for key: at the top level, where the driver's check against
    # the catalog row reads them, and under ``model``, where the harness
    # does; the two are one statement
    assert {k: config[k] for k in source["config"]} == source["config"]
    assert model == source["config"]
    assert config["reduced"] == [] and config["published"] == {}
    assert len(model["layer_types"]) == model["num_hidden_layers"] == 40
    assert [i for i, kind in enumerate(model["layer_types"])
            if kind == "attention"] == [5, 15, 25, 35]
    assert model["rope_scaling"] is None
    assert config["family"] == "granite_hybrid"
    for said in ("assumed", "stands_for", "reckoning"):
        assert config[said]
    assert len(config["why"]) <= 200
    assert "TO BE SET" not in json.dumps(config)
    # dense, but not held to every token: the file's check_why says
    # what the chip read and why the limit is where it is
    assert 0.98 <= config["serving"]["check_near_share"] < 1.0


def test_the_program_is_the_whole_model():
    config = cell.load_json("configs", CONFIG)
    cfg = _cfg()
    assert cfg.period == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    assert (cfg.n_layers, cfg.n_ssm_layers, cfg.n_attn_layers) == (40, 36, 4)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (32, 8, 64)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state) == (64, 64, 128)
    assert cfg.ssm_inner == 2 * cfg.dim == 4096
    assert cfg.conv_channels == 4352 and cfg.ssm_block == 256
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling) \
        == (12.0, 0.22, 0.015625, 8.0)
    assert cfg.vocab_size == 100352 and cfg.max_seq_len == 131072
    # a Mamba-2 layer 25,847,232 in its mixer + 50,331,648 MLP + 4,096
    # norms; an attention layer 10,485,760 + the same; the embedding
    # (tied, once) 205,520,896; the final norm 2,048
    assert ssm.mixer_params(cfg) == 25_847_232
    assert family._counts(cfg) == (25_847_232, 10_485_760, 50_331_648)
    assert family.param_count(cfg) == config["parameters"] == 3_191_396_096
    from deepspeed_tpu.models import granite_hybrid as program
    assert program.param_count(cfg) == config["parameters"]
    # K/V a token: 4 layers x 8 heads x 64 x (K and V) x 2 B = 8 KiB; a
    # slot's state 36 x (2 MiB + 25.5 KiB) = 72.9 MiB: 9,331 tokens' K/V
    assert family.kv_bytes_per_token(cfg) == 8 * 1024
    assert ssm.state_bytes(cfg) == 2 * 2 ** 20 + 3 * 4352 * 2
    assert family.state_bytes_per_slot(cfg) == 36 * ssm.state_bytes(cfg) \
        == 76_437_504
    assert family.state_bytes_per_slot(cfg) // family.kv_bytes_per_token(
        cfg) == 9330
    # a key of the source that says another layer than the program builds
    # stops the run
    model = cell.load_json("configs", CONFIG)["model"]
    with pytest.raises(SystemExit, match="mamba_n_groups"):
        family.program_config(dict(model, mamba_n_groups=8))
    toy = family.program_config(family.toy(model))
    assert toy.period == cfg.period and toy.n_layers == 10


def test_the_cell_is_the_issues_traffic():
    c = cell.load_json("workloads", CELL)
    mix = cell.load_json("traffic", c["traffic"])
    assert c["chips"] == 1 and len(c["why"]) <= 200
    assert mix["kind"] == "serve_backlog_long" and "none" in mix["sharing"]
    assert c["engine"] == {"max_seq": 2048, "max_batch": 96,
                           "num_pages": 6145, "prefill_chunk": 256,
                           "prefill_bucket": 0}
    assert mix["prompt_tokens"] == {"distribution": "lognormal",
                                    "median": 256, "sigma": 0.8,
                                    "lo": 32, "hi": 1024}
    assert mix["output_tokens"] == {"distribution": "lognormal",
                                    "median": 256, "sigma": 0.7,
                                    "lo": 32, "hi": 1024}
    assert (mix["grid"], mix["stratum_block"], mix["warm_seconds"],
            mix["trace_seconds"]) == (32, 16, 20.0, 4.0)
    assert mix["prompt_tokens"]["hi"] + mix["output_tokens"]["hi"] \
        <= c["engine"]["max_seq"]
    assert set(c["end_to_end"]) == {"serve_tokens_per_s", "setup_s"}
    assert "note_build" in c["notes"]
    for m in ("ssm_share_of_device.sat", "ssm_prefill_roofline.sat",
              "ssm_step_roofline.sat"):
        assert NEW + m in c["per_layer"]
        file = cell.metric(NEW + m)
        assert (file["moves"], file["source"], file["layer"],
                file["reader"]) == ("serve_tokens_per_s", "device_trace",
                                    "kernels", "ssm")
    assert "v35.decode_step_roofline.sat" in c["per_layer"]
    assert not [m for m in c["per_layer"] if "gdn_" in m or "expert" in m]


def test_the_manifest_is_the_files_and_the_parents_with_entries_appended():
    """``BENCHMARK.json`` is ``manifest.py --write``'s output, and
    against the parent's (``git show HEAD:BENCHMARK.json``, where the
    tree is a git checkout whose HEAD has not this cell yet) nothing
    that was there is edited, moved or removed: configurations, cells
    and metrics are appended, and a metric's ``workloads`` grows at its
    end alone."""
    assert manifest.main(["--check"]) == 0
    built = manifest.build()
    # (where this cell stands in the lists is the parent comparison's to
    # say, below: a later PR appends behind it, and an assertion that
    # this cell is the last would fail on that PR's tree)
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
    assert [n for n in listed if n.startswith(NEW)] == [
        NEW + "ssm_prefill_roofline.sat", NEW + "ssm_share_of_device.sat",
        NEW + "ssm_step_roofline.sat"]
    assert sum(n.startswith("v37.") for n in listed) == 7
    show = subprocess.run(["git", "show", "HEAD:BENCHMARK.json"], cwd=ROOT,
                          capture_output=True, text=True)
    if show.returncode:
        pytest.skip("not a git checkout")
    parent = json.loads(show.stdout)
    if any(w["name"] == CELL for w in parent["workloads"]):
        pytest.skip("HEAD has the cell already")
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


def test_roofline_arithmetic_of_the_mamba2_layers():
    cfg = _cfg()
    assert ssm.projection_params(cfg) == 2048 * 8512 + 4096 * 2048
    assert ssm.mixer_params(cfg) - ssm.projection_params(cfg) \
        == 5 * 4352 + 3 * 64 + 4096
    assert ssm.rule_flops(cfg, 1) == 6 * 64 * 64 * 128
    assert ssm.flops(cfg, 1) == 2 * ssm.projection_params(cfg) \
        + 2 * 4 * 4352 + 6 * 64 * 64 * 128
    # a chunk of 256 tokens: 14 GFLOP a layer, 71 us at the peak; the
    # layer's weights and the slot's state, 56 MB, take 68 us to stream
    assert ssm.prefill_floor_seconds(cfg, 256, PEAKS) \
        == pytest.approx(ssm.flops(cfg, 256) / 197e12)
    assert 65e-6 < (ssm.weight_bytes(cfg) + 2 * ssm.state_bytes(cfg)) \
        / 819e9 < ssm.prefill_floor_seconds(cfg, 256, PEAKS) < 75e-6
    # a decode step of 96 live slots: 2 x 96 x (2 MiB + 25.5 KiB) of
    # state and 52 MB of weights a layer, bound by the memory: 0.56 ms
    assert ssm.state_bytes(cfg) == 2 * 2 ** 20 + 26112
    assert ssm.step_floor_seconds(cfg, 96, PEAKS) == pytest.approx(
        (2 * 96 * ssm.state_bytes(cfg) + ssm.weight_bytes(cfg)) / 819e9)
    assert ssm.step_floor_seconds(cfg, 0, PEAKS) == pytest.approx(
        ssm.weight_bytes(cfg) / 819e9)
    # the issue's arithmetic: a step of 96 slots streams 6.38 GB of
    # weights and 2 x 7.34 GB of state: 68% of it state
    state = 2 * 96 * family.state_bytes_per_slot(cfg)
    assert state / (state + family.weight_bytes(cfg) + 0.39e9) \
        == pytest.approx(0.68, abs=0.01)


def test_the_state_counts_in_a_decode_steps_floor():
    cfg = _cfg()
    steps = [(0.0, 0.030, 0, 0, 0.5, 0.25, 0)] * 3
    window = {"kind": "serve", "t_open": -1.0, "t_end": 9.0,
              "first_step": 0, "pool_pages": 6144, "page_size": 16,
              "program_config": cfg,
              "ledger": types.SimpleNamespace(steps=steps)}
    run = types.SimpleNamespace(
        window=window, family=family, peaks=PEAKS,
        config={"serving": {"engine": {"page_size": 16}}},
        cell={"engine": {"max_batch": 96}})
    least = (family.weight_bytes(cfg) + 0.25 * 6144 * 16 * 8192
             + 2 * 48 * family.state_bytes_per_slot(cfg)) / 819e9
    assert decode_step_roofline_state.read(run) \
        == pytest.approx(100 * least / 0.030)


@pytest.mark.parametrize("what", ["share_of_busy", "prefill_roofline",
                                  "step_roofline"])
def test_the_reader_reads_nothing_where_no_ssm_word_is(monkeypatch, what):
    """A run that was not traced, and a trace of a program that has no
    ``ssm_`` scope (a recorded piece of a GPT-2 capture, as any parent
    of this PR gives): None, and no exception."""
    run = types.SimpleNamespace(
        traced=None, trace_dir="/nonexistent", peaks=PEAKS,
        window={"kind": "serve", "program_config": _cfg()},
        config={"serving": {"engine": {"page_size": 16}}},
        cell={"engine": {"max_batch": 96, "prefill_chunk": 256}})
    assert ssm_reader.read(run, what) is None
    with open(os.path.join(DATA, "v5e_scoped.xplane.txt")) as f:
        recorded = scopes.from_text_proto(f.read())
    assert recorded.ops
    monkeypatch.setattr(scopes, "of_run", lambda run: recorded)
    assert ssm_reader.read(run, what) is None
    # and a configuration without Mamba-2 layers has no floor to give
    run.window["program_config"] = types.SimpleNamespace()
    assert ssm_reader.read(run, what) is None


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


# the three faults the configuration's ``check_why`` names, as code run
# before the benchmark's entry point
BF16_STATE = """
import jax.numpy as jnp
from deepspeed_tpu.inference import kernels, serving
kernels.STATE_DTYPE = serving.STATE_DTYPE = jnp.bfloat16
"""
PADDING_MOVES_THE_STATE = """
from deepspeed_tpu.inference import serving
programs = serving.serving_programs

def no_last(*a, **kw):
    prefill, chunk, boundary, sweep, decode = programs(*a, **kw)
    _, chunk_all, _, _, _ = programs(*a, **dict(kw, state=False))
    return prefill, chunk_all, boundary, sweep, decode

serving.serving_programs = no_last
"""
UNMASKED_STEPS = """
from deepspeed_tpu.inference import serving
programs = serving.serving_programs

def no_mask(*a, **kw):
    prefill, chunk, boundary, sweep, _ = programs(*a, **kw)
    _, _, _, _, decode_all = programs(*a, **dict(kw, state=False))
    return prefill, chunk, boundary, sweep, decode_all

serving.serving_programs = no_mask
"""


def _probe(lines):
    return next(l["note_check"] for l in lines
                if "note_check" in l)["router_probe"]


@pytest.mark.parametrize("plant", [PADDING_MOVES_THE_STATE, UNMASKED_STEPS],
                         ids=["padding", "unmasked"])
def test_a_planted_fault_is_not_correct(plant):
    """A padded chunk row allowed to move the state; decode steps of
    other slots run over a slot between its prompt's chunks without the
    mask.  Each run comes out failed, by the state probe (which drives
    the serving programs the fault was planted in).  The rehearsal
    serves float32 weights, so a float32 state reads 1e-6 there and
    these faults stand far over the cell's limits (set on the chip,
    where bfloat16 projections feed the state: the configuration's
    ``check_why``)."""
    out, lines = _rehearse(plant)
    assert out.returncode == 1, out.stdout[-2000:] + out.stderr[-2000:]
    assert lines[-1]["rehearsal"] == "failed"
    probe = _probe(lines)
    state = probe["state"]
    assert state["failed"] and family.state_failed(state)
    assert probe["differ"] > probe["limit"]
    assert state["state_dtype"] == "float32"
    assert state["first"]["error_mean"] > 3 * state["first"]["limit"]


def test_a_bfloat16_state_reaches_the_probe_and_reads_higher():
    """State kept in bfloat16.  The probe's limits are set at the cell's
    widths on the chip (64 heads of 64 x 128 over 816 tokens, bfloat16
    projections: float32 state 0.0042-0.0048, bfloat16 0.0063-0.0073
    against the limit 0.0057); at the rehearsal's toy widths in float32
    (8 heads of 32 x 32, 81 tokens) a bfloat16 state reads 0.0056, just
    under it, so here the plant is seen to reach the probe's cache and
    to read a thousand times the same run without it, and the run to
    fail exactly where a reading is over a limit."""
    _, clean = _rehearse()
    out, lines = _rehearse(BF16_STATE)
    state, was = _probe(lines)["state"], _probe(clean)["state"]
    assert (state["state_dtype"], was["state_dtype"]) \
        == ("bfloat16", "float32")
    assert state["first"]["error_mean"] > 1000 * was["first"]["error_mean"]
    assert state["first"]["error_mean"] > 0.004
    assert out.returncode == int(family.state_failed(state))


def test_the_cell_rehearses_on_the_cpu():
    out, lines = _rehearse(trace=1)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    last = lines[-1]
    assert last["rehearsal"] == "passed"
    assert last["metrics"]["v37.build_lower_s"]["value"] > 0
    assert "correct" not in last
    check = next(l["note_check"] for l in lines if "note_check" in l)
    assert check["near"] == check["tokens"] > 0
    probe = check["router_probe"]
    assert probe["differ"] == 0 and probe["by"] == [4, 16]
    assert not family.state_failed(probe["state"])
    assert probe["state"]["first"]["error_mean"] < 1e-4
    assert probe["state"]["tokens"] == 3 * 16 + 1 + 32

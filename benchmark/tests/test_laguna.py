"""The Laguna-S-2.1 configuration, its cell and what reads them: the file
against the source's keys, the manifest, the new readers' arithmetic, and
the cell's rehearsal with the six faults its ``check_why`` names."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import manifest
from benchmark.families import laguna as family
from benchmark.harness import cell, scopes
from benchmark.readers import window as window_reader
from benchmark.roofline import window

ROOT = manifest.ROOT
CONFIG = "v44.laguna-s-2.1-ep16-d13"
CELL = CONFIG + ".serve.code-sat"
NEW = "v44."       # this PR's metric files sort behind the manifest's
DATA = os.path.join(os.path.dirname(__file__), "data")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
REDUCED = {"num_hidden_layers": (48, 13), "num_experts": (256, 16),
           "vocab_size": (100352, 12544)}


@pytest.fixture(scope="module")
def source():
    """The catalog's row for the model (``architectures.jsonl`` beside
    the model-configs guide), copied here as data."""
    with open(os.path.join(DATA, "laguna-s-2.1.catalog.json")) as f:
        return json.load(f)


def _cfg():
    return family.program_config(cell.load_json("configs", CONFIG)["model"])


def test_the_file_holds_the_sources_keys_and_cuts_three(source):
    config = cell.load_json("configs", CONFIG)
    model = config["model"]
    assert config["source"] == source["source_url"]
    assert len(source["config"]) == 29
    # key for key: at the top level, where the driver's check against
    # the catalog row reads them, and under ``model``, where the harness
    # does; the two are one statement
    top = {k: config[k] for k in source["config"]}
    assert top == model
    differ = {k for k in source["config"] if top[k] != source["config"][k]}
    assert differ == set(config["reduced"]) == set(REDUCED)
    assert config["reduced"] == list(REDUCED)
    for key, (was, now) in REDUCED.items():
        assert (source["config"][key], config["published"][key], top[key]) \
            == (was, was, now)
    # the per-layer lists and the rope tables as published, whole
    for key in ("layer_types", "mlp_layer_types", "gating_types",
                "num_attention_heads_per_layer"):
        assert len(model[key]) == 48 and model[key] == source["config"][key]
    assert model["rope_parameters"] == source["config"]["rope_parameters"]
    assert config["family"] == "laguna"
    for said in ("assumed", "stands_for", "reckoning", "published"):
        assert config[said]
    assert len(config["why"]) <= 200
    assert "TO FILL" not in json.dumps(config)
    assert 0.9 <= config["serving"]["check_near_share"] < 1.0


def test_the_program_is_the_share_at_the_published_widths():
    config = cell.load_json("configs", CONFIG)
    cfg = _cfg()
    assert cfg.period == ("sliding", "sliding", "sliding", "full")
    assert (cfg.n_layers, cfg.n_sliding_layers, cfg.n_full_layers) \
        == (13, 9, 4)
    assert (cfg.n_heads_full, cfg.n_heads_sliding, cfg.n_kv_heads,
            cfg.head_dim, cfg.sliding_window) == (48, 72, 8, 128, 512)
    assert (cfg.dim, cfg.ffn_dim, cfg.moe_ffn_dim, cfg.shared_ffn_dim) \
        == (3072, 12288, 1024, 1024)
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.top_k,
            cfg.routed_scaling_factor, cfg.norm_topk_prob) \
        == (256, (0, 16), 10, 2.5, True)
    assert (cfg.rope_theta_full, cfg.rotary_dim_full, cfg.yarn_factor,
            cfg.yarn_original_max, cfg.yarn_beta_fast, cfg.yarn_beta_slow,
            cfg.attention_factor, cfg.rope_theta_sliding) \
        == (500000.0, 64, 128.0, 8192, 32.0, 1.0, 1.4852030263919618,
            10000.0)
    assert cfg.vocab_size == 12544 and cfg.max_seq_len == 1048576
    # a full layer's attention 44.19 M, a sliding layer's 63.14 M (the
    # norms aside), an expert 9.44 M, layer 0's SwiGLU 113.2 M
    full, sliding, mlp, expert, shared, router = family._counts(cfg)
    assert (full, sliding) == (44_187_648, 63_135_744)
    assert (mlp, expert, shared, router) \
        == (113_246_208, 9_437_184, 9_437_184, 786_432)
    assert family.param_count(cfg) == config["parameters"] == 2_869_994_496
    from deepspeed_tpu.models import laguna as program
    assert program.param_count(cfg) == config["parameters"]
    # K/V a token: 4 layers x 8 heads x 128 x (K and V) x 2 B = 16 KiB;
    # a slot's rings 9 x 2 MiB = 18 MiB, whatever its length
    assert family.kv_bytes_per_token(cfg) == 16 * 1024
    assert window.ring_bytes(cfg) == 2 * 2 ** 20
    assert family.state_bytes_per_slot(cfg) == 18 * 2 ** 20
    # a token at 3.3k of context: 2 per weight it meets and the keys of 4
    # full layers over the context and 9 sliding ones over a window
    assert family.serve_flops_per_token(cfg, 3300) == \
        2 * family.routed_param_count(cfg) + 4 * 128 * (
            4 * 48 * 3300 + 9 * 72 * 512)
    # a key of the source that says another layer than the program builds
    # stops the run
    model = config["model"]
    with pytest.raises(SystemExit, match="gating"):
        family.program_config(dict(model, gating="elementwise"))
    with pytest.raises(SystemExit, match="head count"):
        family.program_config(dict(
            model, num_attention_heads_per_layer=[48, 72, 64] + [72] * 45))
    toy = family.program_config(family.toy(model))
    assert toy.period == cfg.period and toy.n_layers == 9
    assert (toy.n_heads_full, toy.n_heads_sliding, toy.n_kv_heads) \
        == (4, 6, 2)


def test_the_cell_is_the_issues_traffic():
    c = cell.load_json("workloads", CELL)
    mix = cell.load_json("traffic", c["traffic"])
    assert c["chips"] == 1 and len(c["why"]) <= 200
    assert mix["kind"] == "serve_backlog_long" and "none" in mix["sharing"]
    assert {k: c["engine"][k] for k in ("max_seq", "max_batch",
                                        "prefill_chunk", "prefill_bucket")} \
        == {"max_seq": 18432, "max_batch": 96, "prefill_chunk": 1024,
            "prefill_bucket": 0}
    assert mix["prompt_tokens"] == {"distribution": "lognormal",
                                    "median": 2048, "sigma": 0.9,
                                    "lo": 256, "hi": 16384}
    assert mix["output_tokens"] == {"distribution": "lognormal",
                                    "median": 512, "sigma": 0.7,
                                    "lo": 64, "hi": 2048}
    assert (mix["grid"], mix["stratum_block"], mix["warm_seconds"],
            mix["trace_seconds"]) == (32, 16, 20.0, 4.0)
    assert mix["prompt_tokens"]["hi"] + mix["output_tokens"]["hi"] \
        <= c["engine"]["max_seq"]
    assert set(c["end_to_end"]) == {"serve_tokens_per_s", "setup_s"}
    for m, source_, layer in (
            ("decode_step_roofline.sat", "host_clock", "models"),
            ("window_share_of_device.sat", "device_trace", "kernels"),
            ("window_step_roofline.sat", "device_trace", "kernels"),
            ("window_chunk_roofline.sat", "device_trace", "kernels")):
        assert NEW + m in c["per_layer"]
        file = cell.metric(NEW + m)
        assert (file["moves"], file["source"], file["layer"],
                file["reader"]) == ("serve_tokens_per_s", source_, layer,
                                    "window")
    assert sum(m.startswith("v33.") for m in c["per_layer"]) == 3
    assert sum(m.startswith("v37.") for m in c["per_layer"]) == 7
    assert not [m for m in c["per_layer"]
                if "gdn_" in m or "ssm_" in m or "mla_" in m
                or m.startswith("v35.")]


def test_the_manifest_is_the_files_and_the_parents_with_entries_appended():
    """``BENCHMARK.json`` is ``manifest.py --write``'s output, and
    against the parent's (``git show HEAD:BENCHMARK.json``, where the
    tree is a git checkout whose HEAD has not this cell yet) nothing
    that was there is edited, moved or removed: configurations, cells
    and metrics are appended, and a metric's ``workloads`` grows at its
    end alone.  Membership and the parent comparison: where this cell
    stands in the lists a later PR may append behind."""
    assert manifest.main(["--check"]) == 0
    built = manifest.build()
    config = cell.load_json("configs", CONFIG)
    assert {"name": CONFIG, "source": config["source"],
            "file": f"benchmark/configs/{CONFIG}.json",
            "reduced": list(REDUCED), "why": config["why"]} \
        in built["configs"]
    assert [w["chips"] for w in built["workloads"]
            if w["name"] == CELL] == [1]
    assert sum(w["chips"] == 4 for w in built["workloads"]) == 1
    listed = [m["name"] for m in built["per_layer"]
              if CELL in m.get("workloads", ())]
    assert [n for n in listed if n.startswith(NEW)] == [
        NEW + "decode_step_roofline.sat", NEW + "window_chunk_roofline.sat",
        NEW + "window_share_of_device.sat", NEW + "window_step_roofline.sat"]
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


def test_roofline_arithmetic_of_the_window_layers():
    cfg = _cfg()
    assert window.row_bytes(cfg) == 4096
    assert window.ring_bytes(cfg) == 512 * 4096
    # a decode step of 96 live slots: 96 x 513 rows of 4 KiB a layer,
    # 0.25 ms at the bandwidth; the nine layers 1.8 GB, 2.2 ms
    assert window.step_bytes(cfg, 96) == 96 * 513 * 4096
    assert window.step_bytes(cfg, 96, 100) == 96 * 101 * 4096
    assert window.step_floor_seconds(cfg, 96, PEAKS) == pytest.approx(
        96 * 513 * 4096 / 819e9)
    assert 0.24e-3 < window.step_floor_seconds(cfg, 96, PEAKS) < 0.25e-3
    # a chunk of 1,024 queries, a window each: 72 heads x 128 x 4 a
    # pair, 19.3 GFLOP a layer, 98 us at the peak
    assert window.pair_flops(cfg) == 72 * 128 * 4
    assert window.chunk_flops(cfg, 1024) == 1024 * 512 * 72 * 128 * 4
    assert window.chunk_floor_seconds(cfg, 1024, PEAKS) == pytest.approx(
        1024 * 512 * 72 * 128 * 4 / 197e12)
    # the issue's arithmetic: ~3.3k live tokens a slot are 5.1 GB of
    # full-layer K/V and 1.8 GB of rings against 5.7 GB of weights
    kv = 96 * 3270 * family.kv_bytes_per_token(cfg)
    rings = 96 * family.state_bytes_per_slot(cfg)
    weights = family.weight_bytes(cfg)
    assert (round(kv / 1e9, 1), round(rings / 1e9, 1),
            round(weights / 1e9, 1)) == (5.1, 1.8, 5.7)
    assert (kv + rings) / (kv + rings + weights) == pytest.approx(0.55,
                                                                   abs=0.01)


def test_the_rings_count_once_in_a_decode_steps_floor():
    cfg = _cfg()
    steps = [(0.0, 0.030, 0, 0, 0.5, 0.25, 0)] * 3
    window_ = {"kind": "serve", "t_open": -1.0, "t_end": 9.0,
               "first_step": 0, "pool_pages": 28672, "page_size": 16,
               "program_config": cfg,
               "ledger": types.SimpleNamespace(steps=steps)}
    run = types.SimpleNamespace(
        window=window_, family=family, peaks=PEAKS,
        config={"serving": {"engine": {"page_size": 16}}},
        cell={"engine": {"max_batch": 96}})
    live = 0.25 * 28672 * 16
    # 48 live slots of 2,389 tokens each: the whole window, once
    least = (family.weight_bytes(cfg) + live * 16384
             + 9 * 48 * 513 * 4096) / 819e9
    assert window_reader.read(run, "decode_step_roofline") \
        == pytest.approx(100 * least / 0.030)
    # slots shorter than the window hold fewer rows
    window_["pool_pages"] = 28672 // 32
    short = 0.25 * (28672 // 32) * 16 / 48
    least = (family.weight_bytes(cfg) + 48 * short * 16384
             + 9 * 48 * (short + 1) * 4096) / 819e9
    assert short < 512
    assert window_reader.read(run, "decode_step_roofline") \
        == pytest.approx(100 * least / 0.030)


@pytest.mark.parametrize("what", ["share_of_busy", "chunk_roofline",
                                  "step_roofline"])
def test_the_reader_reads_nothing_where_no_window_word_is(monkeypatch, what):
    """A run that was not traced, and a trace of a program that has no
    ``win_`` scope (a recorded piece of a GPT-2 capture, as any parent
    of this PR gives): None, and no exception."""
    run = types.SimpleNamespace(
        traced=None, trace_dir="/nonexistent", peaks=PEAKS,
        window={"kind": "serve", "program_config": _cfg()},
        config={"serving": {"engine": {"page_size": 16}}},
        cell={"engine": {"max_batch": 96, "prefill_chunk": 1024}})
    assert window_reader.read(run, what) is None
    with open(os.path.join(DATA, "v5e_scoped.xplane.txt")) as f:
        recorded = scopes.from_text_proto(f.read())
    assert recorded.ops
    monkeypatch.setattr(scopes, "of_run", lambda run: recorded)
    assert window_reader.read(run, what) is None
    # and a configuration without sliding layers has no floor to give
    run.window["program_config"] = types.SimpleNamespace()
    assert window_reader.read(run, what) is None
    assert window_reader.read(run, "decode_step_roofline") is None


def _rehearse(plant="", trace=0):
    """The cell's rehearsal in a process of its own; ``plant`` is code
    run before the benchmark's entry point.  One test alone runs it
    traced: two traced runs of a cell at once share its trace
    directory."""
    code = plant + (
        "import sys\nfrom benchmark import run\n"
        f"sys.exit(run.main(['--workload', '{CELL}', '--seed', "
        f"'{2 ** 31 + 44}', '--trace', '{trace}', '--rehearse']))\n")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT),
        capture_output=True, text=True, timeout=900)
    lines = [json.loads(l) for l in out.stdout.splitlines()
             if l.startswith("{")]
    return out, lines


# the six faults the configuration's ``check_why`` names, as code run
# before the benchmark's entry point; each is planted in the program
# alone (the reference is derived from the same configuration object, so
# a plant in the configuration would move both)
FULL_FOR_SLIDING = """
from benchmark.families import laguna as family
build, ref_kw = family.program_config, family._ref_kw
family.program_config = lambda model, **kw: build(dict(
    model, sliding_window=16 * model["sliding_window"]), **kw)
family._ref_kw = lambda cfg: dict(ref_kw(cfg),
                                  window=cfg.sliding_window // 16)
"""
STALE_RING_ROW = """
from deepspeed_tpu.models import laguna as program
step = program.window_step

def stale(cfg, q, row, rings, pos, live):
    # attends before its own row is in: ring row pos mod W still holds
    # the position a window back
    o, _ = step(cfg, q, row, rings, pos, live & False)
    return o, step(cfg, q, row, rings, pos, live)[1]

program.window_step = stale
"""
OTHER_KINDS_TABLE = """
from deepspeed_tpu.models import laguna as program
tables = program.rope_tables

def swapped(cfg, positions):
    cf, sf, cs, ss = tables(cfg, positions)
    half = cf.shape[-1]
    return cs[..., :half], ss[..., :half], cs, ss

program.rope_tables = swapped
"""
NO_ATTENTION_FACTOR = """
from deepspeed_tpu.models import laguna as program
tables = program.rope_tables

def unscaled(cfg, positions):
    cf, sf, cs, ss = tables(cfg, positions)
    return cf / cfg.attention_factor, sf / cfg.attention_factor, cs, ss

program.rope_tables = unscaled
"""
NO_GATE = """
from deepspeed_tpu.models import laguna as program
program._gated_out = lambda cfg, x, attn, lp: x + attn @ lp["wo"]
"""
BF16_ROUTER = """
import jax, jax.numpy as jnp
from deepspeed_tpu.models import laguna as program

def route(h, gate, top_k, scale=1.0, normalize=True):
    bf = jnp.bfloat16
    s = jax.nn.sigmoid(jnp.dot(h.astype(bf), gate.astype(bf)))
    top, idx = jax.lax.top_k(s, top_k)
    top = top.astype(jnp.float32)
    if normalize:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    return top * scale, idx.astype(jnp.int32)

program.sigmoid_topk_route = route
"""
TOKEN_FAULTS = {"full_for_sliding": FULL_FOR_SLIDING,
                "stale_ring_row": STALE_RING_ROW,
                "other_kinds_table": OTHER_KINDS_TABLE,
                "no_attention_factor": NO_ATTENTION_FACTOR,
                "no_gate": NO_GATE}


@pytest.mark.parametrize("fault", TOKEN_FAULTS)
def test_a_planted_fault_is_not_correct(fault):
    """A sliding layer that attends as a full one, a decode step that
    reads its ring before its own row is in, the sliding layers' table
    in the full layers, the attention factor dropped, the gate dropped:
    each run comes out failed, by the token check."""
    out, lines = _rehearse(TOKEN_FAULTS[fault])
    assert out.returncode == 1, out.stdout[-2000:] + out.stderr[-2000:]
    assert lines[-1]["rehearsal"] == "failed"
    check = next(l["note_check"] for l in lines if "note_check" in l)
    assert check["near"] < check["near_share_asked"] * check["tokens"]
    assert check["router_probe"]["differ"] == 0


def test_a_bfloat16_router_is_not_correct():
    """The program's router fed bfloat16 roundings of its inputs: the
    run comes out failed, by the router probe (the token check does not
    see it on a share)."""
    out, lines = _rehearse(BF16_ROUTER)
    assert out.returncode == 1, out.stdout[-2000:] + out.stderr[-2000:]
    assert lines[-1]["rehearsal"] == "failed"
    problems = next(l["problems"] for l in lines if "problems" in l)
    assert any("router" in p and "held experts" in p for p in problems)
    probe = next(l["note_check"] for l in lines
                 if "note_check" in l)["router_probe"]
    assert probe["differ"] > probe["limit"]


def test_the_cell_rehearses_on_the_cpu():
    out, lines = _rehearse(trace=1)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    last = lines[-1]
    assert last["rehearsal"] == "passed"
    assert last["metrics"]["v37.build_lower_s"]["value"] > 0
    assert last["metrics"]["preemptions.sat"]["value"] == 0
    assert 0 < last["metrics"]["v33.expert_held_share.sat"]["value"] < 100
    assert "correct" not in last
    check = next(l["note_check"] for l in lines if "note_check" in l)
    assert check["near"] == check["tokens"] > 0
    probe = check["router_probe"]
    assert probe["differ"] == 0 and probe["by"] == [4, 32]

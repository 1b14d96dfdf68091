"""The openPangu-Ultra-MoE configuration, its cell and what reads them:
the file against the source's keys, the share's arithmetic, the runner
that windows long answers, the new kernels' roofline arithmetic."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import manifest
from benchmark.families import pangu_ultra_moe as family
from benchmark.harness import cell, serve
from benchmark.readers import expert_rows
from benchmark.roofline import mla, moe
from benchmark.runners import serve_backlog_long as runner

ROOT = manifest.ROOT
CONFIG = "openpangu-ultra-moe-718b-ep16-d5"
CELL = CONFIG + ".serve.think-sat"
NEW = "v33."       # this PR's metric files sort behind the manifest's
REDUCED = {"num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
           "vocab_size"}


@pytest.fixture(scope="module")
def source():
    """The catalog's row for the model (``architectures.jsonl`` beside
    the model-configs guide), copied here as data."""
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "openpangu-ultra-moe-718b.catalog.json")) as f:
        return json.load(f)


def test_the_file_holds_the_sources_keys_and_values(source):
    config = cell.load_json("configs", CONFIG)
    model, published = config["model"], config["published"]
    assert config["source"] == source["source_url"]
    assert set(model) == set(source["config"]) and len(model) == 27
    assert set(config["reduced"]) == set(published) == REDUCED
    assert dict(model, **published) == source["config"]
    # the driver's check reads the keys at the file's top level, the
    # harness reads them under ``model``: the two are one statement
    assert {k: config[k] for k in source["config"]} == model
    for key in REDUCED:
        assert model[key] != source["config"][key]
    assert all(v is not None for v in model.values())
    assert "head_dim" not in model            # the catalog's row has none
    # no width moved: only depth, the experts held and the vocabulary
    widths = [k for k in model if k.endswith(("_dim", "_rank", "_size"))
              and k != "vocab_size"] + ["num_experts_per_tok",
                                        "num_attention_heads"]
    assert all(model[k] == source["config"][k] for k in widths)
    for said in ("assumed", "stands_for", "reckoning"):
        assert config[said]
    assert "TO BE SET" not in json.dumps(config)


def test_the_share_is_rank_0_of_16_at_the_floors(source):
    config = cell.load_json("configs", CONFIG)
    model, published = config["model"], config["published"]
    cfg = family.program_config(model)
    assert published["n_routed_experts"] == family.RANKS * 16
    assert cfg.n_routed_experts == 256 and cfg.experts_held == (0, 16)
    assert cfg.top_k == 8 and cfg.n_dense_layers == 1
    assert cfg.n_expert_layers == 4                   # the floor
    assert cfg.experts_held[1] >= 8                   # the floor
    assert model["vocab_size"] * 8 == published["vocab_size"]
    assert family.param_count(cfg) == config["parameters"]
    # 1,152 B a token a layer are what count; the pool stores 640 lanes
    assert family.kv_bytes_per_token(cfg) == 5 * 1152
    toy = family.program_config(family.toy(model))
    assert toy.n_routed_experts == family.RANKS * toy.experts_held[1]


def test_the_cell_is_the_issues_traffic():
    c = cell.load_json("workloads", CELL)
    mix = cell.load_json("traffic", c["traffic"])
    assert c["chips"] == 1 and len(c["why"]) <= 200
    assert c["engine"] == {"max_seq": 12288, "max_batch": 128,
                           "num_pages": 40961, "prefill_chunk": 1024,
                           "prefill_bucket": 0}
    assert mix["prompt_tokens"] == {"distribution": "lognormal",
                                    "median": 2048, "sigma": 0.7,
                                    "lo": 256, "hi": 8192}
    assert mix["output_tokens"] == {"distribution": "lognormal",
                                    "median": 1536, "sigma": 0.6,
                                    "lo": 256, "hi": 4096}
    assert (mix["grid"], mix["stratum_block"], mix["warm_seconds"],
            mix["trace_seconds"]) == (32, 16, 20.0, 4.0)
    assert mix["prompt_tokens"]["hi"] + mix["output_tokens"]["hi"] \
        <= c["engine"]["max_seq"]
    assert set(c["end_to_end"]) == {"serve_tokens_per_s", "setup_s"}
    for m in ("mla_decode_roofline.sat", "moe_grouped_roofline.sat",
              "expert_load_max_over_mean.sat", "expert_held_share.sat",
              "mla_expand_share_of_device.sat"):
        assert NEW + m in c["per_layer"]
        assert cell.metric(NEW + m)["moves"] == "serve_tokens_per_s"


def test_the_manifest_keeps_what_it_had_and_appends():
    """``manifest.build()`` sorts metrics by file name and the driver
    reads an entry put mid-list as a change to what was there: this PR's
    metric files are named to sort behind every metric the manifest had,
    so that the written file is both what the files say and the parent's
    with entries appended."""
    built = manifest.build()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        assert json.load(f) == built
    names = [m["name"] for m in built["per_layer"]]
    # later PRs appended theirs behind: this PR's stay in one run, behind
    # every name without a version's prefix
    mine = [i for i, n in enumerate(names) if n.startswith(NEW)]
    assert mine == list(range(mine[0], mine[-1] + 1))
    assert all(n[0] == "v" and n[1:3].isdigit() for n in names[mine[0]:])
    assert CONFIG in [c["name"] for c in built["configs"]]
    assert CELL in [w["name"] for w in built["workloads"]]


@pytest.mark.parametrize("rid,plen,total,want", [
    (3, 100, 300, (100, 300)),        # an answer the check reads whole
    (4, 100, 1000, (100, 356)),       # even id: its first 256
    (5, 100, 1000, (744, 1000)),      # odd id: its last 256
])
def test_a_long_answer_is_checked_through_a_window(rid, plen, total, want):
    seq = list(range(total))
    got_plen, got = runner._window_of(plen, seq, rid)
    assert (got_plen, len(got)) == want
    assert got == seq[:len(got)]                # always a served prefix
    assert len(got) - got_plen <= serve.CHECK_TAIL


def test_the_runner_hands_the_check_windows_and_keeps_the_counters(
        monkeypatch):
    """Without an engine: a stand-in for ``run_serving`` calls what the
    real one calls, by the module's names.  The check sees a window, the
    counters' difference over the window (not the warm-up's) lands in
    the window's facts, and the hooks put back what they took."""
    from deepspeed_tpu.telemetry import MetricsRegistry

    reg = MetricsRegistry()
    monkeypatch.setattr(serve, "build_engine", lambda run: (
        types.SimpleNamespace(registry=reg), None, None))
    monkeypatch.setattr(serve, "drive", lambda *a, **k: 0.0)
    monkeypatch.setattr(
        serve, "check_tokens", lambda run, p, c, led, outs, done: (
            led.requests[5].prompt_len, len(outs[5])))
    stand_ins = (serve.build_engine, serve.drive, serve.check_tokens)

    def run_serving(run, backlog):
        serve.build_engine(run)
        serve.drive(None, None, None, run, None, 0.0)            # warm-up
        reg.counter("serving_expert_rows_0").inc(5)
        reg.counter("serving_routed_rows").inc(80)
        serve.drive(None, None, None, run, None, 0.0, tracer=object())
        reg.counter("serving_expert_rows_0").inc(7)
        reg.counter("serving_expert_rows_1").inc(9)
        reg.counter("serving_routed_rows").inc(256)
        led = types.SimpleNamespace(requests={
            5: types.SimpleNamespace(prompt_len=10)})
        return {"window": {"token_check": serve.check_tokens(
            run, None, None, led, {5: list(range(1000))}, [5])}}

    monkeypatch.setattr(serve, "run_serving", run_serving)
    out = runner.run(types.SimpleNamespace(family=None, seed=1))
    assert out["window"]["token_check"] == (744, 1000)
    assert out["window"]["expert_rows"] == [7.0, 9.0]
    assert out["window"]["routed_rows"] == 256.0
    assert (serve.build_engine, serve.drive, serve.check_tokens) == stand_ins


def test_expert_rows_reader():
    run = types.SimpleNamespace(window={"expert_rows": [4.0, 8.0, 4.0, 0.0],
                                        "routed_rows": 256.0})
    assert expert_rows.read(run, "held_share") == pytest.approx(6.25)
    assert expert_rows.read(run, "max_over_mean") == pytest.approx(2.0)
    nothing = types.SimpleNamespace(window={})
    assert expert_rows.read(nothing, "held_share") is None


def test_roofline_arithmetic_of_the_new_kernels():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # one cached token a layer: 278 kFLOP over 1,152 B, at the ridge
    assert mla.flops(128, 576, 512, 1) == 2 * 128 * 1088
    assert mla.bytes_moved(128, 576, 512, 1, 0) == 1152
    ridge = peaks["bf16_flops_per_s"] / peaks["hbm_bytes_per_s"]
    assert abs(mla.flops(128, 576, 512, 1) / 1152 - ridge) < 0.02 * ridge
    live = 128 * 4096
    assert mla.floor_seconds(128, 576, 512, live, 128, peaks) \
        == pytest.approx(max(278528 * live / 197e12,
                             (1152 * live + 128 * 128 * 1088 * 2) / 819e9))
    # a decode step of 128 rows routes 1,024 pairs: under an even router
    # 64 land here and every held expert is read; a router that sends
    # nothing to half of the held experts halves the bytes of the floor
    even = [1 / 256] * 16
    assert moe.routed_rows(1024, even) == pytest.approx(64)
    assert 15.6 < moe.experts_touched(1024, even) < 16
    assert moe.floor_seconds(7680, 2048, 1024, even, peaks) \
        == pytest.approx(moe.bytes_moved(
            7680, 2048, moe.experts_touched(1024, even)) / 819e9)
    skewed = [2 / 256] * 8 + [0.0] * 8
    assert moe.routed_rows(1024, skewed) == pytest.approx(64)
    assert 7.9 < moe.experts_touched(1024, skewed) <= 8
    # a chunk's 8,192 pairs: 512 rows here, bound by the weights still
    assert moe.floor_seconds(7680, 2048, 8192, even, peaks) \
        == pytest.approx(moe.bytes_moved(7680, 2048, 16) / 819e9, rel=1e-6)


def _rehearse(plant=""):
    """The cell's rehearsal in a process of its own; ``plant`` is code
    run before the benchmark's entry point."""
    code = plant + (
        "import sys\nfrom benchmark import run\n"
        f"sys.exit(run.main(['--workload', '{CELL}', '--seed', "
        f"'{2 ** 31 + 33}', '--trace', '1', '--rehearse']))\n")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT),
        capture_output=True, text=True, timeout=900)
    lines = [json.loads(l) for l in out.stdout.splitlines()
             if l.startswith("{")]
    return out, lines


# sigmoid_topk_route without its float32: scores, and so their order, in
# the 8 bits bfloat16 keeps
BF16_ROUTER = """
import jax, jax.numpy as jnp
from deepspeed_tpu.models import pangu_ultra_moe as program

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


def test_a_bfloat16_router_is_not_correct():
    """The control the configuration's ``check_why`` names, planted
    through the harness: the program's router fed bfloat16 roundings of
    its inputs.  The run comes out failed, by the router probe."""
    out, lines = _rehearse(BF16_ROUTER)
    assert out.returncode == 1, out.stdout[-2000:] + out.stderr[-2000:]
    assert lines[-1]["rehearsal"] == "failed"
    problems = next(l["problems"] for l in lines if "problems" in l)
    assert any("router" in p and "held experts" in p for p in problems)
    probe = next(l["note_check"] for l in lines
                 if "note_check" in l)["router_probe"]
    assert probe["differ"] > probe["limit"]


def test_the_cell_rehearses_on_the_cpu():
    out, lines = _rehearse()
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    last = lines[-1]
    assert last["rehearsal"] == "passed"
    assert last["metrics"][NEW + "expert_held_share.sat"]["value"] > 0
    assert "correct" not in last
    probe = next(l["note_check"] for l in lines
                 if "note_check" in l)["router_probe"]
    assert probe["differ"] == 0 and probe["by"] == [4, 32]

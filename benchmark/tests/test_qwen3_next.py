"""The Qwen3-Next configuration, its cell and what reads them: the file
against the source's keys, the share's arithmetic, the manifest, the new
readers' arithmetic, and the cell's rehearsal with its planted faults."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import manifest
from benchmark.families import qwen3_next as family
from benchmark.harness import cell
from benchmark.readers import decode_step_roofline_state
from benchmark.roofline import gdn

ROOT = manifest.ROOT
CONFIG = "qwen3-next-80b-a3b-ep8-d12"
CELL = CONFIG + ".serve.docqa-sat"
NEW = "v35."       # this PR's metric files sort behind the manifest's
REDUCED = {"num_hidden_layers", "num_experts", "vocab_size"}


@pytest.fixture(scope="module")
def source():
    """The catalog's row for the model (``architectures.jsonl`` beside
    the model-configs guide), copied here as data."""
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "qwen3-next-80b-a3b.catalog.json")) as f:
        return json.load(f)


def test_the_file_holds_the_sources_keys_and_values(source):
    config = cell.load_json("configs", CONFIG)
    model, published = config["model"], config["published"]
    assert config["source"] == source["source_url"]
    assert set(model) == set(source["config"]) and len(model) == 29
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert set(published) == REDUCED
    assert dict(model, **published) == source["config"]
    # the driver's check reads the keys at the file's top level, the
    # harness reads them under ``model``: the two are one statement
    assert {k: config[k] for k in source["config"]} == model
    for key in REDUCED:
        assert model[key] != source["config"][key]
    assert model["head_dim"] == 256 and model["rope_scaling"] is None
    assert model["mlp_only_layers"] == []
    # no width moved: only depth, the experts held and the vocabulary
    widths = [k for k in model if k.endswith(("_dim", "_rank", "_size"))
              and k != "vocab_size"] + [
        "num_experts_per_tok", "num_attention_heads", "num_key_value_heads",
        "linear_num_key_heads", "linear_num_value_heads",
        "partial_rotary_factor", "full_attention_interval"]
    assert all(model[k] == source["config"][k] for k in widths)
    for said in ("assumed", "stands_for", "reckoning"):
        assert config[said]
    assert "TO BE SET" not in json.dumps(config)


def test_the_share_is_rank_0_of_8_at_the_floors():
    config = cell.load_json("configs", CONFIG)
    model, published = config["model"], config["published"]
    cfg = family.program_config(model)
    assert published["num_experts"] == family.RANKS * 64
    assert cfg.n_routed_experts == 512 and cfg.experts_held == (0, 64)
    assert cfg.top_k == 10 and cfg.n_layers == 12
    assert (cfg.n_lin_layers, cfg.n_full_layers) == (9, 3)   # whole periods
    assert cfg.experts_held[1] >= 8                          # the floor
    assert model["vocab_size"] * 8 == published["vocab_size"]
    assert family.param_count(cfg) == config["parameters"]
    from deepspeed_tpu.models import qwen3_next as program
    assert program.param_count(cfg) == config["parameters"]
    # K/V a token: 3 layers x 2 heads x 256 x (K and V) x 2 B = 6 KiB;
    # a slot's state 9 x (2 MiB + 48 KiB): as much as 3.1 k tokens
    assert family.kv_bytes_per_token(cfg) == 6 * 1024
    assert family.state_bytes_per_slot(cfg) == 9 * (2 * 2 ** 20
                                                    + 48 * 1024)
    assert family.state_bytes_per_slot(cfg) == 9 * gdn.state_bytes(cfg)
    toy = family.program_config(family.toy(model))
    assert toy.n_routed_experts == family.RANKS * toy.experts_held[1]


def test_the_cell_is_the_issues_traffic():
    c = cell.load_json("workloads", CELL)
    mix = cell.load_json("traffic", c["traffic"])
    assert c["chips"] == 1 and len(c["why"]) <= 200
    assert mix["kind"] == "serve_backlog_long"
    assert c["engine"]["max_seq"] == 17408
    assert c["engine"]["prefill_chunk"] == 1024
    assert c["engine"]["prefill_bucket"] == 0
    assert mix["prompt_tokens"] == {"distribution": "lognormal",
                                    "median": 6144, "sigma": 0.7,
                                    "lo": 1024, "hi": 16384}
    assert mix["output_tokens"] == {"distribution": "lognormal",
                                    "median": 384, "sigma": 0.6,
                                    "lo": 64, "hi": 1024}
    assert (mix["grid"], mix["warm_seconds"]) == (32, 20.0)
    assert mix["prompt_tokens"]["hi"] + mix["output_tokens"]["hi"] \
        <= c["engine"]["max_seq"]
    assert set(c["end_to_end"]) == {"serve_tokens_per_s", "setup_s"}
    assert "decode_step_roofline.sat" not in c["per_layer"]
    for m in ("gdn_share_of_device.sat", "gdn_prefill_roofline.sat",
              "gdn_step_roofline.sat", "decode_step_roofline.sat"):
        assert NEW + m in c["per_layer"]
        assert cell.metric(NEW + m)["moves"] == "serve_tokens_per_s"


def test_the_manifest_is_the_files_and_the_parents_with_entries_appended():
    """``BENCHMARK.json`` is ``manifest.py --write``'s output, and
    against the parent's (``git show HEAD:BENCHMARK.json``, where the
    tree is a git checkout whose HEAD has not this cell yet) nothing
    that was there is edited, moved or removed: configurations, cells
    and metrics are appended, and a metric's ``workloads`` grows at its
    end alone."""
    built = manifest.build()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        assert json.load(f) == built
    assert CONFIG in [c["name"] for c in built["configs"]]
    assert CELL in [w["name"] for w in built["workloads"]]
    names = [m["name"] for m in built["per_layer"]]
    assert len([n for n in names if n.startswith(NEW)]) == 4
    show = subprocess.run(["git", "show", "HEAD:BENCHMARK.json"], cwd=ROOT,
                          capture_output=True, text=True)
    if show.returncode:
        pytest.skip("not a git checkout")
    parent = json.loads(show.stdout)
    if any(w["name"] == CELL for w in parent["workloads"]):
        parent["workloads"] = [w for w in parent["workloads"]
                               if w["name"] != CELL]
        pytest.skip("HEAD has the cell already")
    for key in ("command", "paths", "run_seconds"):
        assert built[key] == parent[key]
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        was, now = parent[key], built[key][:len(parent[key])]
        for a, b in zip(was, now):
            rest = lambda m: {k: v for k, v in m.items() if k != "workloads"}
            assert rest(a) == rest(b)
            assert ("workloads" in a) == ("workloads" in b)
            if "workloads" in a:
                assert b["workloads"][:len(a["workloads"])] == a["workloads"]
                assert set(b["workloads"][len(a["workloads"]):]) <= {CELL}


def test_roofline_arithmetic_of_the_linear_layers():
    cfg = family.program_config(cell.load_json("configs", CONFIG)["model"])
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert gdn.projection_params(cfg) == 2048 * (12288 + 64) + 4096 * 2048
    assert gdn.rule_flops(cfg, 1) == 6 * 32 * 128 * 128
    # a chunk of 1,024 tokens: 71 GFLOP a layer, 0.36 ms at the peak
    assert gdn.prefill_floor_seconds(cfg, 1024, peaks) \
        == pytest.approx(gdn.flops(cfg, 1024) / 197e12)
    assert 0.3e-3 < gdn.prefill_floor_seconds(cfg, 1024, peaks) < 0.4e-3
    # a decode step of 96 live slots: 2 x 96 x 2.05 MiB of state and
    # 67 MB of weights a layer, bound by the memory
    assert gdn.state_bytes(cfg) == 2 * 2 ** 20 + 3 * 8192 * 2
    assert gdn.step_floor_seconds(cfg, 96, peaks) == pytest.approx(
        (2 * 96 * gdn.state_bytes(cfg) + gdn.weight_bytes(cfg)) / 819e9)
    assert gdn.step_floor_seconds(cfg, 0, peaks) == pytest.approx(
        gdn.weight_bytes(cfg) / 819e9)


def test_the_state_counts_in_a_decode_steps_floor():
    cfg = family.program_config(cell.load_json("configs", CONFIG)["model"])
    steps = [(0.0, 0.020, 0, 0, 0.5, 0.25, 0)] * 3
    window = {"kind": "serve", "t_open": -1.0, "t_end": 9.0,
              "first_step": 0, "pool_pages": 65536, "page_size": 16,
              "program_config": cfg,
              "ledger": types.SimpleNamespace(steps=steps)}
    run = types.SimpleNamespace(
        window=window, family=family,
        peaks={"hbm_bytes_per_s": 819e9},
        config={"serving": {"engine": {"page_size": 16}}},
        cell={"engine": {"max_batch": 96}})
    least = (family.weight_bytes(cfg) + 0.25 * 65536 * 16 * 6144
             + 2 * 48 * family.state_bytes_per_slot(cfg)) / 819e9
    assert decode_step_roofline_state.read(run) \
        == pytest.approx(100 * least / 0.020)
    run.family = types.SimpleNamespace()        # a family with no state
    assert decode_step_roofline_state.read(run) is None


def _rehearse(plant="", trace=0):
    """The cell's rehearsal in a process of its own; ``plant`` is code
    run before the benchmark's entry point.  One test alone runs it
    traced: two traced runs of a cell at once share its trace
    directory."""
    code = plant + (
        "import sys\nfrom benchmark import run\n"
        f"sys.exit(run.main(['--workload', '{CELL}', '--seed', "
        f"'{2 ** 31 + 35}', '--trace', '{trace}', '--rehearse']))\n")
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


def _state_failed(state):
    return not (state["error_mean"] <= state["limit"]        # or not a number
                and state["error_worst_head"] <= state["limit_worst_head"])


@pytest.mark.parametrize("plant", [PADDING_MOVES_THE_STATE, UNMASKED_STEPS],
                         ids=["padding", "unmasked"])
def test_a_planted_fault_is_not_correct(plant):
    """A padded chunk row allowed to move the state; decode steps of
    other slots run over a slot between its prompt's chunks without the
    mask.  Each run comes out failed, by the state probe (which drives
    the serving programs the fault was planted in); the token check sees
    the first, and may or may not see the second (a handful of tokens
    added to a prompt of thousands)."""
    out, lines = _rehearse(plant)
    assert out.returncode == 1, out.stdout[-2000:] + out.stderr[-2000:]
    assert lines[-1]["rehearsal"] == "failed"
    check = next(l["note_check"] for l in lines if "note_check" in l)
    probe = check["router_probe"]
    state = probe["state"]
    assert state["failed"] and state["state_dtype"] == "float32"
    assert _state_failed(state)
    assert probe["router_differ"] == 0 and probe["differ"] > probe["limit"]
    if plant is PADDING_MOVES_THE_STATE:
        assert check["near"] < check["near_share_asked"] * check["tokens"]


def test_a_bfloat16_state_reaches_the_probe_and_reads_higher():
    """State kept in bfloat16.  The probe's limits are set at the
    cell's widths on the chip (the configuration's ``check_why``: 32
    value heads of 128 x 128 over 3,120 tokens); at the rehearsal's toy
    widths (4 heads of 32 x 32, 129 tokens) the readings move more with
    the seed (eight seeds: float32 0.0034-0.0041, bfloat16 0.0048-0.0063
    against the limit 0.0044), so here the plant is seen to reach the
    probe's cache and to read higher than the same run without it, and
    the run to fail exactly where the reading is over a limit."""
    _, clean = _rehearse()
    out, lines = _rehearse(BF16_STATE)
    state, was = (next(l["note_check"] for l in ls if "note_check" in l)[
        "router_probe"]["state"] for ls in (lines, clean))
    assert (state["state_dtype"], was["state_dtype"]) \
        == ("bfloat16", "float32")
    assert state["error_mean"] > was["error_mean"]
    assert out.returncode == int(_state_failed(state))


def test_the_cell_rehearses_on_the_cpu():
    out, lines = _rehearse(trace=1)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    last = lines[-1]
    assert last["rehearsal"] == "passed"
    assert last["metrics"]["v33.expert_held_share.sat"]["value"] > 0
    assert "correct" not in last
    probe = next(l["note_check"] for l in lines
                 if "note_check" in l)["router_probe"]
    assert probe["differ"] == 0 and probe["by"] == [4, 32]
    assert not _state_failed(probe["state"])

"""The Nemotron-3-Nano configuration, its cell and what reads them: the
file against the source's keys, the share's arithmetic, the manifest, the
new roofline and reader, and the cell's rehearsal with planted faults."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import manifest
from benchmark.families import nemotron_h as family
from benchmark.harness import cell, scopes
from benchmark.readers import moe_ungated_roofline
from benchmark.roofline import moe, moe_ungated, ssm

ROOT = manifest.ROOT
CONFIG = "v48.nemotron-3-nano-30b-a3b-ep8"
CELL = CONFIG + ".serve.code-sat"
NEW = "v48."       # this PR's metric files sort behind the manifest's
DATA = os.path.join(os.path.dirname(__file__), "data")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
PUBLISHED = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@pytest.fixture(scope="module")
def source():
    """The catalog row's ``config`` (``architectures.jsonl`` beside the
    model-configs guide), copied here as data."""
    with open(os.path.join(DATA, "nemotron-3-nano-30b-a3b.catalog.json")) as f:
        return json.load(f)


def _cfg():
    return family.program_config(cell.load_json("configs", CONFIG)["model"])


def test_the_file_holds_the_sources_keys_twice_and_cuts_two(source):
    config = cell.load_json("configs", CONFIG)
    model = config["model"]
    assert len(source) == 46 and set(model) == set(source)
    # at the top level, where the driver's check against the catalog row
    # reads them, and under ``model``, where the harness does: one
    # statement
    assert {k: config[k] for k in source} == model
    differs = sorted(k for k in source if model[k] != source[k])
    assert differs == sorted(config["reduced"]) \
        == ["n_routed_experts", "vocab_size"]
    assert config["published"] == {k: source[k] for k in config["reduced"]}
    assert (model["n_routed_experts"], model["vocab_size"]) == (16, 16384)
    assert model["num_hidden_layers"] == 52 == len(PUBLISHED)
    assert model["hybrid_override_pattern"] == PUBLISHED
    assert config["source"].endswith(
        "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json")
    assert config["family"] == "nemotron_h"
    for said in ("assumed", "stands_for", "reckoning"):
        assert config[said]
    for ground in ("no_positions", "selection_bias", "two_matrices",
                   "gate_before_norm", "chunk_size", "rank", "stored_width"):
        assert config["assumed"][ground]
    assert len(config["why"]) <= 200
    assert "PENDING" not in json.dumps(config)
    # 23 routed layers and a state that carries a parted choice forward:
    # the share of tokens that must be near is under the siblings' 0.98
    # (the file's check_why has the readings on both sides)
    assert 0.7 <= config["serving"]["check_near_share"] < 0.98


def test_the_program_is_the_share_at_the_whole_depth():
    config = cell.load_json("configs", CONFIG)
    cfg = _cfg()
    assert cfg.sections == (("MEMEM*E", 5), ("ME", 1), ("MEMEM*E", 1),
                            ("ME", 4)) and cfg.pattern == PUBLISHED
    assert (cfg.n_layers, cfg.n_ssm_layers, cfg.n_attn_layers,
            cfg.n_expert_layers) == (52, 23, 6, 23)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (32, 2, 128)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
            cfg.ssm_groups) == (64, 64, 128, 8)
    assert cfg.ssm_inner == 4096 and cfg.conv_channels == 6144
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.top_k) \
        == (128, (0, 16), 6)
    assert (cfg.moe_ffn_dim, cfg.moe_ffn_stored, cfg.shared_ffn_dim) \
        == (1856, 1920, 3712)
    assert cfg.ssm_block == 128 and cfg.vocab_size == 16384
    # the issue's arithmetic: a Mamba layer 38,744,896, an attention
    # layer 23,399,040, an expert 9,977,856, an expert layer here
    # 179,948,288, the share 5,258,420,544
    mixer, attn, expert, shared, router = family._counts(cfg)
    assert (mixer, attn, expert) == (38_744_896, 23_399_040, 9_977_856)
    assert 16 * expert + shared + router + cfg.dim == 179_948_288
    assert family.param_count(cfg) == config["parameters"] == 5_258_420_544
    from deepspeed_tpu.models import nemotron_h as program
    assert program.param_count(cfg) == config["parameters"]
    # the whole model, 128 experts a layer and the whole vocabulary:
    # 31.58 B, of which a token meets 3.2 B
    whole = family.program_config(dict(
        cell.load_json("configs", CONFIG)["model"], vocab_size=131072))
    import dataclasses
    whole = dataclasses.replace(whole, experts_held=(0, 128))
    assert round(family.param_count(whole) / 1e9, 2) == 31.58
    assert round(family.routed_param_count(whole) / 1e9, 1) == 3.2
    # K/V a token: 6 layers x 2 heads x 128 x (K and V) x 2 B = 6 KiB; a
    # slot's state 23 x (2 MiB + 36 KiB) = 46.8 MiB
    assert family.kv_bytes_per_token(cfg) == 6 * 1024
    assert ssm.state_bytes(cfg) == 2 * 2 ** 20 + 3 * 6144 * 2
    assert round(family.state_bytes_per_slot(cfg) / 2 ** 20, 1) == 46.8
    model = cell.load_json("configs", CONFIG)["model"]
    with pytest.raises(SystemExit, match="mlp_hidden_act"):
        family.program_config(dict(model, mlp_hidden_act="silu"))
    toy = family.program_config(family.toy(model))
    assert toy.sections == (("MEM*E", 2), ("ME", 1), ("MEM*E", 1), ("ME", 2))
    assert toy.moe_ffn_stored == 128 and toy.ssm_groups == 2


def test_the_cell_is_the_issues_traffic():
    c = cell.load_json("workloads", CELL)
    mix = cell.load_json("traffic", c["traffic"])
    assert c["chips"] == 1 and len(c["why"]) <= 200
    assert mix["kind"] == "serve_backlog_long" and "none" in mix["sharing"]
    engine = dict(c["engine"])
    pages = engine.pop("num_pages")
    assert engine == {"max_seq": 18432, "max_batch": 64,
                      "prefill_chunk": 1024, "prefill_bucket": 0}
    assert pages % 2 == 1 and 16_000 < pages <= 20_481
    assert mix["prompt_tokens"]["hi"] + mix["output_tokens"]["hi"] \
        <= c["engine"]["max_seq"]
    assert set(c["end_to_end"]) == {"serve_tokens_per_s", "setup_s"}
    # only metrics the manifest had, or this PR's
    parent = {"v33.expert_held_share.sat",
              "v33.expert_load_max_over_mean.sat",
              "v35.decode_step_roofline.sat", "v42.ssm_share_of_device.sat",
              "v42.ssm_prefill_roofline.sat", "v42.ssm_step_roofline.sat"}
    assert parent <= set(c["per_layer"])
    assert [m for m in c["per_layer"] if m.startswith(NEW)] == [
        NEW + "moe_share_of_device.sat",
        NEW + "moe_shared_share_of_device.sat",
        NEW + "moe_ungated_roofline.sat"]
    assert "v33.moe_grouped_roofline.sat" not in c["per_layer"]
    assert "moe_ffn_share_of_device.sat" not in c["per_layer"]
    for name, word in ((NEW + "moe_share_of_device.sat", "moe_ffn"),
                       (NEW + "moe_shared_share_of_device.sat",
                        "moe_shared")):
        file = cell.metric(name)
        assert (file["reader"], file["args"], file["moves"]) == (
            "word_share_of_device", {"word": word}, "serve_tokens_per_s")


def test_the_manifest_is_the_files_and_the_parents_with_entries_appended():
    assert manifest.main(["--check"]) == 0
    built = manifest.build()
    assert [w["chips"] for w in built["workloads"]
            if w["name"] == CELL] == [1]
    assert sum(w["chips"] == 4 for w in built["workloads"]) == 1
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


def test_roofline_arithmetic_of_a_two_matrix_expert():
    d, f = 2688, 1856
    assert moe_ungated.flops(d, f, 1) == 4 * d * f
    assert moe_ungated.bytes_moved(d, f, 1) == 2 * d * f * 2 == 19_955_712
    # two thirds of the gated count: the accepted reader would read this
    # cell's routed part half as much again as it is
    assert moe.flops(d, f, 7) * 2 == moe_ungated.flops(d, f, 7) * 3
    shares = [1 / 128] * 16
    # a decode step of 64 slots routes 384 pairs: 48 to the 16 held
    # experts, 3 each; every held expert but one in twenty gets a row,
    # and its 20 MB set the floor (0.37 ms a layer)
    assert moe.routed_rows(384, shares) == pytest.approx(48)
    assert 15.1 < moe.experts_touched(384, shares) < 15.3
    step = moe_ungated.floor_seconds(d, f, 384, shares, PEAKS)
    assert step == pytest.approx(
        moe_ungated.bytes_moved(d, f, moe.experts_touched(384, shares))
        / 819e9)
    # a chunk of 1,024 rows routes 6,144 pairs, 768 held: still the
    # weights' bytes (0.39 ms), the operations a fifth of them
    chunk = moe_ungated.floor_seconds(d, f, 6144, shares, PEAKS)
    assert chunk == pytest.approx(16 * 19_955_712 / 819e9)
    assert moe_ungated.flops(d, f, 768) / 197e12 < 0.21 * chunk


def test_the_reader_reads_nothing_where_nothing_was_counted(monkeypatch):
    """A run that was not traced; a traced one whose programs counted no
    rows; and a family whose experts are gated: None, no exception."""
    run = types.SimpleNamespace(
        traced=None, trace_dir="/nonexistent", peaks=PEAKS,
        window={"kind": "serve", "program_config": _cfg()},
        config={"serving": {"engine": {"page_size": 16}}},
        cell={"engine": {"max_batch": 64, "prefill_chunk": 1024}})
    assert moe_ungated_roofline.read(run) is None
    with open(os.path.join(DATA, "v5e_scoped.xplane.txt")) as f:
        recorded = scopes.from_text_proto(f.read())
    monkeypatch.setattr(scopes, "of_run", lambda run: recorded)
    assert moe_ungated_roofline.read(run) is None
    run.window.update(expert_rows=[3.0] * 16, routed_rows=384.0,
                      program_config=types.SimpleNamespace())
    assert moe_ungated_roofline.read(run) is None


def _rehearse(plant="", trace=0):
    """The cell's rehearsal in a process of its own; ``plant`` is code
    run before the benchmark's entry point."""
    code = plant + (
        "import sys\nfrom benchmark import run\n"
        f"sys.exit(run.main(['--workload', '{CELL}', '--seed', "
        f"'{2 ** 31 + 48}', '--trace', '{trace}', '--rehearse']))\n")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT),
        capture_output=True, text=True, timeout=900)
    lines = [json.loads(l) for l in out.stdout.splitlines()
             if l.startswith("{")]
    return out, lines


# faults the configuration's ``check_why`` names, as code run before the
# benchmark's entry point
CHOICE_BY_THE_SCORE_ALONE = """
from deepspeed_tpu.models import nemotron_h
route = nemotron_h.sigmoid_topk_route
nemotron_h.sigmoid_topk_route = lambda *a, bias=None, **kw: route(*a, **kw)
"""
ONE_NORM_OVER_ALL_GROUPS = """
from deepspeed_tpu.models import granite_hybrid
norm = granite_hybrid._gated_norm
flat = lambda a: a.reshape(a.shape[:-2] + (-1,))
granite_hybrid._gated_norm = lambda o, z, w, eps: norm(
    flat(o), flat(z), flat(w), eps).reshape(o.shape)
"""
ONE_B_AND_C_FOR_ALL_HEADS = """
import jax.numpy as jnp
from deepspeed_tpu.models import granite_hybrid
step, scan = granite_hybrid.ssm_step, granite_hybrid.ssm_chunk_scan
first = lambda v, axis: jnp.broadcast_to(
    jnp.take(v, jnp.array([0]), axis=axis), v.shape)
granite_hybrid.ssm_step = lambda x, dt, A, Bm, Cm, S: step(
    x, dt, A, first(Bm, 1), first(Cm, 1), S)
granite_hybrid.ssm_chunk_scan = lambda x, dt, A, Bm, Cm, S, block: scan(
    x, dt, A, first(Bm, 2), first(Cm, 2), S, block)
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


def _check(lines):
    return next(l["note_check"] for l in lines if "note_check" in l)


@pytest.mark.parametrize("plant, seen_by", [
    (CHOICE_BY_THE_SCORE_ALONE, "router"),
    (ONE_NORM_OVER_ALL_GROUPS, "tokens"),
    (ONE_B_AND_C_FOR_ALL_HEADS, "state"),
    (UNMASKED_STEPS, "state"),
], ids=["no_bias", "one_norm", "one_group", "unmasked"])
def test_a_planted_fault_is_not_correct(plant, seen_by):
    """The selection bias dropped; the gated norm over all the channels
    at once; every head reading group 0's B and C; decode steps run over
    a slot between its prompt's chunks without the mask.  Each run comes
    out failed, by the limit named."""
    out, lines = _rehearse(plant)
    assert out.returncode == 1, out.stdout[-2000:] + out.stderr[-2000:]
    assert lines[-1]["rehearsal"] == "failed"
    check = _check(lines)
    probe = check["router_probe"]
    if seen_by == "router":
        assert probe["router_differ"] > probe["limit"]
    elif seen_by == "state":
        assert probe["state"]["failed"] and family.state_failed(
            probe["state"])
    else:
        assert check["near"] < check["near_share_asked"] * check["tokens"]


def test_the_cell_rehearses_on_the_cpu():
    out, lines = _rehearse(trace=1)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    last = lines[-1]
    assert last["rehearsal"] == "passed"
    assert last["metrics"]["v37.build_lower_s"]["value"] > 0
    check = _check(lines)
    assert check["near"] == check["tokens"] > 0
    probe = check["router_probe"]
    assert probe["differ"] == 0 and probe["by"] == [4, 32]
    assert probe["routed_here"] > 0
    assert not family.state_failed(probe["state"])
    assert probe["state"]["first"]["error_mean"] < 1e-4
    assert probe["state"]["tokens"] == 3 * 32 + 2 + 32


def test_a_program_without_the_family_fails_the_cell_cleanly():
    """What the parent gives: no module, a message, exit 1, at once."""
    plant = ("import sys\n"
             "sys.modules['deepspeed_tpu.models.nemotron_h'] = None\n")
    out, _ = _rehearse(plant)
    assert out.returncode == 1
    assert "no family nemotron_h" in out.stderr + out.stdout

"""The Ling-3.0-flash-VL configuration, its cell and what reads them: the
file against the source's keys, the share's arithmetic against the closed
forms, the manifest, the new reader's arithmetic, and the cell's
rehearsal with its planted faults."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import manifest
from benchmark.families import ling_flash as family
from benchmark.harness import cell
from benchmark.readers import decode_step_roofline_state
from benchmark.roofline import kda

ROOT = manifest.ROOT
CONFIG = "v51.ling-3.0-flash-vl-ep8-d12"
CELL = CONFIG + ".serve.docqa-sat"
NEW = "v51."       # this PR's files sort behind the manifest's
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "num_experts",
           "vocab_size"]


@pytest.fixture(scope="module")
def source():
    """The catalog's row for the model (``architectures.jsonl`` beside
    the model-configs guide), copied here as data."""
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "ling-3.0-flash-vl.catalog.json")) as f:
        return json.load(f)


def _cfg():
    return family.program_config(cell.load_json("configs", CONFIG)["model"])


def test_the_file_holds_the_sources_keys_and_values(source):
    config = cell.load_json("configs", CONFIG)
    model, published = config["model"], config["published"]
    assert config["source"] == source["source_url"]
    assert set(model) == set(source["config"]) and len(model) == 51
    assert config["reduced"] == REDUCED and list(published) == REDUCED
    assert dict(model, **published) == source["config"]
    # the driver's check reads the keys at the file's top level, the
    # harness reads them under ``model``: the two are one statement
    assert {k: config[k] for k in source["config"]} == model
    for key in REDUCED:
        assert model[key] != source["config"][key]
    # the per-layer lists stand at their published 42 entries, and no
    # clamp is set on a layer that is kept
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        assert len(model[key]) == 42
        assert not any(model[key][:model["num_hidden_layers"]])
    assert model["q_lora_rank"] is None and model["kda_lower_bound"] == -5
    # no width moved: only depth, the dense lead, the experts held and
    # the vocabulary
    widths = [k for k in model if k.endswith(("_dim", "_rank", "_size"))
              and k != "vocab_size"] + [
        "num_experts_per_tok", "num_attention_heads", "num_key_value_heads",
        "n_group", "topk_group", "partial_rotary_factor", "rotary_dim"]
    assert len(widths) > 12
    assert all(model[k] == source["config"][k] for k in widths)
    for said in ("assumed", "stands_for", "reckoning"):
        assert config[said]
    assert "TO BE" not in json.dumps(config)
    for reading in ("period", "kda_gate", "output_gate", "mla", "rope",
                    "router", "conv", "not_built"):
        assert reading in config["assumed"]


def test_the_share_is_rank_0_of_8_at_the_floors():
    config = cell.load_json("configs", CONFIG)
    model, published = config["model"], config["published"]
    cfg = family.program_config(model)
    assert published["num_experts"] == family.RANKS * 64
    assert cfg.n_routed_experts == 512 and cfg.experts_held == (0, 64)
    # as many groups as ranks: the held experts are one whole group
    assert cfg.n_routed_experts // cfg.n_group == cfg.experts_held[1]
    assert (cfg.top_k, cfg.n_group, cfg.topk_group) == (8, 8, 4)
    assert (cfg.n_layers, cfg.n_dense_layers) == (12, 1)
    assert (cfg.n_kda_layers, cfg.n_mla_layers) == (10, 2)   # whole periods
    assert cfg.n_layers - cfg.n_dense_layers >= 4            # the floors
    assert cfg.experts_held[1] >= 8
    assert model["vocab_size"] * 8 == published["vocab_size"]
    toy = family.program_config(family.toy(model))
    assert toy.n_routed_experts == family.RANKS * toy.experts_held[1]
    assert toy.n_routed_experts // toy.n_group == toy.experts_held[1]


def test_parameters_flops_and_bytes_are_the_closed_forms():
    """The issue's count: a KDA layer 52.5 M (three projections and W_o
    41.9, W_f 10.5, two head-wise vectors 0.16), an MLA layer 31.9 M,
    an expert 5,898,240, the shared expert 5.9 M, the router 1.3 M, the
    dense FFN 47.2 M, head and embedding 100.6 M: 4.97 B here; and the
    uncut model's 125 B at an average of 56 M a layer outside its
    experts."""
    from deepspeed_tpu.models import ling_flash as program

    config = cell.load_json("configs", CONFIG)
    cfg = _cfg()
    mixer, mla, mlp, expert, shared, router = family._counts(cfg)
    d, HD = 2560, 32 * 128
    assert kda.projection_params(cfg) == 4 * d * HD + d * HD + 2 * d * 32
    assert mixer == kda.projection_params(cfg) + 4 * 3 * HD + 32 + HD + 128
    assert 52.5e6 < mixer < 52.7e6
    assert mla == (d * 32 * 192 + d * 576 + 512 * 32 * 256 + d * 32
                   + HD * d + 512)
    assert 31.9e6 < mla < 32.0e6
    assert (mlp, expert, shared) == (3 * d * 6144, 5_898_240, 5_898_240)
    assert router == d * 512 + 512
    assert family.param_count(cfg) == program.param_count(cfg) \
        == config["parameters"] == 4_969_904_704
    assert family.weight_bytes(cfg) == 2 * config["parameters"]
    # the uncut model: 42 layers, 2 dense, 512 experts, the whole
    # vocabulary: the catalog's ~125 B, 5.5 B of them a token's
    whole = family.program_config(dict(config["model"],
                                       **config["published"]))
    whole = type(whole)(**dict(vars(whole), n_routed_experts=512,
                               experts_held=(0, 512)))
    assert 124e9 < family.param_count(whole) < 126.5e9
    assert 5.0e9 < family.routed_param_count(whole) + whole.vocab_size \
        * whole.dim < 5.6e9
    outside = (35 * mixer + 7 * mla + 40 * (shared + router)) / 42
    assert 55e6 < outside < 57e6            # "about 56 M a layer"
    # a token: one latent row a latent layer; a slot: ten KDA layers
    assert family.kv_bytes_per_token(cfg) == 2 * 576 * 2
    assert kda.state_bytes(cfg) == 2 * 2 ** 20 + 3 * 12288 * 2
    assert family.state_bytes_per_slot(cfg) == 10 * (2 * 2 ** 20
                                                     + 72 * 1024)
    # a token meets a held expert once on average: 8 x 64 / 512
    assert family.routed_param_count(cfg) == (
        10 * mixer + 2 * mla + mlp + 11 * (expert + shared + router)
        + 19648 * d)
    assert family.serve_flops_per_token(cfg, 1000) == (
        2 * family.routed_param_count(cfg) + 10 * 7 * 32 * 128 * 128
        + 2 * 2 * 32 * 320 * 1000)


def test_roofline_arithmetic_of_the_kda_layers():
    cfg = _cfg()
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert kda.rule_flops(cfg, 1) == 7 * 32 * 128 * 128
    # a chunk of 1,024 tokens: 112 GFLOP a layer, 0.57 ms at the peak,
    # over the 0.13 ms its weights, state and rows take to move
    assert kda.prefill_floor_seconds(cfg, 1024, peaks) \
        == pytest.approx(kda.flops(cfg, 1024) / 197e12)
    assert 0.55e-3 < kda.prefill_floor_seconds(cfg, 1024, peaks) < 0.6e-3
    assert kda.chunk_bytes(cfg, 1024) / 819e9 < 0.15e-3
    # a chunk of 16 tokens is bound by the memory
    assert kda.prefill_floor_seconds(cfg, 16, peaks) \
        == pytest.approx(kda.chunk_bytes(cfg, 16) / 819e9)
    # a decode step of 96 live slots: 2 x 96 x 2.07 MiB of state and
    # 105 MB of weights a layer, bound by the memory
    assert kda.step_floor_seconds(cfg, 96, peaks) == pytest.approx(
        (2 * 96 * kda.state_bytes(cfg) + kda.weight_bytes(cfg)) / 819e9)
    assert kda.step_floor_seconds(cfg, 0, peaks) == pytest.approx(
        kda.weight_bytes(cfg) / 819e9)


def test_the_state_and_the_latent_rows_count_in_a_decode_steps_floor():
    cfg = _cfg()
    steps = [(0.0, 0.030, 0, 0, 0.5, 0.25, 0)] * 3
    window = {"kind": "serve", "t_open": -1.0, "t_end": 9.0,
              "first_step": 0, "pool_pages": 65536, "page_size": 16,
              "program_config": cfg,
              "ledger": types.SimpleNamespace(steps=steps)}
    run = types.SimpleNamespace(
        window=window, family=family,
        peaks={"hbm_bytes_per_s": 819e9},
        config={"serving": {"engine": {"page_size": 16}}},
        cell={"engine": {"max_batch": 96}})
    least = (family.weight_bytes(cfg) + 0.25 * 65536 * 16 * 2304
             + 2 * 48 * family.state_bytes_per_slot(cfg)) / 819e9
    assert decode_step_roofline_state.read(run) \
        == pytest.approx(100 * least / 0.030)
    assert cell.metric("v35.decode_step_roofline.sat")["reader"] \
        == "decode_step_roofline_state"


def test_the_cell_is_the_issues_traffic():
    c = cell.load_json("workloads", CELL)
    mix = cell.load_json("traffic", c["traffic"])
    assert c["chips"] == 1 and len(c["why"]) <= 200
    assert c["traffic"] == "serve.docqa-sat"
    assert mix["kind"] == "serve_backlog_long"
    assert c["engine"] == {"max_seq": 17408, "max_batch": 96,
                           "num_pages": c["engine"]["num_pages"],
                           "prefill_chunk": 1024, "prefill_bucket": 0}
    assert mix["prompt_tokens"]["hi"] + mix["output_tokens"]["hi"] \
        <= c["engine"]["max_seq"]
    assert set(c["end_to_end"]) == {"serve_tokens_per_s", "setup_s"}
    for m in ("kda_share_of_device.sat", "kda_prefill_roofline.sat",
              "kda_step_roofline.sat"):
        assert NEW + m in c["per_layer"]
        assert cell.metric(NEW + m)["moves"] == "serve_tokens_per_s"
    # the accepted metrics whose layers run here, under the names and
    # through the readers they have: the decode step's floor is the
    # recurrent sibling's reading (one reader, one name), the build's
    # ledger moves ``setup_s``, the expert layer's words are the
    # sectioned share's
    for m in ("compile_cache_hits", "compiles_steady", "serve_mfu",
              "v33.expert_held_share.sat", "v33.moe_grouped_roofline.sat",
              "v33.mla_decode_roofline.sat",
              "v33.mla_expand_share_of_device.sat",
              "v35.decode_step_roofline.sat", "v37.build_cache_load_s",
              "v37.build_compile_s", "v37.build_lower_s",
              "v37.build_other_programs", "v37.build_other_s",
              "v37.build_trace_s", "v37.package_import_s",
              "v48.moe_share_of_device.sat",
              "v48.moe_shared_share_of_device.sat"):
        assert m in c["per_layer"]
    assert {cell.metric(m)["moves"] for m in c["per_layer"]
            if m.startswith("v37.")} == {"setup_s"}


def test_the_manifest_is_the_files_and_the_parents_with_entries_appended():
    """``BENCHMARK.json`` is ``manifest.py --write``'s output, and
    against the parent's (``git show HEAD:BENCHMARK.json``, where the
    tree is a git checkout whose HEAD has not this cell yet) nothing
    that was there is edited, moved or removed: one configuration, one
    cell and three metrics are appended, and a metric's ``workloads``
    grows at its end alone."""
    built = manifest.build()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        assert json.load(f) == built
    assert CONFIG in [c["name"] for c in built["configs"]]
    assert [w["chips"] for w in built["workloads"]
            if w["name"] == CELL] == [1]
    assert sum(w["chips"] == 4 for w in built["workloads"]) == 1
    names = [m["name"] for m in built["per_layer"]]
    assert len([n for n in names if n.startswith(NEW)]) == 3
    for always in ("compile_cache_hits", "compiles_steady"):
        assert "workloads" not in built["per_layer"][names.index(always)]
    show = subprocess.run(["git", "show", "HEAD:BENCHMARK.json"], cwd=ROOT,
                          capture_output=True, text=True)
    if show.returncode:
        pytest.skip("not a git checkout")
    parent = json.loads(show.stdout)
    if any(w["name"] == CELL for w in parent["workloads"]):
        pytest.skip("HEAD has the cell already")
    for key in ("command", "paths", "run_seconds"):
        assert built[key] == parent[key]
    assert len(built["workloads"]) == len(parent["workloads"]) + 1
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        was, now = parent[key], built[key][:len(parent[key])]
        for a, b in zip(was, now):
            rest = lambda m: {k: v for k, v in m.items() if k != "workloads"}
            assert rest(a) == rest(b)
            assert ("workloads" in a) == ("workloads" in b)
            if "workloads" in a:
                assert b["workloads"][:len(a["workloads"])] == a["workloads"]
                assert set(b["workloads"][len(a["workloads"]):]) <= {CELL}


def _rehearse(plant="", trace=0):
    """The cell's rehearsal in a process of its own; ``plant`` is code
    run before the benchmark's entry point.  One test alone runs it
    traced: two traced runs of a cell at once share its trace
    directory."""
    code = plant + (
        "import sys\nfrom benchmark import run\n"
        f"sys.exit(run.main(['--workload', '{CELL}', '--seed', "
        f"'{2 ** 31 + 51}', '--trace', '{trace}', '--rehearse']))\n")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT),
        capture_output=True, text=True, timeout=900)
    lines = [json.loads(l) for l in out.stdout.splitlines()
             if l.startswith("{")]
    return out, lines


# the faults the configuration's ``check_why`` names, as code run before
# the benchmark's entry point
BF16_STATE = """
import jax.numpy as jnp
from deepspeed_tpu.inference import kernels, serving
kernels.STATE_DTYPE = serving.STATE_DTYPE = jnp.bfloat16
"""
SCALAR_GATE = """
import jax.numpy as jnp
from deepspeed_tpu.models import ling_flash as program
chunk, step = program.kda_chunk, program.kda_step
flat = lambda g: jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
program.kda_chunk = lambda q, k, v, g, *rest: chunk(q, k, v, flat(g), *rest)
program.kda_step = lambda q, k, v, g, *rest: step(q, k, v, flat(g), *rest)
"""
NO_GROUP_LIMIT = """
from deepspeed_tpu.models import ling_flash as program
from deepspeed_tpu.models import pangu_ultra_moe
program.expert_layer = lambda cfg, h, lp: pangu_ultra_moe.expert_layer(
    cfg, h, lp, bias=lp["gate_bias"])
"""
BF16_ROUTER = """
import jax.numpy as jnp
from deepspeed_tpu.models import pangu_ultra_moe
route = pangu_ultra_moe.sigmoid_topk_route

def rounded(h, gate, *a, **kw):
    bf = jnp.bfloat16
    logits = jnp.dot(h.astype(bf), gate.astype(bf))
    return route(logits, jnp.eye(gate.shape[1], dtype=bf), *a, **kw)

pangu_ultra_moe.sigmoid_topk_route = rounded
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


@pytest.mark.parametrize("plant,by", [
    (SCALAR_GATE, "state"), (UNMASKED_STEPS, "state"),
    (NO_GROUP_LIMIT, "router"), (BF16_ROUTER, "router")],
    ids=["scalar_gate", "unmasked", "no_group_limit", "bf16_router"])
def test_a_planted_fault_is_not_correct(plant, by):
    """The gate averaged over a head's channels (Gated DeltaNet under
    this model's name); decode steps of other slots run unmasked over a
    slot between its prompt's chunks; the router's group limit left out;
    router logits in bfloat16.  Each run comes out failed: the first two
    by the state probe, which drives the serving programs the fault was
    planted in (in the first KDA layer already), the last two by the
    router probe (a router that chooses otherwise moves the last KDA
    layer's state too, and that probe may fail beside it)."""
    out, lines = _rehearse(plant)
    assert out.returncode == 1, out.stdout[-2000:] + out.stderr[-2000:]
    assert lines[-1]["rehearsal"] == "failed"
    probe = _probe(lines)
    assert probe["differ"] > probe["limit"]
    assert (probe["router_differ"] > probe["limit"]) == (by == "router")
    if by == "state":
        assert probe["state"]["failed"]
        assert probe["state"]["first"]["error_mean"] \
            > probe["state"]["first"]["limit"]


def test_a_bfloat16_state_reaches_the_probe_and_reads_higher():
    """State kept in bfloat16.  The probe's limits are set at the cell's
    widths on the chip, where bfloat16 weights feed a float32 state (the
    configuration's ``check_why``); the rehearsal's weights are float32
    and its clean readings are rounding alone, so here the plant is seen
    to reach the probe's cache and to read a hundred times higher than
    the same run without it, in the first KDA layer and in the last, and
    the run to fail exactly where a reading is over its limit."""
    _, clean = _rehearse()
    out, lines = _rehearse(BF16_STATE)
    state, was = _probe(lines)["state"], _probe(clean)["state"]
    assert (state["state_dtype"], was["state_dtype"]) \
        == ("bfloat16", "float32")
    for layer in ("first", "last"):
        assert state[layer]["error_mean"] > 100 * was[layer]["error_mean"]
    assert out.returncode == int(state["failed"])


def test_the_cell_rehearses_on_the_cpu():
    out, lines = _rehearse(trace=1)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    last = lines[-1]
    assert last["rehearsal"] == "passed"
    # group 0 is chosen by half the rows and then holds two of their
    # four: the even share, an eighth
    assert 9 < last["metrics"]["v33.expert_held_share.sat"]["value"] < 16
    assert "correct" not in last
    probe = _probe(lines)
    assert probe["differ"] == 0 and probe["by"] == [4, 32]
    assert not probe["state"]["failed"]

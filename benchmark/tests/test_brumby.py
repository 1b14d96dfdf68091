"""The Brumby configuration, its cell and what reads them: the file against
the catalog's row (one key reduced), the manifest, the new readers'
arithmetic, and the cell's rehearsal with its planted faults."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import manifest
from benchmark.families import brumby as family
from benchmark.harness import cell, scopes
from benchmark.readers import retention as reader
from benchmark.roofline import retention

ROOT = manifest.ROOT
CONFIG = "v59.brumby-14b-base-d10"
CELL = CONFIG + ".serve.docqa-sat"
NEW = "v59."       # this PR's metric files sort behind the manifest's
DATA = os.path.join(os.path.dirname(__file__), "data")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
ASSUMED = ("degree", "gate", "normaliser", "qk_norm_and_rope", "b_g", "init",
           "eps", "state_dtype", "layout")


@pytest.fixture(scope="module")
def source():
    """The catalog's row for the model (``architectures.jsonl`` beside
    the model-configs guide), copied here as data."""
    with open(os.path.join(DATA, "brumby-14b-base.catalog.json")) as f:
        return json.load(f)


def _cfg():
    return family.program_config(cell.load_json("configs", CONFIG)["model"])


def test_the_file_holds_the_sources_keys_and_cuts_the_depth_alone(source):
    config = cell.load_json("configs", CONFIG)
    model = config["model"]
    assert config["source"] == source["source_url"]
    assert len(source["config"]) == 18
    # key for key: at the top level, where the driver's check against the
    # catalog row reads them, and under ``model``, where the harness does;
    # the three are one statement but for the one reduced key
    assert {k: config[k] for k in source["config"]} == model
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 40} == {
        k: source["config"][k] for k in config["reduced"]}
    assert dict(model, **config["published"]) == source["config"]
    assert model["num_hidden_layers"] == 10
    assert config["family"] == "brumby"
    for said in ("assumed", "stands_for", "reckoning"):
        assert config[said]
    for reading in ASSUMED:
        assert config["assumed"][reading]
    # each ground says where it is from, and none claims the model's code
    assert "as published in the model's code" not in json.dumps(
        {k: v for k, v in config["assumed"].items() if k != "about"})
    assert len(config["why"]) <= 200
    assert "TO BE SET" not in json.dumps(config)
    assert config["serving"]["check_near_share"] == 1.0


def test_the_program_is_ten_whole_layers_and_the_whole_vocabulary(source):
    config = cell.load_json("configs", CONFIG)
    cfg = _cfg()
    assert (cfg.n_layers, cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.ffn_dim, cfg.vocab_size) == (10, 5120, 40, 8, 128, 17408,
                                             151936)
    assert family.layer_params(cfg) == 330_352_904
    assert family.param_count(cfg) == config["parameters"] == 4_859_358_800
    # the whole model: the card's 14B
    whole = family.program_config(source["config"])
    assert 14.7e9 < family.param_count(whole) < 14.8e9
    from deepspeed_tpu.models import brumby

    assert brumby.param_count(cfg) == family.param_count(cfg)
    assert brumby.FAMILY.pool_layers(cfg) == 0
    assert family.kv_bytes_per_token(cfg) == 0
    # a slot's state as the program lays it out against the exact size
    laid = cfg.n_layers * 4 * cfg.state_shape[0] * cfg.state_shape[1] \
        * cfg.state_shape[2]
    assert 1.06 < laid / family.state_bytes_per_slot(cfg) < 1.07


def test_the_cell_is_the_issues_traffic():
    c = cell.load_json("workloads", CELL)
    mix = cell.load_json("traffic", c["traffic"])
    qwen = cell.load_json(
        "workloads", "qwen3-next-80b-a3b-ep8-d12.serve.docqa-sat")
    assert c["chips"] == 1 and len(c["why"]) <= 200
    assert mix["kind"] == "serve_backlog_long" and mix["grid"] == 32
    engine = dict(c["engine"])
    assert engine.pop("max_batch") in (14, 16)
    assert engine == {"max_seq": 17408, "num_pages": 1,
                      "prefill_chunk": 1024, "prefill_bucket": 0}
    # the same traffic, chunks and max_seq as the sibling docqa-sat cells
    assert {k: qwen["engine"][k] for k in ("max_seq", "prefill_chunk")} \
        == {k: engine[k] for k in ("max_seq", "prefill_chunk")}
    assert set(c["end_to_end"]) == {"serve_tokens_per_s", "setup_s"}
    for m, (what, source) in {
            "ret_step_roofline.sat": ("step_roofline", "device_trace"),
            "ret_chunk_roofline.sat": ("chunk_roofline", "device_trace"),
            "ret_share_of_device.sat": ("share_of_busy", "device_trace")
    }.items():
        assert NEW + m in c["per_layer"]
        file = cell.metric(NEW + m)
        assert (file["moves"], file["reader"], file["source"]) \
            == ("serve_tokens_per_s", "retention", source)
        assert file["args"]["what"] == what
    # weights once and the state twice: the recurrent cells' own metric
    # (PR 35's reader counts this family's bytes), not a copy of it
    assert cell.metric("v35.decode_step_roofline.sat")["reader"] \
        == "decode_step_roofline_state"
    for m in ("serve_mfu", "compile_cache_hits", "compiles_steady",
              "hbm_peak_gib.sat", "v35.decode_step_roofline.sat"):
        assert m in c["per_layer"]
    assert not os.path.exists(os.path.join(
        ROOT, "benchmark", "metrics", NEW + "decode_step_roofline.sat.json"))
    # no pool: nothing to read of pages
    assert "kv_pages_peak_share.sat" not in c["per_layer"]


def test_the_manifest_is_the_files_and_the_parents_with_entries_appended():
    """``BENCHMARK.json`` is ``manifest.py --write``'s output, and
    against the parent's (``git show HEAD:BENCHMARK.json``, where the
    tree is a git checkout whose HEAD has not this cell yet) nothing
    that was there is edited, moved or removed."""
    assert manifest.main(["--check"]) == 0
    built = manifest.build()
    assert built["configs"][-1] == {
        "name": CONFIG, "source": cell.load_json("configs", CONFIG)["source"],
        "file": f"benchmark/configs/{CONFIG}.json",
        "reduced": ["num_hidden_layers"],
        "why": cell.load_json("configs", CONFIG)["why"]}
    assert built["workloads"][-1]["name"] == CELL
    assert built["workloads"][-1]["chips"] == 1
    assert sum(w["chips"] == 4 for w in built["workloads"]) == 1
    assert [m["name"] for m in built["per_layer"][-3:]] == [
        NEW + m for m in ("ret_chunk_roofline.sat",
                          "ret_share_of_device.sat",
                          "ret_step_roofline.sat")]
    assert all(m["workloads"] == [CELL] for m in built["per_layer"][-3:])
    show = subprocess.run(["git", "show", "HEAD:BENCHMARK.json"], cwd=ROOT,
                          capture_output=True, text=True)
    if show.returncode:
        pytest.skip("not a git checkout")
    parent = json.loads(show.stdout)
    if any(w["name"] == CELL for w in parent["workloads"]):
        pytest.skip("HEAD has the cell already")
    assert len(built["workloads"]) == len(parent["workloads"]) + 1 == 12
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


def test_roofline_arithmetic_of_the_retention_layers():
    cfg = _cfg()
    assert retention.phi_size(cfg) == 8256
    # the issue's count: 2 x 8,256 x 128 x 48 a token a layer
    assert retention.rule_flops(cfg, 1) == 2 * 8256 * 128 * 48
    assert retention.state_bytes(cfg) == 8 * (8256 * 128 + 8256) * 4
    # 16 live slots' state out and in, a layer: 1.09 GB, 1.33 ms
    floor = retention.step_floor_seconds(cfg, 16, PEAKS)
    assert floor == pytest.approx(2 * 16 * 34_080_768 / 819e9)
    # a chunk of 1,024: the products (104 GFLOP, 0.53 ms) over the state's
    # bytes (0.083 ms)
    assert retention.chunk_floor_seconds(cfg, 1024, PEAKS) \
        == pytest.approx(1024 * 2 * 8256 * 128 * 48 / 197e12)
    assert retention.chunk_floor_seconds(cfg, 8, PEAKS) \
        == pytest.approx(2 * 34_080_768 / 819e9)
    # the state is 56% of a decode step's bytes at 16 slots
    state = 2 * 16 * family.state_bytes_per_slot(cfg)
    weights = family.weight_bytes(cfg) - 2 * cfg.vocab_size * cfg.dim
    assert 0.55 < state / (state + weights) < 0.58
    # the recurrence is 13% of a layer's FLOPs before its blocks
    assert 0.12 < retention.rule_flops(cfg, 1) / (
        2 * family.layer_params(cfg) + retention.rule_flops(cfg, 1)) < 0.14


@pytest.mark.parametrize("what", ["share_of_busy", "step_roofline",
                                  "chunk_roofline"])
def test_the_reader_reads_nothing_where_there_is_nothing_to_read(
        monkeypatch, what):
    """On the parent, and in an untraced run, the line leaves the metric
    out: no trace, a trace with no operation, a program without the
    family's config."""
    run = types.SimpleNamespace(
        traced=None, window={"program_config": _cfg()}, peaks=PEAKS)
    monkeypatch.setattr(scopes, "of_run", lambda run: None)
    assert reader.read(run, what) is None
    recorded = types.SimpleNamespace(ops={}, programs={})
    monkeypatch.setattr(scopes, "of_run", lambda run: recorded)
    assert reader.read(run, what) is None
    recorded.ops = {0: []}
    monkeypatch.setattr(scopes, "by_scope", lambda s: {})
    monkeypatch.setattr(scopes, "self_seconds", lambda ops: [])
    run.window["program_config"] = types.SimpleNamespace()
    assert reader.read(run, what) is None


# ---------------------------------------------------------- the rehearsal
def _rehearse(plant="", trace=0):
    """The cell's rehearsal in a process of its own; ``plant`` is code
    run before the benchmark's entry point."""
    code = plant + (
        "import sys\nfrom benchmark import run\n"
        f"sys.exit(run.main(['--workload', '{CELL}', '--seed', "
        f"'{2 ** 31 + 59}', '--trace', '{trace}', '--rehearse']))\n")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT),
        capture_output=True, text=True, timeout=900)
    lines = [json.loads(l) for l in out.stdout.splitlines()
             if l.startswith("{")]
    return out, lines


# the faults the configuration's ``check_why`` names, as code run before
# the benchmark's entry point
PLANTS = {
    "bf16_state": """
import jax.numpy as jnp
from deepspeed_tpu.inference import kernels, serving
kernels.STATE_DTYPE = serving.STATE_DTYPE = jnp.bfloat16
""",
    # the constant value channel gone: the quotient divides by eps alone
    "no_normaliser": """
import jax.numpy as jnp
from deepspeed_tpu.models import brumby as M
M._extended = lambda v: jnp.concatenate(
    [v, jnp.zeros(v.shape[:-1] + (8,), v.dtype)], -1)
""",
    # S <- g (S + v phi(k)) for g S + v phi(k): phi is quadratic in k, so
    # that is k scaled by g^(1/2)
    "decay_after_the_write": """
import jax.numpy as jnp
from deepspeed_tpu.models import brumby as M
step, rule = M.ret_step, M.ret_chunk_rule
M.ret_step = lambda cfg, q, k, v, g, S: step(
    cfg, q, k * jnp.sqrt(g)[..., None], v, g, S)
M.ret_chunk_rule = lambda cfg, q, k, v, logg, S: rule(
    cfg, q, k * jnp.exp(logg / 2)[..., None], v, logg, S)
""",
    "no_root_two_off_the_diagonal": """
from deepspeed_tpu.models import brumby as M
M.OFF_DIAGONAL = 1.0
""",
    "a_padded_row_moves_the_state": """
import dataclasses
import jax.numpy as jnp
from deepspeed_tpu.models import brumby as M
mix = M.ret_mix
def planted(cfg, x, lp, state, valid, *rest):
    return mix(cfg, x, lp, state, jnp.full_like(valid, x.shape[1]), *rest)
M.FAMILY = dataclasses.replace(M.FAMILY, recurrent=dataclasses.replace(
    M.FAMILY.recurrent, mix=planted))
""",
}


def _check(lines):
    return next(l["note_check"] for l in lines if "note_check" in l)


@pytest.mark.parametrize("plant", PLANTS)
def test_a_planted_fault_is_not_correct(plant):
    """Each fault the configuration's ``check_why`` names, planted in the
    programs the harness serves and checks, fails the run at the
    rehearsal's sizes: by the tokens (a layer that computes another
    thing), by the state the timed engine's own cache held at the window's
    close (a state that other rows or fewer bits moved), or both.  The rehearsal serves float32, so what a fault moves
    stands far over what a clean run reads (1e-6)."""
    out, lines = _rehearse(PLANTS[plant])
    assert out.returncode == 1, out.stdout[-2000:] + out.stderr[-2000:]
    assert lines[-1]["rehearsal"] == "failed"
    check = _check(lines)
    state = check["router_probe"]["state"]
    by_state = family.state_failed(state)
    by_tokens = check["near"] < check["near_share_asked"] * check["tokens"]
    assert by_state or by_tokens
    if plant in ("bf16_state", "a_padded_row_moves_the_state",
                 "decay_after_the_write"):
        assert by_state
        assert state["first"]["S"]["error_mean"] > 5e-4
    if plant == "bf16_state":
        assert state["state_dtype"] == "bfloat16"
    if plant in ("no_normaliser", "no_root_two_off_the_diagonal"):
        assert by_tokens


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
    state = probe["state"]
    assert not family.state_failed(state)
    assert state["state_dtype"] == "float32"
    for layer in ("first", "last"):
        for part in ("S", "z"):
            assert state[layer][part]["error_worst_head"] < 1e-4
    # a live slot of the engine that was timed, past its prompt
    assert state["taken"] == state["prompt"] + state["decode_steps"]
    assert state["decode_steps"] > 0 and state["slots_live"] > 1


def test_the_stated_layout_is_the_programs():
    """``_laid_out`` of the definition's sums (no phi) is what the
    program's own ``phi`` and ``[v | 1 | 0]`` make of the same keys and
    values: the benchmark's reading of ``assumed.layout``, written apart
    from the program, names the same numbers."""
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models import brumby as program

    rng = np.random.default_rng(59)
    T, KV, Dh = 40, 2, 16
    k, v = (jnp.asarray(rng.normal(size=(T, KV, Dh)), jnp.float32)
            for _ in range(2))
    G = jnp.cumsum(jnp.asarray(-rng.uniform(0.01, 0.3, (T, KV)),
                               jnp.float32), 0)
    mine = family._laid_out(np.asarray(family._moments(k, v, G)))
    decay = jnp.exp(G[-1][None] - G)
    theirs = np.asarray(jnp.einsum(
        "skr,skdi->kdri", program._extended(v),
        program.phi(k) * decay[..., None, None]))
    assert mine.shape == (KV, Dh // 2 + 1, Dh + 1, Dh)
    np.testing.assert_allclose(mine, theirs[:, :, :Dh + 1], rtol=1e-5,
                               atol=1e-5)
    assert not theirs[:, :, Dh + 1:].any()


def test_nothing_read_is_not_correct():
    """A window whose close left no slot's state (the hook did not run,
    or the engine keeps a pool) fails the run; it never passes for want
    of a reading."""
    family._HELD.clear()
    probe = family.router_probe(None, None, 0, 4, 32)
    assert probe["differ"] > probe["limit"]
    assert probe["state"]["failed"] and family.state_failed(probe["state"])
    pooled = types.SimpleNamespace(cache=types.SimpleNamespace(
        state=object(), k=object()), slots=[])
    assert family.held_state(pooled) == {}
    assert family.held_state(types.SimpleNamespace(
        cache=types.SimpleNamespace(state=None, k=None), slots=[])) == {}

"""Elastic fleet (ISSUE 11): autoscaling on control-plane signals,
streamed warm cold-start with the live resident flip, rolling weight
updates with halt-and-rollback, the spawn/retire fleet verbs, the
``scale`` fault rules, and the per-version SLO rollup.

Correctness oracle throughout: single fault-free engines per weight
version — whatever the elastic machinery does (spawn, drain, retire,
swap, roll back), a COMPLETED request's tokens must match the oracle
of SOME weight version that was legitimately serving (greedy decode is
a pure function of prompt + weights)."""

import os
import sys
import time
from collections import Counter

import numpy as np
import pytest

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

from deepspeed_tpu import faults
from deepspeed_tpu.autoscale import FleetAutoscaler
from deepspeed_tpu.config import AutoscaleConfig
from deepspeed_tpu.faults import FaultRule
from deepspeed_tpu.fleet import DEAD, DRAINING, HEALTHY, fleet_router
from deepspeed_tpu.inference.serving import (EngineClosed, RequestFailed,
                                             RequestShed, serving_engine)
from deepspeed_tpu.models import gpt2, llama
from deepspeed_tpu.slo import fleet_rollup
from deepspeed_tpu.telemetry import MetricsRegistry

KW = dict(max_batch=2, page_size=8, num_pages=12, max_seq=64,
          prefill_bucket=8)
LKW = dict(max_batch=2, page_size=8, num_pages=32, max_seq=64,
           prefill_bucket=8)
# fast-reacting autoscaler for tests: evaluate every router step, one
# pressured eval scales up, three idle evals scale down, no cooldown
FAST = dict(min_replicas=1, max_replicas=3, eval_interval_steps=1,
            scale_up_queue_depth=2.0, scale_down_queue_depth=0.5,
            up_after=1, down_after=3, cooldown_s=0.0)


@pytest.fixture(scope="module")
def gpt2_model():
    cfg = gpt2.GPT2Config.tiny(dim=64, n_layers=2, n_heads=4,
                               max_seq_len=128)
    p0 = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    p1 = gpt2.init_params(jax.random.PRNGKey(1), cfg)
    return cfg, p0, p1


@pytest.fixture(scope="module")
def llama_model():
    cfg = llama.LlamaConfig.tiny(dim=64, n_layers=3, n_heads=4,
                                 n_kv_heads=2)
    p0 = llama.init_params(jax.random.PRNGKey(0), cfg)
    p1 = llama.init_params(jax.random.PRNGKey(1), cfg)
    return cfg, p0, p1


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    faults.clear_fault_plan()
    yield
    faults.clear_fault_plan()


def prompts(vocab, n=6, seed=0, length=10):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, length).tolist() for _ in range(n)]


def oracle_outputs(params, cfg, ps, max_new=4, kw=KW):
    eng = serving_engine(params, cfg, **kw)
    for i, p in enumerate(ps):
        eng.submit(f"o{i}", p, max_new_tokens=max_new)
    out = eng.run()
    eng.shutdown()
    return [out[f"o{i}"] for i in range(len(ps))]


def make_elastic(params, cfg, n=1, autoscale=None, fleet_over=None,
                 **router_kw):
    """(router, autoscaler): a gpt2 fleet plus a factory building
    fleet-compatible replicas (shared tracer, per-replica metric
    namespaces) — the pattern the autoscaler docs prescribe."""
    router = fleet_router(
        params, cfg, fleet={"replicas": n, **(fleet_over or {})},
        prefix_cache=True, tracing={"ring_capacity": 16384},
        **router_kw, **KW)
    slo = router_kw.get("slo")

    def factory(rid, streamed=False):
        return serving_engine(
            params, cfg, replica_id=rid, prefix_cache=True,
            tracing=router.tracer, slo=slo,
            telemetry=MetricsRegistry(namespace=f"dstpu_{rid}"), **KW)

    a = FleetAutoscaler(router, factory,
                        autoscale={**FAST, **(autoscale or {})})
    return router, a


def assert_clean(router):
    assert router.check_leaks() == []
    assert router.orphaned() == []


# ------------------------------------------------------------- config
def test_autoscale_config_validation():
    c = AutoscaleConfig.coerce({"min_replicas": 2, "max_replicas": 5})
    assert c.enabled and c.min_replicas == 2 and c.max_replicas == 5
    assert not AutoscaleConfig.coerce(None).enabled
    assert AutoscaleConfig.coerce(
        {"enabled": False, "max_replicas": 9}).enabled is False
    with pytest.raises(ValueError):
        AutoscaleConfig.coerce({"min_replicas": 0})
    with pytest.raises(ValueError):
        AutoscaleConfig.coerce({"min_replicas": 3, "max_replicas": 2})
    with pytest.raises(ValueError):
        AutoscaleConfig.coerce({"scale_up_queue_depth": 1.0,
                                "scale_down_queue_depth": 2.0})
    with pytest.raises(ValueError):
        AutoscaleConfig.coerce({"cold_start": "lukewarm"})
    with pytest.raises(ValueError):
        AutoscaleConfig.coerce({"cooldown_s": -1})
    with pytest.raises(TypeError):
        AutoscaleConfig.coerce("fast")


def test_scale_fault_rule_validation():
    FaultRule(subsystem="scale", mode="error", match="r3")
    FaultRule(subsystem="scale", mode="latency", latency_s=0.5)
    with pytest.raises(ValueError):
        FaultRule(subsystem="scale", mode="degrade")


# ------------------------------------------------------ spawn / retire
def test_spawn_and_retire_verbs(gpt2_model):
    cfg, p0, _ = gpt2_model
    router = fleet_router(
        p0, cfg, fleet={"replicas": 1}, prefix_cache=True,
        slo={"tiers": {"t": {"ttft_s": 60.0}}, "default_tier": "t"},
        **KW)
    eng = serving_engine(p0, cfg, prefix_cache=True,
                         slo=router.replicas["r0"].engine.slo_cfg, **KW)
    rid = router.spawn(eng)
    assert rid == "r1" and router.replicas[rid].state == HEALTHY
    ps = prompts(cfg.vocab_size, n=6, seed=3)
    oracle = oracle_outputs(p0, cfg, ps)
    for i, p in enumerate(ps):
        router.submit(f"a{i}", p, max_new_tokens=4)
    out = router.run()
    assert [out[f"a{i}"] for i in range(len(ps))] == oracle
    # both replicas served (least-loaded spread)
    assert router.replicas["r1"].completed > 0
    served_r1 = router.replicas["r1"].completed
    # retire needs a drain first
    with pytest.raises(ValueError):
        router.retire("r1")
    router.drain("r1")
    assert router.drained("r1")
    router.retire("r1")
    assert "r1" not in router.replicas
    st = router.statusz()
    assert st["fleet"]["spawns"] == 1 and st["fleet"]["retires"] == 1
    # the retired replica's SLO lifetime survived in the rollup
    life = st["slo"]["tiers"]["t"]["lifetime"]
    assert life["attained"] + life["violated"] == len(ps)
    assert served_r1 > 0
    # the last live replica refuses to retire
    router.drain("r0")
    with pytest.raises(ValueError, match="last live"):
        router.retire("r0")
    router.rejoin("r0")
    assert_clean(router)
    router.shutdown()


def test_spawn_rejects_closed_or_duplicate(gpt2_model):
    cfg, p0, _ = gpt2_model
    router = fleet_router(p0, cfg, fleet={"replicas": 1},
                          prefix_cache=True, **KW)
    stale = serving_engine(p0, cfg, prefix_cache=True, **KW)
    stale.shutdown()
    with pytest.raises(EngineClosed):
        router.spawn(stale)
    with pytest.raises(ValueError, match="duplicate"):
        router.spawn(serving_engine(p0, cfg, prefix_cache=True, **KW),
                     "r0")
    router.shutdown()


# ------------------------------------------------------- autoscaling
def test_scale_up_on_pressure_then_down_when_idle(gpt2_model):
    cfg, p0, _ = gpt2_model
    router, a = make_elastic(p0, cfg, n=1)
    ps = prompts(cfg.vocab_size, n=20, seed=1)
    oracle = oracle_outputs(p0, cfg, ps)
    for i, p in enumerate(ps):
        router.submit(f"q{i}", p, max_new_tokens=4)
        a.step()
    out = a.run()
    st = a.status()
    assert st["scale_ups"] >= 1, "queue pressure must add a replica"
    assert [out[f"q{i}"] for i in range(len(ps))] == oracle
    # idle evaluations walk the fleet back down to min_replicas
    for _ in range(30):
        a.step()
        time.sleep(0.002)
    live = [r for r, rep in router.replicas.items()
            if rep.state != DEAD]
    st = a.status()
    assert st["scale_downs"] >= 1 and len(live) == 1
    assert st["live_replicas"] == 1
    # scale/rollout events land in the trace ring exactly once each
    ring = router.tracer.recorder.events()
    ring_kinds = Counter(e[3] for e in ring
                         if e[3].startswith(("autoscale_", "rollout_")))
    led = Counter(e["kind"] for e in a.events)
    assert led and dict(ring_kinds) == dict(led)
    assert_clean(router)
    router.shutdown()


def test_hysteresis_and_cooldown_gate_scaling(gpt2_model):
    cfg, p0, _ = gpt2_model
    router, a = make_elastic(
        p0, cfg, n=1, autoscale={"up_after": 3, "cooldown_s": 60.0})
    ps = prompts(cfg.vocab_size, n=8, seed=2)
    for i, p in enumerate(ps):
        router.submit(f"q{i}", p, max_new_tokens=2)
    # two pressured evaluations: under up_after=3, no scale yet
    a.step()
    a.step()
    assert a.status()["scale_ups"] == 0
    assert a.status()["pressure"]["up_streak"] == 2
    a.step()
    assert a.status()["scale_ups"] == 1
    # a 60 s cooldown pins the fleet no matter the pressure
    for _ in range(5):
        a.step()
    assert a.status()["scale_ups"] == 1
    assert a.status()["cooldown_remaining_s"] > 0
    a.run()
    assert_clean(router)
    router.shutdown()


def test_heal_back_to_min_after_death(gpt2_model):
    cfg, p0, _ = gpt2_model
    router, a = make_elastic(
        p0, cfg, n=2, autoscale={"min_replicas": 2, "up_after": 99,
                                 "cooldown_s": 60.0})
    router.kill("r1")
    a.step()            # under the floor: heals regardless of
    a.step()            # streaks and cooldown
    st = a.status()
    assert st["scale_ups"] == 1 and st["live_replicas"] == 2
    router.submit("a", [5, 6, 7], max_new_tokens=2)
    out = a.run()
    assert isinstance(out["a"], list)
    assert_clean(router)
    router.shutdown()


def test_scale_factory_failure_and_slow_cold_start(gpt2_model):
    cfg, p0, _ = gpt2_model
    router, a = make_elastic(
        p0, cfg, n=1,
        faults={"rules": [
            # first spawn attempt: factory failure (retried later);
            # second: a 50 ms slow cold-start
            {"subsystem": "scale", "mode": "error", "count": 1},
            {"subsystem": "scale", "mode": "latency",
             "latency_s": 0.05, "count": 1, "after": 1},
        ]})
    ps = prompts(cfg.vocab_size, n=16, seed=5)
    for i, p in enumerate(ps):
        router.submit(f"q{i}", p, max_new_tokens=4)
    a.step()        # pressured eval: spawn attempt → injected failure
    st = a.status()
    assert st["factory_failures"] == 1 and st["scale_ups"] == 0
    assert st["live_replicas"] == 1
    a.step()        # retry: slow cold-start (latency rule), succeeds
    a.run()
    st = a.status()
    assert st["factory_failures"] == 1
    assert st["scale_ups"] >= 1, "the failed spawn must retry"
    snap = router.registry.snapshot()
    hist = snap["histograms"]["autoscale_cold_start_seconds"]
    assert hist["count"] >= 1 and hist["sum"] >= 0.05
    kinds = Counter(e["kind"] for e in a.events)
    assert kinds["autoscale_up_failed"] == 1
    assert_clean(router)
    router.shutdown()


# --------------------------------------------- streamed warm cold-start
def test_streamed_cold_start_serves_then_flips(llama_model):
    cfg, p0, _ = llama_model
    from deepspeed_tpu.inference.serving import serving_engine

    router = fleet_router(
        p0, cfg, fleet={"replicas": 1}, prefix_cache=True,
        tracing={"ring_capacity": 16384},
        engine_builder=lambda params, c, **kw: serving_engine(
            params, c, **kw), **LKW)

    def factory(rid, streamed=False):
        zi = ({"enabled": True, "tier": "host"} if streamed else None)
        return serving_engine(
            p0, cfg, replica_id=rid, prefix_cache=True,
            zero_inference=zi, tracing=router.tracer,
            telemetry=MetricsRegistry(namespace=f"dstpu_{rid}"), **LKW)

    a = FleetAutoscaler(router, factory, autoscale={
        **FAST, "cold_start": "streamed",
        "promote_layers_per_tick": 1, "down_after": 9999})
    ps = prompts(cfg.vocab_size, n=14, seed=6)
    oracle = oracle_outputs(p0, cfg, ps, kw=LKW)
    for i, p in enumerate(ps):
        router.submit(f"q{i}", p, max_new_tokens=4)
        a.step()
    out = a.run()
    st = a.status()
    assert st["scale_ups"] >= 1
    assert st["cold_flips"] >= 1, \
        "the streamed cold-start must flip to resident"
    # the spawned replica is now fully resident and token-identical
    spawned = [rep for rid, rep in router.replicas.items()
               if rid != "r0" and rep.state != DEAD]
    assert spawned and all(rep.engine.fully_resident
                           for rep in spawned)
    assert [out[f"q{i}"] for i in range(len(ps))] == oracle
    flips = [e for e in a.events if e["kind"] == "autoscale_flip"]
    assert flips and flips[0]["cold_start_s"] > 0
    assert_clean(router)
    router.shutdown()


# ------------------------------------------------------ rolling update
def test_rollout_walks_fleet_token_identical(gpt2_model):
    cfg, p0, p1 = gpt2_model
    router, a = make_elastic(p0, cfg, n=2,
                             autoscale={"rollout_soak_steps": 1})
    ps = prompts(cfg.vocab_size, n=12, seed=7)
    oracle0 = oracle_outputs(p0, cfg, ps)
    oracle1 = oracle_outputs(p1, cfg, ps)
    a.rollout(p1, version="v1")
    assert a.rollout_active
    with pytest.raises(RuntimeError, match="in progress"):
        a.rollout(p1, version="v2")
    for i, p in enumerate(ps):
        router.submit(f"q{i}", p, max_new_tokens=4)
        a.step()
    out = a.run()
    assert not a.rollout_active
    assert a.last_rollout["completed"] and \
        not a.last_rollout["rolled_back"]
    assert all(str(rep.version) == "v1"
               for rep in router.replicas.values()
               if rep.state != DEAD)
    # every request completed (never dropped) on ONE of the versions
    # that was legitimately serving when it ran
    for i in range(len(ps)):
        assert out[f"q{i}"] in (oracle0[i], oracle1[i])
    kinds = Counter(e["kind"] for e in a.events)
    assert kinds["rollout_start"] == 1 and kinds["rollout_done"] == 1
    assert kinds["rollout_step"] == 2
    # a post-rollout scale-up serves the NEW version
    for i, p in enumerate(ps):
        router.submit(f"w{i}", p, max_new_tokens=4)
        a.step()
    a.run()
    assert all(str(rep.version) == "v1"
               for rep in router.replicas.values()
               if rep.state != DEAD)
    assert_clean(router)
    router.shutdown()


def test_rollout_halts_and_rolls_back_on_burn(gpt2_model):
    cfg, p0, p1 = gpt2_model
    slo = {"tiers": {
        "lax": {"ttft_s": 60.0, "target": 0.5},
        # impossible objective: every finished request violates, so
        # burn = 1/(1-0.5) = 2.0 on any traffic
        "strict": {"ttft_s": 1e-6, "target": 0.5}},
        "default_tier": "lax", "burn_windows_s": [30.0]}
    router, a = make_elastic(
        p0, cfg, n=2, slo=slo,
        autoscale={"rollout_soak_steps": 40,
                   "rollback_burn_threshold": 1.0,
                   "rollback_min_finished": 1})
    ps = prompts(cfg.vocab_size, n=10, seed=8)
    oracle0 = oracle_outputs(p0, cfg, ps)
    oracle1 = oracle_outputs(p1, cfg, ps)
    a.rollout(p1, version="v1")
    i = 0
    # drive strict-tier traffic through the rollout: the first updated
    # replica's violations trip the new version's burn rate
    while (a.rollout_active or router.has_work) and i < 400:
        if i < len(ps):
            router.submit(f"q{i}", ps[i], max_new_tokens=4,
                          tier="strict")
        a.step()
        i += 1
    out = dict(router.finished)
    assert a.last_rollout is not None
    assert a.last_rollout["halted"] and a.last_rollout["rolled_back"]
    assert a.last_rollout["halt_burn"] > 1.0
    # every replica is back on the ORIGINAL version
    assert all(str(rep.version) == "0"
               for rep in router.replicas.values()
               if rep.state != DEAD), "rollback must restore v0"
    # nothing dropped: every submitted request completed on a version
    # that was serving (v0 before/after, v1 in the halted window)
    for k, v in out.items():
        if isinstance(v, list):
            idx = int(k[1:])
            assert v in (oracle0[idx], oracle1[idx])
        else:
            assert not isinstance(v, (RequestFailed, RequestShed)), v
    kinds = Counter(e["kind"] for e in a.events)
    assert kinds["rollout_halt"] == 1
    assert kinds["rollout_rolled_back"] == 1
    st = a.status()
    assert st["rollbacks"] == 1
    assert_clean(router)
    router.shutdown()


def test_rollout_survives_mid_rollout_death(gpt2_model):
    cfg, p0, p1 = gpt2_model
    router, a = make_elastic(
        p0, cfg, n=3, autoscale={"rollout_soak_steps": 1,
                                 "min_replicas": 1})
    ps = prompts(cfg.vocab_size, n=8, seed=9)
    a.rollout(p1, version="v1")
    killed = False
    i = 0
    while (a.rollout_active or router.has_work) and i < 400:
        if i < len(ps):
            router.submit(f"q{i}", ps[i], max_new_tokens=4)
        a.step()
        ro = a._rollout
        if not killed and ro is not None and ro["updated"]:
            # the first replica just updated: kill the NEXT target
            # before its turn (the mid-rollout death)
            nxt = next((r for r in ro["plan"][ro["i"]:]
                        if r in router.replicas
                        and router.replicas[r].state != DEAD), None)
            if nxt is not None:
                router.kill(nxt, error="mid-rollout death")
                killed = True
        i += 1
    assert killed
    assert a.last_rollout["completed"]
    assert len(a.last_rollout["skipped"]) == 1
    # survivors all updated; the dead one skipped, its work salvaged
    assert all(str(rep.version) == "v1"
               for rep in router.replicas.values()
               if rep.state != DEAD)
    kinds = Counter(e["kind"] for e in a.events)
    assert kinds["rollout_target_died"] == 1
    assert_clean(router)
    router.shutdown()


def test_heal_during_rollout_joins_plan(gpt2_model):
    # a mid-rollout death must not leave the fleet under its floor for
    # the rest of the walk: healing keeps running during a rollout,
    # and the healed spawn joins the plan so it finishes on the NEW
    # version
    cfg, p0, p1 = gpt2_model
    router, a = make_elastic(
        p0, cfg, n=2, autoscale={"min_replicas": 2,
                                 "rollout_soak_steps": 2})
    ps = prompts(cfg.vocab_size, n=8, seed=13)
    a.rollout(p1, version="v1")
    killed = False
    i = 0
    while (a.rollout_active or router.has_work) and i < 600:
        if i < len(ps):
            router.submit(f"q{i}", ps[i], max_new_tokens=4)
        a.step()
        ro = a._rollout
        if not killed and ro is not None and ro["updated"]:
            nxt = next((r for r in ro["plan"][ro["i"]:]
                        if r in router.replicas
                        and router.replicas[r].state != DEAD), None)
            if nxt is not None:
                router.kill(nxt, error="mid-rollout death")
                killed = True
        i += 1
    assert killed and a.last_rollout["completed"]
    live = {rid: rep for rid, rep in router.replicas.items()
            if rep.state != DEAD}
    assert len(live) == 2, "the heal must replace the casualty"
    # the healed spawn was appended to the plan and updated in turn
    assert all(str(rep.version) == "v1" for rep in live.values())
    assert a.status()["scale_ups"] >= 1
    assert_clean(router)
    router.shutdown()


# ------------------------------------------------- per-version rollup
def test_fleet_rollup_by_version_unit():
    def snap(att, vio):
        return {"enabled": True, "default_tier": "t", "tiers": {"t": {
            "objective": {"ttft_s": 1.0}, "target": 0.9,
            "window_s": 60.0, "window_finished": att + vio,
            "window_attained": att, "attainment": 0.0,
            "goodput_tokens_per_s": float(att),
            "burn_rates": {"60s": float(vio)}, "burn_threshold": 2.0,
            "alert_active": vio > 2,
            "lifetime": {"attained": att, "violated": vio},
            "in_flight": 0}}}

    out = fleet_rollup([snap(8, 0), snap(4, 4), snap(0, 6)],
                       versions=["v0", "v0", "v1"])
    assert out["enabled"] and out["replicas"] == 3
    t = out["tiers"]["t"]
    assert t["lifetime"]["attained"] == 12
    assert t["burn_rates"]["60s"] == 6.0        # max across replicas
    by = out["by_version"]
    assert set(by) == {"v0", "v1"}
    assert by["v0"]["tiers"]["t"]["lifetime"]["attained"] == 12
    assert by["v0"]["tiers"]["t"]["burn_rates"]["60s"] == 4.0
    assert by["v1"]["tiers"]["t"]["lifetime"]["violated"] == 6
    # single version: no by_version key (the common steady state)
    assert "by_version" not in fleet_rollup(
        [snap(1, 0), snap(2, 0)], versions=["v0", "v0"])
    with pytest.raises(ValueError, match="align"):
        fleet_rollup([snap(1, 0)], versions=["a", "b"])


def test_statusz_versions_and_elastic_block(gpt2_model):
    cfg, p0, p1 = gpt2_model
    slo = {"tiers": {"t": {"ttft_s": 60.0}}, "default_tier": "t"}
    router, a = make_elastic(p0, cfg, n=2, slo=slo,
                             autoscale={"rollout_soak_steps": 0})
    ps = prompts(cfg.vocab_size, n=6, seed=11)
    for i, p in enumerate(ps):
        router.submit(f"q{i}", p, max_new_tokens=2)
    a.run()
    # swap ONE replica by hand to leave the fleet mid-version
    router.drain("r0")
    while not router.drained("r0"):
        router.step()
    router.replicas["r0"].engine.swap_params(p1, version="v1")
    router.rejoin("r0")
    st = router.statusz()
    vers = {r["replica"]: r["version"]
            for r in st["fleet"]["replicas"]}
    assert vers == {"r0": "v1", "r1": "0"}
    assert set(st["slo"]["by_version"]) == {"0", "v1"}
    el = st["elastic"]
    assert el["enabled"] and el["min_replicas"] == 1
    assert "pressure" in el and "rollout" in el
    # dstpu_top renders the elastic row + version column
    import dstpu_top
    lines = dstpu_top.render(st, router.healthz())
    joined = "\n".join(lines)
    assert "elast target" in joined and "v1" in joined
    assert_clean(router)
    router.shutdown()


def test_swap_params_guards(gpt2_model):
    cfg, p0, p1 = gpt2_model
    eng = serving_engine(p0, cfg, prefix_cache=True, **KW)
    eng.submit("a", list(range(2, 18)), max_new_tokens=2)
    with pytest.raises(RuntimeError, match="drained"):
        eng.swap_params(p1)
    eng.run()
    bad = {k: v for k, v in p0.items() if k != "wpe"}
    with pytest.raises(ValueError, match="does not match"):
        eng.swap_params(bad)
    # a real swap invalidates the warm prefix pool (old-version KV
    # must never serve the new version)
    assert eng.allocator.pool
    eng.swap_params(p1, version="v1")
    assert not eng.allocator.pool and not eng.allocator.index
    assert eng.weights_version == "v1"
    assert eng.check_leaks() == []
    eng.shutdown()
    with pytest.raises(EngineClosed):
        eng.swap_params(p0)


def test_swap_params_invalidates_spill_tier(gpt2_model, tmp_path):
    # a weight swap must poison-drop BOTH warm tiers: the HBM pool
    # and the host/NVMe spill — a demoted old-version page matching a
    # new-version prompt would serve stale KV
    cfg, p0, p1 = gpt2_model
    eng = serving_engine(
        p0, cfg, prefix_cache=True,
        kv_tier={"enabled": True, "host_pool_bytes": 4096,
                 "nvme_dir": str(tmp_path)}, **KW)
    rng = np.random.default_rng(0)
    pref = list(range(2, 18))
    for i in range(6):
        eng.submit(f"a{i}", pref + rng.integers(1, 200, 3).tolist(),
                   max_new_tokens=4)
    for i in range(4):      # churn: the shared prefix demotes
        eng.submit(f"f{i}", rng.integers(1, 200, 24).tolist(),
                   max_new_tokens=4)
    eng.run()
    assert eng._kv_pool.entries and eng.allocator.pool
    eng.swap_params(p1, version="v1")
    assert not eng._kv_pool.entries and not eng.allocator.pool
    assert not eng.allocator.index
    assert eng.check_leaks() == []
    oracle = oracle_outputs(p1, cfg, [pref + [5, 6, 7]])
    eng.submit("x", pref + [5, 6, 7], max_new_tokens=4)
    assert eng.run()["x"] == oracle[0]
    assert eng.check_leaks() == []
    eng.shutdown()


def test_zi_budget_bound_flip_blocked(llama_model):
    # a >HBM engine's steady state IS streamed: the promoter must
    # stop at the budget and report resident_flip_blocked instead of
    # promising a flip that can never land (the autoscaler closes the
    # cold start there rather than spinning forever)
    cfg, p0, _ = llama_model
    from deepspeed_tpu.inference.serving import serving_engine
    from deepspeed_tpu.inference.zero_inference import plan_residency

    probe = serving_engine(
        p0, cfg, zero_inference={"enabled": True, "tier": "host"},
        **LKW)
    plan = probe.plan
    probe.shutdown()
    # one byte under the full image: the plan streams, and no
    # promotion can ever land (residency + the streaming working set
    # would exceed the budget)
    budget = plan["weight_image_bytes"] + plan["cache_bytes"] - 1
    assert plan_residency(
        n_layers=plan["n_layers"], layer_bytes=plan["layer_bytes"],
        stem_head_bytes=plan["stem_head_bytes"],
        cache_bytes=plan["cache_bytes"], budget=budget,
        prefetch_depth=plan["prefetch_depth"])["n_resident"] \
        < plan["n_layers"]
    zi = serving_engine(
        p0, cfg, zero_inference={"enabled": True, "tier": "host",
                                 "hbm_budget_bytes": budget}, **LKW)
    assert not zi.fully_resident
    zi.promote_resident_layers(10)
    assert zi.resident_flip_blocked and not zi.fully_resident
    zi.submit("a", [5, 9, 2], max_new_tokens=4)
    assert isinstance(zi.run()["a"], list)   # still serves, streamed
    zi.shutdown()


def test_zi_swap_weights_token_identical(llama_model):
    cfg, p0, p1 = llama_model
    from deepspeed_tpu.inference.serving import serving_engine

    oracle1 = oracle_outputs(p1, cfg, [[5, 9, 2]], max_new=6, kw=LKW)
    zi = serving_engine(
        p0, cfg, zero_inference={"enabled": True, "tier": "host"},
        **LKW)
    zi.submit("a", [5, 9, 2], max_new_tokens=6)
    zi.run()
    with pytest.raises(NotImplementedError, match="swap_weights"):
        zi.swap_params(p1)
    stem = {"embed": p1["embed"]}
    head = {"final_norm": p1["final_norm"], "lm_head": p1["lm_head"]}
    zi.swap_weights(stem, p1["blocks"], head, version="v1")
    assert zi.weights_version == "v1"
    zi.submit("b", [5, 9, 2], max_new_tokens=6)
    assert zi.run()["b"] == oracle1[0]
    with pytest.raises(ValueError, match="does not match"):
        zi.swap_weights(stem, p1["blocks"]["wq"], head)
    zi.shutdown()

#!/usr/bin/env python
"""Chaos soak: drive serving traffic under a deterministic injected
fault schedule and assert graceful degradation (ISSUE 9 acceptance).

The engine under test runs the full I/O-dependent stack — prefix
cache, tiered KV with an NVMe spill dir, SLO tiers, tracing, load
shedding — while a seeded :class:`~deepspeed_tpu.faults.FaultPlan`
injects aio read/write failures, read-latency spikes, spilled-page
corruption, slot-level exceptions, and a queue-pressure burst.  A
fault-free ORACLE engine (no tier, no faults, no shedding) serves
every distinct prompt first; the soak then asserts:

1. **zero token mismatches**: every request the chaos engine COMPLETED
   is token-identical to the oracle (greedy decode: output is a pure
   function of the prompt, so degraded paths — retries, sync
   fallbacks, checksum re-prefills, tier disablement — must never
   change tokens);
2. **no hangs**: a watchdog petted per step never fires, and the drive
   loop finishes under its wall cap;
3. **clean drain**: ``has_work`` goes false and the page-accounting
   leak check (``engine.check_leaks``) comes back empty;
4. **failures accounted for**: submitted == completed + failed + shed,
   and the counts reconcile across the typed results, the telemetry
   registry, the SLO per-tier lifetime counters, and the flight
   recorder's ``request_failed``/``request_shed`` events.

Stamped as CHAOS_SOAK.json (atomic) and gated by tools/bench_gate.py
(mismatched_requests / leak_count / watchdog_fired must stay 0,
accounting_ok must stay 1).

    python tools/chaos_soak.py --cpu --json-out CHAOS_SOAK.json
"""

import argparse
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

MAX_NEW = 6
STEP_CAP = 3000
WALL_CAP_S = 480.0

# history + incidents blocks for the soaks (ISSUE 15): fast cadences
# AND a 50 ms fine ring so the short CPU run records real trajectories
# (the default 1 s ring would fold a whole soak wave into one bucket),
# a dedup window longer than any soak so each incident class yields
# EXACTLY one bundle, and a 60 s pre-window on a ring set whose span
# covers the >= 30 s acceptance bound
HISTORY_BLOCK = {"sample_interval_s": 0.05,
                 "rings": ((0.05, 600), (1.0, 120), (10.0, 360))}


def incidents_block(out_dir):
    return {"dir": out_dir, "eval_interval_s": 0.05,
            "pre_window_s": 60.0, "dedup_window_s": 600.0,
            "max_bundles": 8}


def incidents_summary(mgr, oracle_bundles=None):
    """Per-class bundle accounting for a soak stamp."""
    by_class = {}
    for b in mgr.bundles:
        by_class[b["incident"]] = by_class.get(b["incident"], 0) + 1
    out = {
        "bundles": len(mgr.bundles),
        "by_class": by_class,
        "suppressed": int(mgr.snapshot().get("suppressed", 0)),
        "pre_window_s": mgr.cfg.pre_window_s,
    }
    if oracle_bundles is not None:
        out["oracle_bundles"] = oracle_bundles
    return out


def load_bundle(mgr, cls):
    """First on-disk bundle of one incident class (None if absent)."""
    for b in mgr.bundles:
        if b["incident"] == cls and b.get("path"):
            with open(b["path"]) as f:
                return json.load(f)
    return None


def bundle_well_formed(bundle, trigger_phase):
    """The acceptance shape: the bundle's timeline carries the
    triggering event and the configured pre-window covers >= 30 s of
    history for the tracked series."""
    if bundle is None:
        return False
    trig = bundle.get("trigger", {})
    if trig.get("phase") != trigger_phase:
        return False
    if bundle.get("pre_window_s", 0) < 30.0:
        return False
    hist = bundle.get("history", {})
    rings = hist.get("rings", [])
    span_ok = any(r["period_s"] * r["capacity"] >= 30.0 for r in rings)
    return span_ok and bool(hist.get("series")) and \
        bool(bundle.get("ring"))


def build_traffic(vocab):
    """Deterministic phased workload: warm a shared prefix, flush it
    out of the small HBM pool (demote to the tier), revisit it (tier
    promotion), plus a burst wave and born-expired requests.  Returns
    ``(waves, burst_prompts, expired_prompts)`` — waves drain between
    submissions so the churn is reproducible."""
    import numpy as np

    rng = np.random.default_rng(11)
    pref = rng.integers(1, vocab, 16).tolist()
    mk = lambda: pref + rng.integers(1, vocab, 3).tolist()
    flush = [rng.integers(1, vocab, 24).tolist() for _ in range(4)]
    waves = [
        [mk(), mk()],                     # warm the shared prefix
        flush,                            # churn: prefix demotes
        [mk(), mk()],                     # revisit: tier promotion
        flush[:2] + [mk()],               # churn again + revisit
        [mk(), mk()],
    ]
    burst = [rng.integers(1, vocab, 12).tolist() for _ in range(10)]
    expired = [rng.integers(1, vocab, 8).tolist() for _ in range(3)]
    return waves, burst, expired


FAULT_RULES = [
    # transient aio read failures: retried, then sync-fallback
    {"subsystem": "aio_read", "rate": 0.5, "count": 8},
    # read-latency spikes
    {"subsystem": "aio_read", "mode": "latency", "latency_s": 0.02,
     "count": 5},
    # spill-write failures: bounded retry, then the entry drops
    {"subsystem": "aio_write", "rate": 0.3, "count": 4},
    # corrupt the first eight demoted pages: promote-side checksums
    # must catch every revisit of them and fall back to re-prefill
    {"subsystem": "kv_corrupt", "rate": 1.0, "count": 8},
    # slot-level exceptions targeting two requests that serve (r03 is
    # a burst request that beats the shed cut; r16 a tier revisit)
    {"subsystem": "slot", "match": "r03", "count": 1},
    {"subsystem": "slot", "match": "r16", "count": 1},
    # one queue-pressure burst (consumed by the traffic generator)
    {"subsystem": "burst", "rate": 1.0, "count": 1},
]


FLEET_FAULT_RULES = [
    # kill replica r1 on its 4th router-step poll — mid-traffic, with
    # requests queued and in flight there (failover: queued and
    # zero-token work re-submits to survivors, token-bearing slots
    # fail typed)
    {"subsystem": "replica", "mode": "error", "match": "r1",
     "count": 1, "after": 3},
    # one queue-pressure burst (consumed by the traffic generator):
    # aggregate depth past the fleet shed threshold → fleet-level
    # typed sheds on top of any per-replica ones
    {"subsystem": "burst", "rate": 1.0, "count": 1},
]


def fleet_main(args) -> int:
    """--fleet: the 3-replica soak (ISSUE 10 acceptance).  A seeded
    schedule kills one replica mid-traffic while the script drains and
    rejoins another; asserts every accepted request completes token-
    identical to a single-replica oracle or returns typed, zero leaks
    on every replica (dead one included), zero orphans, bounded
    failover recovery, and fleet accounting that reconciles across
    typed results, router counters and the rollup registry.  Stamps
    FLEET_SOAK.json, gated by tools/bench_gate.py."""
    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    from deepspeed_tpu import faults
    from deepspeed_tpu.fleet import DEAD, DRAINING, fleet_router
    from deepspeed_tpu.inference.serving import (RequestFailed,
                                                 RequestShed,
                                                 serving_engine)
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.utils.evidence import atomic_write_json

    t_start = time.perf_counter()
    cfg = gpt2.GPT2Config.tiny(dim=64, n_layers=2, n_heads=4,
                               max_seq_len=128)
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    waves, burst, expired = build_traffic(cfg.vocab_size)
    kw = dict(max_batch=2, page_size=8, num_pages=12, max_seq=64,
              prefill_bucket=8)

    # ---- single-replica fault-free oracle
    oracle_eng = serving_engine(params, cfg, prefix_cache=True, **kw)
    distinct, seen = [], set()
    for p in [p for w in waves for p in w] + burst + expired:
        t = tuple(p)
        if t not in seen:
            seen.add(t)
            distinct.append(p)
    for i, p in enumerate(distinct):
        oracle_eng.submit(f"o{i}", p, max_new_tokens=MAX_NEW)
    oracle_out = oracle_eng.run()
    oracle = {tuple(p): oracle_out[f"o{i}"]
              for i, p in enumerate(distinct)}
    oracle_eng.shutdown()

    router = fleet_router(
        params, cfg,
        fleet={"replicas": 3, "retry_budget": 2, "shed_queue_depth": 10,
               "digest_refresh_steps": 2},
        prefix_cache=True,
        slo={"tiers": {
            "interactive": {"ttft_s": 60.0, "deadline_s": 300.0},
            "expired": {"deadline_s": 0.001, "target": 0.5}},
            "default_tier": "interactive"},
        tracing={"ring_capacity": 65536},
        faults={"seed": args.seed, "rules": FLEET_FAULT_RULES},
        shed_queue_depth=4, shed_expired_deadline=True, **kw)

    prompts_by_id = {}
    rid = 0

    def submit(p, tier=None):
        nonlocal rid
        req_id = f"r{rid:02d}"
        rid += 1
        prompts_by_id[req_id] = p
        router.submit(req_id, p, max_new_tokens=MAX_NEW, tier=tier)
        return req_id

    t_kill = None
    salvaged = set()
    recovery_s = None

    def drive():
        nonlocal t_kill, salvaged, recovery_s
        steps = 0
        while router.has_work:
            router.step()
            if t_kill is None and router.last_failover is not None:
                # failover just ran inside this step: the router's
                # ledger names exactly the requests salvage re-placed
                # (inferring from resubmit counts would also catch
                # unrelated shed retries)
                t_kill = router.last_failover["t"]
                salvaged = set(router.last_failover["resubmitted"])
            if t_kill is not None and recovery_s is None and \
                    all(k in router.finished for k in salvaged):
                recovery_s = time.perf_counter() - t_kill
            steps += 1
            if steps > STEP_CAP or \
                    time.perf_counter() - t_start > WALL_CAP_S:
                return False
        return True

    hang = False
    drain_ok = True
    for w, wave in enumerate(waves):
        for p in wave:
            submit(p)
        _delay, fire = faults.poll("burst")
        if fire is not None:
            for p in burst:
                submit(p)
        hang = hang or not drive()
        if w == 1:
            # planned drain + rejoin of r2 between waves (the rolling-
            # restart primitive), while r1's kill rule is arming
            router.drain("r2")
            hang = hang or not drive()
            drain_ok = drain_ok and router.drained("r2") and \
                router.replicas["r2"].state == DRAINING
            router.rejoin("r2")
            drain_ok = drain_ok and \
                router.replicas["r2"].state == "healthy"
    for p in expired:
        submit(p, tier="expired")
    time.sleep(0.05)
    hang = hang or not drive()
    if recovery_s is None and t_kill is not None:
        recovery_s = time.perf_counter() - t_kill

    # ---- reconcile
    finished = dict(router.finished)
    completed = {k: v for k, v in finished.items()
                 if isinstance(v, list)}
    failed = {k: v for k, v in finished.items()
              if isinstance(v, RequestFailed)}
    shed = {k: v for k, v in finished.items()
            if isinstance(v, RequestShed)}
    mismatched = [k for k, v in completed.items()
                  if v != oracle[tuple(prompts_by_id[k])]]
    leaks = router.check_leaks()
    orphaned = router.orphaned()
    cnt = router.registry.snapshot()["counters"]
    status = router.statusz()
    ring = router.replicas["r0"].engine.tracer.recorder.events()
    checks = {
        "typed_results_partition":
            len(finished) == rid and
            len(completed) + len(failed) + len(shed) == rid,
        "router_counts":
            router._n_completed == len(completed) and
            router._n_failed == len(failed) and
            router._n_shed == len(shed),
        "registry_counters":
            int(cnt.get("fleet_completed_requests", 0)) ==
            len(completed) and
            int(cnt.get("fleet_failed_requests", 0)) == len(failed)
            and int(cnt.get("fleet_shed_requests", 0)) == len(shed),
        "failover_happened":
            router.replicas["r1"].state == DEAD and
            int(cnt.get("fleet_failovers", 0)) == 1,
        "trace_replica_events":
            sum(1 for e in ring if e[3] == "replica_dead") == 1 and
            sum(1 for e in ring if e[3] == "replica_drain") == 1 and
            sum(1 for e in ring if e[3] == "replica_rejoin") == 1,
        "drain_rejoin": drain_ok,
    }
    plan_snap = router._fault_plan.snapshot()
    router.shutdown()
    ok = (not mismatched and not hang and not leaks and not orphaned
          and all(checks.values()) and plan_snap["injected"] > 0
          and recovery_s is not None and recovery_s < 60.0)
    stamp = {
        "t": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "backend": jax.default_backend(),
        "model": "gpt2-tiny",
        "seed": args.seed,
        "replicas": 3,
        "ok": ok,
        "submitted": rid,
        "completed": len(completed),
        "failed": len(failed),
        "shed": len(shed),
        "shed_by_reason": dict(router._shed_by_reason),
        "resubmits": router._n_resubmits,
        "mismatched_requests": len(mismatched),
        "mismatched_ids": mismatched[:8],
        "hang": int(hang),
        "leak_count": len(leaks),
        "leaks": leaks[:8],
        "orphaned_requests": len(orphaned),
        "recovery_s": round(recovery_s, 3)
        if recovery_s is not None else None,
        "accounting_ok": int(all(checks.values())),
        "accounting": checks,
        "fleet": {k: v for k, v in status["fleet"].items()
                  if k != "replicas"},
        "replica_states": {r["replica"]: r["state"]
                           for r in status["fleet"]["replicas"]},
        "injected": plan_snap,
        "duration_s": round(time.perf_counter() - t_start, 2),
    }
    atomic_write_json(stamp, args.json_out)
    print(json.dumps({k: v for k, v in stamp.items()
                      if k not in ("injected", "fleet")},
                     indent=1, sort_keys=True))
    print("→", args.json_out)
    return 0 if ok else 1


DISAGG_FAULT_RULES = [
    # the first fabric EXPORT opportunity fails: that migration falls
    # back to re-prefill (counted, never wrong)
    {"subsystem": "fabric", "mode": "error", "match": "export",
     "count": 1},
    # fetch-latency spikes push migrations toward their timeout
    {"subsystem": "fabric", "mode": "latency", "match": "fetch",
     "latency_s": 0.01, "count": 3},
    # corrupt the first two pages published INTO the fabric after
    # their checksums were recorded: the admitting replica's
    # promotion-time crc must catch them and re-prefill (the
    # corrupt-after-checksum leg)
    {"subsystem": "fabric", "mode": "error", "match": "corrupt",
     "count": 2},
    # kill decode replica r2 mid-traffic — handed-off decode legs
    # queued or zero-token in flight there re-place on the survivors,
    # prefill legs re-run from the prompt
    {"subsystem": "replica", "mode": "error", "match": "r2",
     "count": 1, "after": 4},
    # one queue-pressure burst (consumed by the traffic generator)
    {"subsystem": "burst", "rate": 1.0, "count": 1},
]


def disagg_main(args) -> int:
    """--disagg: the disaggregated prefill/decode + KV-fabric soak
    (ISSUE 12 acceptance).  A roles-split fleet (1 prefill, 2 decode)
    serves phased shared-prefix traffic while the seeded schedule
    fails fabric exports, delays fetches, corrupts in-fabric pages
    after their checksums, and kills a decode replica mid-handoff;
    the script also drains + rejoins the ONLY prefill replica (role
    fallback).  Asserts: every completed request token-identical to a
    single-engine oracle, typed partition with zero orphans, zero
    leaks on every replica (dead one included), handoffs + migrations
    actually happened, and the corruption was caught by the importer's
    checksum.  Stamps DISAGG_SOAK.json, gated by bench_gate."""
    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    from deepspeed_tpu import faults
    from deepspeed_tpu.fleet import DEAD, DRAINING, fleet_router
    from deepspeed_tpu.inference.serving import (RequestFailed,
                                                 RequestShed,
                                                 serving_engine)
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.utils.evidence import atomic_write_json

    t_start = time.perf_counter()
    cfg = gpt2.GPT2Config.tiny(dim=64, n_layers=2, n_heads=4,
                               max_seq_len=128)
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    waves, burst, expired = build_traffic(cfg.vocab_size)
    kw = dict(max_batch=2, page_size=8, num_pages=24, max_seq=64,
              prefill_bucket=8, prefix_cache=True,
              kv_tier={"host_pool_bytes": 64 << 20})

    # ---- single-engine fault-free oracle
    oracle_eng = serving_engine(params, cfg, **kw)
    distinct, seen = [], set()
    for p in [p for w in waves for p in w] + burst + expired:
        t = tuple(p)
        if t not in seen:
            seen.add(t)
            distinct.append(p)
    for i, p in enumerate(distinct):
        oracle_eng.submit(f"o{i}", p, max_new_tokens=MAX_NEW)
    oracle_out = oracle_eng.run()
    oracle = {tuple(p): oracle_out[f"o{i}"]
              for i, p in enumerate(distinct)}
    oracle_eng.shutdown()

    router = fleet_router(
        params, cfg,
        fleet={"replicas": 3, "retry_budget": 2,
               "shed_queue_depth": 10, "digest_refresh_steps": 2,
               "roles": {"prefill": 1, "decode": 2}},
        fabric=True,
        slo={"tiers": {
            "interactive": {"ttft_s": 60.0, "deadline_s": 300.0},
            "expired": {"deadline_s": 0.001, "target": 0.5}},
            "default_tier": "interactive"},
        tracing={"ring_capacity": 65536},
        faults={"seed": args.seed, "rules": DISAGG_FAULT_RULES},
        shed_queue_depth=4, shed_expired_deadline=True, **kw)

    prompts_by_id = {}
    rid = 0

    def submit(p, tier=None):
        nonlocal rid
        req_id = f"r{rid:02d}"
        rid += 1
        prompts_by_id[req_id] = p
        router.submit(req_id, p, max_new_tokens=MAX_NEW, tier=tier)
        return req_id

    t_kill = None
    salvaged = set()
    recovery_s = None

    def drive():
        nonlocal t_kill, salvaged, recovery_s
        steps = 0
        while router.has_work:
            router.step()
            if t_kill is None and router.last_failover is not None:
                t_kill = router.last_failover["t"]
                salvaged = set(router.last_failover["resubmitted"])
            if t_kill is not None and recovery_s is None and \
                    all(k in router.finished for k in salvaged):
                recovery_s = time.perf_counter() - t_kill
            steps += 1
            if steps > STEP_CAP or \
                    time.perf_counter() - t_start > WALL_CAP_S:
                return False
        return True

    hang = False
    drain_ok = True
    for w, wave in enumerate(waves):
        for p in wave:
            submit(p)
        _delay, fire = faults.poll("burst")
        if fire is not None:
            for p in burst:
                submit(p)
        hang = hang or not drive()
        if w == 1:
            # drain + rejoin the ONLY prefill replica mid-soak: role
            # preference must degrade (prefill legs fall back to the
            # decode pool) and come back after rejoin
            router.drain("r0")
            hang = hang or not drive()
            drain_ok = drain_ok and router.drained("r0") and \
                router.replicas["r0"].state == DRAINING
            router.rejoin("r0")
            drain_ok = drain_ok and \
                router.replicas["r0"].state == "healthy"
    for p in expired:
        submit(p, tier="expired")
    time.sleep(0.05)
    hang = hang or not drive()
    if recovery_s is None and t_kill is not None:
        recovery_s = time.perf_counter() - t_kill

    # ---- reconcile
    finished = dict(router.finished)
    completed = {k: v for k, v in finished.items()
                 if isinstance(v, list)}
    failed = {k: v for k, v in finished.items()
              if isinstance(v, RequestFailed)}
    shed = {k: v for k, v in finished.items()
            if isinstance(v, RequestShed)}
    mismatched = [k for k, v in completed.items()
                  if v != oracle[tuple(prompts_by_id[k])]]
    leaks = router.check_leaks()
    orphaned = router.orphaned()
    cnt = router.registry.snapshot()["counters"]
    status = router.statusz()
    fab = status["fleet"]["fabric"]
    checksum_caught = sum(
        int(rep.engine.registry.snapshot()["counters"].get(
            "kv_tier_checksum_failures", 0))
        for rep in router.replicas.values())
    checks = {
        "typed_results_partition":
            len(finished) == rid and
            len(completed) + len(failed) + len(shed) == rid,
        "router_counts":
            router._n_completed == len(completed) and
            router._n_failed == len(failed) and
            router._n_shed == len(shed),
        "registry_counters":
            int(cnt.get("fleet_completed_requests", 0)) ==
            len(completed) and
            int(cnt.get("fleet_failed_requests", 0)) == len(failed)
            and int(cnt.get("fleet_shed_requests", 0)) == len(shed),
        "failover_happened":
            router.replicas["r2"].state == DEAD and
            int(cnt.get("fleet_failovers", 0)) == 1,
        "handoffs_happened": fab["handoffs"] > 0,
        "migrations_happened": fab["migrations"] >= 1,
        "export_faults_fell_back":
            fab["export_failures"] >= 1 and
            fab["migration_fallbacks"] >= 1,
        "corruption_caught_by_importer":
            fab["corrupted"] >= 1 and checksum_caught >= 1,
        "drain_rejoin": drain_ok,
    }
    plan_snap = router._fault_plan.snapshot()
    router.shutdown()
    ok = (not mismatched and not hang and not leaks and not orphaned
          and all(checks.values()) and plan_snap["injected"] > 0
          and recovery_s is not None and recovery_s < 60.0)
    stamp = {
        "t": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "backend": jax.default_backend(),
        "model": "gpt2-tiny",
        "seed": args.seed,
        "replicas": 3,
        "roles": {"prefill": 1, "decode": 2},
        "ok": ok,
        "submitted": rid,
        "completed": len(completed),
        "failed": len(failed),
        "shed": len(shed),
        "shed_by_reason": dict(router._shed_by_reason),
        "resubmits": router._n_resubmits,
        "handoffs": fab["handoffs"],
        "migrations": fab["migrations"],
        "migration_fallbacks": fab["migration_fallbacks"],
        "fabric_bytes_moved": fab["bytes_moved"],
        "checksum_caught": checksum_caught,
        "mismatched_requests": len(mismatched),
        "mismatched_ids": mismatched[:8],
        "hang": int(hang),
        "leak_count": len(leaks),
        "leaks": leaks[:8],
        "orphaned_requests": len(orphaned),
        "recovery_s": round(recovery_s, 3)
        if recovery_s is not None else None,
        "accounting_ok": int(all(checks.values())),
        "accounting": checks,
        "replica_states": {r["replica"]: r["state"]
                           for r in status["fleet"]["replicas"]},
        "injected": plan_snap,
        "duration_s": round(time.perf_counter() - t_start, 2),
    }
    atomic_write_json(stamp, args.json_out)
    print(json.dumps({k: v for k, v in stamp.items()
                      if k not in ("injected",)},
                     indent=1, sort_keys=True))
    print("→", args.json_out)
    return 0 if ok else 1


ELASTIC_FAULT_RULES = [
    # the FIRST autoscaler spawn attempt: engine-factory failure (the
    # scale-up aborts, is counted, and retries next evaluation)
    {"subsystem": "scale", "mode": "error", "count": 1},
    # the retry: a 30 ms slow cold-start (lands in the
    # autoscale_cold_start_seconds histogram)
    {"subsystem": "scale", "mode": "latency", "latency_s": 0.03,
     "count": 1, "after": 1},
]


def elastic_main(args) -> int:
    """--elastic: the autoscaler soak (ISSUE 11 acceptance).  A
    scripted load sine wave drives replica count up (through an
    injected factory failure + slow cold-start) and back down, a
    rolling weight update runs with one scripted mid-rollout replica
    kill, and a second rollout is halted and rolled back by an
    injected burn-rate trip.  Asserts: every completed request
    token-identical to the oracle (rollouts swap VALUE-identical
    weights relabeled v2/v3, so greedy outputs never change), every
    submitted request reaches a typed terminal result (nothing
    dropped), zero orphans and leaks on every replica, scale events
    observed in both directions, and every scale/rollout event in the
    trace ring exactly once.  Stamps ELASTIC_SOAK.json, gated by
    tools/bench_gate.py."""
    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    from deepspeed_tpu.autoscale import FleetAutoscaler
    from deepspeed_tpu.fleet import DEAD, fleet_router
    from deepspeed_tpu.inference.serving import (RequestFailed,
                                                 RequestShed,
                                                 serving_engine)
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.telemetry import MetricsRegistry
    from deepspeed_tpu.utils.evidence import atomic_write_json

    t_start = time.perf_counter()
    cfg = gpt2.GPT2Config.tiny(dim=64, n_layers=2, n_heads=4,
                               max_seq_len=128)
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    # value-identical trees under new version labels: the swap/rollback
    # machinery runs for real, while greedy outputs stay a pure
    # function of the prompt — the oracle stays valid across versions
    v2_params = jax.tree.map(lambda x: x, params)
    v3_params = jax.tree.map(lambda x: x, params)

    import numpy as np

    rng = np.random.default_rng(17)
    pref = rng.integers(1, cfg.vocab_size, 16).tolist()
    mk = lambda: pref + rng.integers(1, cfg.vocab_size, 3).tolist()
    low = [[rng.integers(1, cfg.vocab_size, 10).tolist(), mk()]
           for _ in range(3)]
    crest = [rng.integers(1, cfg.vocab_size, 12).tolist()
             for _ in range(22)]
    trickle = [mk() for _ in range(6)]
    strict_wave = [rng.integers(1, cfg.vocab_size, 8).tolist()
                   for _ in range(8)]

    all_prompts = [p for w in low for p in w] + crest + trickle \
        + strict_wave
    distinct, seen = [], set()
    for p in all_prompts:
        t = tuple(p)
        if t not in seen:
            seen.add(t)
            distinct.append(p)
    kw = dict(max_batch=2, page_size=8, num_pages=12, max_seq=64,
              prefill_bucket=8)
    oracle_eng = serving_engine(params, cfg, prefix_cache=True, **kw)
    for i, p in enumerate(distinct):
        oracle_eng.submit(f"o{i}", p, max_new_tokens=MAX_NEW)
    oracle_out = oracle_eng.run()
    oracle = {tuple(p): oracle_out[f"o{i}"]
              for i, p in enumerate(distinct)}
    oracle_eng.shutdown()

    slo = {"tiers": {
        "lax": {"ttft_s": 60.0, "deadline_s": 300.0, "target": 0.5},
        # impossible objective: any finished strict request violates,
        # so burn = 1/(1-0.5) = 2.0 — the injected burn-rate trip.
        # Short window: after the rollback the violations must age
        # out fast enough for the final trough to read as calm (a
        # burn still in-window is up-pressure, by design)
        "strict": {"ttft_s": 1e-6, "target": 0.5}},
        "default_tier": "lax", "window_s": 8.0,
        "burn_windows_s": [8.0]}
    ekw = dict(prefix_cache=True, slo=slo, shed_queue_depth=6, **kw)
    inc_dir = tempfile.mkdtemp(prefix="dstpu_elastic_inc_")
    router = fleet_router(
        params, cfg,
        fleet={"replicas": 2, "retry_budget": 2,
               "shed_queue_depth": 16,
               # scaling, not quarantine, is the elastic response to
               # crest-of-wave shed activity
               "quarantine_after": 10_000,
               "digest_refresh_steps": 2},
        tracing={"ring_capacity": 131072},
        faults={"seed": args.seed, "rules": ELASTIC_FAULT_RULES},
        # fleet-level incident engine (ISSUE 15): the shared flight
        # recorder carries every replica's slo_burn_alert plus the
        # autoscaler's rollout_halt/rolled_back — the scripted burn
        # rollback below must land a "rollback" bundle
        history=dict(HISTORY_BLOCK),
        incidents=incidents_block(inc_dir),
        **ekw)

    def factory(rid, streamed=False):
        return serving_engine(
            params, cfg, replica_id=rid, tracing=router.tracer,
            telemetry=MetricsRegistry(namespace=f"dstpu_{rid}"),
            **ekw)

    auto = FleetAutoscaler(router, factory, autoscale={
        # floor 2: the trough must not shrink the fleet below the
        # rollout script's needs (a real fleet would pick its floor
        # for the same reason — rolling updates need a survivor)
        "min_replicas": 2, "max_replicas": 3,
        "eval_interval_steps": 2, "scale_up_queue_depth": 3.0,
        "scale_down_queue_depth": 0.5, "up_after": 1, "down_after": 6,
        # the tiny CPU model drains a burst in tens of milliseconds —
        # any wall-clock cooldown would outlive the pressure window,
        # so the soak runs uncooled and leans on the streak hysteresis
        "cooldown_s": 0.0, "rollout_soak_steps": 25,
        "rollback_burn_threshold": 1.0, "rollback_min_finished": 1})

    prompts_by_id = {}
    rid_n = 0

    def submit(p, tier=None):
        nonlocal rid_n
        req_id = f"r{rid_n:03d}"
        rid_n += 1
        prompts_by_id[req_id] = p
        router.submit(req_id, p, max_new_tokens=MAX_NEW, tier=tier)
        return req_id

    hang = False

    def drive(until=None):
        """Step until idle (and `until` satisfied, when given)."""
        nonlocal hang
        steps = 0
        while router.has_work or auto.rollout_active \
                or auto._retiring or (until is not None and
                                      not until()):
            auto.step()
            steps += 1
            if steps > STEP_CAP or \
                    time.perf_counter() - t_start > WALL_CAP_S:
                hang = True
                return

    def idle_until_live(n, timeout_s=20.0):
        """Tick the idle fleet until the live replica count reaches
        ``n`` (scale-down retires the surplus; heal spawns cover a
        deficit) — the trough half of the sine wave."""
        nonlocal hang
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < timeout_s:
            auto.step()
            live_n = sum(1 for rep in router.replicas.values()
                         if rep.state != DEAD)
            if live_n == n and not auto._retiring \
                    and not router.has_work:
                return
            time.sleep(0.002)
        hang = True

    # ---- phase A: trough traffic (2 replicas idle along)
    for wave in low:
        for p in wave:
            submit(p)
        drive()
    # ---- phase B: crest — a burst the 2-replica fleet cannot absorb
    # scales up THROUGH the injected factory failure (first attempt)
    # and the slow cold-start (the retry lands at the next pressured
    # evaluation while the queue is still deep)
    for p in crest:
        submit(p)
    drive()
    scale_up_seen = auto.status()["scale_ups"]
    # ---- phase B2: trough — sustained idle retires the crest's
    # extra replica back down to the floor
    idle_until_live(2)
    scale_down_seen = auto.status()["scale_downs"]
    # ---- phase C: rolling update to v2 with one scripted mid-rollout
    # replica kill (the next not-yet-updated target dies right after
    # the first replica updates; the walk continues on survivors)
    auto.rollout(v2_params, version="v2")
    killed = None
    ti = 0
    steps = 0
    while auto.rollout_active or router.has_work:
        if ti < len(trickle):
            submit(trickle[ti])
            ti += 1
        auto.step()
        ro = auto._rollout
        if killed is None and ro is not None and ro["updated"]:
            nxt = next(
                (r for r in ro["plan"][ro["i"]:]
                 if r in router.replicas
                 and router.replicas[r].state != DEAD
                 and r not in ro["updated"]), None)
            if nxt is not None:
                router.kill(nxt, error="scripted mid-rollout death")
                killed = nxt
        steps += 1
        if steps > STEP_CAP or \
                time.perf_counter() - t_start > WALL_CAP_S:
            hang = True
            break
    rollout1 = dict(auto.last_rollout or {})
    # ---- phase C2: the kill left the fleet under its floor — the
    # next evaluations heal it back up, and the fresh replica swaps
    # onto v2 (the completed rollout's version) before it serves
    idle_until_live(2)
    # ---- phase D: rollout to v3 halted by the strict tier's burn
    # trip and rolled back — versions must return to v2
    auto.rollout(v3_params, version="v3")
    si = 0
    steps = 0
    while auto.rollout_active or router.has_work:
        if si < len(strict_wave):
            submit(strict_wave[si], tier="strict")
            si += 1
        auto.step()
        steps += 1
        if steps > STEP_CAP or \
                time.perf_counter() - t_start > WALL_CAP_S:
            hang = True
            break
    rollout2 = dict(auto.last_rollout or {})
    # ---- phase E: final trough — the fleet settles at its floor
    idle_until_live(auto.cfg.min_replicas)
    # final evaluation: classify anything the last steps landed
    router.incident_mgr.evaluate()

    # ---- reconcile
    finished = dict(router.finished)
    completed = {k: v for k, v in finished.items()
                 if isinstance(v, list)}
    failed = {k: v for k, v in finished.items()
              if isinstance(v, RequestFailed)}
    shed = {k: v for k, v in finished.items()
            if isinstance(v, RequestShed)}
    mismatched = [k for k, v in completed.items()
                  if v != oracle[tuple(prompts_by_id[k])]]
    leaks = router.check_leaks()
    orphaned = router.orphaned()
    cnt = router.registry.snapshot()["counters"]
    st = auto.status()
    # incidents (ISSUE 15): the burn-tripped rollback must have
    # produced a (deduped) rollback bundle carrying the rollout_halt
    # trigger and the pre-trip history window
    inc = incidents_summary(router.incident_mgr)
    inc["rollback_bundles"] = inc["by_class"].get("rollback", 0)
    rb_bundle = load_bundle(router.incident_mgr, "rollback")
    inc["rollback_bundle_well_formed"] = int(
        bundle_well_formed(rb_bundle, "rollout_halt"))
    incidents_ok = (inc["rollback_bundles"] >= 1
                    and inc["rollback_bundle_well_formed"] == 1)
    live_versions = {rep.id: str(rep.version)
                     for rep in router.replicas.values()
                     if rep.state != DEAD}
    ring = router.tracer.recorder.events()
    from collections import Counter
    ring_kinds = Counter(e[3] for e in ring
                         if e[3].startswith(("autoscale_",
                                             "rollout_")))
    led_kinds = Counter(e["kind"] for e in auto.events)
    checks = {
        "typed_results_partition":
            len(finished) == rid_n and
            len(completed) + len(failed) + len(shed) == rid_n,
        "router_counts":
            router._n_completed == len(completed) and
            router._n_failed == len(failed) and
            router._n_shed == len(shed),
        "registry_counters":
            int(cnt.get("fleet_completed_requests", 0)) ==
            len(completed) and
            int(cnt.get("fleet_failed_requests", 0)) == len(failed)
            and int(cnt.get("fleet_shed_requests", 0)) == len(shed),
        "scaled_up": st["scale_ups"] >= 2 and scale_up_seen >= 1,
        "scaled_down": st["scale_downs"] >= 1
            and scale_down_seen >= 1,
        "factory_failure_retried":
            st["factory_failures"] == 1 and st["scale_ups"] >= 1,
        "rollout_completed_with_kill":
            rollout1.get("completed", False) and killed is not None
            and rollout1.get("skipped") == [killed],
        "rollback_on_burn_trip":
            rollout2.get("halted", False)
            and rollout2.get("rolled_back", False),
        "versions_on_v2":
            bool(live_versions)
            and all(v == "v2" for v in live_versions.values()),
        "events_exactly_once":
            bool(led_kinds) and dict(ring_kinds) == dict(led_kinds),
    }
    plan_snap = router._fault_plan.snapshot()
    router.shutdown()
    ok = (not mismatched and not hang and not leaks and not orphaned
          and all(checks.values()) and plan_snap["injected"] >= 2
          and incidents_ok)
    stamp = {
        "t": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "backend": jax.default_backend(),
        "model": "gpt2-tiny",
        "seed": args.seed,
        "ok": ok,
        "submitted": rid_n,
        "completed": len(completed),
        "failed": len(failed),
        "shed": len(shed),
        "shed_by_reason": dict(router._shed_by_reason),
        "mismatched_requests": len(mismatched),
        "mismatched_ids": mismatched[:8],
        "hang": int(hang),
        "leak_count": len(leaks),
        "leaks": leaks[:8],
        "orphaned_requests": len(orphaned),
        "accounting_ok": int(all(checks.values())),
        "accounting": checks,
        "scale_ups": st["scale_ups"],
        "scale_downs": st["scale_downs"],
        "factory_failures": st["factory_failures"],
        "killed_mid_rollout": killed,
        "rollout_v2": rollout1,
        "rollout_v3": rollout2,
        "live_versions": live_versions,
        "event_counts": dict(led_kinds),
        "incidents": inc,
        "injected": plan_snap,
        "duration_s": round(time.perf_counter() - t_start, 2),
    }
    atomic_write_json(stamp, args.json_out)
    print(json.dumps({k: v for k, v in stamp.items()
                      if k not in ("injected",)},
                     indent=1, sort_keys=True))
    print("→", args.json_out)
    return 0 if ok else 1


PROC_FAULT_RULES = [
    # one corrupted parent->r0 frame: the CHILD's ring consumer
    # rejects it by crc (the frame never decodes to wrong bytes) and
    # the proxy's idempotent rpc retry resends — correctness never
    # rides the wire.  Armed past the warmup sends so the one rpc
    # timeout it costs lands mid-soak, not on a first-compile step
    {"subsystem": "transport", "mode": "error", "match": "corrupt:r0",
     "count": 1, "after": 30},
    # recv-side latency spikes on r2's channel (the wire slows, the
    # stream stays ordered)
    {"subsystem": "transport", "mode": "latency", "match": "recv:r2",
     "latency_s": 0.01, "count": 5},
    # one injected recv failure on r2, absorbed by the rpc retry
    {"subsystem": "transport", "mode": "error", "match": "recv:r2",
     "count": 1},
]


def procs_main(args) -> int:
    """--procs: the out-of-process fleet soak (ISSUE 20 acceptance).
    Three REAL child replica processes serve behind the wire while the
    scripted schedule corrupts and delays transport frames, and the
    soak delivers an ACTUAL SIGKILL to one child mid-generation.
    Asserts: every completed request token-identical to a single
    in-process oracle, typed partition (nothing silently dropped,
    nothing generated twice), zero leaks on the survivors and zero
    orphaned requests, bounded recovery measured from the kill
    SIGNAL, exactly one replica_failover incident bundle, and no
    orphan child processes after shutdown.  Stamps PROC_SOAK.json,
    gated by tools/bench_gate.py."""
    import signal as _signal

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    # the children pin this flag (tools/replica_child.py); the oracle
    # must draw the same init params or every token comparison is
    # cross-model noise
    jax.config.update("jax_threefry_partitionable", True)

    import numpy as np

    from deepspeed_tpu.inference.serving import (RequestFailed,
                                                 RequestShed,
                                                 serving_engine)
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.proc_fleet import (DEFAULT_CHILD_SPEC,
                                          proc_fleet_router)
    from deepspeed_tpu.utils.evidence import atomic_write_json

    t_start = time.perf_counter()
    spec = DEFAULT_CHILD_SPEC
    cfg = gpt2.GPT2Config.tiny(**{k: v for k, v in
                                  spec["model"].items()
                                  if k != "family"})
    params = gpt2.init_params(jax.random.PRNGKey(spec["seed"]), cfg)
    rng = np.random.default_rng(args.seed + 31)
    # enough tokens that the fleet is still mid-generation when the
    # kill lands: the children step their engines autonomously
    # between polls, so a short workload can drain before the router
    # ever observes the death
    max_new = 12
    prompts = [rng.integers(1, cfg.vocab_size, 6).tolist()
               for _ in range(24)]

    # ---- single in-process fault-free oracle (identical params: the
    # children rebuild from the same (model, seed) spec)
    oracle_eng = serving_engine(params, cfg, **spec["engine"])
    for i, p in enumerate(prompts):
        oracle_eng.submit(f"o{i}", p, max_new_tokens=max_new)
    oracle_out = oracle_eng.run()
    oracle = {f"r{i:02d}": oracle_out[f"o{i}"]
              for i in range(len(prompts))}
    oracle_eng.shutdown()

    inc_dir = tempfile.mkdtemp(prefix="dstpu-proc-incidents-")
    # poll_timeout_s stays at its 10 s default: a child's FIRST steps
    # pay XLA compiles, and a tighter rpc bound reads a compiling
    # child as a dead one on a slow box
    router = proc_fleet_router(
        spec,
        proc_fleet={"replicas": 3},
        fleet={"replicas": 3, "retry_budget": 2,
               "digest_refresh_steps": 2},
        tracing={"ring_capacity": 65536},
        faults={"seed": args.seed, "rules": PROC_FAULT_RULES},
        history=dict(HISTORY_BLOCK),
        incidents=incidents_block(inc_dir))

    spawn_s = time.perf_counter() - t_start
    t_kill = None
    salvaged = set()
    recovery_s = None
    hang = False
    try:
        for i, p in enumerate(prompts):
            router.submit(f"r{i:02d}", p, max_new_tokens=max_new)
        steps = 0
        while router.has_work:
            router.step()
            steps += 1
            if t_kill is None and steps == 1:
                # a REAL SIGKILL mid-generation, right after the first
                # harvest: no drain, no goodbye frame — the address
                # space just vanishes with requests queued and in
                # flight on r1
                t_kill = router.kill_child("r1", _signal.SIGKILL)
            fo_now = router.last_failover
            if not salvaged and fo_now is not None and \
                    fo_now.get("replica") == "r1":
                salvaged = set(fo_now["resubmitted"])
            if t_kill is not None and recovery_s is None and \
                    fo_now is not None and \
                    fo_now.get("replica") == "r1" and \
                    all(k in router.finished for k in salvaged):
                recovery_s = time.perf_counter() - t_kill
            if steps > STEP_CAP or \
                    time.perf_counter() - t_start > WALL_CAP_S:
                hang = True
                break
        if recovery_s is None and t_kill is not None:
            recovery_s = time.perf_counter() - t_kill

        # ---- reconcile
        finished = dict(router.finished)
        completed = {k: v for k, v in finished.items()
                     if isinstance(v, list)}
        failed = {k: v for k, v in finished.items()
                  if isinstance(v, RequestFailed)}
        shed = {k: v for k, v in finished.items()
                if isinstance(v, RequestShed)}
        mismatched = [k for k, v in completed.items()
                      if list(v) != list(oracle[k])]
        leaks = router.check_leaks()
        orphaned = router.orphaned()
        cnt = router.registry.snapshot()["counters"]
        fo = router.last_failover or {}
        ring = router.tracer.recorder.events()
        # wire accounting: every channel lives in THIS process, so the
        # injected schedule must be visible in the per-replica
        # transport families (the child-side corrupt detection happens
        # in the child; the router sees the injection + the retry)
        wire = {}
        for rep in router.replicas.values():
            c = rep.engine.registry.snapshot()["counters"]
            for k, v in c.items():
                if k.startswith("transport_"):
                    wire[k] = wire.get(k, 0) + int(v)
        inc = incidents_summary(router.incident_mgr)
        fo_bundles = inc["by_class"].get("replica_failover", 0)
        fo_bundle = load_bundle(router.incident_mgr,
                                "replica_failover")
        plan_snap = router._fault_plan.snapshot()
        checks = {
            "typed_results_partition":
                len(finished) == len(prompts) and
                len(completed) + len(failed) + len(shed)
                == len(prompts),
            "failover_happened":
                fo.get("replica") == "r1" and
                int(cnt.get("fleet_failovers", 0)) == 1,
            "never_double_generate":
                set(fo.get("resubmitted", [])).isdisjoint(
                    fo.get("failed_typed", [])),
            "trace_replica_dead":
                sum(1 for e in ring if e[3] == "replica_dead") == 1,
            "failover_bundle":
                fo_bundles == 1 and
                bundle_well_formed(fo_bundle, "replica_dead"),
            "wire_faults_injected":
                wire.get("transport_injected_faults", 0) >= 2 and
                plan_snap["injected"] >= 2,
            "wire_moved_bytes":
                wire.get("transport_tx_frames", 0) > 0 and
                wire.get("transport_rx_bytes", 0) > 0,
        }
        replica_states = {rid: rep.state
                          for rid, rep in router.replicas.items()}
    finally:
        procs = [rep.engine.proc
                 for rep in router.replicas.values()]
        router.shutdown()
    reaped = all(p.poll() is not None for p in procs)
    checks["no_orphan_processes"] = reaped
    ok = (not mismatched and not hang and not leaks and not orphaned
          and all(checks.values())
          and recovery_s is not None and recovery_s < 60.0)
    stamp = {
        "t": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "backend": jax.default_backend(),
        "model": "gpt2-tiny",
        "seed": args.seed,
        "replicas": 3,
        "transport": "shm",
        "ok": ok,
        "submitted": len(prompts),
        "completed": len(completed),
        "failed": len(failed),
        "shed": len(shed),
        "resubmitted": len(fo.get("resubmitted", [])),
        "failed_typed": len(fo.get("failed_typed", [])),
        "mismatched_requests": len(mismatched),
        "mismatched_ids": mismatched[:8],
        "hang": int(hang),
        "leak_count": len(leaks),
        "orphaned_requests": len(orphaned),
        "orphan_processes": int(not reaped),
        "recovery_s": round(recovery_s, 3)
        if recovery_s is not None else None,
        "spawn_s": round(spawn_s, 2),
        "accounting_ok": int(all(checks.values())),
        "accounting": checks,
        "replica_states": replica_states,
        "wire": wire,
        "incidents": inc,
        "injected": plan_snap,
        "duration_s": round(time.perf_counter() - t_start, 2),
    }
    atomic_write_json(stamp, args.json_out)
    print(json.dumps({k: v for k, v in stamp.items()
                      if k not in ("injected", "wire", "incidents")},
                     indent=1, sort_keys=True))
    print("→", args.json_out)
    return 0 if ok else 1


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend in-process")
    ap.add_argument("--seed", type=int, default=0,
                    help="fault-plan seed (same seed = same schedule)")
    ap.add_argument("--fleet", action="store_true",
                    help="run the 3-replica fleet soak (replica kill + "
                         "drain/rejoin) instead of the single-engine "
                         "soak; stamps FLEET_SOAK.json by default")
    ap.add_argument("--elastic", action="store_true",
                    help="run the autoscaler soak (load sine wave, "
                         "scale up/down through injected scale "
                         "faults, rolling update with a mid-rollout "
                         "kill, burn-trip rollback); stamps "
                         "ELASTIC_SOAK.json by default")
    ap.add_argument("--disagg", action="store_true",
                    help="run the disaggregated prefill/decode + KV "
                         "fabric soak (fabric export/fetch/corrupt "
                         "faults + mid-handoff decode-replica kill + "
                         "prefill-pool drain); stamps "
                         "DISAGG_SOAK.json by default")
    ap.add_argument("--procs", action="store_true",
                    help="run the out-of-process fleet soak (3 child "
                         "replica processes over the shm wire, "
                         "scripted transport corrupt/latency faults, "
                         "a real mid-generation SIGKILL); stamps "
                         "PROC_SOAK.json by default")
    ap.add_argument("--json-out", default=None)
    args = ap.parse_args()
    if args.json_out is None:
        args.json_out = os.path.join(
            REPO, "ELASTIC_SOAK.json" if args.elastic
            else "DISAGG_SOAK.json" if args.disagg
            else "FLEET_SOAK.json" if args.fleet
            else "PROC_SOAK.json" if args.procs
            else "CHAOS_SOAK.json")
    if args.elastic:
        return elastic_main(args)
    if args.disagg:
        return disagg_main(args)
    if args.fleet:
        return fleet_main(args)
    if args.procs:
        return procs_main(args)

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    from deepspeed_tpu import faults
    from deepspeed_tpu.inference.serving import (RequestFailed,
                                                 RequestShed,
                                                 serving_engine)
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.utils.evidence import atomic_write_json
    from deepspeed_tpu.utils.watchdog import Watchdog

    t_start = time.perf_counter()
    cfg = gpt2.GPT2Config.tiny(dim=64, n_layers=2, n_heads=4,
                               max_seq_len=128)
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    waves, burst, expired = build_traffic(cfg.vocab_size)

    kw = dict(max_batch=2, page_size=8, num_pages=12, max_seq=64,
              prefill_bucket=8,
              # compile sentinel on BOTH arms: a soak that survives
              # faults, shedding and tier churn must also never
              # recompile after its first token — the stamp's
              # steady_state_recompiles is gated at exactly 0
              devprof=True)

    # ---- fault-free oracle: every distinct prompt's greedy completion.
    # The oracle ALSO runs history+incidents (same cadences as the
    # chaos arm): it is the false-positive gate — a fault-free run must
    # produce ZERO bundles (gated in BENCH_BASELINE).
    oracle_inc_dir = tempfile.mkdtemp(prefix="dstpu_chaos_oracle_inc_")
    oracle_eng = serving_engine(params, cfg, prefix_cache=True,
                                history=dict(HISTORY_BLOCK),
                                incidents=incidents_block(oracle_inc_dir),
                                **kw)
    distinct = []
    seen = set()
    for p in [p for w in waves for p in w] + burst + expired:
        t = tuple(p)
        if t not in seen:
            seen.add(t)
            distinct.append(p)
    for i, p in enumerate(distinct):
        oracle_eng.submit(f"o{i}", p, max_new_tokens=MAX_NEW)
    oracle_out = oracle_eng.run()
    oracle = {tuple(p): oracle_out[f"o{i}"]
              for i, p in enumerate(distinct)}
    oracle_bundles = len(oracle_eng.incident_mgr.bundles)
    oracle_eng.shutdown()

    # ---- the chaos engine: full I/O-tier stack + shedding + faults +
    # the incident engine.  burn_threshold 1.5 makes the expired tier's
    # burn (violation rate 1.0 / budget 0.5 = 2.0) a SCRIPTED trip in
    # every window — the slo_burn_alert the incident engine must turn
    # into exactly one bundle.
    nvme_dir = tempfile.mkdtemp(prefix="dstpu_chaos_nvme_")
    dump_dir = tempfile.mkdtemp(prefix="dstpu_chaos_dump_")
    inc_dir = tempfile.mkdtemp(prefix="dstpu_chaos_inc_")
    eng = serving_engine(
        params, cfg, prefix_cache=True,
        kv_tier={"enabled": True, "host_pool_bytes": 4096,
                 "nvme_dir": nvme_dir, "io_retries": 2,
                 "io_retry_backoff_s": 0.01, "disable_after": 0},
        slo={"tiers": {
            "interactive": {"ttft_s": 60.0, "deadline_s": 300.0},
            "expired": {"deadline_s": 0.001, "target": 0.5}},
            "default_tier": "interactive", "burn_threshold": 1.5},
        tracing={"ring_capacity": 65536, "dump_dir": dump_dir},
        faults={"seed": args.seed, "rules": FAULT_RULES},
        history=dict(HISTORY_BLOCK),
        incidents=incidents_block(inc_dir),
        shed_queue_depth=6, shed_expired_deadline=True, **kw)
    wd = Watchdog(timeout_s=120.0, abort_on_timeout=False).start()
    eng.attach_watchdog(wd)

    prompts_by_id = {}
    rid = 0

    def submit(p, tier=None):
        nonlocal rid
        req_id = f"r{rid:02d}"
        rid += 1
        prompts_by_id[req_id] = p
        eng.submit(req_id, p, max_new_tokens=MAX_NEW, tier=tier)
        return req_id

    def drive():
        steps = 0
        while eng.has_work:
            eng.step()
            wd.pet()
            steps += 1
            if steps > STEP_CAP or \
                    time.perf_counter() - t_start > WALL_CAP_S:
                return False
        return True

    hang = False
    for w, wave in enumerate(waves):
        for p in wave:
            submit(p)
        # the burst rule fires once (deterministically) between waves:
        # a saturation spike past shed_queue_depth → queue-depth sheds
        _delay, fire = faults.poll("burst")
        if fire is not None:
            for p in burst:
                submit(p)
        hang = hang or not drive()
    # born-expired requests: deadline shedding at admission
    for p in expired:
        submit(p, tier="expired")
    time.sleep(0.05)
    hang = hang or not drive()
    wd.stop()
    # one final evaluation: a trigger event landed by the very last
    # step must still be classified (the drive loop exits before the
    # next tick would have drained it)
    eng.incident_mgr.evaluate()

    # ---- reconcile
    finished = dict(eng.finished)
    completed = {k: v for k, v in finished.items()
                 if isinstance(v, list)}
    failed = {k: v for k, v in finished.items()
              if isinstance(v, RequestFailed)}
    shed = {k: v for k, v in finished.items()
            if isinstance(v, RequestShed)}
    mismatched = [k for k, v in completed.items()
                  if v != oracle[tuple(prompts_by_id[k])]]
    leaks = eng.check_leaks()

    cnt = eng.registry.snapshot()["counters"]
    slo_snap = eng.slo_tracker.snapshot()
    slo_shed = sum(t["lifetime"]["shed"]
                   for t in slo_snap["tiers"].values())
    slo_failed = sum(t["lifetime"]["failed"]
                     for t in slo_snap["tiers"].values())
    ring = eng.tracer.recorder.events()
    ring_shed = sum(1 for e in ring if e[3] == "request_shed")
    ring_failed = sum(1 for e in ring if e[3] == "request_failed")
    checks = {
        "typed_results_partition":
            len(finished) == rid and
            len(completed) + len(failed) + len(shed) == rid,
        "engine_counts":
            eng._n_shed == len(shed) and eng._n_failed == len(failed),
        "telemetry_counters":
            int(cnt.get("serving_shed_requests", 0)) == len(shed) and
            int(cnt.get("serving_failed_requests", 0)) == len(failed),
        "slo_lifetime":
            slo_shed == len(shed) and slo_failed == len(failed),
        "trace_events":
            ring_shed == len(shed) and ring_failed == len(failed),
    }
    # ---- incidents (ISSUE 15 acceptance): the scripted burn trip
    # (slot faults -> interactive-tier violations -> multiwindow burn)
    # must yield EXACTLY ONE slo_burn bundle whose timeline carries the
    # triggering event plus a >= 30 s pre-window of history; the
    # fault-free oracle arm must have produced ZERO bundles.
    inc = incidents_summary(eng.incident_mgr,
                            oracle_bundles=oracle_bundles)
    burn_bundle = load_bundle(eng.incident_mgr, "slo_burn")
    inc["burn_bundles"] = inc["by_class"].get("slo_burn", 0)
    inc["burn_bundle_well_formed"] = int(
        bundle_well_formed(burn_bundle, "slo_burn_alert"))
    incidents_ok = (inc["burn_bundles"] == 1
                    and inc["burn_bundle_well_formed"] == 1
                    and inc["oracle_bundles"] == 0)
    if burn_bundle is not None:
        # the committed sample the slow lane re-stamps each cadence:
        # incident_report renders it, tier-1 parses it
        sample_path = os.path.join(REPO, "INCIDENT_SAMPLE.json")
        from deepspeed_tpu.utils.evidence import atomic_write_json \
            as _awj
        _awj(burn_bundle, sample_path)
        inc["sample"] = os.path.basename(sample_path)

    plan_snap = eng._fault_plan.snapshot()
    devprof_snap = eng.statusz().get("devprof", {})
    eng.shutdown()

    healthz = eng.healthz()
    robustness = eng._robustness_status(time.perf_counter())
    ok = (not mismatched and not hang and not wd.fired
          and not leaks and all(checks.values())
          and plan_snap["injected"] > 0 and len(failed) > 0
          and len(shed) > 0 and incidents_ok)
    stamp = {
        "t": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "backend": jax.default_backend(),
        "model": "gpt2-tiny",
        "seed": args.seed,
        "ok": ok,
        "submitted": rid,
        "completed": len(completed),
        "failed": len(failed),
        "shed": len(shed),
        "shed_by_reason": dict(eng._shed_by_reason),
        "mismatched_requests": len(mismatched),
        "mismatched_ids": mismatched[:8],
        "watchdog_fired": int(wd.fired),
        "hang": int(hang),
        "leak_count": len(leaks),
        "leaks": leaks[:8],
        "accounting_ok": int(all(checks.values())),
        "accounting": checks,
        "kv_tier": {
            "demoted": int(eng.allocator.demoted),
            "promoted": int(eng.allocator.promoted),
            "fallback_events": eng._n_kvt_fallbacks,
            "checksum_failures": eng._n_kvt_checksum,
            "spill_failures": eng._kv_pool.spill_failures,
            "disabled": eng._kv_pool.disabled,
        },
        "io_retries": {k: int(v) for k, v in cnt.items()
                       if k.endswith(("_io_retries", "_sync_fallbacks",
                                      "_write_retries")) and v},
        "incidents": inc,
        # the zero-recompile contract under chaos: faults, shedding and
        # tier churn must never push the engine onto an uncompiled
        # shape after its first token (bench_gate pins this at 0)
        "steady_state_recompiles": int(
            devprof_snap.get("compiles_steady", 0)),
        "devprof": {
            "compiles_warmup": int(
                devprof_snap.get("compiles_warmup", 0)),
        },
        "injected": plan_snap,
        "degraded_at_end": healthz["degraded"],
        "robustness": robustness,
        "duration_s": round(time.perf_counter() - t_start, 2),
    }
    atomic_write_json(stamp, args.json_out)
    print(json.dumps({k: v for k, v in stamp.items()
                      if k not in ("injected", "robustness")},
                     indent=1, sort_keys=True))
    print("→", args.json_out)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

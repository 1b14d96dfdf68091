#!/usr/bin/env python
"""Open-loop fleet bench: goodput-vs-load and failover-recovery curves
for the replicated serving front end (``deepspeed_tpu/fleet.py``).

Open-loop means arrivals come from a Poisson process whose rate does
NOT slow down when the fleet saturates — the regime a million-user
front end actually lives in, and the one closed-loop benches (submit →
wait → submit) structurally cannot show: past saturation a closed loop
self-throttles, while an open loop keeps offering load and the fleet
must shed it.  Two stamps:

- **goodput vs load** (``load_curve``): sweep arrival rates past
  saturation; per rate record offered vs completed throughput, goodput
  (SLO-attained tokens/s from the fleet rollup), attainment, shed
  rate, and the affinity hit rate.  The headline shape: throughput
  plateaus at saturation while goodput holds (shedding keeps accepted
  work inside its deadlines) — if goodput collapses instead, admission
  control is mis-tuned.
- **failover recovery** (``failover``): at a fixed mid-saturation
  rate, kill one of the replicas mid-traffic and record the completion
  throughput in 0.5 s buckets around the kill, plus ``recovery_s`` —
  the time until every request salvaged off the dead replica reached a
  terminal result.

``--disagg`` (ISSUE 12): stamps ``DISAGG_BENCH.json`` — two A/Bs for
the KV fabric.  (a) **affinity-miss TTFT, migration on/off**: one
replica warms a long shared prefix and DRAINS (its digest hints hand
to the survivor, its pages stay exportable); every following
same-prefix request is an affinity miss on the cold survivor.  With
the fabric, the router migrates the serialized chain and the miss
serves by promotion; without, it re-prefills — the p50 TTFT ratio is
the headline (gated ≥ 1), with ``mismatched_requests`` = 0 against a
single-engine oracle.  (b) **goodput, prefill-heavy vs decode-heavy
mixes, with/without the role split**: open-loop Poisson traffic
against a classic 3-replica fleet vs the same ring split
``{"prefill": 1, "decode": 2}`` with fabric handoff — when disagg
wins (prefill-heavy mixes, where long prompts stall decode batches)
and when it does not is the README's capacity story.

``--elastic`` (ISSUE 11): a third stamp, ``ELASTIC_BENCH.json`` — a
scripted load **sine wave** drives a :class:`~deepspeed_tpu.autoscale.
FleetAutoscaler` up and down between its bounds while a **live rolling
weight update** runs mid-wave.  Recorded: goodput and p99 TTFT through
the wave (from the flight recorder's queued→first-token spans),
replica count per bucket, scale-up-decision→first-token latency
(``scale_up_to_first_token_s``, the streamed-cold-start headline), and
the invariants the gate pins: ``rollout_dropped`` / ``orphaned`` /
``leak_count`` all 0.

    python bench_fleet.py --cpu --json-out FLEET_BENCH.json
    python bench_fleet.py --cpu --rates 2,5,10 --duration 4
    python bench_fleet.py --cpu --elastic
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

MAX_NEW = 8
WALL_CAP_S = 120.0


def build_prompts(vocab, n_users: int, seed: int):
    """Shared-prefix workload: ``n_users`` system prompts, each request
    = one of them + a unique tail (the affinity router's case)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    prefixes = [rng.integers(1, vocab, 16).tolist()
                for _ in range(n_users)]

    def make(i: int):
        return prefixes[i % n_users] + \
            rng.integers(1, vocab, 3).tolist()

    return make


def poisson_arrivals(rate_per_s: float, duration_s: float, seed: int):
    """Cumulative Poisson arrival times within [0, duration_s)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    out, t = [], 0.0
    while True:
        t += float(rng.exponential(1.0 / rate_per_s))
        if t >= duration_s:
            return out
        out.append(t)


def build_router(params, cfg, args, seed: int):
    from deepspeed_tpu.fleet import fleet_router

    return fleet_router(
        params, cfg,
        fleet={"replicas": args.replicas, "retry_budget": 2,
               "shed_queue_depth": args.fleet_shed,
               "digest_refresh_steps": 2},
        prefix_cache=True,
        slo={"tiers": {"interactive": {
            "ttft_s": args.slo_ttft_s,
            "deadline_s": args.slo_deadline_s}},
            "default_tier": "interactive"},
        shed_queue_depth=args.replica_shed,
        max_batch=args.slots, page_size=8,
        num_pages=args.num_pages, max_seq=64, prefill_bucket=8,
        seed=seed)


def sine_arrivals(rate_lo: float, rate_hi: float, period_s: float,
                  duration_s: float, seed: int):
    """Arrival times of a time-varying Poisson process whose rate
    follows a sine wave between ``rate_lo`` and ``rate_hi`` (thinning:
    draw at the peak rate, accept with rate(t)/rate_hi)."""
    import math

    import numpy as np

    rng = np.random.default_rng(seed)
    mid = (rate_hi + rate_lo) / 2.0
    amp = (rate_hi - rate_lo) / 2.0
    out, t = [], 0.0
    while True:
        t += float(rng.exponential(1.0 / rate_hi))
        if t >= duration_s:
            return out
        rate = mid + amp * math.sin(2.0 * math.pi * t / period_s)
        if rng.random() < rate / rate_hi:
            out.append(t)


def ttft_percentiles(ring, completed_ids):
    """p50/p99 TTFT (s) from the flight-recorder ring: first `queued`
    → first `first_token` per completed request (failover resubmits
    keep the FIRST queued stamp — the user's clock)."""
    import numpy as np

    queued, first = {}, {}
    for t_ns, req, _slot, phase, _attrs in ring:
        if phase == "queued" and req not in queued:
            queued[req] = t_ns
        elif phase == "first_token" and req not in first:
            first[req] = t_ns
    ttfts = [(first[r] - queued[r]) / 1e9 for r in completed_ids
             if r in queued and r in first]
    if not ttfts:
        return {"n": 0}
    arr = np.array(sorted(ttfts))
    return {"n": len(arr),
            "p50_s": round(float(np.percentile(arr, 50)), 4),
            "p99_s": round(float(np.percentile(arr, 99)), 4)}


def _init_jax(args):
    """Import jax on the platform ``--cpu`` asks for, with the one
    persistent compilation cache every chip program shares."""
    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    from deepspeed_tpu.utils.backend import enable_compile_cache

    enable_compile_cache()
    return jax


def elastic_main(args) -> int:
    """--elastic: sine-wave load vs the autoscaler + a live rolling
    weight update; stamps ELASTIC_BENCH.json."""
    jax = _init_jax(args)

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
    new_params = gpt2.init_params(jax.random.PRNGKey(1), cfg)
    make_prompt = build_prompts(cfg.vocab_size, args.users, args.seed)
    # a loose objective on purpose: this stamp measures TTFT
    # percentiles itself, and the tier exists for goodput accounting
    # and the rollout's burn gate — a crest-of-wave TTFT blip must not
    # read as "the new version is bad" (the default 0.99 target turns
    # one violation into burn ≫ 1 and vetoes every upgrade)
    slo = {"tiers": {"interactive": {
        "ttft_s": 10.0, "deadline_s": 30.0, "target": 0.9}},
        "default_tier": "interactive"}
    kw = dict(max_batch=args.slots, page_size=8,
              num_pages=args.num_pages, max_seq=64, prefill_bucket=8,
              prefix_cache=True, slo=slo,
              shed_queue_depth=args.replica_shed)

    import tempfile

    inc_dir = tempfile.mkdtemp(prefix="dstpu_elastic_bench_inc_")
    router = fleet_router(
        params, cfg,
        fleet={"replicas": 1, "retry_budget": 2,
               "shed_queue_depth": args.fleet_shed,
               # saturation shedding must NOT quarantine the fleet out
               # of rotation here — scaling, not quarantine, is the
               # elastic response to crest-of-wave shed activity
               "quarantine_after": 10_000,
               "digest_refresh_steps": 2},
        tracing={"ring_capacity": 262144}, seed=args.seed,
        # fault-free arm of the incident gate (ISSUE 15): history +
        # incidents run live through the wave with ONLY the hard
        # triggers armed (crest-of-wave sheds are expected load
        # behavior here, not an incident; no anomaly detectors) — a
        # fault-free bench that writes any bundle is a false positive,
        # gated at 0 in BENCH_BASELINE
        history={"sample_interval_s": 0.25},
        incidents={"dir": inc_dir, "eval_interval_s": 0.25,
                   "shed_storm_threshold": 0, "detect": (),
                   "pre_window_s": 60.0},
        **kw)

    def factory(rid, streamed=False):
        return serving_engine(
            params, cfg, replica_id=rid, tracing=router.tracer,
            telemetry=MetricsRegistry(namespace=f"dstpu_{rid}"),
            seed=args.seed, **kw)

    auto = FleetAutoscaler(router, factory, autoscale={
        "min_replicas": 1, "max_replicas": args.replicas,
        "eval_interval_steps": 2, "scale_up_queue_depth": 3.0,
        "scale_down_queue_depth": 0.5, "up_after": 1, "down_after": 6,
        "cooldown_s": 1.0, "rollout_soak_steps": 2})

    # warmup: compile the serving programs outside the timed wave
    router.submit("warm", make_prompt(0), max_new_tokens=4)
    auto.run()
    router.drain_finished()

    duration = args.duration * 3           # one wave needs room
    arrivals = sine_arrivals(args.wave_lo, args.wave_hi,
                             duration, duration, args.seed + 3)
    t_rollout = duration * 0.55
    t0 = time.perf_counter()
    next_i = 0
    rollout_started = False
    buckets = {}
    while True:
        now = time.perf_counter() - t0
        while next_i < len(arrivals) and arrivals[next_i] <= now:
            router.submit(f"e{next_i:05d}", make_prompt(next_i),
                          max_new_tokens=MAX_NEW)
            next_i += 1
        if not rollout_started and now >= t_rollout:
            auto.rollout(new_params, version="v2")
            rollout_started = True
        done = auto.step()
        b = int((time.perf_counter() - t0) / 0.5)
        rec = buckets.setdefault(b, {"completed": 0, "replicas": 0})
        rec["completed"] += len(done)
        rec["replicas"] = sum(1 for rep in router.replicas.values()
                              if rep.state != DEAD)
        if next_i >= len(arrivals) and not router.has_work \
                and not auto.rollout_active and not auto._retiring:
            break
        if now > WALL_CAP_S:
            break
    elapsed = time.perf_counter() - t0
    # idle tail: the trough after the wave — sustained low pressure
    # must walk the fleet back down to min_replicas
    t_tail = time.perf_counter()
    while time.perf_counter() - t_tail < 15.0:
        auto.step()
        live = sum(1 for rep in router.replicas.values()
                   if rep.state != DEAD)
        b = int((time.perf_counter() - t0) / 0.5)
        buckets.setdefault(b, {"completed": 0, "replicas": live})[
            "replicas"] = live
        if live <= auto.cfg.min_replicas and not auto._retiring:
            break
        time.sleep(0.002)

    # final evaluation: a trigger event landed during the wave's last
    # steps (after the last 0.25 s tick) must still be classified, or
    # the incident_bundles == 0 gate passes on an undrained ring
    router.incident_mgr.evaluate()

    fin = router.finished
    completed = [k for k, v in fin.items() if isinstance(v, list)]
    failed = [k for k, v in fin.items()
              if isinstance(v, RequestFailed)]
    shed = [k for k, v in fin.items() if isinstance(v, RequestShed)]
    slo_roll = router.statusz()["slo"]
    life = {"attained": 0, "violated": 0, "tokens": 0,
            "goodput_tokens": 0}
    if slo_roll.get("enabled"):
        for t in slo_roll["tiers"].values():
            for k in life:
                life[k] += t["lifetime"].get(k, 0)
    ring = router.tracer.recorder.events()
    ttft = ttft_percentiles(ring, set(completed))
    replica_counts = [rec["replicas"] for _, rec in sorted(
        buckets.items())]
    first_tok = [rec["first_token_s"]
                 for rec in auto.cold_history
                 if rec.get("first_token_s") is not None]
    st = auto.status()
    out = {
        "t": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "backend": jax.default_backend(),
        "model": "gpt2-tiny",
        "seed": args.seed,
        "wave": {"rate_lo": args.wave_lo, "rate_hi": args.wave_hi,
                 "period_s": duration, "duration_s": duration},
        "offered": next_i,
        "completed": len(completed),
        "shed": len(shed),
        "failed": len(failed),
        "elapsed_s": round(elapsed, 2),
        "tokens_per_s": round(life["tokens"] / max(elapsed, 1e-9), 2),
        "goodput_tokens_per_s": round(
            life["goodput_tokens"] / max(elapsed, 1e-9), 2),
        "attainment": round(
            life["attained"]
            / max(life["attained"] + life["violated"], 1), 4),
        "ttft": ttft,
        "scale_ups": st["scale_ups"],
        "scale_downs": st["scale_downs"],
        "replicas_min": min(replica_counts) if replica_counts else 0,
        "replicas_max": max(replica_counts) if replica_counts else 0,
        "scale_up_to_first_token_s": round(max(first_tok), 3)
        if first_tok else None,
        "rollout": dict(auto.last_rollout or {}),
        # the gate rows: an elastic fleet that drops, strands or leaks
        # even one request regressed — and a fault-free wave that
        # writes an incident bundle is a false positive (gated at 0)
        "rollout_dropped": len(failed),
        "orphaned_requests": len(router.orphaned()),
        "leak_count": len(router.check_leaks()),
        "incident_bundles": len(router.incident_mgr.bundles),
        "incident_suppressed": int(
            router.incident_mgr.snapshot().get("suppressed", 0)),
        "history_series": len(router.history.series_names()),
        "replica_buckets": [
            {"t_s": round(b * 0.5, 1), **rec}
            for b, rec in sorted(buckets.items())],
        "duration_s": round(time.perf_counter() - t_start, 2),
    }
    router.shutdown()
    print(json.dumps({k: v for k, v in out.items()
                      if k != "replica_buckets"}, indent=1,
                     sort_keys=True))
    atomic_write_json(out, args.json_out)
    print("→", args.json_out)
    ok = (out["rollout_dropped"] == 0 and out["orphaned_requests"] == 0
          and out["leak_count"] == 0 and out["scale_ups"] >= 1
          and out["scale_downs"] >= 1
          and out["incident_bundles"] == 0
          and (auto.last_rollout or {}).get("completed", False))
    return 0 if ok else 1


def disagg_main(args) -> int:
    """--disagg: the KV-fabric A/Bs; stamps DISAGG_BENCH.json."""
    jax = _init_jax(args)

    import numpy as np

    from deepspeed_tpu.fleet import fleet_router
    from deepspeed_tpu.inference.serving import serving_engine
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.utils.evidence import atomic_write_json

    t_start = time.perf_counter()
    cfg = gpt2.GPT2Config.tiny(dim=128, n_layers=2, n_heads=4,
                               max_seq_len=256)
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(args.seed)
    kw = dict(max_batch=args.slots, page_size=8, num_pages=48,
              max_seq=128, prefill_bucket=8, prefix_cache=True,
              kv_tier={"host_pool_bytes": 256 << 20})

    # ---------------- (a) affinity-miss TTFT, migration on/off
    # distinct prefixes → every timed request is a TRUE miss on the
    # survivor (same-prefix repeats would warm it after the first)
    prefixes = [rng.integers(1, cfg.vocab_size, 88).tolist()
                for _ in range(args.miss_requests)]
    miss_prompts = [pref + rng.integers(1, cfg.vocab_size, 3).tolist()
                    for pref in prefixes]
    oracle_eng = serving_engine(params, cfg, **kw)
    for i, p in enumerate(miss_prompts):
        oracle_eng.submit(f"o{i}", p, max_new_tokens=MAX_NEW)
    oracle = oracle_eng.run()
    oracle_eng.shutdown()

    def miss_arm(with_fabric: bool):
        router = fleet_router(
            params, cfg,
            fleet={"replicas": 2, "affinity": True,
                   "digest_refresh_steps": 1},
            fabric=True if with_fabric else None,
            tracing={"ring_capacity": 65536}, seed=args.seed,
            # split-fuse: the production prefill discipline (one long
            # admission must not stall in-flight decodes) — and the
            # regime the migration targets: a miss re-prefill costs
            # prefix/chunk sequential forwards, a migrated admission
            # one batched promotion + the tail chunk
            prefill_chunk=8, **kw)
        # warm r0 with every prefix, then drain it: each following
        # prefixed request is an affinity miss on r1
        for i, pref in enumerate(prefixes):
            router.submit(f"warm{i}", pref, max_new_tokens=MAX_NEW)
            router.run()
        router.refresh_digests()
        warm = next(r for r in router.replicas.values() if r.digest)
        router.drain(warm.id)
        # TTFT measured from ROUTER submit on the ring's own clock
        # (monotonic_ns): the migration's export+fetch cost lands
        # INSIDE the on-arm TTFT, same as the off arm's re-prefill —
        # the engine-side queued event would start the clock after the
        # migration already ran
        sub_ns = {}
        for i, p in enumerate(miss_prompts):
            sub_ns[f"m{i}"] = time.monotonic_ns()
            router.submit(f"m{i}", p, max_new_tokens=MAX_NEW)
            router.run()
        out = dict(router.finished)
        mism = [i for i in range(len(miss_prompts))
                if out.get(f"m{i}") != oracle[f"o{i}"]]
        ring = router.tracer.recorder.events()
        first = {}
        for t_ns, req, _s, phase, _a in ring:
            if phase == "first_token" and req not in first:
                first[req] = t_ns
        ttfts = sorted(
            (first[r] - sub_ns[r]) / 1e9
            for r in sub_ns if r in first)
        fab = (router.statusz()["fleet"].get("fabric") or {})
        leaks = len(router.check_leaks())
        orphans = len(router.orphaned())
        router.shutdown()
        p50 = ttfts[len(ttfts) // 2] if ttfts else None
        return {"n_miss": len(ttfts),
                "ttft_p50_s": round(p50, 5) if p50 else None,
                "ttft_mean_s": round(sum(ttfts) / len(ttfts), 5)
                if ttfts else None,
                "mismatched": len(mism), "leaks": leaks,
                "orphans": orphans,
                "migrations": fab.get("migrations", 0),
                "migration_pages": fab.get("migration_pages", 0),
                "bytes_moved": fab.get("bytes_moved", 0)}

    # on-arm FIRST (its compile warms shared jit caches; the off arm
    # then starts warm — bias, if any, is AGAINST the migration win)
    arm_on = miss_arm(True)
    arm_off = miss_arm(False)
    migration = {
        "prefix_tokens": len(prefixes[0]),
        "requests": len(miss_prompts),
        "off": arm_off,
        "on": arm_on,
        "ttft_speedup": round(
            arm_off["ttft_p50_s"] / arm_on["ttft_p50_s"], 3)
        if arm_off["ttft_p50_s"] and arm_on["ttft_p50_s"] else None,
        "mismatched_requests": arm_off["mismatched"]
        + arm_on["mismatched"],
        "leak_count": arm_off["leaks"] + arm_on["leaks"],
    }
    print(json.dumps({"migration": migration}), flush=True)

    # ---------------- (b) goodput: mixes x role split
    slo = {"tiers": {"interactive": {
        "ttft_s": args.slo_ttft_s, "deadline_s": args.slo_deadline_s}},
        "default_tier": "interactive"}
    mixes = {
        # long prompts, short answers: prompt work dominates — the
        # regime where a prefill pool keeps decode batches dense
        "prefill_heavy": (48, 4),
        # short prompts, long answers: decode dominates — role split
        # overhead (handoff) with little to amortize it
        "decode_heavy": (8, 24),
    }

    def mix_arm(mix, roles: bool):
        plen, mnew = mixes[mix]
        prefs = [rng.integers(1, cfg.vocab_size, plen).tolist()
                 for _ in range(4)]
        prompts = [prefs[i % 4][:-3]
                   + rng.integers(1, cfg.vocab_size, 3).tolist()
                   for i in range(256)]
        fleet = {"replicas": 3, "digest_refresh_steps": 2,
                 "shed_queue_depth": args.fleet_shed}
        if roles:
            fleet["roles"] = {"prefill": 1, "decode": 2}
        router = fleet_router(
            params, cfg, fleet=fleet,
            fabric=True if roles else None,
            slo=slo, shed_queue_depth=args.replica_shed,
            seed=args.seed, **kw)
        router.submit("warm", prompts[0], max_new_tokens=mnew)
        router.run()
        router.drain_finished()
        arrivals = poisson_arrivals(args.rate, args.duration,
                                    args.seed + 11)
        t0 = time.perf_counter()
        next_i = 0
        while True:
            now = time.perf_counter() - t0
            while next_i < len(arrivals) and arrivals[next_i] <= now:
                router.submit(f"g{next_i:04d}",
                              prompts[next_i % len(prompts)],
                              max_new_tokens=mnew)
                next_i += 1
            router.step()
            if next_i >= len(arrivals) and not router.has_work:
                break
            if now > WALL_CAP_S:
                break
        drove = {"submitted": next_i,
                 "elapsed_s": time.perf_counter() - t0}
        row = summarize(router, drove, args.rate)
        st = router.statusz()["fleet"]
        row["handoffs"] = (st.get("fabric") or {}).get("handoffs", 0)
        row["leaks"] = len(router.check_leaks())
        row["orphans"] = len(router.orphaned())
        router.shutdown()
        return row

    role_split = {}
    for mix in mixes:
        role_split[mix] = {"off": mix_arm(mix, False),
                           "on": mix_arm(mix, True)}
        print(json.dumps({mix: role_split[mix]}), flush=True)

    out = {
        "t": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "backend": jax.default_backend(),
        "model": "gpt2-tiny-d128",
        "seed": args.seed,
        "migration": migration,
        "role_split": role_split,
        "duration_s": round(time.perf_counter() - t_start, 2),
    }
    atomic_write_json(out, args.json_out)
    print("→", args.json_out)
    ok = (migration["mismatched_requests"] == 0
          and migration["leak_count"] == 0
          and (migration["ttft_speedup"] or 0) >= 1.0
          and arm_on["migrations"] >= 1
          and all(r[a]["leaks"] == 0 and r[a]["orphans"] == 0
                  for r in role_split.values() for a in ("off", "on")))
    return 0 if ok else 1


# the migration child spec: a dim-64 model with prefix cache + spill
# tier + a child-local transit fabric, so exported chains carry real
# int8-quantizable pages across the wire
PROC_MIG_SPEC = {
    "model": {"family": "gpt2", "dim": 64, "n_layers": 2,
              "n_heads": 4, "max_seq_len": 128},
    "engine": {"max_batch": 2, "page_size": 8, "num_pages": 24,
               "max_seq": 64, "prefill_bucket": 8,
               "prefix_cache": True,
               "kv_tier": {"host_pool_bytes": 64 << 20}},
    "fabric": {"capacity_bytes": 64 << 20},
    "seed": 0,
}


def procs_main(args) -> int:
    """--procs: the out-of-process fleet A/Bs (ISSUE 20); stamps
    PROC_FLEET_BENCH.json.  Three measurements:

    (a) **throughput, in-proc vs out-of-proc**: the same closed batch
        served by a classic in-process 3-replica fleet and by three
        child PROCESSES behind the shm wire — the ratio prices the
        wire (process isolation buys SIGKILL-survivable failover and
        per-replica address spaces; the A/B keeps the cost honest),
        with token identity REQUIRED between the arms;
    (b) **affinity-miss migration latency, shm vs tcp vs off**: a
        drained owner's warm chains migrate over each real transport
        to the cold survivor — per-kind p50 miss latency, pages and
        bytes moved, with cross-arm token identity (off = re-prefill
        = ground truth);
    (c) **SIGKILL recovery**: a real kill mid-generation on the
        out-of-process fleet; recovery_s measured from the signal,
        salvage partition recorded, completed tokens still identical
        to the in-process arm."""
    jax = _init_jax(args)
    # the children pin this flag (tools/replica_child.py): the
    # in-process arm must draw identical init params
    jax.config.update("jax_threefry_partitionable", True)

    import signal as _signal

    import numpy as np

    from deepspeed_tpu.fleet import fleet_router
    from deepspeed_tpu.inference.serving import RequestFailed
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
    rng = np.random.default_rng(args.seed + 47)
    prompts = [rng.integers(1, cfg.vocab_size, 6).tolist()
               for _ in range(24)]

    def gen_tokens(fin, ids):
        n = 0
        for i, rid in enumerate(ids):
            v = fin.get(rid)
            if isinstance(v, list):
                n += len(v) - len(prompts[i])
        return n

    # ------------- (a) throughput: in-proc fleet vs process fleet
    def ab_arm(router, tag):
        router.submit(f"{tag}-warm", prompts[0], max_new_tokens=4)
        router.run()
        router.drain_finished()
        ids = [f"{tag}{i:02d}" for i in range(len(prompts))]
        t0 = time.perf_counter()
        for rid, p in zip(ids, prompts):
            router.submit(rid, p, max_new_tokens=MAX_NEW)
        while router.has_work:
            router.step()
            if time.perf_counter() - t0 > WALL_CAP_S:
                break
        el = time.perf_counter() - t0
        fin = dict(router.finished)
        toks = gen_tokens(fin, ids)
        return {"completed": sum(1 for r in ids
                                 if isinstance(fin.get(r), list)),
                "generated_tokens": toks,
                "tokens_per_s": round(toks / max(el, 1e-9), 2),
                "elapsed_s": round(el, 3),
                "leaks": len(router.check_leaks()),
                "orphans": len(router.orphaned())}, fin, ids

    router = fleet_router(params, cfg, fleet={"replicas": 3},
                          seed=args.seed, **spec["engine"])
    row_in, fin_in, ids_in = ab_arm(router, "i")
    router.shutdown()

    prouter = proc_fleet_router(spec, proc_fleet={"replicas": 3})
    try:
        row_out, fin_out, ids_out = ab_arm(prouter, "p")
        ab_mismatch = sum(
            1 for a, b in zip(ids_in, ids_out)
            if isinstance(fin_in.get(a), list)
            and isinstance(fin_out.get(b), list)
            and list(fin_in[a]) != list(fin_out[b]))
        throughput = {
            "requests": len(prompts),
            "inproc": row_in,
            "outproc": row_out,
            "wire_cost_ratio": round(
                row_in["tokens_per_s"]
                / max(row_out["tokens_per_s"], 1e-9), 3),
            "mismatched_requests": ab_mismatch,
        }
        print(json.dumps({"throughput": throughput}), flush=True)

        # ------------- (c) SIGKILL recovery on the same process fleet
        prouter.drain_finished()
        fids = [f"f{i:02d}" for i in range(len(prompts))]
        for rid, p in zip(fids, prompts):
            prouter.submit(rid, p, max_new_tokens=MAX_NEW)
        t_kill = None
        salvaged = set()
        recovery_s = None
        t0 = time.perf_counter()
        while prouter.has_work:
            prouter.step()
            if t_kill is None:
                # right after the first harvest: queued + in-flight
                # work dies with the address space
                t_kill = prouter.kill_child("r1", _signal.SIGKILL)
            fo = prouter.last_failover
            if not salvaged and fo is not None and \
                    fo.get("replica") == "r1":
                salvaged = set(fo["resubmitted"])
            if t_kill is not None and recovery_s is None and \
                    fo is not None and fo.get("replica") == "r1" \
                    and all(k in prouter.finished for k in salvaged):
                recovery_s = time.perf_counter() - t_kill
            if time.perf_counter() - t0 > WALL_CAP_S:
                break
        if recovery_s is None and t_kill is not None:
            recovery_s = time.perf_counter() - t_kill
        ffin = dict(prouter.finished)
        fo = prouter.last_failover or {}
        fo_mismatch = sum(
            1 for a, b in zip(ids_in, fids)
            if isinstance(fin_in.get(a), list)
            and isinstance(ffin.get(b), list)
            and list(fin_in[a]) != list(ffin[b]))
        failover = {
            "killed_replica": "r1",
            "recovery_s": round(recovery_s, 3)
            if recovery_s is not None else None,
            "completed": sum(1 for r in fids
                             if isinstance(ffin.get(r), list)),
            "failed_typed": sum(1 for r in fids
                                if isinstance(ffin.get(r),
                                              RequestFailed)),
            "resubmitted": len(fo.get("resubmitted", [])),
            "mismatched_requests": fo_mismatch,
            "leaks": len(prouter.check_leaks()),
            "orphans": len(prouter.orphaned()),
        }
        print(json.dumps({"failover": failover}), flush=True)
    finally:
        prouter.shutdown()

    # ------------- (b) migration latency over each transport
    mig_rng = np.random.default_rng(args.seed + 53)
    mcfg = gpt2.GPT2Config.tiny(
        **{k: v for k, v in PROC_MIG_SPEC["model"].items()
           if k != "family"})
    prefixes = [mig_rng.integers(1, mcfg.vocab_size, 40).tolist()
                for _ in range(4)]
    miss_prompts = [pref
                    + mig_rng.integers(1, mcfg.vocab_size, 3).tolist()
                    for pref in prefixes]

    def mig_arm(kind, with_fabric=True):
        router = proc_fleet_router(
            PROC_MIG_SPEC,
            transport={"kind": kind},
            proc_fleet={"replicas": 2},
            fleet={"replicas": 2, "affinity": True,
                   "digest_refresh_steps": 1},
            fabric=True if with_fabric else None)
        try:
            for i, pref in enumerate(prefixes):
                router.submit(f"w{i}", pref, max_new_tokens=4)
                router.run()
            router.refresh_digests()
            warm = next((r for r in router.replicas.values()
                         if r.digest), None)
            if warm is not None:
                router.drain(warm.id)
            lats = []
            fin = {}
            for i, p in enumerate(miss_prompts):
                t0 = time.perf_counter()
                router.submit(f"m{i}", p, max_new_tokens=MAX_NEW)
                router.run()
                lats.append(time.perf_counter() - t0)
                fin[f"m{i}"] = router.finished.get(f"m{i}")
            fab = (router.statusz()["fleet"].get("fabric") or {})
            lats.sort()
            return {"kind": kind if with_fabric else "off",
                    "n_miss": len(lats),
                    "latency_p50_s": round(lats[len(lats) // 2], 4),
                    "migrations": fab.get("migrations", 0),
                    "migration_pages": fab.get("migration_pages", 0),
                    "bytes_moved": fab.get("bytes_moved", 0),
                    "leaks": len(router.check_leaks()),
                    "orphans": len(router.orphaned())}, fin
        finally:
            router.shutdown()

    row_shm, fin_shm = mig_arm("shm")
    print(json.dumps({"migration_shm": row_shm}), flush=True)
    row_tcp, fin_tcp = mig_arm("tcp")
    print(json.dumps({"migration_tcp": row_tcp}), flush=True)
    row_off, fin_off = mig_arm("shm", with_fabric=False)
    print(json.dumps({"migration_off": row_off}), flush=True)
    mig_mismatch = sum(
        1 for k in fin_off
        if not (isinstance(fin_off[k], list)
                and list(fin_off[k]) == list(fin_shm.get(k) or [])
                and list(fin_off[k]) == list(fin_tcp.get(k) or [])))
    migration = {
        "prefix_tokens": len(prefixes[0]),
        "requests": len(miss_prompts),
        "shm": row_shm,
        "tcp": row_tcp,
        "off": row_off,
        "shm_vs_tcp": round(
            row_tcp["latency_p50_s"]
            / max(row_shm["latency_p50_s"], 1e-9), 3),
        "mismatched_requests": mig_mismatch,
        "leak_count": row_shm["leaks"] + row_tcp["leaks"]
        + row_off["leaks"],
    }

    ok = (throughput["mismatched_requests"] == 0
          and failover["mismatched_requests"] == 0
          and migration["mismatched_requests"] == 0
          and row_in["leaks"] == 0 and row_out["leaks"] == 0
          and failover["leaks"] == 0
          and migration["leak_count"] == 0
          and row_in["orphans"] == 0 and row_out["orphans"] == 0
          and failover["orphans"] == 0
          and row_shm["orphans"] == 0 and row_tcp["orphans"] == 0
          and failover["recovery_s"] is not None
          and failover["recovery_s"] < 60.0
          and row_shm["migrations"] >= 1
          and row_tcp["migrations"] >= 1
          and row_shm["bytes_moved"] > 0
          and row_tcp["bytes_moved"] > 0)
    out = {
        "t": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "backend": jax.default_backend(),
        "model": "gpt2-tiny",
        "seed": args.seed,
        "replicas": 3,
        "ok": ok,
        "throughput": throughput,
        "failover": failover,
        "migration": migration,
        "mismatched_requests":
            throughput["mismatched_requests"]
            + failover["mismatched_requests"]
            + migration["mismatched_requests"],
        "leak_count": row_in["leaks"] + row_out["leaks"]
        + failover["leaks"] + migration["leak_count"],
        "orphaned_requests": row_in["orphans"] + row_out["orphans"]
        + failover["orphans"] + row_shm["orphans"]
        + row_tcp["orphans"] + row_off["orphans"],
        "recovery_s": failover["recovery_s"],
        "duration_s": round(time.perf_counter() - t_start, 2),
    }
    atomic_write_json(out, args.json_out)
    print("→", args.json_out)
    return 0 if ok else 1


def drive_open_loop(router, arrivals, make_prompt, *, kill=None,
                    bucket_s: float = 0.5):
    """Submit arrivals on their schedule while stepping the fleet;
    returns (stats dict, per-bucket completion counts).  ``kill`` =
    (t_offset_s, replica_id) fires a replica death mid-run."""
    t0 = time.perf_counter()
    next_i = 0
    buckets = {}
    killed_at = None
    salvaged = set()
    recovery_s = None
    submitted = 0
    first_tok = {}
    while True:
        now = time.perf_counter() - t0
        while next_i < len(arrivals) and arrivals[next_i] <= now:
            router.submit(f"b{next_i:04d}", make_prompt(next_i),
                          max_new_tokens=MAX_NEW)
            submitted += 1
            next_i += 1
        if kill is not None and killed_at is None and \
                now >= kill[0]:
            router.kill(kill[1], error="bench kill")
            killed_at = time.perf_counter() - t0
            # the router's failover ledger names exactly the salvage
            # set — resubmit counts would also catch shed retries
            fo = router.last_failover
            salvaged = set(fo["resubmitted"]) if fo else set()
        done = router.step()
        if done:
            b = int((time.perf_counter() - t0) / bucket_s)
            buckets[b] = buckets.get(b, 0) + len(done)
        if killed_at is not None and recovery_s is None and \
                all(k in router.finished for k in salvaged):
            recovery_s = (time.perf_counter() - t0) - killed_at
        if next_i >= len(arrivals) and not router.has_work:
            break
        if now > WALL_CAP_S:
            break
    elapsed = time.perf_counter() - t0
    return {"submitted": submitted, "elapsed_s": elapsed,
            "killed_at_s": killed_at, "recovery_s": recovery_s,
            "salvaged": len(salvaged)}, buckets


def summarize(router, drove, rate):
    from deepspeed_tpu.inference.serving import (RequestFailed,
                                                 RequestShed)

    fin = router.finished
    completed = [v for v in fin.values() if isinstance(v, list)]
    shed = sum(1 for v in fin.values() if isinstance(v, RequestShed))
    failed = sum(1 for v in fin.values()
                 if isinstance(v, RequestFailed))
    slo = router.statusz()["slo"]
    # generated-token numerators from the SLO rollup for BOTH rates, so
    # goodput/throughput compare like for like (completed lists carry
    # prompt tokens too — counting those would inflate throughput)
    life = {"attained": 0, "violated": 0, "tokens": 0,
            "goodput_tokens": 0}
    if slo.get("enabled"):
        for t in slo["tiers"].values():
            for k in life:
                life[k] += t["lifetime"].get(k, 0)
    tokens = life["tokens"]
    n_class = life["attained"] + life["violated"]
    aff = router.statusz()["fleet"]["affinity"]
    el = max(drove["elapsed_s"], 1e-9)
    return {
        "rate_per_s": rate,
        "offered": drove["submitted"],
        "completed": len(completed),
        "shed": shed,
        "failed": failed,
        "shed_rate": round(shed / max(drove["submitted"], 1), 4),
        "tokens_per_s": round(tokens / el, 2),
        "goodput_tokens_per_s": round(
            life["goodput_tokens"] / el, 2),
        "attainment": round(life["attained"] / n_class, 4)
        if n_class else 1.0,
        "affinity_hit_rate": aff["hit_rate"],
        "elapsed_s": round(el, 2),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--num-pages", type=int, default=12)
    ap.add_argument("--rates", default="2,6,14",
                    help="comma-separated arrival rates (req/s); make "
                         "the last one sit past saturation")
    ap.add_argument("--duration", type=float, default=4.0,
                    help="offered-traffic window per rate (s)")
    ap.add_argument("--users", type=int, default=4,
                    help="distinct shared prefixes (affinity targets)")
    ap.add_argument("--fleet-shed", type=int, default=24,
                    help="fleet-level aggregate queue-depth shed")
    ap.add_argument("--replica-shed", type=int, default=8,
                    help="per-replica queue-depth shed")
    ap.add_argument("--slo-ttft-s", type=float, default=3.0)
    ap.add_argument("--slo-deadline-s", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--elastic", action="store_true",
                    help="run the autoscaler sine-wave + live weight "
                         "swap bench instead of the load/failover "
                         "curves; stamps ELASTIC_BENCH.json by default")
    ap.add_argument("--disagg", action="store_true",
                    help="run the KV-fabric A/Bs (affinity-miss TTFT "
                         "with migration on/off; goodput under "
                         "prefill- vs decode-heavy mixes with/without "
                         "the role split); stamps DISAGG_BENCH.json "
                         "by default")
    ap.add_argument("--miss-requests", type=int, default=8,
                    help="--disagg: affinity-miss requests per arm")
    ap.add_argument("--rate", type=float, default=6.0,
                    help="--disagg: arrival rate for the mix arms "
                         "(req/s)")
    ap.add_argument("--wave-lo", type=float, default=1.0,
                    help="--elastic: sine-wave trough arrival rate "
                         "(req/s)")
    ap.add_argument("--wave-hi", type=float, default=10.0,
                    help="--elastic: sine-wave crest arrival rate "
                         "(req/s)")
    ap.add_argument("--procs", action="store_true",
                    help="run the out-of-process fleet A/Bs "
                         "(in-proc vs child processes, shm vs tcp "
                         "migration, SIGKILL recovery); stamps "
                         "PROC_FLEET_BENCH.json by default")
    ap.add_argument("--json-out", default=None)
    args = ap.parse_args()
    if args.json_out is None:
        args.json_out = os.path.join(
            REPO, "ELASTIC_BENCH.json" if args.elastic
            else "DISAGG_BENCH.json" if args.disagg
            else "PROC_FLEET_BENCH.json" if args.procs
            else "FLEET_BENCH.json")
    if args.elastic:
        return elastic_main(args)
    if args.disagg:
        return disagg_main(args)
    if args.procs:
        return procs_main(args)

    jax = _init_jax(args)

    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.utils.evidence import atomic_write_json

    t_start = time.perf_counter()
    cfg = gpt2.GPT2Config.tiny(dim=64, n_layers=2, n_heads=4,
                               max_seq_len=128)
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    make_prompt = build_prompts(cfg.vocab_size, args.users, args.seed)
    rates = [float(r) for r in args.rates.split(",") if r]

    # warmup: compile the serving programs outside the timed windows
    router = build_router(params, cfg, args, seed=args.seed)
    router.submit("warm", make_prompt(0), max_new_tokens=4)
    router.run()
    router.shutdown()

    load_curve = []
    for rate in rates:
        router = build_router(params, cfg, args, seed=args.seed)
        arrivals = poisson_arrivals(rate, args.duration,
                                    args.seed + int(rate * 1000))
        drove, _ = drive_open_loop(router, arrivals, make_prompt)
        row = summarize(router, drove, rate)
        load_curve.append(row)
        print(json.dumps(row), flush=True)
        router.shutdown()

    # failover recovery at the middle rate: kill one replica a third
    # of the way into the offered window
    mid = rates[len(rates) // 2]
    router = build_router(params, cfg, args, seed=args.seed)
    arrivals = poisson_arrivals(mid, args.duration, args.seed + 7)
    drove, buckets = drive_open_loop(
        router, arrivals, make_prompt,
        kill=(args.duration / 3.0, "r1"))
    fo_row = summarize(router, drove, mid)
    failover = {
        **fo_row,
        "killed_replica": "r1",
        "killed_at_s": round(drove["killed_at_s"], 3)
        if drove["killed_at_s"] is not None else None,
        "recovery_s": round(drove["recovery_s"], 3)
        if drove["recovery_s"] is not None else None,
        "salvaged_requests": drove["salvaged"],
        "orphaned_requests": len(router.orphaned()),
        "leak_count": len(router.check_leaks()),
        "throughput_buckets": [
            {"t_s": round(b * 0.5, 1), "completed": n}
            for b, n in sorted(buckets.items())],
    }
    print(json.dumps({k: v for k, v in failover.items()
                      if k != "throughput_buckets"}), flush=True)
    router.shutdown()

    out = {
        "t": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "backend": jax.default_backend(),
        "model": "gpt2-tiny",
        "replicas": args.replicas,
        "duration_per_rate_s": args.duration,
        "load_curve": load_curve,
        "failover": failover,
        "duration_s": round(time.perf_counter() - t_start, 2),
    }
    atomic_write_json(out, args.json_out)
    print("→", args.json_out)
    return 0


if __name__ == "__main__":
    sys.exit(main())

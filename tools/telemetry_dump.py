#!/usr/bin/env python
"""Telemetry sampler: drive a short serving loop and pretty-print the
registry snapshot (ISSUE 2 satellite).

Runs a tiny gpt2 ServingEngine on whatever backend is available (pass
--cpu to force the CPU backend), serves a handful of requests, then

  1. pretty-prints ``registry.snapshot()`` (the on-demand JSON sink),
  2. writes the Prometheus text exposition next to the JSON stamp and
     parses it back (the same round-trip the tests assert),
  3. stamps TELEMETRY_SAMPLE.json (atomic) with the snapshot + run
     metadata, so slow-lane runs (tools/run_slow_lane.sh) leave a
     standing record of what a live registry looks like, and
  4. stamps STATUSZ_SAMPLE.json from the engine's introspection server
     (ISSUE 6): /statusz, /healthz and a /requestz drill-down fetched
     over REAL HTTP from the live engine — the snapshot schema is
     versioned in-repo and round-trip-parsed by a tier-1 test, and
  5. stamps DEVPROF_SAMPLE.json (ISSUE 17): the devprof block from
     /statusz plus the /profilez round-trip and a short on-demand
     jax.profiler capture, all over the same real HTTP server — the
     standing record of the compile sentinel (steady_state_compiles
     must read 0) and of the build ledger (/statusz ``build``).

    python tools/telemetry_dump.py --cpu
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend in-process")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=24)
    ap.add_argument("--json-out",
                    default=os.path.join(REPO, "TELEMETRY_SAMPLE.json"))
    ap.add_argument("--statusz-out",
                    default=os.path.join(REPO, "STATUSZ_SAMPLE.json"))
    ap.add_argument("--devprof-out",
                    default=os.path.join(REPO, "DEVPROF_SAMPLE.json"))
    ap.add_argument("--capture-s", type=float, default=0.2,
                    help="on-demand /profilez device-trace length "
                         "(0 skips the capture)")
    args = ap.parse_args()

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    import numpy as np

    from deepspeed_tpu.inference.serving import serving_engine
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.telemetry import parse_prometheus_text
    from deepspeed_tpu.utils.evidence import atomic_write_json

    cfg = gpt2.GPT2Config.tiny(dim=64, n_layers=2, n_heads=4,
                               max_seq_len=128)
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    prompt_len = 24
    max_seq = prompt_len + args.new_tokens
    # prefix caching + speculation on: the sample registry carries LIVE
    # prefix_cache_* AND spec_* metric families (shared-prefix traffic
    # below produces real hits; the repetitive histories greedy decode
    # settles into give the ngram drafter real acceptances)
    # slo + introspection on: the stamps carry live slo_* families and
    # the /statusz sample comes over REAL HTTP (ephemeral port) from
    # the same traced engine
    eng = serving_engine(
        params, cfg, max_batch=4, page_size=8,
        num_pages=4 * (-(-max_seq // 8)) + 16, max_seq=max_seq,
        prefill_bucket=8, decode_chunk=4, prefix_cache=True,
        speculative={"draft_tokens": 4},
        slo={"tiers": {"interactive": {"ttft_s": 10.0,
                                       "deadline_s": 60.0},
                       "batch": {"deadline_s": 300.0, "target": 0.9}},
             "default_tier": "interactive"},
        telemetry={"http_port": 0, "interval_s": 0.0},
        # the compile sentinel and its build-time warm-up: the stamp
        # carries the zero-recompile contract and the build ledger
        devprof=True)

    rng = np.random.default_rng(0)
    prefix = rng.integers(1, cfg.vocab_size, prompt_len - 4).tolist()
    t0 = time.perf_counter()
    for i in range(args.requests):
        eng.submit(i, prefix + rng.integers(1, cfg.vocab_size, 4).tolist(),
                   max_new_tokens=args.new_tokens,
                   tier="batch" if i % 2 else "interactive")
    out = eng.run()
    eng.step()                   # settle gauges after the drain
    wall = time.perf_counter() - t0

    snap = eng.registry.snapshot()
    print(json.dumps(snap, indent=1, sort_keys=True))

    prom_path = args.json_out.rsplit(".", 1)[0] + ".prom"
    eng.registry.write_prometheus(prom_path)
    with open(prom_path) as f:
        families = parse_prometheus_text(f.read())
    print(f"# prometheus exposition: {prom_path} "
          f"({len(families)} families, parsed back OK)")

    atomic_write_json({
        "t": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "backend": jax.default_backend(),
        "model": "gpt2-tiny",
        "requests": args.requests,
        "completed": len(out),
        "wall_s": round(wall, 2),
        "prometheus_families": len(families),
        "snapshot": snap,
    }, args.json_out)
    print("→", args.json_out)

    # introspection sample over real HTTP: the engine registered its
    # /statusz, /healthz and /requestz providers on the telemetry
    # server at construction — fetch all three so the stamped schema is
    # exactly what a fleet supervisor or dstpu_top would see
    import urllib.request

    base = f"http://127.0.0.1:{eng._tel_exporter.port}"

    def get(path, timeout=10):
        with urllib.request.urlopen(base + path, timeout=timeout) as r:
            return json.loads(r.read().decode())

    statusz = get("/statusz")
    healthz = get("/healthz")
    requestz = get("/requestz?id=0")
    atomic_write_json({
        "t": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "backend": jax.default_backend(),
        "model": "gpt2-tiny",
        "endpoints": ["/statusz", "/healthz", "/requestz?id=",
                      "/metrics"],
        "statusz": statusz,
        "healthz": healthz,
        "requestz_sample": requestz,
    }, args.statusz_out)
    print(f"# introspection: fetched /statusz /healthz /requestz over "
          f"http from {base}")
    print("→", args.statusz_out)

    # device-truth sample over the same real HTTP server (ISSUE 17):
    # /profilez without a query returns the devprof status block;
    # with capture_s it runs a bounded jax.profiler capture and
    # returns the capture reference
    profilez = get("/profilez")
    capture = None
    if args.capture_s > 0:
        # profiler session start/stop costs ~15 s on some backends —
        # the capture fetch gets a generous client timeout
        capture = get(f"/profilez?capture_s={args.capture_s}",
                      timeout=120)
        capture.pop("devprof", None)   # already stamped above
    dp = statusz.get("devprof", {})
    atomic_write_json({
        "t": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "backend": jax.default_backend(),
        "model": "gpt2-tiny",
        "endpoints": ["/profilez", "/profilez?capture_s="],
        # the zero-recompile contract's standing evidence: this loop
        # served real traffic after warmup, so steady must be true and
        # steady_state_compiles must read 0
        "steady": dp.get("steady"),
        "steady_state_compiles": dp.get("compiles_steady"),
        "devprof": dp,
        # what making the loop's programs ready cost, program by
        # program (/statusz "build": the process-wide build ledger)
        "build": statusz.get("build", {}),
        "profilez": profilez,
        "capture": capture,
    }, args.devprof_out)
    print(f"# devprof: fetched /profilez over http from {base} "
          f"(capture_s={args.capture_s})")
    print("→", args.devprof_out)
    eng.shutdown()


if __name__ == "__main__":
    main()

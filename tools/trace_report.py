#!/usr/bin/env python
"""Per-request trace reporter (ISSUE 4 tentpole CLI).

Ingests either flight-recorder export — the JSONL structured log or the
Chrome trace-event JSON — and prints per-request WATERFALLS plus a
critical-path breakdown (queue wait vs prefill vs decode vs
stream-stall seconds, p50/p95):

    python tools/trace_report.py /tmp/dstpu_flight/flight_*.jsonl
    python tools/trace_report.py serving_trace.json

``--merge a.jsonl b.jsonl`` (or Chrome files) folds N per-process
segments into ONE monotone Chrome trace: each file's stamped clock
offset (obs_wire's min-RTT estimate, carried in the JSONL header /
``otherData``) shifts its events onto the local monotonic axis,
request spans stitch across replica tags, and the summary gains a
per-source segment count.

    python tools/trace_report.py --merge r0.jsonl r1.jsonl \\
        --merge-out merged.chrome.json

``--selftest`` drives a short traced gpt2 serving workload end to end,
exports BOTH formats next to ``--json-out``, validates the Chrome
export (parses back, monotonic ``ts``, matched async begin/end per
request), cross-checks the trace-derived TTFT against the telemetry
histogram (must agree within 1 ms — the two pillars measure the same
edges), prints the report, and stamps ``TRACE_SAMPLE.json`` (atomic) —
the slow lane (tools/run_slow_lane.sh) runs this on every pass.

    python tools/trace_report.py --selftest --cpu
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


# ------------------------------------------------------------- ingestion
def breakdown_from_chrome(trace: dict) -> dict:
    """Per-request components from the async span pairs of a Chrome
    export (same shape as ``request_breakdown``'s result, seconds).

    Requests still in flight at export time (the export force-closes
    their spans with ``args.truncated=true`` so the file always loads —
    exactly the hung requests a postmortem dump is about) are excluded
    from the stats and counted in ``summary.truncated_requests``,
    matching the JSONL path, which only measures observed edges."""
    spans = {}   # (id, name) -> [begin_ts, end_ts] in us
    truncated = set()
    spec = {}    # id -> {sweeps, drafted, accepted} from spec_accept
    kv = {}      # id -> {pages, wait_s} from kv_promote
    for ev in trace.get("traceEvents", []):
        if ev.get("cat") != "request":
            continue
        if ev.get("ph") == "n" and ev.get("name") == "spec_accept":
            args = ev.get("args") or {}
            rec = spec.setdefault(
                ev["id"], {"sweeps": 0, "drafted": 0, "accepted": 0})
            rec["sweeps"] += 1
            rec["drafted"] += int(args.get("drafted", 0))
            rec["accepted"] += int(args.get("accepted", 0))
            continue
        if ev.get("ph") == "n" and ev.get("name") == "kv_promote":
            args = ev.get("args") or {}
            rec = kv.setdefault(ev["id"], {"pages": 0, "wait_s": 0.0})
            rec["pages"] += int(args.get("pages", 0))
            rec["wait_s"] += float(args.get("wait_s", 0.0))
            continue
        if ev.get("ph") not in ("b", "e"):
            continue
        if (ev.get("args") or {}).get("truncated"):
            truncated.add(ev["id"])
            continue
        key = (ev["id"], ev["name"])
        rec = spans.setdefault(key, [None, None])
        rec[0 if ev["ph"] == "b" else 1] = ev["ts"]
    per = {}
    for (rid, name), (t0, t1) in spans.items():
        if t0 is None or t1 is None or rid in truncated:
            continue
        row = per.setdefault(rid, {})
        dur_s = (t1 - t0) / 1e6
        if name == "queued":
            row["queue_wait_s"] = dur_s
        elif name == "prefill":
            row["prefill_s"] = dur_s
        elif name == "decode":
            row["decode_s"] = dur_s
        elif name == "request":
            row["total_s"] = dur_s
    for row in per.values():
        if "queue_wait_s" in row and "prefill_s" in row:
            row["ttft_s"] = row["queue_wait_s"] + row["prefill_s"]
    stall = sum(ev.get("dur", 0.0) / 1e6
                for ev in trace.get("traceEvents", [])
                if ev.get("ph") == "X"
                and str(ev.get("name", "")).endswith("_stall"))
    from deepspeed_tpu.request_trace import (attach_kv_promotions,
                                             attach_speculation,
                                             kv_tier_summary,
                                             speculation_summary,
                                             summarize_components)

    spec = {rid: rec for rid, rec in spec.items()
            if rid not in truncated}
    kv = {rid: rec for rid, rec in kv.items() if rid not in truncated}
    attach_speculation(per, spec)
    attach_kv_promotions(per, kv)
    summary = summarize_components(per, stall)
    sp = speculation_summary(spec)
    if sp:
        summary["speculation"] = sp
    kt = kv_tier_summary(kv)
    if kt:
        summary["kv_tier"] = kt
    if truncated:
        summary["truncated_requests"] = sorted(str(r) for r in truncated)
    return {"requests": per, "summary": summary}


def compile_summary(rows) -> dict:
    """Aggregate the compile sentinel's ``xla_compile`` events into the
    report's device section: how many programs each half made ready
    and the build ledger's seconds for them.  ``rows`` is an iterable
    of ``(name, attrs)`` pairs — both export formats reduce to it."""
    compiles = {"warmup": 0, "steady": 0, "seconds": 0.0}
    for name, attrs in rows:
        if name == "xla_compile":
            attrs = attrs or {}
            compiles["steady" if attrs.get("steady") else
                     "warmup"] += int(attrs.get("n", 1))
            compiles["seconds"] += float(attrs.get("duration_s") or 0.0)
    if not (compiles["warmup"] or compiles["steady"]):
        return {}
    compiles["seconds"] = round(compiles["seconds"], 6)
    return {"compiles": compiles}


def load_breakdown(path: str) -> dict:
    from deepspeed_tpu.request_trace import read_jsonl, request_breakdown

    if path.endswith(".jsonl"):
        evs = read_jsonl(path)
        bd = request_breakdown(evs)
        dev = compile_summary((e[3], e[4]) for e in evs)
    else:
        with open(path) as f:
            trace = json.load(f)
        bd = breakdown_from_chrome(trace)
        dev = compile_summary(
            (ev.get("name"), ev.get("args"))
            for ev in trace.get("traceEvents", []))
    if dev:
        bd["summary"]["device"] = dev
    return bd


# ----------------------------------------------------------------- merge
def load_segment(path: str):
    """One trace file as a merge segment: ``(events, meta)`` where
    events are flight-recorder tuples and meta carries the per-file
    clock offset / replica tag when the exporter stamped them (JSONL:
    the ``flight_recorder`` header line; Chrome: ``otherData``)."""
    from deepspeed_tpu.request_trace import events_from_dicts

    if path.endswith(".jsonl"):
        meta, dicts = {}, []
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                d = json.loads(line)
                if "flight_recorder" in d:
                    meta = d["flight_recorder"]
                    continue
                dicts.append(d)
        return events_from_dicts(dicts), meta
    with open(path) as f:
        trace = json.load(f)
    od = trace.get("otherData", {})
    base = int(od.get("base_monotonic_ns", 0))
    # reconstruct absolute-monotonic tuples from the chrome ts (µs
    # from base); async request spans reduce to their begin/end edges
    events = []
    names = {"request": None, "queued": "queued", "prefill": "admitted",
             "decode": "first_token"}
    for ev in trace.get("traceEvents", []):
        if ev.get("ph") == "M":
            continue
        t_ns = base + int(float(ev.get("ts", 0.0)) * 1000)
        if ev.get("cat") == "request":
            rid = ev.get("id")
            if ev["ph"] == "b" and ev["name"] in names:
                phase = names[ev["name"]]
                if phase:
                    events.append((t_ns, rid, -1, phase,
                                   ev.get("args")))
                elif ev["name"] == "request":
                    events.append((t_ns, rid, -1, "queued",
                                   None))
            elif ev["ph"] == "e" and ev["name"] == "request":
                events.append((t_ns, rid, -1, "finish",
                               ev.get("args")))
            elif ev["ph"] == "n":
                events.append((t_ns, rid, -1, ev["name"],
                               ev.get("args")))
        else:
            events.append((t_ns, None, -1, ev.get("name", "?"),
                           ev.get("args")))
    # dedup the double-begin the reconstruction above can produce for
    # the queued edge (request + queued open at the same ts)
    seen = set()
    uniq = []
    for e in sorted(events, key=lambda e: e[0]):
        k = (e[0], str(e[1]), e[3])
        if k in seen:
            continue
        seen.add(k)
        uniq.append(e)
    return uniq, od


def merge_traces(paths, out_path: str):
    """Fold N per-process exports into ONE monotone Chrome trace,
    applying each file's stamped clock offset (obs_wire's min-RTT
    estimate) so all segments share the local monotonic axis."""
    from deepspeed_tpu.obs_wire import merge_trace_segments

    segments = []
    sources = {}
    for i, path in enumerate(paths):
        events, meta = load_segment(path)
        tag = str(meta.get("replica")
                  or meta.get("pid") or f"seg{i}")
        segments.append({
            "events": events,
            "offset_ns": int(meta.get("clock_offset_ns") or 0),
            "err_ns": int(meta.get("clock_offset_err_ns") or 0),
            "replica": tag,
        })
        sources[os.path.basename(path)] = {
            "replica": tag, "events": len(events),
            "offset_ns": int(meta.get("clock_offset_ns") or 0)}
    merged = merge_trace_segments(segments)
    validate_chrome(merged)
    with open(out_path, "w") as f:
        json.dump(merged, f)
    bd = breakdown_from_chrome(merged)
    bd["summary"]["sources"] = sources
    return merged, bd


# -------------------------------------------------------------- printing
def print_report(bd: dict, limit: int = 20) -> None:
    per, summary = bd["requests"], bd["summary"]
    ms = lambda s: f"{1000 * s:9.2f}"
    print(f"{'request':>12} | {'queue ms':>9} | {'prefill ms':>10} | "
          f"{'decode ms':>9} | {'total ms':>9}  waterfall")
    shown = list(per.items())[:limit]
    for req, row in shown:
        total = row.get("total_s", 0.0)
        bar = ""
        if total > 0:
            width = 28
            for comp, ch in (("queue_wait_s", "."), ("prefill_s", "#"),
                             ("decode_s", "=")):
                bar += ch * max(int(width * row.get(comp, 0.0) / total),
                                1 if row.get(comp, 0.0) > 0 else 0)
        spec = (f"  spec×{row['spec_sweeps']} "
                f"len={row['spec_mean_accept_len']:.2f}"
                if row.get("spec_sweeps") else "")
        print(f"{str(req)[:12]:>12} | {ms(row.get('queue_wait_s', 0)):>9} | "
              f"{ms(row.get('prefill_s', 0)):>10} | "
              f"{ms(row.get('decode_s', 0)):>9} | "
              f"{ms(row.get('total_s', 0)):>9}  {bar}{spec}")
    if len(per) > len(shown):
        print(f"... {len(per) - len(shown)} more requests")
    print("\ncritical path (seconds):")
    for comp in ("queue_wait_s", "prefill_s", "decode_s", "ttft_s",
                 "total_s", "kv_promote_s"):
        if comp in summary:
            c = summary[comp]
            print(f"  {comp:<13} p50={c['p50']:.4f}  p95={c['p95']:.4f}  "
                  f"mean={c['mean']:.4f}  (n={c['n']})")
    print(f"  stream_stall_s total={summary['stream_stall_s']:.4f}")
    sp = summary.get("speculation")
    if sp:
        # decode-time attribution: each verify sweep is one model sweep
        # (one full weight stream under ZeRO-Inference) amortized over
        # mean_accept_len emitted tokens
        print(f"  speculation: {sp['sweeps']} verify sweeps, "
              f"{sp['drafted_tokens']} drafted / "
              f"{sp['accepted_tokens']} accepted "
              f"({sp['rejected_tokens']} rolled back), "
              f"mean accept len {sp['mean_accept_len']:.2f} "
              f"tokens/sweep")
    kt = summary.get("kv_tier")
    if kt:
        # promotion waits sit INSIDE prefill/TTFT: an evicted prefix
        # that cost a DMA shows here instead of as re-prefill compute
        print(f"  kv_tier: {kt['promotions']} promotions, "
              f"{kt['promoted_pages']} pages streamed back, "
              f"{kt['promote_wait_s']:.4f}s inside TTFT")
    dv = summary.get("device")
    if dv:
        c = dv["compiles"]
        print(f"  xla compiles: {c['warmup']} warmup, "
              f"{c['steady']} steady, {c.get('seconds', 0.0):.2f}s"
              + ("  <-- STEADY-STATE RECOMPILE (shape drift)"
                 if c["steady"] else ""))
    if summary.get("truncated_requests"):
        print(f"  still in flight at export (excluded from stats): "
              f"{', '.join(summary['truncated_requests'])}")


# -------------------------------------------------------------- selftest
def validate_chrome(trace: dict) -> None:
    """The catapult contract the tests also assert: parses back,
    non-decreasing ``ts``, and every async begin has its end."""
    blob = json.dumps(trace)
    trace = json.loads(blob)
    evs = [e for e in trace["traceEvents"] if e["ph"] != "M"]
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts), "trace ts not monotonic"
    assert all(t >= 0 for t in ts), "negative ts"
    depth = {}
    for e in evs:
        if e.get("cat") == "request" and e["ph"] in ("b", "e"):
            d = depth.get(e["id"], 0) + (1 if e["ph"] == "b" else -1)
            assert d >= 0, f"async end before begin for {e['id']}"
            depth[e["id"]] = d
    dangling = {k: v for k, v in depth.items() if v}
    assert not dangling, f"unmatched async begins: {dangling}"


def selftest(args) -> int:
    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    import numpy as np

    from deepspeed_tpu.inference.serving import serving_engine
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.request_trace import request_breakdown
    from deepspeed_tpu.utils.evidence import atomic_write_json

    cfg = gpt2.GPT2Config.tiny(dim=64, n_layers=2, n_heads=4,
                               max_seq_len=128)
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    prompt_len = 24
    max_seq = prompt_len + args.new_tokens
    # speculation on: the stamped sample demonstrates draft/verify/
    # rollback attribution (spec_accept instants inside request spans,
    # sweep events on the speculative track, summary.speculation)
    # devprof on: the stamped sample carries the compile sentinel's
    # ledger next to the host breakdown
    eng = serving_engine(
        params, cfg, max_batch=4, page_size=8,
        num_pages=4 * (-(-max_seq // 8)) + 16, max_seq=max_seq,
        prefill_bucket=8, decode_chunk=4, prefix_cache=True,
        speculative={"draft_tokens": 4},
        tracing={"sample_rate": 1.0},
        devprof=True)

    rng = np.random.default_rng(0)
    prefix = rng.integers(1, cfg.vocab_size, prompt_len - 4).tolist()
    t0 = time.perf_counter()
    for i in range(args.requests):
        eng.submit(i, prefix + rng.integers(1, cfg.vocab_size, 4).tolist(),
                   max_new_tokens=args.new_tokens)
    out = eng.run()
    wall = time.perf_counter() - t0

    eng.tracer.fold_comms()
    base = args.json_out.rsplit(".", 1)[0]
    chrome_path, jsonl_path = base + ".chrome.json", base + ".jsonl"
    trace = eng.tracer.export_chrome(chrome_path)
    eng.tracer.export_jsonl(jsonl_path)
    validate_chrome(trace)
    with open(chrome_path) as f:
        validate_chrome(json.load(f))
    print(f"# chrome export OK: {chrome_path} "
          f"({len(trace['traceEvents'])} events; load it in Perfetto or "
          "chrome://tracing)")
    print(f"# jsonl export:     {jsonl_path}")

    events = eng.tracer.recorder.events()
    bd = request_breakdown(events)
    dev = compile_summary((e[3], e[4]) for e in events)
    if dev:
        bd["summary"]["device"] = dev
    print_report(bd)

    # the acceptance cross-check: trace-derived mean TTFT must agree
    # with the telemetry histogram (same submit→first-token edges,
    # independent clocks/plumbing) within 1 ms
    h = eng.registry.snapshot()["histograms"]["serving_ttft_seconds"]
    tel_ttft = h["mean"]
    trace_ttft = bd["summary"]["ttft_s"]["mean"]
    delta_ms = abs(tel_ttft - trace_ttft) * 1000
    print(f"\nTTFT mean: telemetry {1000 * tel_ttft:.3f} ms, "
          f"trace {1000 * trace_ttft:.3f} ms, delta {delta_ms:.4f} ms")
    ok = delta_ms < 1.0
    if not ok:
        print("FAIL: trace/telemetry TTFT disagree by >= 1 ms")

    atomic_write_json({
        "t": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "backend": jax.default_backend(),
        "model": "gpt2-tiny",
        "requests": args.requests,
        "completed": len(out),
        "wall_s": round(wall, 2),
        "events_recorded": len(events),
        "dropped_events": eng.tracer.recorder.dropped,
        "chrome_trace_events": len(trace["traceEvents"]),
        "ttft_telemetry_ms": round(1000 * tel_ttft, 3),
        "ttft_trace_ms": round(1000 * trace_ttft, 3),
        "ttft_delta_ms": round(delta_ms, 4),
        "ttft_within_1ms": ok,
        "breakdown": bd["summary"],
        "devprof": eng.statusz().get("devprof", {}),
    }, args.json_out)
    print("→", args.json_out)
    return 0 if ok else 1


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("trace", nargs="?",
                    help="flight-recorder export to report on "
                         "(.jsonl structured log or .json Chrome trace)")
    ap.add_argument("--merge", nargs="+", metavar="TRACE",
                    help="merge N per-process exports (.jsonl or "
                         "Chrome) into one monotone Chrome trace, "
                         "applying per-file clock offsets from the "
                         "trace meta; report on the merged view")
    ap.add_argument("--merge-out", default="merged_trace.chrome.json",
                    help="where --merge writes the merged Chrome "
                         "trace")
    ap.add_argument("--selftest", action="store_true",
                    help="drive a short traced gpt2 serving workload, "
                         "validate the exports, stamp TRACE_SAMPLE.json")
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend in-process")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=24)
    ap.add_argument("--limit", type=int, default=20,
                    help="max per-request waterfall rows printed")
    ap.add_argument("--json-out",
                    default=os.path.join(REPO, "TRACE_SAMPLE.json"))
    args = ap.parse_args()

    if args.selftest:
        sys.exit(selftest(args))
    if args.merge:
        merged, bd = merge_traces(args.merge, args.merge_out)
        print(f"# merged {len(args.merge)} segments -> "
              f"{args.merge_out} "
              f"({len(merged['traceEvents'])} events, monotone)")
        for src, rec in bd["summary"]["sources"].items():
            print(f"#   {src}: {rec['events']} events "
                  f"[{rec['replica']}] offset {rec['offset_ns']}ns")
        print_report(bd, limit=args.limit)
        return
    if not args.trace:
        ap.error("give a trace file, --merge, or --selftest")
    print_report(load_breakdown(args.trace), limit=args.limit)


if __name__ == "__main__":
    main()

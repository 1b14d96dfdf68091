#!/usr/bin/env python
"""Serving scheduler-overhead breakdown (round-4 verdict task 6).

FastGen's claim is iteration-level scheduling with negligible host cost;
this tool bounds OUR host cost without needing the TPU: for each
(model, slots, decode_chunk) point it

  1. drives the full ServingEngine (submit/admit/prefill/decode/retire)
     and records wall-clock per decode step, then
  2. replays the engine's OWN compiled decode-chunk function on the
     final cache state, giving pure jit ms per decode step, so

     scheduler_ms_per_step = total_ms_per_step - jit_ms_per_step

is the host's bookkeeping cost (sampling bookkeeping, page-table
uploads, queue management, slot retire).  Prompts are kept short and
generations long so prefill contributes little to the total; the
residual is reported per point, not hidden.

Writes SERVING_OVERHEAD.json.  Runs on any backend; CPU numbers bound
the scheduler cost (the host work is backend-independent; only
jit_ms_per_step changes on the TPU).
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def measure_point(model_name, slots, decode_chunk, prompt_len=8,
                  new_tokens=48, requests=None, telemetry=True,
                  tracing=True, slo=False, history=False,
                  devprof=False, obs_wire=False):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference.serving import serving_engine
    from deepspeed_tpu.models import gpt2, llama, mixtral

    if model_name == "mixtral":
        mod, cfg = mixtral, mixtral.MixtralConfig.tiny(
            dim=64, n_layers=2, n_heads=4, n_kv_heads=2, num_experts=4)
    elif model_name == "gpt2":
        mod, cfg = gpt2, gpt2.GPT2Config.tiny(dim=64, n_layers=2,
                                              n_heads=4, max_seq_len=128)
    else:
        mod, cfg = llama, llama.LlamaConfig.tiny(dim=64, n_layers=2,
                                                 n_heads=4, n_kv_heads=2)
    params = mod.init_params(jax.random.PRNGKey(0), cfg)
    requests = requests or 2 * slots
    max_seq = prompt_len + new_tokens
    # the slo arm declares real objectives on the default tier so the
    # enabled path pays classification + window bookkeeping, not a
    # degenerate no-objective fast path
    slo_block = {"tiers": {"default": {
        "ttft_s": 30.0, "itl_s": 5.0, "deadline_s": 120.0}}} \
        if slo else None
    # the history arm runs BOTH new blocks at their production
    # cadences (1 s sampling / 1 s evaluation): the claim under test is
    # that the per-step cost of the shared tick pass is one monotonic
    # compare, whatever the rings record when a tick lands
    history_block = {"sample_interval_s": 1.0} if history else None
    incidents_block = None
    if history:
        import tempfile

        incidents_block = {
            "dir": tempfile.mkdtemp(prefix="dstpu_overhead_inc_"),
            "eval_interval_s": 1.0}
    # the obs_wire arm serves a REAL ephemeral-port HTTP exporter and
    # keeps a RemoteReplica scraping it throughout the timed loop —
    # the enabled delta is the price of being observed over the wire
    # (the exporter handles requests on its own thread; the engine
    # step loop itself has no obs_wire code path)
    telemetry_block = {"http_port": 0} if obs_wire else telemetry
    eng = serving_engine(
        params, cfg, max_batch=slots, page_size=8,
        num_pages=slots * (-(-max_seq // 8)) + 8, max_seq=max_seq,
        prefill_bucket=prompt_len, decode_chunk=decode_chunk,
        telemetry=telemetry_block, tracing=tracing, slo=slo_block,
        history=history_block, incidents=incidents_block,
        devprof=bool(devprof))
    scrape_stop = scraper = rem = None
    if obs_wire:
        import threading

        from deepspeed_tpu.config import ObsWireConfig
        from deepspeed_tpu.obs_wire import RemoteReplica

        rem = RemoteReplica(
            f"http://127.0.0.1:{eng._tel_exporter.port}", "ab",
            cfg=ObsWireConfig(enabled=True, poll_interval_s=0.05,
                              timeout_s=1.0, retries=1))
        scrape_stop = threading.Event()

        def _scrape_loop():
            while not scrape_stop.is_set():
                rem.maybe_poll()
                scrape_stop.wait(0.02)

        scraper = threading.Thread(target=_scrape_loop, daemon=True)
        scraper.start()

    def decode_steps():
        return int(eng.registry.snapshot()["counters"]
                   .get("serving_decode_steps", 0))

    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, prompt_len).tolist()
               for _ in range(requests)]
    # warmup: compile prefill + decode chunk
    eng.submit("warmup", prompts[0], max_new_tokens=2)
    eng.run()
    eng.drain_finished()

    warmup_steps = decode_steps()

    for i, p in enumerate(prompts):
        eng.submit(i, p, max_new_tokens=new_tokens)
    t0 = time.perf_counter()
    calls = 0
    while eng.has_work:
        eng.step()
        calls += 1
    wall = time.perf_counter() - t0
    if scrape_stop is not None:
        scrape_stop.set()
        scraper.join(timeout=5)
    out = eng.drain_finished()
    generated = sum(len(v) - prompt_len for v in out.values())
    # warmup's decode steps are outside the timed window — they must
    # not dilute the per-step cost
    steps = decode_steps() - warmup_steps
    if steps <= 0:
        # telemetry disabled: the registry counters are no-ops; every
        # iteration of this workload runs one K-step decode chunk
        # (prompts admit whole, slots never idle), so calls*K is the
        # same count the stats path reports
        steps = calls * eng.decode_chunk
    total_ms = 1000 * wall / max(steps, 1)

    # pure jit cost of one decode step: replay the engine's compiled
    # chunk fn, feeding the returned cache back in (its donated input)
    K = eng.decode_chunk
    tok = jnp.zeros((slots, 1), jnp.int32)
    temps = jnp.zeros((slots,), jnp.float32)
    keys = (eng._key, jnp.int32(0))   # base key, dispatch ordinal
    c = eng.cache
    toks, c = eng._decode_chunk_fn(eng.params, tok, c, *keys, temps)
    float(jnp.sum(toks))  # ensure compiled + done
    iters = 30
    t0 = time.perf_counter()
    for _ in range(iters):
        toks, c = eng._decode_chunk_fn(eng.params, tok, c, *keys, temps)
    float(jnp.sum(toks))
    jit_ms = 1000 * (time.perf_counter() - t0) / (iters * K)

    return {
        "model": model_name, "slots": slots, "decode_chunk": K,
        "requests": requests, "generated": generated,
        "telemetry": bool(telemetry), "tracing": bool(tracing),
        "slo": bool(slo), "history": bool(history),
        "devprof": bool(devprof), "obs_wire": bool(obs_wire),
        "scrapes_during_run": rem.scrapes if rem is not None else 0,
        "scrape_errors_during_run":
            rem.scrape_errors if rem is not None else 0,
        "decode_steps": steps,
        "prefill_chunks": int(eng.registry.snapshot()["counters"]
                              .get("serving_prefill_chunks", 0)),
        "total_ms_per_step": round(total_ms, 3),
        "jit_ms_per_step": round(jit_ms, 3),
        "scheduler_ms_per_step": round(max(total_ms - jit_ms, 0.0), 3),
        "scheduler_fraction": round(
            max(total_ms - jit_ms, 0.0) / total_ms, 3) if total_ms else None,
    }


def _ab(param, best_of=3, **fixed):
    """Best-of-N A/B of one measure_point flag: the decode loop with
    the feature DISABLED must sit within noise of the enabled loop's
    cost (CPU wall jitter dominates a single rep)."""
    ab = {}
    for on in (True, False):
        reps = [measure_point("llama", 4, decode_chunk=8,
                              **{param: on}, **fixed)
                for _ in range(best_of)]
        best = min(reps, key=lambda r: r["total_ms_per_step"])
        ab["enabled" if on else "disabled"] = best
        print(json.dumps({f"{param}_ab": best}), flush=True)
    d_ms = (ab["enabled"]["total_ms_per_step"]
            - ab["disabled"]["total_ms_per_step"])
    return ab, {
        "enabled_ms_per_step": ab["enabled"]["total_ms_per_step"],
        "disabled_ms_per_step": ab["disabled"]["total_ms_per_step"],
        "enabled_minus_disabled_ms": round(d_ms, 3),
        "enabled_overhead_fraction": round(
            max(d_ms, 0.0) / ab["disabled"]["total_ms_per_step"], 4)
        if ab["disabled"]["total_ms_per_step"] else None,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true",
                    help="force the CPU backend in-process")
    ap.add_argument("--ab-only", action="store_true",
                    help="re-run only the telemetry/tracing overhead "
                         "A/Bs and merge into an existing json-out "
                         "(keeps the full sweep's rows)")
    ap.add_argument("--json-out",
                    default=os.path.join(REPO, "SERVING_OVERHEAD.json"))
    args = ap.parse_args()
    if args.ab_only and not os.path.exists(args.json_out):
        ap.error(f"--ab-only merges into an existing --json-out, but "
                 f"{args.json_out} does not exist (run the full sweep "
                 "first, or fix the path)")

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    rows = []
    if not args.ab_only:
        # slots sweep at the default chunking, all three families
        for model in ("llama", "mixtral", "gpt2"):
            for slots in (1, 2, 4, 8):
                rows.append(measure_point(model, slots, decode_chunk=8))
                print(json.dumps(rows[-1]), flush=True)
        # sync-amortization sweep: K=1 pays one host sync per token
        for k in (1, 2, 4):
            rows.append(measure_point("llama", 4, decode_chunk=k))
            print(json.dumps(rows[-1]), flush=True)

    # telemetry-overhead A/B (ISSUE 2 acceptance): registry on vs off.
    # The enabled delta is the price of TTFT/ITL histograms + gauges on
    # every step.
    _, telemetry_overhead = _ab("telemetry", tracing=False)
    telemetry_overhead["backend"] = jax.default_backend()
    telemetry_overhead["note"] = (
        "best-of-3 ms/decode-step, registry enabled vs disabled on the "
        "same build; disabled path = no-op metric singletons, no clock "
        "reads in the decode loop")

    # tracing-overhead A/B (ISSUE 4 acceptance): flight recorder on vs
    # off, telemetry on in BOTH arms — the enabled delta is the price
    # of the lifecycle events (one ring append per edge + one per
    # decode sync).
    _, tracing_overhead = _ab("tracing")
    tracing_overhead["backend"] = jax.default_backend()
    tracing_overhead["note"] = (
        "best-of-3 ms/decode-step, flight recorder enabled vs disabled "
        "(telemetry on in both arms); disabled path = shared no-op "
        "tracer, no clock read, no ring append")

    # slo-overhead A/B (ISSUE 6 acceptance): per-tier classification +
    # rolling windows + burn gauges on vs off, telemetry/tracing on in
    # both arms — the enabled delta is the price of one shared clock
    # read per token and the finish-time classification.
    _, slo_overhead = _ab("slo")
    slo_overhead["backend"] = jax.default_backend()
    slo_overhead["note"] = (
        "best-of-3 ms/decode-step, SLO tracker enabled (default tier "
        "with ttft/itl/deadline objectives) vs disabled on the same "
        "build (telemetry+tracing on in both arms); disabled path = "
        "shared no-op tracker")

    # history+incidents-overhead A/B (ISSUE 15 acceptance): rings +
    # incident detectors on vs off, telemetry/tracing/slo on in BOTH
    # arms — the enabled delta is the price of the exporter tick-hook
    # pass in the step loop (one monotonic compare until a hook is
    # due; sampling itself lands at most once per second, off the
    # decode hot path).
    _, history_overhead = _ab("history", slo=True)
    history_overhead["backend"] = jax.default_backend()
    history_overhead["note"] = (
        "best-of-3 ms/decode-step, history rings + incident engine "
        "enabled (1 s sampling / 1 s evaluation cadence) vs disabled "
        "on the same build (telemetry+tracing+slo on in both arms); "
        "the enabled path adds one tick-hook compare per step")

    # devprof-overhead A/B (ISSUE 17 acceptance): compile sentinel on
    # vs off, telemetry/tracing on in BOTH arms — the enabled delta is
    # the price of the sentinel's cache-size check per dispatch.
    _, devprof_overhead = _ab("devprof")
    devprof_overhead["backend"] = jax.default_backend()
    devprof_overhead["note"] = (
        "best-of-3 ms/decode-step, devprof enabled (the compile "
        "sentinel's cache-size check per dispatch) vs disabled on the "
        "same build "
        "(telemetry+tracing on in both arms); disabled path = shared "
        "NULL_DEVPROF, wrap() is the identity")

    # obs_wire-overhead A/B (ISSUE 19 acceptance): a real HTTP
    # exporter on an ephemeral port + a RemoteReplica actively
    # scraping statusz/healthz/historyz at a 50 ms cadence during the
    # timed decode loop, vs the plain in-process registry —
    # telemetry/tracing on in BOTH arms.  The enabled delta is the
    # price of being observed over the wire; the decode loop itself
    # has no obs_wire branch, so the cost is exporter-thread GIL
    # contention only.
    _, obs_wire_overhead = _ab("obs_wire")
    obs_wire_overhead["backend"] = jax.default_backend()
    obs_wire_overhead["note"] = (
        "best-of-3 ms/decode-step, ephemeral-port HTTP exporter + "
        "live RemoteReplica scrape loop (50 ms cadence) vs in-process "
        "registry only (telemetry+tracing on in both arms); the "
        "engine step loop has no obs_wire code path — the delta is "
        "serving-the-scrapes contention")

    if args.ab_only and os.path.exists(args.json_out):
        with open(args.json_out) as f:
            out = json.load(f)
    else:
        out = {
            "metric": "serving_scheduler_overhead",
            "backend": jax.default_backend(),
            "note": ("scheduler_ms_per_step = wall/decode_steps minus "
                     "pure-jit replay of the engine's compiled decode "
                     "chunk; host cost is backend-independent, so the "
                     "CPU rows bound the TPU scheduler overhead"),
            "rows": rows,
        }
    out["telemetry_overhead"] = telemetry_overhead
    out["tracing_overhead"] = tracing_overhead
    out["slo_overhead"] = slo_overhead
    out["history_overhead"] = history_overhead
    out["devprof_overhead"] = devprof_overhead
    out["obs_wire_overhead"] = obs_wire_overhead
    with open(args.json_out, "w") as f:
        json.dump(out, f, indent=1)
    print("→", args.json_out)


if __name__ == "__main__":
    main()

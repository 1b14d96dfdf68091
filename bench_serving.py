#!/usr/bin/env python
"""Serving throughput benchmark: continuous-batching decode on the local
chip (round-2 verdict task 6; crash-proofed per round-5 verdict weak #2).

Drives :class:`deepspeed_tpu.inference.serving.ServingEngine` with B=8
slots over a stream of staggered requests and reports generated tokens
per second (decode throughput, the FastGen headline unit).

Crash-proof output contract: the run is a LIST of configs, and the
output JSON is rewritten after EVERY completed config (``partial: true``
until the last one lands) — a run killed at its time limit still leaves one row per
config that finished.  Any single config's measure loop is capped at
~``DSTPU_SERVING_CAP_S`` (default 120 s) of wall clock: the loop stops
stepping at the cap and the row reports the truncated token count
honestly (``truncated: true``) rather than burning the window.

    python bench_serving.py               # real chip, one config
    python bench_serving.py --cpu         # smoke on CPU
    python bench_serving.py --zero-inference
        # adds the ZeRO-Inference weight-streamed config next to the
        # resident baseline (same model, same traffic) — the >HBM
        # serving A/B; --hbm-budget-mb pins layers, default streams all
    python bench_serving.py --prefix-cache
        # shared-prefix workload (N users x one system prompt + short
        # unique tails) served twice — prefix caching OFF then ON —
        # reporting TTFT, tokens/s and the token-level hit rate per
        # row; the slow lane stamps this as PREFIX_BENCH.json
    python bench_serving.py --speculative
        # repetitive-motif workload (the traffic prompt-lookup
        # drafting exists for) served with speculation OFF then ON —
        # tokens/s, TTFT and the mean accepted length per verify
        # sweep; combined with --zero-inference it adds a streamed
        # pair whose rows record weight bytes streamed PER GENERATED
        # TOKEN (the ZeRO-Inference amortization contract); the slow
        # lane stamps this as SPEC_BENCH.json
    python bench_serving.py --tp 2
        # tensor-parallel A/B: the same traffic on a 1-device engine
        # vs an N-device model-axis mesh (GSPMD shards wq/wk/wv/w1/w3
        # column-wise, wo/w2 row-wise, KV heads over the mesh) —
        # decode tokens/s, TTFT and a token-identity gate
        # (mismatched_requests must be 0; sharding is an execution
        # strategy).  With --cpu the devices are virtual host CPUs;
        # the slow lane stamps this as TP_BENCH.json
    python bench_serving.py --kv-tier
        # eviction-churn workload (--prefix-groups distinct system
        # prompts revisited in a second pass, over a KV pool sized to
        # hold only ~1.5 of them) served with the spill tier OFF then
        # ON — hit rate, p50 TTFT, demote/promote volume, and a
        # token-identity check between the arms (the bit-exact spill
        # contract); the slow lane stamps this as KV_TIER_BENCH.json
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

CAP_S = float(os.environ.get("DSTPU_SERVING_CAP_S", "120"))


def build_cfg(args, mod_name):
    from deepspeed_tpu.models import gpt2, llama, mixtral

    # --cpu-dim/--cpu-layers scale the CPU smoke model past cache-
    # resident size: the default 64-dim toy fits in L2, so decode is
    # dispatch/FLOP-bound and bandwidth optimizations (speculation's
    # one-weight-read-per-sweep) can't show.  A ~14M-param config
    # (dim 512 x 4 layers, ~28 MB bf16) spills the cache hierarchy and
    # makes each decode step pay the weight read the paper's memory-
    # wall analysis is about — the regime TPU decode always lives in.
    scale = {}
    if args.cpu and (args.cpu_dim or args.cpu_layers):
        dim = args.cpu_dim or 64
        heads = max(4, dim // 64)
        scale = {"dim": dim, "n_layers": args.cpu_layers or 2,
                 "n_heads": heads,
                 "vocab_size": max(256, 2 * dim),
                 "max_seq_len": max(256,
                                    args.prompt_len + args.new_tokens)}
    if mod_name == "mixtral":
        mod = mixtral
        kw = {"n_kv_heads": scale.get("n_heads", 2), "num_experts": 4,
              **scale} if scale else \
             {"dim": 64, "n_layers": 2, "n_heads": 4, "n_kv_heads": 2,
              "num_experts": 4}
        cfg = (mixtral.MixtralConfig.tiny(**kw)
               if args.cpu else
               # ~0.24B-active / ~0.76B-total MoE decode model (8
               # experts, top-2) — smaller active than the 0.42B dense
               # llama row; compare per-active-param, not head-to-head
               mixtral.MixtralConfig(
                   vocab_size=16384, dim=1024, n_layers=8, n_heads=8,
                   n_kv_heads=4, ffn_dim=3584, num_experts=8, top_k=2,
                   max_seq_len=1024, rope_theta=500000.0))
    elif mod_name == "gpt2":
        mod = gpt2
        kw = scale or {"dim": 64, "n_layers": 2, "n_heads": 4,
                       "max_seq_len": 256}
        cfg = (gpt2.GPT2Config.tiny(**kw)
               if args.cpu else
               gpt2.GPT2Config(vocab_size=16384, dim=1536, n_layers=12,
                               n_heads=12, max_seq_len=1024))
    else:
        mod = llama
        kw = {"n_kv_heads": scale["n_heads"], **scale} if scale else \
             {"dim": 64, "n_layers": 2, "n_heads": 4, "n_kv_heads": 2}
        cfg = (llama.LlamaConfig.tiny(**kw)
               if args.cpu else
               # ~0.5B decode model; paged decode attention is the hot
               # kernel
               llama.LlamaConfig(
                   vocab_size=16384, dim=1536, n_layers=12, n_heads=12,
                   n_kv_heads=4, ffn_dim=5376, max_seq_len=1024,
                   rope_theta=500000.0))
    return mod, cfg


def commit(out, path):
    """Rewrite the evidence file NOW — every completed row survives a
    kill (verified by SIGKILLing mid-run and reading the file back);
    atomic, so the kill can only ever truncate the temp file."""
    from deepspeed_tpu.utils.evidence import atomic_write_json

    atomic_write_json(out, path)


def build_prompts(args, cfg):
    """Request workload.  Default: independent random prompts.
    ``--prefix-cache``: the shared-prefix fleet shape — N users behind
    ONE long system prompt, each with a short unique tail — the traffic
    prefix caching exists for.  ``--speculative``: repetitive prompts
    (a per-request random motif tiled to prompt_len) — the
    templated/code/multi-turn shape prompt-lookup drafting exists for;
    greedy decode settles into the motif's loop, so drafts accept."""
    import numpy as np

    rng = np.random.default_rng(0)
    if args.kv_tier:
        # eviction churn: G distinct shared prefixes visited in TWO
        # passes.  The pool holds ~1.5 prefixes beyond the decode
        # working set, so by the time pass 2 revisits a group its
        # pages were reclaimed — dropped (tier off: re-prefill) or
        # demoted (tier on: promoted back by DMA)
        groups = [rng.integers(1, cfg.vocab_size,
                               args.prefix_len).tolist()
                  for _ in range(args.prefix_groups)]
        per = max(args.requests // (2 * args.prefix_groups), 1)
        return [g + rng.integers(1, cfg.vocab_size,
                                 args.tail_len).tolist()
                for _ in range(2) for g in groups for _ in range(per)]
    if args.prefix_cache:
        prefix = rng.integers(1, cfg.vocab_size, args.prefix_len).tolist()
        return [prefix + rng.integers(1, cfg.vocab_size,
                                      args.tail_len).tolist()
                for _ in range(args.requests)]
    if args.speculative:
        prompts = []
        for _ in range(args.requests):
            motif = rng.integers(1, cfg.vocab_size,
                                 args.motif_len).tolist()
            reps = -(-args.prompt_len // args.motif_len)
            prompts.append((motif * reps)[:args.prompt_len])
        return prompts
    return [rng.integers(1, cfg.vocab_size, args.prompt_len).tolist()
            for _ in range(args.requests)]


def measure_config(name, args, params, mod, cfg, phase, prompts,
                   zero_inference=None, prefix_cache=None,
                   speculative=None, kv_tier=None, tp=0):
    """Build one engine flavor, warm it, drive the request stream under
    the wall-clock cap; returns ``(evidence row, finished outputs)`` —
    the outputs feed the kv-tier A/B's token-identity check."""
    import jax
    import numpy as np

    from deepspeed_tpu.inference import init_serving

    max_seq = args.prompt_len + args.new_tokens
    t_build = time.perf_counter()
    config = {}
    if zero_inference is not None:
        config["zero_inference"] = zero_inference
    if prefix_cache is not None:
        config["prefix_cache"] = prefix_cache
    if speculative is not None:
        config["speculative"] = speculative
    if kv_tier is not None:
        config["kv_tier"] = kv_tier
    # device-truth observability rides every row: the compile sentinel
    # proves the steady-state run never recompiled (bench_gate pins
    # detail.devprof.steady_state_compiles at 0)
    config["devprof"] = True
    # SLO classification rides every row (--slo-ttft-ms 0 disables):
    # the same engine that reports tokens/s reports how many of those
    # tokens came from requests that met their latency objective —
    # goodput next to throughput, so an A/B win that only moved
    # throughput is visible as such
    objective = {}
    if args.slo_ttft_ms > 0:
        objective["ttft_s"] = args.slo_ttft_ms / 1000.0
    if args.slo_itl_ms > 0:
        objective["itl_s"] = args.slo_itl_ms / 1000.0
    if args.slo_deadline_ms > 0:
        objective["deadline_s"] = args.slo_deadline_ms / 1000.0
    if objective:
        config["slo"] = {"tiers": {"default": objective}}
    # prefix rows absorb a cache-hit's uncached suffix in
    # prefill_bucket-token continuation chunks — a page-sized bucket
    # (vs the whole padded prompt) is what turns the skipped prefix
    # into skipped COMPUTE, for the miss row too (same bucket, A/B
    # stays apples-to-apples)
    bucket = 16 if (args.prefix_cache or args.kv_tier) \
        else args.prompt_len
    num_pages = args.slots * (-(-max_seq // 16)) + 32
    if args.kv_tier:
        # pool sized to FORCE eviction: room for ~2 of the
        # --prefix-groups shared prefixes (prompts SHARE their group's
        # prefix pages, so that is the real working set) plus each
        # slot's private tail+decode pages.  With >2 groups cycling,
        # publishing group C's prefix must reclaim group A's — so pass
        # 2's revisits always find their group demoted (tier on) or
        # dropped (tier off)
        prefix_pages = -(-args.prefix_len // 16)
        tail_pages = 1 + -(-(args.tail_len + args.new_tokens) // 16)
        num_pages = (2 * prefix_pages
                     + args.slots * tail_pages + 2)
        if name == "kv_tier_ref":
            # the no-eviction oracle: every prefix stays warm — the
            # identity gate compares the on arm against this row
            num_pages = (args.slots * (-(-max_seq // 16))
                         + args.prefix_groups * prefix_pages + 8)
    mesh = None
    if tp and tp > 1:
        # the TP A/B arm: this engine spans tp devices on the model
        # axis (CPU: virtual host devices forced in main before the
        # backend came up)
        from deepspeed_tpu.topology import MeshSpec

        mesh = MeshSpec.build({"model": tp},
                              devices=jax.devices()[:tp])
    engine = init_serving(
        params, cfg, config=config or None, max_batch=args.slots,
        page_size=16, num_pages=num_pages,
        max_seq=max_seq, prefill_bucket=bucket,
        decode_chunk=args.decode_chunk, prefill_chunk=args.prefill_chunk,
        weight_dtype=args.weight_dtype, mesh=mesh)

    rng = np.random.default_rng(1)
    phase(f"[{name}] warmup (compile prefill + decode)")
    t_compile = time.perf_counter()
    # a prefix-cached engine also compiles the continuation-chunk
    # program the hit path runs: warm up with the SAME disjoint prompt
    # twice (second admission hits the first's pages) so no timed
    # request pays a compile
    warm = rng.integers(1, cfg.vocab_size, args.prompt_len).tolist()
    reps = 2 if (prefix_cache or {}).get("enabled") else 1
    for i in range(reps):
        engine.submit(f"warmup{i}", warm, max_new_tokens=4)
        engine.run()
    engine.drain_finished()
    compile_s = time.perf_counter() - t_compile
    # the flight recorder saw the warmup lifecycle (compile-dominated
    # spans): drop it so trace_breakdown covers timed traffic only
    if engine.tracer.enabled:
        engine.tracer.recorder.clear()

    # warmup traffic must not pollute the timed rows' comparison:
    # histogram/counter deltas against this snapshot isolate it
    snap0 = engine.registry.snapshot()
    ttft0 = snap0["histograms"].get("serving_ttft_seconds", {})
    cnt0 = snap0["counters"]

    phase(f"[{name}] timed run (cap {CAP_S:.0f}s)")
    for i, p in enumerate(prompts):
        engine.submit(i, p, max_new_tokens=args.new_tokens)
    t0 = time.perf_counter()
    truncated = False
    while engine.has_work:
        engine.step()
        if time.perf_counter() - t0 > CAP_S:
            truncated = True
            break
    dt = time.perf_counter() - t0
    out = engine.drain_finished()
    generated = sum(len(v) - args.prompt_len for v in out.values())
    # count in-flight tokens too when truncated: they were produced
    generated += sum(len(s.generated) for s in engine.slots
                     if s is not None)
    tps = generated / dt if dt > 0 else 0.0
    phase(f"[{name}] done: {generated} tokens in {dt:.1f}s")
    # one registry snapshot per row: TTFT/inter-token distributions,
    # queue/occupancy/KV gauges, stall and bandwidth provenance all ride
    # in detail.telemetry (the old stats keys stay as flat conveniences)
    snap = engine.registry.snapshot()
    cnt = snap["counters"]
    row = {
        "config": name,
        "value": round(tps, 1),
        "unit": "tokens/s",
        "detail": {
            "backend": jax.default_backend(),
            "model": args.model,
            "model_params": mod.param_count(cfg),
            "decode_chunk": args.decode_chunk,
            "slots": args.slots,
            "requests": args.requests,
            "completed_requests": len(out),
            "prompt_len": args.prompt_len,
            "new_tokens": args.new_tokens,
            "generated_total": generated,
            "wall_s": round(dt, 2),
            "build_s": round(t_compile - t_build, 1),
            "compile_s": round(compile_s, 1),
            "truncated": truncated,
            "decode_steps": int(cnt.get("serving_decode_steps", 0)),
            "prefill_chunks": int(cnt.get("serving_prefill_chunks", 0)),
            "prefill_chunk": args.prefill_chunk,
            "weight_dtype": args.weight_dtype,
            "preempted": int(cnt.get("serving_preempted_requests", 0)),
            "ms_per_decode_step": round(
                1000 * dt / max(int(cnt.get("serving_decode_steps", 0)),
                                1), 2),
            "telemetry": snap,
        },
    }
    if hasattr(engine, "_kernels"):
        # the readers this engine's compiled programs baked (what
        # /statusz reports — resolved once at build)
        row["detail"]["kernels"] = engine._kernels.as_dict()
    # compile ledger + roofline for this row: steady_state_compiles
    # is the zero-recompile contract (gated at exactly 0), MFU/MBU are
    # the device-truth utilization next to the tokens/s headline
    row["detail"]["devprof"] = engine.statusz().get("devprof", {})
    ttft = snap["histograms"].get("serving_ttft_seconds", {})
    d_count = int(ttft.get("count", 0)) - int(ttft0.get("count", 0))
    if d_count > 0:
        row["detail"]["ttft_ms"] = round(
            1000 * (ttft.get("sum", 0.0) - ttft0.get("sum", 0.0))
            / d_count, 2)
    if engine.tracer.enabled:
        # per-request critical path from the flight recorder (queue
        # wait / prefill / decode / stream-stall, p50/p95 over the
        # timed traffic — warmup was cleared from the ring above)
        from deepspeed_tpu.request_trace import request_breakdown

        row["detail"]["trace_breakdown"] = request_breakdown(
            engine.tracer.recorder.events())["summary"]
    def delta(key):
        # counter delta over the TIMED traffic only (warmup delta'd away)
        return int(cnt.get(key, 0)) - int(cnt0.get(key, 0))

    if objective:
        fin = delta("slo_default_attained_requests") + \
            delta("slo_default_violated_requests")
        good = delta("slo_default_goodput_tokens")
        row["detail"]["slo"] = {
            "tier": "default",
            "objective": objective,
            "finished": fin,
            "attained": delta("slo_default_attained_requests"),
            "attainment": (round(
                delta("slo_default_attained_requests") / fin, 4)
                if fin else 1.0),
            "ttft_violations": delta("slo_default_ttft_violations"),
            "itl_violations": delta("slo_default_itl_violations"),
            "deadline_violations": delta(
                "slo_default_deadline_violations"),
            # tokens from SLO-attained requests over the same wall the
            # tokens/s headline uses: goodput next to throughput
            "goodput_tokens_per_s": (round(good / dt, 1)
                                     if dt > 0 else 0.0),
        }
    if args.speculative:
        slots = delta("spec_verify_slots")
        emitted = delta("spec_emitted_tokens")
        row["detail"]["speculative"] = {
            "enabled": bool((speculative or {}).get("enabled")),
            "draft_tokens": args.draft_tokens,
            "motif_len": args.motif_len,
            "drafted": delta("spec_drafted_tokens"),
            "accepted": delta("spec_accepted_tokens"),
            "rejected": delta("spec_rejected_tokens"),
            "verify_sweeps": delta("spec_verify_sweeps"),
            # accepted prefix + bonus token, per slot per verify sweep —
            # the amortization factor (1.0 = no draft ever accepted)
            "mean_accepted_len": (round(emitted / slots, 3)
                                  if slots else None),
        }
    if args.kv_tier:
        pt = delta("prefix_cache_prompt_tokens")
        ct = delta("prefix_cache_cached_tokens")
        row["detail"]["kv_tier"] = {
            "enabled": bool((kv_tier or {}).get("enabled")),
            "prefix_groups": args.prefix_groups,
            "prefix_len": args.prefix_len,
            "num_pages": num_pages,
            "hit_rate": round(ct / pt, 4) if pt else 0.0,
            "hits": delta("prefix_cache_hits"),
            "misses": delta("prefix_cache_misses"),
            "evicted_pages": delta("prefix_cache_evicted_pages"),
            "demoted_pages": delta("kv_tier_demoted_pages"),
            "promoted_pages": delta("kv_tier_promoted_pages"),
            "promote_deferrals": delta("kv_tier_promote_deferrals"),
            "admit_waits": delta("kv_tier_admit_waits"),
            "occupancy": (engine._kv_pool.occupancy()
                          if engine._kv_pool is not None else None),
        }
        tb = row["detail"].get("trace_breakdown", {})
        if "ttft_s" in tb:
            row["detail"]["kv_tier"]["ttft_p50_ms"] = round(
                1000 * tb["ttft_s"]["p50"], 2)
    if args.prefix_cache:
        # token-level hit rate over the TIMED traffic only: warmup used
        # a disjoint prompt, so its miss + self-hit are delta'd away
        pt = delta("prefix_cache_prompt_tokens")
        ct = delta("prefix_cache_cached_tokens")
        row["detail"]["prefix_cache"] = {
            "enabled": bool((prefix_cache or {}).get("enabled")),
            "hits": delta("prefix_cache_hits"),
            "misses": delta("prefix_cache_misses"),
            "cached_tokens": ct,
            "prompt_tokens": pt,
            "hit_rate": round(ct / pt, 4) if pt else 0.0,
            "published_pages": delta("prefix_cache_published_pages"),
            "evicted_pages": delta("prefix_cache_evicted_pages"),
            "pool_pages": len(engine.allocator.pool),
            "prefix_len": args.prefix_len,
            "tail_len": args.tail_len,
        }
    if zero_inference is not None:
        zi_wait = snap["histograms"].get("zi_prefetch_wait_seconds", {})
        row["detail"]["zero_inference"] = {
            **{k: v for k, v in engine.plan.items()},
            "tier": engine._zi.tier,
            "layer_h2d_uploads": int(cnt.get("zi_layer_h2d_uploads", 0)),
            "prefetch_wait_s": round(zi_wait.get("sum", 0.0), 3),
            # THE amortization number: one verify sweep = one layer-
            # weight stream scoring K+1 positions, so speculation
            # divides this by ≈ the mean accepted length
            "bytes_streamed_per_token": (
                round(delta("zi_bytes_uploaded") / generated, 1)
                if generated else None),
        }
    if args.tp:
        row["detail"]["tp"] = {
            "tp": max(tp, 1),
            "mesh": engine.mesh_info(),
        }
    outputs = {str(k): list(map(int, v)) for k, v in out.items()}
    del engine
    if mesh is not None:
        from deepspeed_tpu.topology import set_current_mesh

        set_current_mesh(None)
    return row, outputs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=128)
    ap.add_argument("--decode-chunk", type=int, default=8,
                    help="decode steps per host sync (1 = sync per token)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="split-fuse: absorb prompts N tokens/iteration "
                         "between decodes (0 = whole-prompt prefill)")
    ap.add_argument("--weight-dtype", default="bfloat16",
                    choices=["bfloat16", "int8"],
                    help="int8 = weight-only quantized serving")
    ap.add_argument("--model", default="llama",
                    choices=["llama", "mixtral", "gpt2"],
                    help="model family served through the registry")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="A/B the shared-prefix workload with prefix "
                         "caching off vs on (TTFT, tokens/s, hit rate)")
    ap.add_argument("--prefix-len", type=int, default=240,
                    help="shared system-prompt length for the "
                         "--prefix-cache workload (page-aligned helps)")
    ap.add_argument("--tail-len", type=int, default=8,
                    help="per-user unique tail length for the "
                         "--prefix-cache workload")
    ap.add_argument("--kv-tier", action="store_true",
                    help="A/B the eviction-churn workload with the "
                         "tiered KV cache (host/NVMe spill) off vs on "
                         "(hit rate, p50 TTFT, token identity)")
    ap.add_argument("--prefix-groups", type=int, default=4,
                    help="distinct shared prefixes in the --kv-tier "
                         "workload (the pool holds ~1.5 of them)")
    ap.add_argument("--kv-host-pool-mb", type=int, default=64,
                    help="host pool size for the --kv-tier on arm")
    ap.add_argument("--kv-nvme-dir", default=None,
                    help="also spill host-pool overflow to NVMe files "
                         "under this dir in the --kv-tier on arm")
    ap.add_argument("--kv-quantize-cold", action="store_true",
                    help="int8-quantize demoted pages in the on arm "
                         "(disables the bit-exact identity check)")
    ap.add_argument("--speculative", action="store_true",
                    help="A/B the repetitive-motif workload with "
                         "speculative decoding off vs on (tokens/s, "
                         "TTFT, mean accepted length per verify sweep)")
    ap.add_argument("--motif-len", type=int, default=8,
                    help="repeating motif length for the --speculative "
                         "workload (prompts tile it to --prompt-len)")
    ap.add_argument("--draft-tokens", type=int, default=4,
                    help="speculation window K for the --speculative "
                         "A/B (drafts per verify sweep)")
    ap.add_argument("--tp", type=int, default=0,
                    help="A/B the same traffic on a 1-device engine vs "
                         "an N-device model-axis (tensor-parallel) "
                         "mesh — decode tokens/s, TTFT, and a token-"
                         "identity gate (sharding is an execution "
                         "strategy, so tokens must match exactly).  "
                         "With --cpu the N virtual host devices are "
                         "forced before the backend comes up; the slow "
                         "lane stamps this as TP_BENCH.json")
    ap.add_argument("--zero-inference", action="store_true",
                    help="also measure the ZeRO-Inference weight-streamed "
                         "engine (host-tier layer streaming) next to the "
                         "resident baseline")
    ap.add_argument("--hbm-budget-mb", type=int, default=0,
                    help="zero-inference HBM budget; 0 = no budget "
                         "(stream every layer)")
    ap.add_argument("--zi-tier", default="host", choices=["host", "nvme"],
                    help="zero-inference weight tier")
    ap.add_argument("--cpu-dim", type=int, default=0,
                    help="scale the --cpu smoke model's width (0 = the "
                         "64-dim toy).  512 x --cpu-layers 4 is ~14M "
                         "params / 28 MB bf16 — past cache-resident, so "
                         "decode pays real weight reads and bandwidth "
                         "A/Bs (--speculative) measure the right regime")
    ap.add_argument("--cpu-layers", type=int, default=0,
                    help="scale the --cpu smoke model's depth (0 = 2)")
    ap.add_argument("--slo-ttft-ms", type=float, default=5000.0,
                    help="SLO TTFT objective for the default tier; "
                         "rows then record attainment + goodput next "
                         "to tokens/s (0 disables the slo block)")
    ap.add_argument("--slo-itl-ms", type=float, default=0.0,
                    help="SLO worst inter-token-gap objective (0 = "
                         "unset)")
    ap.add_argument("--slo-deadline-ms", type=float, default=0.0,
                    help="SLO end-to-end deadline (0 = unset)")
    ap.add_argument("--repeats", type=int, default=1,
                    help="measure each config N times and keep the best "
                         "row (tokens/s) — rides out scheduler noise on "
                         "shared CPU hosts")
    ap.add_argument("--json-out", default=os.path.join(REPO,
                                                       "SERVING_BENCH.json"))
    args = ap.parse_args()
    if args.tp and (args.kv_tier or args.prefix_cache
                    or args.speculative or args.zero_inference):
        raise SystemExit("--tp is its own A/B")
    if args.tp and args.tp < 2:
        raise SystemExit("--tp needs N >= 2 (the A/B is 1 vs N devices)")
    if args.tp and args.cpu:
        # N virtual host devices for the sharded arm — must land before
        # the first backend touch below
        from deepspeed_tpu.mesh import host_device_count

        host_device_count(args.tp)
    if args.kv_tier and (args.prefix_cache or args.speculative
                         or args.zero_inference):
        raise SystemExit("--kv-tier is its own A/B")
    if args.prefix_cache:
        if args.zero_inference:
            raise SystemExit(
                "--prefix-cache and --zero-inference are separate A/Bs")
        if args.speculative:
            raise SystemExit(
                "--prefix-cache and --speculative are separate A/Bs")
    if args.prefix_cache or args.kv_tier:
        # the workload defines the prompt length
        args.prompt_len = args.prefix_len + args.tail_len

    import jax

    from deepspeed_tpu.utils.backend import (device_info,
                                             enable_compile_cache,
                                             require_tpu)

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
        device = device_info()
    else:
        # full-size models are timed on a TPU or not at all
        device = require_tpu()
    enable_compile_cache()

    mod, cfg = build_cfg(args, args.model)
    # phase timestamps: a run killed at its time limit must show which
    # phase was in flight
    t_start = time.perf_counter()

    def phase(msg):
        print(f"[{time.perf_counter() - t_start:7.1f}s] {msg}",
              flush=True)

    phase(f"backend={jax.default_backend()} — init params")
    params = mod.init_params(jax.random.PRNGKey(0), cfg)

    # (name, zero_inference, prefix_cache, speculative, kv_tier, tp)
    # per engine flavor
    configs = [("resident", None, None, None, None, 0)]
    if args.tp:
        # same model, same traffic: the 1-device oracle vs the
        # N-device model-axis mesh — sharding is an execution
        # strategy, so the identity gate below must see 0 mismatches
        configs = [("tp1", None, None, None, None, 0),
                   (f"tp{args.tp}", None, None, None, None, args.tp)]
    if args.prefix_cache:
        configs = [("prefix_off", None, {"enabled": False}, None, None),
                   ("prefix_on", None, {"enabled": True}, None, None)]
    if args.kv_tier:
        # BOTH arms run the prefix cache — the A/B is the spill tier
        kvt_on = {"enabled": True,
                  "host_pool_bytes": args.kv_host_pool_mb << 20,
                  "quantize_cold": args.kv_quantize_cold}
        if args.kv_nvme_dir:
            kvt_on["nvme_dir"] = args.kv_nvme_dir
        configs = [
            ("kv_tier_off", None, {"enabled": True}, None, None),
            ("kv_tier_on", None, {"enabled": True}, None, kvt_on),
            # the oracle row: same traffic over a pool that never
            # evicts.  Promotions restore the ORIGINAL page bytes, so
            # the on arm must match this row token-for-token on the
            # bit-exact path — that is the identity the gate enforces.
            # (The off arm may diverge from it on greedy near-ties:
            # its partial-prefix re-prefills recompute KV through the
            # continuation-chunk path, whose bf16 rounding differs
            # from the whole-prompt flash prefill that wrote the
            # original pages — a pre-existing cross-strategy property
            # of the prefix cache, reported as off_path_divergences.)
            ("kv_tier_ref", None, {"enabled": True}, None, None)]
    spec_on = {"enabled": True, "draft_tokens": args.draft_tokens}
    if args.speculative:
        configs = [("spec_off", None, None, None, None),
                   ("spec_on", None, None, spec_on, None)]
    if args.zero_inference:
        if args.model == "gpt2":
            raise SystemExit("--zero-inference serves llama/mixtral")
        zi = {"enabled": True, "tier": args.zi_tier,
              "hbm_budget_bytes": (args.hbm_budget_mb * (1 << 20)
                                   or None)}
        if args.speculative:
            # the amortization pair: same streamed engine, speculation
            # off vs on — rows record weight bytes streamed per
            # generated token
            configs += [("zi_spec_off", zi, None, None, None),
                        ("zi_spec_on", zi, None, spec_on, None)]
        else:
            configs.append(("zero_inference", zi, None, None, None))

    prompts = build_prompts(args, cfg)
    out = {"metric": "serving_generated_tokens_per_sec",
           "backend": jax.default_backend(), "device": device,
           "partial": True, "rows": []}
    commit(out, args.json_out)
    outputs_by_config = {}
    for cfg_row in configs:
        name, zi, pc, spec, kvt, *rest = cfg_row
        tp = rest[0] if rest else 0
        row = outs = None
        for rep in range(max(args.repeats, 1)):
            cand, c_outs = measure_config(
                name, args, params, mod, cfg, phase, prompts,
                zero_inference=zi, prefix_cache=pc, speculative=spec,
                kv_tier=kvt, tp=tp)
            if row is None or cand["value"] > row["value"]:
                row, outs = cand, c_outs
        outputs_by_config[name] = outs
        row["detail"]["repeats"] = max(args.repeats, 1)
        out["rows"].append(row)
        # one JSON commit per completed config: a killed window keeps
        # every finished row (round-5: 900 s serving stage, zero output)
        commit(out, args.json_out)
        print(json.dumps(row))
    out["partial"] = False
    # headline compatibility: top-level value mirrors the first row
    out["value"] = out["rows"][0]["value"]
    out["unit"] = "tokens/s"
    if args.speculative and len(out["rows"]) >= 2:
        rows = {r["config"]: r for r in out["rows"]}
        off, on = rows["spec_off"], rows["spec_on"]
        sd = on["detail"]["speculative"]
        out["spec_ab"] = {
            "tokens_per_s_off": off["value"],
            "tokens_per_s_on": on["value"],
            # did the throughput win also move goodput? (None when the
            # slo block was disabled via --slo-ttft-ms 0)
            "goodput_off": off["detail"].get(
                "slo", {}).get("goodput_tokens_per_s"),
            "goodput_on": on["detail"].get(
                "slo", {}).get("goodput_tokens_per_s"),
            "attainment_off": off["detail"].get(
                "slo", {}).get("attainment"),
            "attainment_on": on["detail"].get(
                "slo", {}).get("attainment"),
            "speedup": (round(on["value"] / off["value"], 3)
                        if off["value"] else None),
            "ttft_off_ms": off["detail"].get("ttft_ms"),
            "ttft_on_ms": on["detail"].get("ttft_ms"),
            "mean_accepted_len": sd["mean_accepted_len"],
            "draft_tokens": sd["draft_tokens"],
        }
        if "zi_spec_on" in rows:
            zoff, zon = rows["zi_spec_off"], rows["zi_spec_on"]
            bpt_off = zoff["detail"]["zero_inference"][
                "bytes_streamed_per_token"]
            bpt_on = zon["detail"]["zero_inference"][
                "bytes_streamed_per_token"]
            out["spec_ab"]["zero_inference"] = {
                "tokens_per_s_off": zoff["value"],
                "tokens_per_s_on": zon["value"],
                "bytes_per_token_off": bpt_off,
                "bytes_per_token_on": bpt_on,
                # should track mean_accepted_len up to prefill's
                # shared, unamortized streams
                "stream_amortization": (round(bpt_off / bpt_on, 3)
                                        if bpt_off and bpt_on else None),
                "mean_accepted_len": zon["detail"]["speculative"][
                    "mean_accepted_len"],
            }
    if args.tp and len(out["rows"]) == 2:
        one, sh = out["rows"]
        o_one = outputs_by_config["tp1"]
        o_sh = outputs_by_config[f"tp{args.tp}"]
        # identity over the requests both arms completed (the wall
        # cap can truncate different subsets)
        both = sorted(set(o_one) & set(o_sh))
        mismatched = sum(1 for k in both if o_one[k] != o_sh[k])
        out["tp_ab"] = {
            "tp": args.tp,
            "tokens_per_s_1dev": one["value"],
            "tokens_per_s_tp": sh["value"],
            "speedup": (round(sh["value"] / one["value"], 3)
                        if one["value"] else None),
            "ttft_1dev_ms": one["detail"].get("ttft_ms"),
            "ttft_tp_ms": sh["detail"].get("ttft_ms"),
            "compared_requests": len(both),
            # THE gate: sharding is an execution strategy — any
            # mismatch is a correctness bug
            "mismatched_requests": mismatched,
            "mesh": sh["detail"]["tp"]["mesh"],
        }
    if args.kv_tier and len(out["rows"]) == 3:
        off_r, on_r, _ref_r = out["rows"]
        off_d, on_d = off_r["detail"], on_r["detail"]
        off_kt, on_kt = off_d["kv_tier"], on_d["kv_tier"]
        # token identity against the no-eviction ORACLE row, over the
        # requests both runs completed (the wall-clock cap can
        # truncate different subsets): a promotion serves the exact
        # bytes the original pages held, so on the bit-exact path any
        # on-vs-ref mismatch is a correctness bug the gate must catch
        o_off = outputs_by_config["kv_tier_off"]
        o_on = outputs_by_config["kv_tier_on"]
        o_ref = outputs_by_config["kv_tier_ref"]
        both = sorted(set(o_ref) & set(o_on))
        mismatched = sum(1 for k in both if o_ref[k] != o_on[k])
        off_div = sum(1 for k in sorted(set(o_ref) & set(o_off))
                      if o_ref[k] != o_off[k])
        out["kv_tier_ab"] = {
            "hit_rate_off": off_kt["hit_rate"],
            "hit_rate_on": on_kt["hit_rate"],
            "ttft_p50_off_ms": off_kt.get("ttft_p50_ms",
                                          off_d.get("ttft_ms")),
            "ttft_p50_on_ms": on_kt.get("ttft_p50_ms",
                                        on_d.get("ttft_ms")),
            "tokens_per_s_off": off_r["value"],
            "tokens_per_s_on": on_r["value"],
            "evicted_pages_off": off_kt["evicted_pages"],
            "demoted_pages_on": on_kt["demoted_pages"],
            "promoted_pages_on": on_kt["promoted_pages"],
            "quantize_cold": args.kv_quantize_cold,
            "compared_requests": len(both),
            "mismatched_requests": mismatched,
            # informational: the off arm's partial-hit re-prefills may
            # flip greedy near-ties vs the oracle (cross-strategy bf16
            # rounding, pre-existing prefix-cache property)
            "off_path_divergences": off_div,
        }
        t_off = out["kv_tier_ab"]["ttft_p50_off_ms"]
        t_on = out["kv_tier_ab"]["ttft_p50_on_ms"]
        out["kv_tier_ab"]["ttft_speedup"] = (
            round(t_off / t_on, 3) if t_off and t_on else None)
    if args.prefix_cache and len(out["rows"]) == 2:
        off_d, on_d = (r["detail"] for r in out["rows"])
        out["prefix_ab"] = {
            "ttft_off_ms": off_d.get("ttft_ms"),
            "ttft_on_ms": on_d.get("ttft_ms"),
            "ttft_speedup": (
                round(off_d["ttft_ms"] / on_d["ttft_ms"], 2)
                if off_d.get("ttft_ms") and on_d.get("ttft_ms")
                else None),
            "tokens_per_s_off": out["rows"][0]["value"],
            "tokens_per_s_on": out["rows"][1]["value"],
            "hit_rate": on_d["prefix_cache"]["hit_rate"],
            "goodput_off": off_d.get("slo", {}).get(
                "goodput_tokens_per_s"),
            "goodput_on": on_d.get("slo", {}).get(
                "goodput_tokens_per_s"),
            "attainment_off": off_d.get("slo", {}).get("attainment"),
            "attainment_on": on_d.get("slo", {}).get("attainment"),
        }
    commit(out, args.json_out)


if __name__ == "__main__":
    main()

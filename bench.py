#!/usr/bin/env python
"""Benchmark: Llama train-step throughput on the local chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "detail": ...}

Headline metric (BASELINE.json): tokens/sec/chip for a ZeRO-style LLM
train step.  ``vs_baseline`` reports measured MFU / 0.45 — the
north-star MFU target from BASELINE.json — so >1.0 beats the reference
target.

Runs on a TPU and fails without one: there is no CPU arm, and a failed
phase is an exception and a non-zero exit, never a field in a result
that exits 0.  Every result names the device it ran on
(``detail.device``).  One process, because a chip belongs to one
process at a time.  (Its rebuild into benchmark cells is ROADMAP S1.)
"""

import gc
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))


def main():
    sys.path.insert(0, REPO)
    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu as dstpu
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.timers import device_peak_flops
    from deepspeed_tpu.utils.backend import enable_compile_cache, require_tpu

    device = require_tpu()
    enable_compile_cache()

    # ~0.6B-param Llama slice sized for one v5e (16G HBM) with f32
    # master + Adam moments resident; same per-layer math as 8B.
    cfg = llama.LlamaConfig(
        vocab_size=16384, dim=2048, n_layers=8, n_heads=16, n_kv_heads=8,
        ffn_dim=7168, max_seq_len=2048, rope_theta=500000.0,
        remat="save_dots")
    batch, seq, steps = 4, 2048, 20
    toks_per_step = batch * seq
    data = {"tokens": jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size,
                                          (batch, seq + 1)), jnp.int32)}

    def measure_stage(stage: int, n_steps: int):
        """A fresh engine at this ZeRO stage: (engine, cold seconds,
        seconds for n_steps, last loss).  The clock stops at a fetched
        value."""
        engine, _, _, _ = dstpu.initialize(
            loss_fn=llama.loss_fn(cfg), params=llama.init_params(
                jax.random.PRNGKey(0), cfg),
            config={
                "train_micro_batch_size_per_gpu": batch,
                "zero_optimization": {"stage": stage},
                "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
                "bf16": {"enabled": True},
            })
        t0 = time.perf_counter()
        float(engine.train_batch(data))   # compile
        cold_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(n_steps):
            loss = engine.train_batch(data)
        loss = float(loss)
        return engine, cold_s, time.perf_counter() - t0, loss

    engine, compile_s, dt, loss_val = measure_stage(0, steps)
    tps = toks_per_step * steps / dt
    flops_per_tok = 6 * llama.param_count(cfg) + 12 * cfg.n_layers * cfg.dim * seq
    mfu = tps * flops_per_tok / device_peak_flops()

    result = {
        "metric": "llama_train_tokens_per_sec_per_chip",
        "value": round(tps, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.45, 4),
        "detail": {"mfu": round(mfu, 4), "loss": loss_val,
                   "params": llama.param_count(cfg),
                   "step_ms": round(1000 * dt / steps, 2),
                   "compile_s": round(compile_s, 1),
                   "device": device,
                   # telemetry provenance rides every emitted row
                   "telemetry": engine.telemetry_snapshot()},
    }

    # extra configurations so regressions off the ZeRO-0 hot path stay
    # visible: ZeRO-3, and ZeRO-2 (BASELINE config #2 is a ~1.3B GPT-2
    # at stage 2, but 1.3B stage-2 state is 12N = 15.6 GB f32 + 2.6 GB
    # bf16 — over one v5e's HBM with dp=1 sharding nothing, so the
    # stage-2 STEP PATH is measured at the bench size)
    del engine
    gc.collect()
    for stage in (3, 2):
        engine, _, dt_s, _ = measure_stage(stage, steps // 2)
        result["detail"][f"zero{stage}_tokens_per_sec"] = round(
            toks_per_step * (steps // 2) / dt_s, 1)
        result["detail"][f"zero{stage}_step_ms"] = round(
            1000 * dt_s / (steps // 2), 2)
        del engine
        gc.collect()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()

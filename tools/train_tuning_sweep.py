#!/usr/bin/env python
"""Train-step tuning sweep on the local chip: remat policy × batch size
(× optional loss_chunk) for the bench llama config.

Decides whether bench.py's ``remat="save_dots", batch=4`` leaves MFU on
the table (BENCH_r02: 48.7% MFU / 300.9 ms).  Each configuration runs in
THIS process sequentially (a chip belongs to one process at a time);
run the whole script under an outer deadline.

    timeout 1500 python tools/train_tuning_sweep.py
    python tools/train_tuning_sweep.py --cpu --quick   # smoke
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--json-out", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "TRAIN_SWEEP.json"))
    args = ap.parse_args()

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu as dstpu
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.timers import device_peak_flops

    on_tpu = jax.default_backend() == "tpu"
    steps = 3 if args.quick else 12
    if on_tpu:
        base = dict(vocab_size=16384, dim=2048, n_layers=8, n_heads=16,
                    n_kv_heads=8, ffn_dim=7168, max_seq_len=2048,
                    rope_theta=500000.0)
        seq = 2048
        grid = [("save_dots", 4, 0), ("none", 4, 0), ("save_dots", 8, 0),
                ("none", 8, 0), ("save_dots", 4, 8192),
                # save_attn keeps the tagged attention context, so the
                # backward skips the quadratic recompute
                ("save_attn", 4, 0), ("save_attn", 8, 0)]
    else:
        base = dict(vocab_size=256, dim=32, n_layers=2, n_heads=4,
                    n_kv_heads=2, max_seq_len=64)
        seq = 32
        grid = [("save_dots", 4, 0), ("none", 4, 0), ("save_dots", 4, 64)]

    rows = []
    for remat, batch, loss_chunk in grid:
        cfg = llama.LlamaConfig(**base, remat=remat, loss_chunk=loss_chunk)
        row = {"remat": remat, "batch": batch, "loss_chunk": loss_chunk}
        try:
            engine, _, _, _ = dstpu.initialize(
                loss_fn=llama.loss_fn(cfg),
                params=llama.init_params(jax.random.PRNGKey(0), cfg),
                config={"train_micro_batch_size_per_gpu": batch,
                        "zero_optimization": {"stage": 0},
                        "optimizer": {"type": "adamw",
                                      "params": {"lr": 1e-4}},
                        "bf16": {"enabled": True}})
            toks = jnp.asarray(np.random.default_rng(0).integers(
                0, cfg.vocab_size, (batch, seq + 1)), jnp.int32)
            data = {"tokens": toks}
            float(engine.train_batch(data))          # compile
            t0 = time.perf_counter()
            for _ in range(steps):
                loss = engine.train_batch(data)
            float(loss)                              # value fetch = sync
            dt = (time.perf_counter() - t0) / steps
            tps = batch * seq / dt
            fl = 6 * llama.param_count(cfg) \
                + 12 * cfg.n_layers * cfg.dim * seq
            row.update(step_ms=round(1e3 * dt, 1), tokens_per_s=round(tps),
                       mfu=round(tps * fl / device_peak_flops(), 4))
            del engine
        except Exception as e:                       # OOM etc: record
            row["error"] = str(e)[:200]
        print(json.dumps(row), flush=True)
        rows.append(row)

    out = {"backend": jax.default_backend(), "steps": steps, "rows": rows}
    with open(args.json_out, "w") as f:
        json.dump(out, f, indent=1)
    print("→", args.json_out)


if __name__ == "__main__":
    main()

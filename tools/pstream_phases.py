#!/usr/bin/env python
"""Param-stream phase evidence: is the optimizer pass hidden?

Runs the layer-streaming engine twice on the same model/batch —
``overlap_step`` on (default) vs off (the strict serialized pass) — and
records each mode's ``phase_report()``.  The claim under test (round-4
verdict weak #6): with overlap on, layer l's CPU-Adam + tier write runs
behind the vjps of layers l-1..0, so the EXPOSED optimizer cost is
``update_wait`` (the end-of-step join), which should be well under the
total ``host_adam`` work actually done — and the step should be faster
than strict mode by roughly the hidden fraction.

CPU-tier by default so it runs on any backend; --nvme measures the aio
tier.  Writes PARAM_STREAM_PHASES.json.

Usage:  python tools/pstream_phases.py [--layers 8] [--dim 256] [--nvme]
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# CPU-tier phase evidence must NEVER land on the TPU (a chip belongs to
# one process at a time): force the platform in-process before any
# backend init.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def build(overlap, args, nvme_dir=None):
    import jax

    import deepspeed_tpu as dstpu
    from deepspeed_tpu.models import llama

    cfg = llama.LlamaConfig.tiny(
        dim=args.dim, n_layers=args.layers, n_heads=8, n_kv_heads=4,
        vocab_size=2048)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    off = {"device": "nvme", "nvme_path": nvme_dir} if nvme_dir else \
        {"device": "cpu", "scheduled": True}
    off["overlap_step"] = overlap
    eng, _, _, _ = dstpu.initialize(
        params=llama.layered_model(cfg, params),
        config={"train_micro_batch_size_per_gpu": args.batch,
                "zero_optimization": {"stage": 3, "offload_param": off},
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "bf16": {"enabled": True}})
    return cfg, eng


def measure(eng, cfg, steps, seq):
    import numpy as np

    import jax.numpy as jnp

    toks = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (eng.train_batch_size, seq + 1))
    batch = {"tokens": jnp.asarray(toks, jnp.int32)}
    eng.train_batch(batch)                       # compile + warm tier
    reports, times = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        eng.train_batch(batch)
        times.append(time.perf_counter() - t0)
        reports.append(eng.phase_report())
    mean = {k: round(sum(r[k] for r in reports) / len(reports), 4)
            for k in reports[0]}
    mean["step_s"] = round(sum(times) / len(times), 4)
    return mean


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--dim", type=int, default=256)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--nvme", action="store_true")
    ap.add_argument("--json-out", default=os.path.join(
        REPO, "PARAM_STREAM_PHASES.json"))
    args = ap.parse_args()

    import tempfile

    import jax

    out = {"backend": jax.default_backend(),
           "model": {"layers": args.layers, "dim": args.dim,
                     "batch": args.batch, "seq": args.seq},
           "tier": "nvme" if args.nvme else "cpu", "modes": {}}
    for overlap in (True, False):
        nvme_dir = tempfile.mkdtemp(prefix="dstpu_phases_") \
            if args.nvme else None
        cfg, eng = build(overlap, args, nvme_dir)
        out["modes"]["overlap" if overlap else "strict"] = measure(
            eng, cfg, args.steps, args.seq)
    ov, st = out["modes"]["overlap"], out["modes"]["strict"]
    out["exposed_optimizer_s"] = {
        "overlap (update_wait)": ov["update_wait"],
        "strict (host_adam+tier_write)":
            round(st["host_adam"] + st["tier_write"], 4)}
    out["hidden_fraction"] = round(
        1.0 - ov["update_wait"] / max(ov["host_adam"], 1e-9), 4)
    out["step_speedup_strict_over_overlap"] = round(
        st["step_s"] / max(ov["step_s"], 1e-9), 4)
    with open(args.json_out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()

"""Baseline config 5: ZeRO-Infinity offload — optimizer state streamed
HBM ↔ host ↔ NVMe around each sub-group update (ref: deepspeed
ZeRO-Infinity, runtime/swap_tensor/partitioned_optimizer_swapper.py).

The scheduled engine (deepspeed_tpu/infinity.py) keeps only the bf16
compute copy resident on-chip; the f32 master + Adam moments (12
bytes/param) live as leaf files on NVMe, double-buffered through the C++
aio pool so reads of group k+1 and writes of group k-1 overlap group k's
jitted update.  This prints the resident-bytes evidence per step.

    python examples/zero_infinity_offload.py --steps 3
    python examples/zero_infinity_offload.py --dim 1024 --layers 4
    python examples/zero_infinity_offload.py --scale 405b --dry-config
"""
import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp

import deepspeed_tpu as dstpu
from deepspeed_tpu.models import llama


def infinity_config(nvme_dir: str, sub_group: int = 2 ** 21) -> dict:
    return {
        "train_micro_batch_size_per_gpu": 2,
        "zero_optimization": {
            "stage": 3,
            "sub_group_size": sub_group,
            "offload_optimizer": {"device": "nvme", "nvme_path": nvme_dir},
        },
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "bf16": {"enabled": True},
    }


def build_cfg_1p4b():
    """~1.38B params: f32 master+moments = 12N ≈ 16.5 GB — MORE than one
    v5e chip's ~16 GB HBM.  The plain in-HBM engine cannot hold this
    optimizer state; the Infinity engine streams it."""
    return llama.LlamaConfig(
        vocab_size=32000, dim=2048, n_layers=22, n_heads=16, n_kv_heads=8,
        ffn_dim=7168, max_seq_len=512, remat="full")


def probe_plain(cfg, seq: int) -> None:
    """Try the NON-offload engine at this size (expected: RESOURCE_EXHAUSTED
    allocating the f32 master+moments).  Run in a subprocess — an HBM OOM
    can take the client down with it."""
    params = llama.init_params(jax.random.PRNGKey(0), cfg,
                               dtype=jnp.bfloat16)
    engine, _, _, _ = dstpu.initialize(
        loss_fn=llama.loss_fn(cfg), params=params,
        config={"train_micro_batch_size_per_gpu": 1,
                "zero_optimization": {"stage": 0},
                "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
                "bf16": {"enabled": True}})
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (engine.train_batch_size, seq + 1)), jnp.int32)
    print("plain loss:", float(engine.train_batch({"tokens": toks})))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", choices=["tiny", "1p4b", "405b"],
                    default="tiny")
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--dim", type=int, default=0,
                    help="override model width (bigger = better demo)")
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--dry-config", action="store_true",
                    help="print the config and exit")
    ap.add_argument("--probe-plain", action="store_true",
                    help="try the non-offload engine at this size instead "
                         "(expected to OOM above ~0.9B params on one v5e)")
    ap.add_argument("--json-out", default="",
                    help="write evidence JSON (peak-params-per-chip story)")
    args = ap.parse_args()

    if args.scale == "405b":
        cfg = llama.LlamaConfig(
            vocab_size=128256, dim=16384, n_layers=126, n_heads=128,
            n_kv_heads=8, ffn_dim=53248, max_seq_len=8192,
            rope_theta=500000.0, remat="full")
    elif args.scale == "1p4b":
        cfg = build_cfg_1p4b()
    elif args.dim:
        cfg = llama.LlamaConfig(
            vocab_size=8192, dim=args.dim, n_layers=args.layers,
            n_heads=max(4, args.dim // 128),
            n_kv_heads=max(2, args.dim // 256),
            ffn_dim=args.dim * 3, max_seq_len=512)
    else:
        cfg = llama.LlamaConfig.tiny(dim=64, n_layers=2, n_heads=4,
                                     n_kv_heads=2)
    seq = 64 if args.scale == "tiny" and not args.dim else 256
    if args.probe_plain:
        probe_plain(cfg, seq)
        return

    nvme = tempfile.mkdtemp(prefix="dstpu_nvme_")
    big = args.scale == "1p4b"
    config = infinity_config(nvme, sub_group=2 ** 26 if big else 2 ** 21)
    if big:
        # bf16 grad shards halve the transient grad HBM at this scale
        config["zero_optimization"]["offload_optimizer"]["bf16_grads"] = True
        # CPU-Adam (ref parity): only bf16 grads/params cross the
        # host↔device link — 4 bytes/param/step instead of 24
        config["zero_optimization"]["offload_optimizer"]["update"] = "host"
        config["train_micro_batch_size_per_gpu"] = 1
    if args.dry_config:
        print(json.dumps(config, indent=2))
        print(f"params: {llama.param_count(cfg)/1e9:.1f}B")
        return

    params = llama.init_params(jax.random.PRNGKey(0), cfg,
                               dtype=jnp.bfloat16 if big else jnp.float32)
    n_params = llama.param_count(cfg)
    engine, _, _, _ = dstpu.initialize(
        loss_fn=llama.loss_fn(cfg), params=params, config=config)
    del params
    print(f"params={n_params/1e6:.2f}M  tier(f32 master+moments)="
          f"{12*n_params/1e9:.3f} GB  on-chip state="
          f"{engine.hbm_state_bytes()/1e9:.4f} GB (bf16 compute copy)  "
          f"groups={len(engine.groups)}  backend={jax.default_backend()}")

    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (engine.train_batch_size, seq + 1)), jnp.int32)

    def swap_bytes_now():
        swap_dir = os.path.join(nvme, "proc0")
        return sum(os.path.getsize(os.path.join(swap_dir, f))
                   for f in os.listdir(swap_dir))

    from deepspeed_tpu.io.aio import AioHandle
    from deepspeed_tpu.ops.cpu_adam import native_available
    native = AioHandle(1).native
    _native_adam = native_available()

    def write_evidence(losses, times):
        if not args.json_out:
            return
        evidence = {
            "backend": jax.default_backend(),
            "params": n_params,
            "f32_state_bytes_total": 12 * n_params,
            "hbm_resident_state_bytes": engine.hbm_state_bytes(),
            "tier_local_bytes": engine.tier_local_bytes(),
            "nvme_file_bytes": swap_bytes_now(),
            "groups": len(engine.groups),
            "seq": seq,
            "micro_batch": engine.train_batch_size,
            "steps_completed": len(losses),
            "losses": losses,
            "step_time_s": times,
            "native_aio": bool(native),
            "update_mode": engine.update_mode,
            "native_cpu_adam": _native_adam,
            # per-phase seconds of the LAST step — the viability
            # breakdown (phases overlap; parts can sum past total)
            "phase_breakdown_s": {
                k: round(v, 3)
                for k, v in engine.phase_report().items()},
        }
        from deepspeed_tpu.utils.evidence import atomic_write_json

        # atomic: the per-step flush exists to survive a killed window,
        # so the flush itself must not be killable into truncation
        atomic_write_json(evidence, args.json_out)

    losses, times = [], []
    for step in range(args.steps):
        t0 = time.perf_counter()
        loss = float(engine.train_batch({"tokens": toks}))
        dt = time.perf_counter() - t0
        losses.append(loss)
        times.append(round(dt, 4))
        print(f"step {step}: loss={loss:.4f} step_time={1000*dt:.0f} ms "
              f"on-chip state={engine.hbm_state_bytes()/1e9:.4f} GB",
              flush=True)
        # evidence flushed per step: at the 1B+ scale one step takes
        # minutes and a timeout must not erase the run
        write_evidence(losses, times)
    if len(losses) >= 3 and not losses[-1] < losses[0]:
        raise SystemExit("loss did not drop")

    print(f"NVMe tier holds {swap_bytes_now()/1e9:.3f} GB "
          f"({swap_bytes_now() // max(n_params, 1)} bytes/param) via "
          f"{'native C++ aio' if native else 'python fallback'} — OK")
    if args.json_out:
        print("evidence →", args.json_out)


if __name__ == "__main__":
    main()

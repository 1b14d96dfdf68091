"""FLOPs profiler (ref: deepspeed/profiling/flops_profiler/profiler.py).

The reference hooks every torch module to count MACs/params and prints a
per-module table plus aggregate FLOPS/latency.  The TPU-native design
has two complementary sources of truth:

- **XLA cost analysis**: ``jit(fn).lower(...).compile().cost_analysis()``
  returns the compiler's own flops / bytes-accessed estimate for the real
  fused program — more honest than module hooks, since it sees what
  actually runs after fusion.
- **Analytic formulas** for transformer train/inference FLOPs (the
  standard 6*N*T + attention terms), used for MFU targets and for
  per-component tables where compilation is too coarse.

``get_model_profile`` mirrors the reference's entrypoint name.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import numpy as np

from deepspeed_tpu.timers import device_peak_flops


# ----------------------------------------------------------------- analytic
def transformer_train_flops(n_params: float, tokens: float,
                            n_layers: int = 0, hidden: int = 0,
                            seq_len: int = 0,
                            checkpoint_activations: bool = False) -> float:
    """FLOPs for one train step over ``tokens`` tokens.

    Standard decomposition (Kaplan/Chinchilla accounting): 6*N per token
    for fwd+bwd matmuls (8*N with full activation rematerialisation), plus
    the seq-quadratic attention term 12*L*H*T^2 per sequence-token batch.
    """
    mult = 8.0 if checkpoint_activations else 6.0
    flops = mult * n_params * tokens
    if n_layers and hidden and seq_len:
        attn_mult = 4.0 if checkpoint_activations else 3.0
        flops += attn_mult * 4.0 * n_layers * hidden * seq_len * tokens
    return flops


def transformer_decode_flops(n_params: float, n_layers: int, hidden: int,
                             kv_len: int) -> float:
    """FLOPs for decoding ONE token with a ``kv_len`` KV cache."""
    return 2.0 * n_params + 4.0 * n_layers * hidden * kv_len


def params_count(params: Any) -> int:
    """Total leaf elements of a pytree (ref: profiler's params column)."""
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params)
               if hasattr(x, "shape"))


# ------------------------------------------------------------- XLA-measured
def xla_cost_analysis_lowered(lowered) -> Dict[str, float]:
    """Compiler-reported flops / bytes for an already-lowered program
    (``jit(fn).lower(...)`` — concrete args or ShapeDtypeStructs both
    work): for a caller that has lowered its OWN jitted program and
    would not re-jit it through :func:`xla_cost_analysis`."""
    ca = lowered.compile().cost_analysis()
    if isinstance(ca, (list, tuple)):  # older jax returns a per-computation list
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return {}
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0))}


def xla_cost_analysis(fn: Callable, *args,
                      static_argnums=()) -> Dict[str, float]:
    """Compiler-reported flops / bytes for the fused program."""
    return xla_cost_analysis_lowered(
        jax.jit(fn, static_argnums=static_argnums).lower(*args))


class FlopsProfiler:
    """Measure a jitted step: XLA flops, wall latency, achieved TFLOPS, MFU.

    ref: deepspeed/profiling/flops_profiler — ``start_profile`` /
    ``stop_profile`` / ``print_model_profile`` flow, minus torch hooks.
    """

    def __init__(self, fn: Callable, static_argnums=()):
        self.fn = fn
        self.static_argnums = static_argnums
        self.flops = 0.0
        self.bytes_accessed = 0.0
        self.latency = 0.0

    def profile(self, *args, iters: int = 5, warmup: int = 2) -> Dict[str, float]:
        cost = xla_cost_analysis(self.fn, *args, static_argnums=self.static_argnums)
        self.flops = cost["flops"]
        self.bytes_accessed = cost["bytes_accessed"]
        jfn = jax.jit(self.fn, static_argnums=self.static_argnums)
        for _ in range(warmup):
            jax.block_until_ready(jfn(*args))
        t0 = time.perf_counter()
        for _ in range(iters):
            out = jfn(*args)
        jax.block_until_ready(out)
        self.latency = (time.perf_counter() - t0) / iters
        return self.summary()

    def summary(self) -> Dict[str, float]:
        tflops = self.flops / self.latency / 1e12 if self.latency else 0.0
        peak = device_peak_flops()
        return {
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "latency_s": self.latency,
            "tflops": tflops,
            "mfu": tflops * 1e12 / peak if peak else 0.0,
            "arithmetic_intensity": (self.flops / self.bytes_accessed
                                     if self.bytes_accessed else 0.0),
        }


def get_model_profile(fn: Callable, args: Tuple, params: Optional[Any] = None,
                      iters: int = 5, print_profile: bool = True,
                      static_argnums=()) -> Dict[str, float]:
    """One-call profile (ref: flops_profiler.get_model_profile)."""
    prof = FlopsProfiler(fn, static_argnums=static_argnums)
    out = prof.profile(*args, iters=iters)
    if params is not None:
        out["params"] = float(params_count(params))
    if print_profile:
        from deepspeed_tpu.utils.logging import log_dist

        rows = [f"  {k:>22}: {v:.4g}" for k, v in out.items()]
        log_dist("flops profile:\n" + "\n".join(rows))
    return out

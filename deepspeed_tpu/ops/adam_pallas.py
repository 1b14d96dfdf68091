"""Pallas fused Adam/AdamW update (ref: deepspeed/ops/adam/fused_adam.py +
csrc/adam/multi_tensor_apply — one CUDA kernel sweeping flat param chunks).

TPU design: one pallas kernel makes a single pass over a (rows, 128) view
of each tensor, reading (g, m, v, p) and writing (u, m, v) per block —
exactly one HBM round-trip for the whole optimizer step, the analogue of
the reference's multi_tensor_applier.  The update delta ``u`` (not new
params) is emitted so the engine's ``params + updates`` contract and
weight-donation path stay unchanged.

XLA already fuses the elementwise chain in ops/optim.py well; the pallas
path exists to (a) pin the layout to VPU-native (8, 128) tiles, (b) keep
m/v in one VMEM residency per block, and (c) guarantee no multi-pass
fusion breakup for very large leaves.
"""

from __future__ import annotations

import functools
import os
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from deepspeed_tpu.ops.optim import Optimizer, ScalarOrSchedule, _lr_at

_LANES = 128
_DEFAULT_ROWS = 512  # 512*128 f32 = 256 KiB per operand block in VMEM

# Measured crossover (KERNEL_BENCH.json adam_pallas_vs_xla, v5e): XLA's
# fused elementwise chain WINS below ~64M params — 0.49x at 4M (pallas
# 7.8 ms vs XLA 3.8 ms; grid/dispatch overhead dominates), parity 0.96x
# at 64M — and the single-pass VMEM-residency argument only pays above.
_PALLAS_MIN_PARAMS = 1 << 26


def pallas_adam_gate(n_params: int) -> bool:
    """One measured policy for when the pallas fused Adam beats the XLA
    elementwise chain: below
    the crossover the kernel is demoted to plain XLA (identical math),
    above it the pallas path holds.  ``DSTPU_FORCE_ADAM_PALLAS=1``
    forces the kernel at every size (read at trace time)."""
    if os.environ.get("DSTPU_FORCE_ADAM_PALLAS", "") == "1":
        return True
    return n_params >= _PALLAS_MIN_PARAMS


def _adam_update_xla(g, m, v, p, c1, c2, lr_, *, b1, b2, eps, wd):
    """XLA twin of :func:`_adam_kernel` (same math, same dtypes) — the
    demoted small-tensor path; fuses into one elementwise chain."""
    g = g.astype(jnp.float32)
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    upd = (m * c1) / (jnp.sqrt(v * c2) + eps)
    if wd:
        upd = upd + wd * p.astype(jnp.float32)
    return -lr_ * upd, m, v


def _adam_kernel(g_ref, m_ref, v_ref, p_ref, c1_ref, c2_ref, lr_ref,
                 u_ref, mo_ref, vo_ref, *, b1, b2, eps, wd):
    g = g_ref[...].astype(jnp.float32)
    m = m_ref[...]
    v = v_ref[...]
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    mo_ref[...] = m
    vo_ref[...] = v
    mhat = m * c1_ref[0, 0]               # 1/(1-b1^t)
    vhat = v * c2_ref[0, 0]               # 1/(1-b2^t)
    upd = mhat / (jnp.sqrt(vhat) + eps)
    if wd:
        upd = upd + wd * p_ref[...].astype(jnp.float32)
    u_ref[...] = -lr_ref[0, 0] * upd


def _pad_rows(flat: jnp.ndarray, rows_pad: int) -> jnp.ndarray:
    n = flat.shape[0]
    pad = rows_pad * _LANES - n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(rows_pad, _LANES)


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "wd",
                                             "block_rows", "interpret"))
def adam_update_flat(g, m, v, p, step, lr, *, b1=0.9, b2=0.999, eps=1e-8,
                     wd=0.0, block_rows=_DEFAULT_ROWS, interpret=False):
    """Single fused pass over one tensor: returns (u, m_new, v_new).

    g/p may be bf16; m/v/u are f32.  Any shape (flattened internally).
    """
    shape = g.shape
    n = int(np.prod(shape)) if shape else 1
    t_ = step.astype(jnp.float32) + 1.0
    if not interpret and not pallas_adam_gate(n):
        # below the measured crossover: identical math through XLA's
        # fused chain (interpret=True still exercises the kernel — it
        # is an explicit request, e.g. the numerics tests)
        u, mo, vo = _adam_update_xla(
            g, m.astype(jnp.float32), v.astype(jnp.float32), p,
            1.0 / (1.0 - jnp.float32(b1) ** t_),
            1.0 / (1.0 - jnp.float32(b2) ** t_),
            jnp.asarray(lr, jnp.float32), b1=b1, b2=b2, eps=eps, wd=wd)
        return u, mo, vo
    rows = -(-n // _LANES)
    br = min(block_rows, max(8, rows))
    rows_pad = -(-rows // br) * br
    gf = _pad_rows(g.reshape(-1), rows_pad)
    mf = _pad_rows(m.reshape(-1).astype(jnp.float32), rows_pad)
    vf = _pad_rows(v.reshape(-1).astype(jnp.float32), rows_pad)
    pf = _pad_rows(p.reshape(-1), rows_pad)
    c1 = 1.0 / (1.0 - jnp.float32(b1) ** t_)
    c2 = 1.0 / (1.0 - jnp.float32(b2) ** t_)
    lr_ = jnp.asarray(lr, jnp.float32)

    grid = (rows_pad // br,)
    blk = pl.BlockSpec((br, _LANES), lambda i: (i, 0))
    one = pl.BlockSpec((1, 1), lambda i: (0, 0))
    u, mo, vo = pl.pallas_call(
        functools.partial(_adam_kernel, b1=b1, b2=b2, eps=eps, wd=wd),
        grid=grid,
        in_specs=[blk, blk, blk, blk, one, one, one],
        out_specs=[blk, blk, blk],
        out_shape=[jax.ShapeDtypeStruct((rows_pad, _LANES), jnp.float32),
                   jax.ShapeDtypeStruct((rows_pad, _LANES), jnp.float32),
                   jax.ShapeDtypeStruct((rows_pad, _LANES), jnp.float32)],
        interpret=interpret,
        name="dstpu_adam",
    )(gf, mf, vf, pf, c1.reshape(1, 1), c2.reshape(1, 1), lr_.reshape(1, 1))
    u = u.reshape(-1)[:n].reshape(shape)
    mo = mo.reshape(-1)[:n].reshape(shape)
    vo = vo.reshape(-1)[:n].reshape(shape)
    return u, mo, vo


class FusedAdamState(NamedTuple):
    step: jnp.ndarray
    mu: Any
    nu: Any


def fused_adam(lr: ScalarOrSchedule = 1e-3, betas=(0.9, 0.999),
               eps: float = 1e-8, weight_decay: float = 0.0,
               block_rows: int = _DEFAULT_ROWS,
               interpret: bool = False) -> Optimizer:
    """Optimizer-contract wrapper over the pallas kernel (drop-in for
    ops.optim.adam; AdamW decoupled decay semantics)."""
    b1, b2 = betas

    def init(params):
        z = lambda p: jnp.zeros(p.shape, jnp.float32)
        return FusedAdamState(jnp.zeros((), jnp.int32),
                              jax.tree.map(z, params),
                              jax.tree.map(z, params))

    def update(grads, state, params):
        # LR at step+1, matching ops.optim.adam's schedule convention
        # (and the kernel's bias correction at t = step + 1).
        lr_val = _lr_at(lr, state.step + 1)
        outs = jax.tree.map(
            lambda g, m, v, p: adam_update_flat(
                g, m, v, p, state.step, lr_val, b1=b1, b2=b2, eps=eps,
                wd=weight_decay, block_rows=block_rows,
                interpret=interpret),
            grads, state.mu, state.nu, params)
        # tree.transpose splits the per-leaf (u, m, v) triples without
        # misfiring on tuple/NamedTuple container nodes inside params.
        u, mu, nu = jax.tree.transpose(
            jax.tree.structure(grads), jax.tree.structure((0, 0, 0)), outs)
        return u, FusedAdamState(state.step + 1, mu, nu)

    return Optimizer(init=init, update=update, name="fused_adam_pallas")

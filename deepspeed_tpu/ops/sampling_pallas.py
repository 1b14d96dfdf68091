"""Pallas fused boundary sampling for the serving decode sweep (ref:
deepspeed/ops — the FastGen serving stack fuses its logits→token step;
here the greedy argmax runs as one pallas reduction and the chosen token
feeds the decode scan carry directly, so sample + append share one
dispatch per step and the host transfer stays one token row).

TPU design: logits land as one [B, V] f32 block in VMEM; the kernel
computes the row max and the FIRST index attaining it (bit-exact with
``jnp.argmax``'s first-occurrence contract — the greedy serving identity
gates depend on it) in a single pass.  Temperature rows reuse the exact
categorical math of the XLA sampler (``serving._sample_rows``) via the
same per-row key streams, guarded by a ``lax.cond`` so an all-greedy
batch never pays the softmax.  The "append" half of the fusion lives in
the serving scan: the token this kernel emits is the next step's input
inside the SAME jitted program, so no separate write dispatch exists to
fuse away — what the XLA path paid was a distinct sample kernel between
decode steps, and that is what folds into the sweep here.

Gate pattern mirrors :mod:`deepspeed_tpu.ops.adam_pallas`: a measured
crossover constant + an XLA twin below it; the policy is resolved ONCE
at engine build (``resolve_serving_kernels``), never at trace time.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30
_LANES = 128

# Measured crossover (KERNEL_BENCH.json fused_sample_vs_xla): the jitted
# XLA sampler wins at EVERY serving shape in the committed sweep —
# sampling is one [B, V] argmax reduction, which XLA already emits as a
# single fused pass, so there is no second HBM trip for the kernel to
# remove at serving batch sizes.  The constant records where a future
# chip re-stamp would have to put the crossover (rows*vocab) for auto to
# flip on; until then the fused kernel is the forced arm
# (kernels.fused_sampling: on / DSTPU_FORCE_FUSED_SAMPLING=1) and the
# bit-exact greedy identity gates keep it honest.
_FUSED_SAMPLE_MIN_ROWS_X_VOCAB = 1 << 24


def pallas_sample_gate(batch: Optional[int] = None,
                       vocab: Optional[int] = None, *,
                       interpret: bool = False) -> bool:
    """The ``auto`` policy for fused sampling — pure shape math, no env
    reads (env/config overrides resolve at engine build in
    :func:`~deepspeed_tpu.inference.kernels.resolve_serving_kernels`).
    With unknown shapes (engine build time — vocab is a property of the
    params, not the engine) auto resolves conservatively off, which is
    also what the committed crossover sweep says for every measured
    shape."""
    if interpret:
        return False
    if batch is None or vocab is None:
        return False
    return batch * vocab >= _FUSED_SAMPLE_MIN_ROWS_X_VOCAB


def _greedy_kernel(l_ref, o_ref, *, vocab):
    """One-pass greedy argmax over [B8, Vp] f32 logits: row max, then
    the smallest index attaining it (first-occurrence, matching
    ``jnp.argmax`` bit-exactly).  The index is broadcast across the
    lane dim — (B8, 128) int32 is a natively tiled store; the wrapper
    reads column 0."""
    x = l_ref[...].astype(jnp.float32)
    m = jnp.max(x, axis=1, keepdims=True)
    iota = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    idx = jnp.min(jnp.where(x == m, iota, vocab), axis=1, keepdims=True)
    o_ref[...] = jnp.broadcast_to(idx, o_ref.shape)


# dstpu: hot-path
def fused_greedy_rows(logits: jnp.ndarray,
                      interpret: bool = False) -> jnp.ndarray:
    """Pallas greedy token per row: [B, V] logits → [B] int32, equal to
    ``jnp.argmax(logits, -1)`` bit-for-bit (the serving identity gates
    assert this across every decode mode).  Rows pad to the f32 sublane
    (8) with zeros, vocab pads to the lane (128) with ``NEG_INF`` so
    padding can never win a row."""
    B, V = logits.shape
    b8 = -(-B // 8) * 8
    vp = -(-V // _LANES) * _LANES
    x = logits.astype(jnp.float32)
    if vp != V:
        x = jnp.concatenate(
            [x, jnp.full((B, vp - V), NEG_INF, jnp.float32)], axis=1)
    if b8 != B:
        x = jnp.concatenate(
            [x, jnp.zeros((b8 - B, vp), jnp.float32)], axis=0)
    out = pl.pallas_call(
        functools.partial(_greedy_kernel, vocab=vp),
        out_shape=jax.ShapeDtypeStruct((b8, _LANES), jnp.int32),
        interpret=interpret,
        name="dstpu_fused_sample",
    )(x)
    return out[:B, 0]


# dstpu: hot-path
@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_sample_rows(logits: jnp.ndarray, keys: jnp.ndarray,
                      temps: jnp.ndarray,
                      interpret: bool = False) -> jnp.ndarray:
    """Drop-in twin of ``serving._sample_rows`` with the greedy path
    through the pallas kernel: [B, V] logits + [B] keys + [B] temps →
    [B] tokens.  Greedy rows (temp 0) are bit-exact vs the XLA sampler
    (same first-occurrence argmax); temperature rows run the IDENTICAL
    categorical math on the same per-row key streams, so the two
    samplers agree on every row — the kernel only changes how the
    argmax is computed.  ``lax.cond`` skips the softmax entirely for
    the all-greedy batch (the common serving case)."""
    greedy = fused_greedy_rows(logits, interpret=interpret)

    def with_temp(_):
        scaled = logits.astype(jnp.float32) \
            / jnp.maximum(temps, 1e-6)[:, None]
        sampled = jax.vmap(jax.random.categorical)(keys, scaled)
        return jnp.where(temps == 0.0, greedy, sampled.astype(jnp.int32))

    return jax.lax.cond(jnp.any(temps > 0.0), with_temp,
                        lambda _: greedy, None)

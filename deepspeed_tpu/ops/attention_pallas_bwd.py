"""The flash kernel's gradient where a head lies whole in the vector
memory (ref: FlashAttention-2's backward, laid out for the TPU): which
backward a head runs, and the one kernel of a head that fits.  A leaf:
:mod:`~deepspeed_tpu.ops.attention_pallas` has the forward kernel, the
split pair and, at its end, the custom VJP that runs what the rule says.

:func:`flash_backward` is the rule of the shapes, as ``window_reader``
and ``paged_reader`` are for theirs.  ``fused`` (``dstpu_flash_bwd``): a
head's ``q``, ``k``, ``v`` and ``dO`` lie whole in the vector memory, the
body walks the block pairs the mask leaves and computes each pair's
scores, probabilities and ``dO v^T`` ONCE for dQ, dK and dV: five
products and one ``exp`` a pair.  ``split``: the two kernels of
:mod:`~deepspeed_tpu.ops.attention_pallas` (``dstpu_flash_bwd_dq``,
``dstpu_flash_bwd_dkv``), which stream blocks and so take any length,
a group of query heads a K/V head, packed segments and ``T != S``.

The scores are taken TRANSPOSED (``k q^T``: keys down the sublanes,
queries along the lanes), so that the forward's log-sum and ``delta``
are rows ``[1, T]``: lane-dense in memory between the passes (a column
``[T, 1]`` of f32 pads every number to 128 lanes) and broadcast down
the sublanes for free, and the two products that make dK and dV
contract the lanes as they lie.  dQ is summed transposed (``k^T ds``,
``[D, T]`` in f32 scratch) and turned once a head: the operand a
transposed product has to turn is then the key block, not the scores.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# the names the forward kernel's context and log-sum carry out of
# ``flash_attention_tpu``, for ``remat.policy`` to keep: what a
# ``pallas_call`` returns is a product no checkpoint policy sees
FLASH_NAMES = ("flash_out", "flash_lse")

# the most bytes of one operand's head as the vector memory holds it
# (the lanes padded to 128): 2048 x 128 in bf16, 1024 x 128 in f32.  Of
# those heads q, k, v, dO, dQ, dK, dV double-buffered are 14 (7 MiB),
# beside the f32 dQ and the pairs' f32 temporaries: Mosaic's own count
# for a v5e is 14.3 MiB at 2048 x 128 in bf16 without a mask (10.0 under
# the causal one), 11.1 at 1024 x 128 in f32, 4.9 at the training
# cell's 1024 x 128 in bf16 (tests/test_aot_tpu_compile.py compiles the
# three edges, both masks).  Twice those bytes do NOT fit under every
# mask: 4096 x 128 in bf16 without one asks for 35.8 MiB
_FUSED_MAX_HEAD_BYTES = 2 * 2048 * 128
_FUSED_VMEM_LIMIT = 32 << 20
# key rows a step (the diagonal piece is that square, masked) and the
# most query rows of one product below it: swept at T = 1,024 and 2,048
# on a v5e (PERF.md 7, PR 62)
_FUSED_BLOCK_K = 256
_FUSED_BLOCK_Q = 1024


def flash_backward(T: int, S: int, D: int, heads: int, kv_heads: int,
                   segment_ids: bool = False, itemsize: int = 2):
    """Which backward a flash call of these shapes runs, and why:
    ("fused" | "split", reason), from the shapes alone (``itemsize``:
    the operands' bytes a number)."""
    for off, why in (
            (segment_ids, "packed segments: the split kernels carry the "
                          "segment operands"),
            (T != S, "T != S: the fused body walks one square of blocks"),
            (heads != kv_heads, "GQA: dK and dV sum over a K/V head's "
                                "query heads in the split sweep"),
            (itemsize * T * max(D, 128) > _FUSED_MAX_HEAD_BYTES,
             "a head does not fit the vector memory whole: the split "
             "kernels stream it in blocks"),
            (T % _FUSED_BLOCK_K != 0, "T is not whole blocks of "
                                      f"{_FUSED_BLOCK_K} key rows")):
        if off:
            return "split", why
    return "fused", "a head resident: each block pair's scores once"


def _pieces(j: int, T: int, causal: bool):
    """The query rows key block ``j`` meets, as (first row, rows, on the
    diagonal): under a causal mask the square on the diagonal and then
    every row below it, in products of at most ``_FUSED_BLOCK_Q`` rows."""
    out, at = [], 0
    if causal:
        out.append((j * _FUSED_BLOCK_K, _FUSED_BLOCK_K, True))
        at = (j + 1) * _FUSED_BLOCK_K
    while at < T:
        rows = min(_FUSED_BLOCK_Q, T - at)
        out.append((at, rows, False))
        at += rows
    return out


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dq_scr, *, scale, causal):
    (_, T, D), block_k = q_ref.shape, _FUSED_BLOCK_K
    nt = (((1,), (1,)), ((), ()))       # a b^T
    nn = (((1,), (0,)), ((), ()))       # a b
    tn = (((0,), (0,)), ((), ()))       # a^T b
    dot = functools.partial(jax.lax.dot_general,
                            preferred_element_type=jnp.float32)
    for j in range(T // block_k):
        keys = slice(j * block_k, (j + 1) * block_k)
        k, v = k_ref[0, keys, :], v_ref[0, keys, :]
        dk = jnp.zeros((block_k, D), jnp.float32)
        dv = jnp.zeros((block_k, D), jnp.float32)
        for first, rows, diagonal in _pieces(j, T, causal):
            at = slice(first, first + rows)
            q, do = q_ref[0, at, :], do_ref[0, at, :]
            s = dot(k, q, nt) * scale                       # [BK, rows]
            if diagonal:
                # the one square the mask cuts: key row > query column
                key = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                query = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                s = jnp.where(query >= key, s, NEG_INF)
            p = jnp.exp(s - lse_ref[0, :, at])              # [BK, rows]
            dv += dot(p.astype(do.dtype), do, nn)
            # scale waits for the [T, D] sums: dK and dQ are linear in ds
            ds = (p * (dot(v, do, nt) - delta_ref[0, :, at])).astype(q.dtype)
            dk += dot(ds, q, nn)
            # dQ^T = k^T ds: the transposed operand is the key block,
            # [BK, D] once, not the pair's [BK, rows]; dQ turns once a head
            dq = dot(k, ds, tn)                             # [D, rows]
            if j == 0:          # key block 0 meets every row: it starts dQ
                dq_scr[:, at] = dq
            else:
                dq_scr[:, at] += dq
        dk_ref[0, keys, :] = (dk * scale).astype(dk_ref.dtype)
        dv_ref[0, keys, :] = dv.astype(dv_ref.dtype)
    dq_ref[0] = (dq_scr[:].T * scale).astype(dq_ref.dtype)


def _flash_bwd_fused(q, k, v, lse, delta, do, *, causal: bool,
                     interpret: bool):
    """q, k, v, do: [B H, T, D]; lse, delta: [B H, 1, T] f32 → dq, dk, dv."""
    BH, T, D = q.shape
    head = pl.BlockSpec((1, T, D), lambda b: (b, 0, 0))
    row = pl.BlockSpec((1, 1, T), lambda b: (b, 0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=1.0 / np.sqrt(D),
                          causal=causal),
        grid=(BH,),
        in_specs=[head, head, head, head, row, row],
        out_specs=[head, head, head],
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in (q, k, v)],
        scratch_shapes=[pltpu.VMEM((D, T), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_FUSED_VMEM_LIMIT),
        interpret=interpret,
        name="dstpu_flash_bwd",
    )(q, k, v, do, lse, delta)

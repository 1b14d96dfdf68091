"""Pallas TPU flash attention (ref: deepspeed/ops/transformer CUDA
attention kernels; algorithm: block-tiled online-softmax a la
FlashAttention-2, re-derived for the TPU memory hierarchy).

Forward: grid (batch*q_heads, Tq/BQ, Tk/BK) with the K axis innermost;
running max/denominator live in VMEM scratch that persists across the K
block sweep, output is rescaled once at the last block.  Causal blocks
above the diagonal are skipped via masking (the index map keeps the sweep
dense; skipped blocks cost one compare).

Backward, where a head does not lie whole in VMEM (``attention_pallas_bwd``
has the rule and the fused kernel): one pallas kernel computes dQ (sweep
over K blocks), a second dK/dV (sweep over Q blocks), from p = exp(qk - lse).

GQA: logical-head BlockSpec index maps — query head h reads kv head
h // (H // KV) directly (``_kv_row``), so K/V are never repeated in HBM
and their traffic is cut by the group factor; dK/dV accumulate the sum
over each kv head's query group inside the backward sweep.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.attention_pallas_bwd import (FLASH_NAMES, NEG_INF,
                                                    _flash_bwd_fused,
                                                    flash_backward)


def _fwd_kernel(*refs, scale: float, causal: bool, block_q: int,
                block_k: int, num_k_blocks: int, has_seg: bool = False):
    if has_seg:
        (q_ref, k_ref, v_ref, sq_ref, sk_ref,
         o_ref, lse_ref, m_scr, l_scr, acc_scr) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    run = True
    if causal:
        # block is fully above the diagonal → skip
        run = (ki * block_k) <= (qi * block_q + block_q - 1)

    @pl.when(run)
    def _():
        q = q_ref[0]                        # [BQ, D]
        k = k_ref[0]                        # [BK, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [BQ, BK]
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        if has_seg:
            s = jnp.where(sq_ref[0] == sk_ref[0], s, NEG_INF)

        m_prev = m_scr[:]                   # [BQ, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)              # [BQ, BK] f32
        if has_seg:
            # a block whose every entry is cross-segment has m_new ==
            # NEG_INF and would yield p == exp(0) == 1 row-wide (the
            # causal path never hits this: the diagonal block always
            # holds live entries) — mask p explicitly
            p = jnp.where(s > NEG_INF / 2, p, 0.0)
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_scr[:] = m_new
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == num_k_blocks - 1)
    def _():
        l = l_scr[:]
        l = jnp.where(l == 0.0, 1.0, l)     # fully-masked rows → zero output
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:] + jnp.log(l)


def _bwd_dq_kernel(*refs, scale, causal, block_q, block_k, num_k_blocks,
                   has_seg: bool = False):
    if has_seg:
        (q_ref, k_ref, v_ref, sq_ref, sk_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_scr) = refs
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    run = True
    if causal:
        run = (ki * block_k) <= (qi * block_q + block_q - 1)

    @pl.when(run)
    def _():
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        if has_seg:
            s = jnp.where(sq_ref[0] == sk_ref[0], s, NEG_INF)
        p = jnp.exp(s - lse_ref[0])                     # [BQ, BK]
        dov = jax.lax.dot_general(
            do_ref[0].astype(jnp.float32), v_ref[0].astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dov - delta_ref[0]) * scale           # [BQ, BK]
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == num_k_blocks - 1)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, block_q, block_k, num_q_blocks,
                    n_rep, has_seg: bool = False):
    if has_seg:
        (q_ref, k_ref, v_ref, sq_ref, sk_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
    ki = pl.program_id(1)
    # inner axis sweeps (query-head-in-group, q block): dk/dv accumulate
    # over every query head sharing this kv head (GQA)
    qi = pl.program_id(2) % num_q_blocks

    @pl.when(pl.program_id(2) == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = True
    if causal:
        # q block entirely before this k block → no contribution
        run = (qi * block_q + block_q - 1) >= (ki * block_k)

    @pl.when(run)
    def _():
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        if has_seg:
            s = jnp.where(sq_ref[0] == sk_ref[0], s, NEG_INF)
        p = jnp.exp(s - lse_ref[0])                     # [BQ, BK]
        do = do_ref[0].astype(jnp.float32)
        dv_scr[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # [BK, D]
        dov = jax.lax.dot_general(do, v_ref[0].astype(jnp.float32),
                                  (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        ds = p * (dov - delta_ref[0]) * scale
        dk_scr[:] += jax.lax.dot_general(
            ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # [BK, D]

    @pl.when(pl.program_id(2) == num_q_blocks * n_rep - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _pick_blocks(T: int, S: int):
    """Tile sizes measured on v5e (a sweep older than the ledger, at
    B=4 T=S=2048 H=16 D=128): (512,512) fwd 5.0ms / fwd+bwd 11.0ms vs
    (256,256) 6.0/15.2 and (128,128) 8.8/25.4 — larger tiles amortize
    the softmax rescale and keep the MXU fed; VMEM still fits at 512
    with D=128."""
    pick = lambda n: 512 if n % 512 == 0 else 256 if n % 256 == 0 else 128
    return pick(T), pick(S)


def _kv_row(b, heads, kv_heads):
    """Logical-head map: flat q row b = batch*H + h → flat kv row
    batch*KV + h // (H // KV).  The DMA engine reads each kv block once
    per group instead of materialising a repeated copy in HBM."""
    g = heads // kv_heads
    return (b // heads) * kv_heads + (b % heads) // g


def _seg_operands(seg):
    """[B, T] segment ids → the two layouts the kernels read: q-side
    ``[B, T, 1]`` (a column, like lse) and k-side ``[B, 1, T]`` (a row),
    so ``sq == sk`` broadcasts to the [BQ, BK] mask with no in-kernel
    relayout.  A bare ``[B, T]`` operand cannot be blocked ``(1, BQ)``:
    Mosaic needs the second-to-last block dim divisible by 8 or equal
    to the array's, which the unit middle axis gives."""
    return [seg[:, :, None], seg[:, None, :]]


def _seg_specs(heads: int, block_q: int, block_k: int):
    """BlockSpecs for :func:`_seg_operands` on the fwd/dq grids, which
    run over flat q rows (b = batch*H + h).  The dkv grid (flat kv
    rows, q block riding program_id(2)) builds its specs inline — it
    needs the kv_heads/nq closure."""
    return [
        pl.BlockSpec((1, block_q, 1),
                     lambda b, i, j, H=heads: (b // H, i, 0)),
        pl.BlockSpec((1, 1, block_k),
                     lambda b, i, j, H=heads: (b // H, 0, j)),
    ]


def _flash_fwd_impl(q, k, v, seg, *, causal: bool, block_q: int,
                    block_k: int, heads: int, kv_heads: int,
                    interpret: bool):
    """q: [B*H, T, D]; k/v: [B*KV, S, D]; seg: [B, T] int32 or None
    → (out, lse)."""
    BH, T, D = q.shape
    S = k.shape[1]
    scale = 1.0 / np.sqrt(D)
    nq, nk = T // block_q, S // block_k
    grid = (BH, nq, nk)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, num_k_blocks=nk, has_seg=seg is not None)
    kv_spec = pl.BlockSpec(
        (1, block_k, D),
        lambda b, i, j: (_kv_row(b, heads, kv_heads), j, 0))
    in_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        kv_spec,
        kv_spec,
    ]
    operands = [q, k, v]
    if seg is not None:
        in_specs += _seg_specs(heads, block_q, block_k)
        operands += _seg_operands(seg)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), q.dtype),
            jax.ShapeDtypeStruct((BH, T, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
        name="dstpu_flash_fwd",
    )(*operands)
    return out, lse


def _flash_bwd_impl(q, k, v, seg, out, lse, do, *, causal, block_q,
                    block_k, heads, kv_heads, interpret):
    BH, T, D = q.shape
    BKV, S = k.shape[0], k.shape[1]
    G = heads // kv_heads
    scale = 1.0 / np.sqrt(D)
    nq, nk = T // block_q, S // block_k
    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1, keepdims=True)        # [BH, T, 1]

    kv_spec = pl.BlockSpec(
        (1, block_k, D),
        lambda b, i, j: (_kv_row(b, heads, kv_heads), j, 0))
    dq_in_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        kv_spec,
        kv_spec,
    ]
    dq_operands = [q, k, v]
    if seg is not None:
        dq_in_specs += _seg_specs(heads, block_q, block_k)
        dq_operands += _seg_operands(seg)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_k_blocks=nk,
                          has_seg=seg is not None),
        grid=(BH, nq, nk),
        in_specs=dq_in_specs + [
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
        name="dstpu_flash_bwd_dq",
    )(*dq_operands, do, lse, delta)

    # dk/dv grid runs over KV heads; the inner axis sweeps (group member,
    # q block) so the scratch accumulates the sum over the G query heads
    # sharing each kv head — the GQA head-sum fused into the sweep.
    def q_row(b, i):
        return ((b // kv_heads) * heads + (b % kv_heads) * G + i // nq,
                i % nq, 0)

    dkv_in_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, j, i: q_row(b, i)),
        pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
    ]
    dkv_operands = [q, k, v]
    if seg is not None:
        # batch = flat kv row // KV; q block index rides program_id(2)
        dkv_in_specs += [
            pl.BlockSpec((1, block_q, 1),
                         lambda b, j, i: (b // kv_heads, i % nq, 0)),
            pl.BlockSpec((1, 1, block_k),
                         lambda b, j, i: (b // kv_heads, 0, j)),
        ]
        dkv_operands += _seg_operands(seg)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_q_blocks=nq,
                          n_rep=G, has_seg=seg is not None),
        grid=(BKV, nk, nq * G),
        in_specs=dkv_in_specs + [
            pl.BlockSpec((1, block_q, D), lambda b, j, i: q_row(b, i)),
            pl.BlockSpec((1, block_q, 1), lambda b, j, i: q_row(b, i)),
            pl.BlockSpec((1, block_q, 1), lambda b, j, i: q_row(b, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BKV, S, D), k.dtype),
            jax.ShapeDtypeStruct((BKV, S, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        interpret=interpret,
        name="dstpu_flash_bwd_dkv",
    )(*dkv_operands, do, lse, delta)
    return dq, dk, dv


def _flash_bhtd(q, k, v, seg, causal: bool, interpret: bool, heads: int,
                kv_heads: int):
    """The forward kernel ONCE a call, as an ordinary call on operands
    cut from the gradient and OUTSIDE any custom VJP, its two results
    named: a remat policy sees no product in a ``pallas_call``, and what
    a VJP's forward rule makes is not saveable under any policy, so the
    old rule's residuals ``out`` and ``lse`` cost a second run of the
    whole kernel in every ``jax.checkpoint``-ed backward (PERF.md 6, PR
    62).  ``remat.policy`` joins the names to every policy that keeps
    anything; the gradient comes after, from a VJP whose forward rule
    runs no kernel (:func:`attach`, at the file's end), and a caller
    that never differentiates compiles the kernel call alone.

    The log-sum leaves as ``[B H, 1, T]``: a row, lane-dense in memory
    between the passes, where the kernel's ``[B H, T, 1]`` column pads
    each number to 128 lanes.

    (``attach`` stands below the file's last kernel, and this function
    with the imports keeps the old VJP's count of lines: the latent and
    the window kernels below are lowered from the lines they stand on,
    and a moved line is a new compile-cache key for every serving
    program that holds one, ROADMAP S2 (c).)"""
    block_q, block_k = _pick_blocks(q.shape[1], k.shape[1])
    out, lse = _flash_fwd_impl(
        *map(jax.lax.stop_gradient, (q, k, v)), seg, causal=causal,
        block_q=block_q, block_k=block_k, heads=heads, kv_heads=kv_heads,
        interpret=interpret)
    out = checkpoint_name(out, FLASH_NAMES[0])
    lse = checkpoint_name(lse.reshape(lse.shape[0], 1, -1), FLASH_NAMES[1])
    return attach(q, k, v, seg, out, lse, causal, interpret, heads, kv_heads)


def flash_attention_tpu(q, k, v, causal: bool = True, segment_ids=None,
                        interpret: bool = False):
    """[B,T,H,D] x [B,S,KV,D]^2 → [B,T,H,D]; GQA via logical-head index
    maps — kv blocks are DMA'd once per group, never repeated in HBM.

    segment_ids: optional [B, T] int32 — packed-sequence attention
    masking (positions attend only within their own segment id; ref:
    the variable-length batching the reference's sparse/dense kernels
    support).  The non-packed path compiles the EXACT graph it always
    did: the seg operands and their mask ops exist only when
    segment_ids is passed."""
    B, T, H, D = q.shape
    S, KV = k.shape[1], k.shape[2]
    if T % 128 or S % 128:
        raise ValueError(
            f"flash_attention_tpu needs T and S divisible by 128 (the block"
            f" tiling would silently drop trailing keys), got T={T} S={S}")
    if H % KV:
        raise ValueError(f"n_heads {H} not a multiple of kv_heads {KV}")
    if segment_ids is not None:
        if T != S:
            raise ValueError("segment_ids requires T == S (self-attention "
                             "over one packed layout)")
        segment_ids = jnp.asarray(segment_ids, jnp.int32)
        if segment_ids.shape != (B, T):
            raise ValueError(
                f"segment_ids shape {segment_ids.shape} != {(B, T)}")
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, S, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, S, D)
    out = _flash_bhtd(qf, kf, vf, segment_ids, causal, interpret, H, KV)
    return out.reshape(B, H, T, D).transpose(0, 2, 1, 3)


# ------------------------------------------------- latent (MLA) forward
def _latent_fwd_kernel(start_ref, qn_ref, qr_ref, kn_ref, kr_ref, v_ref,
                       o_ref, m_scr, l_scr, acc_scr, *, scale, heads,
                       block_q, block_k, num_k_blocks):
    """:func:`_fwd_kernel` with the score in two parts (per-head
    ``qn . kn`` plus ``qr . kr`` against the one rotated key part all
    heads share), a value width of its own, and the queries standing at
    ``start`` in the keys' positions.  Forward only."""
    qi, ki = pl.program_id(1), pl.program_id(2)
    first = start_ref[pl.program_id(0) // heads] + qi * block_q

    @pl.when(ki == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(ki * block_k <= first + block_q - 1)
    def _():
        dims = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(qn_ref[0], kn_ref[0], dims,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qr_ref[0], kr_ref[0], dims,
                                   preferred_element_type=jnp.float32)
             ) * scale                                       # [BQ, BK]
        rows = first + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # key 0 is at or before every query: block 0 leaves m finite,
        # and a masked score's exponential is 0 from then on
        p = jnp.exp(s - m_new)
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_scr[:] = m_new
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == num_k_blocks - 1)
    def _():
        o_ref[0] = (acc_scr[:] / l_scr[:]).astype(o_ref.dtype)


def latent_flash_attention_tpu(qn, qr, kn, kr, v, start, scale: float,
                               interpret: bool = False):
    """See :func:`deepspeed_tpu.ops.attention.latent_flash_attention`;
    T and S multiples of 128."""
    B, T, H, Dn = qn.shape
    S, Dr, Dv = kn.shape[1], qr.shape[-1], v.shape[-1]
    block_q, block_k = _pick_blocks(T, S)
    nk = S // block_k
    heads_first = lambda a: a.transpose(0, 2, 1, 3).reshape(
        B * H, a.shape[1], a.shape[3])

    def key_block(b, i, j, start_ref):
        # past the last block a query block sees, stay on it: no copy
        last = (start_ref[b // H] + (i + 1) * block_q - 1) // block_k
        return jnp.minimum(j, last)

    q_spec = lambda D: pl.BlockSpec((1, block_q, D),
                                    lambda b, i, j, st: (b, i, 0))
    k_spec = lambda D: pl.BlockSpec(
        (1, block_k, D), lambda b, i, j, st: (b, key_block(b, i, j, st), 0))
    out = pl.pallas_call(
        functools.partial(_latent_fwd_kernel, scale=scale, heads=H,
                          block_q=block_q, block_k=block_k,
                          num_k_blocks=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * H, T // block_q, nk),
            in_specs=[
                q_spec(Dn), q_spec(Dr), k_spec(Dn),
                pl.BlockSpec((1, block_k, Dr), lambda b, i, j, st: (
                    b // H, key_block(b, i, j, st), 0)),
                k_spec(Dv),
            ],
            out_specs=q_spec(Dv),
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, Dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B * H, T, Dv), qn.dtype),
        interpret=interpret,
        name="dstpu_latent_flash_fwd",
    )(start.astype(jnp.int32), heads_first(qn), heads_first(qr),
      heads_first(kn), kr, heads_first(v))
    return out.reshape(B, H, T, Dv).transpose(0, 2, 1, 3)


# ------------------------------------------- sliding window, forward only
def _band_seen(qpos, kpos, window: int):
    """Which keys a sliding layer's query sees, from the positions: the
    ``window`` at and before its own, none before position 0 (a slot's
    first chunks find ring rows that hold nothing of this request)."""
    return (kpos <= qpos) & (kpos > qpos - window) & (kpos >= 0)


def _window_fwd_kernel(start_ref, q_ref, k_ref, v_ref, o_ref, *, scale,
                       window, block_q, group):
    """One K/V head's ``group`` query heads over one block of ``block_q``
    queries and the ``window + block_q`` keys its band can touch: rows
    ``i block_q ..`` of ``[ring in order | chunk]``, where row ``c``
    holds position ``start - window + c``.  The whole band of a block is
    here at once, so the softmax is the plain one (a row's max, its
    exponentials, their sum), in f32, and the scores go nowhere."""
    b, i = pl.program_id(0), pl.program_id(2)
    Dh = k_ref.shape[-1]
    span = window + block_q
    first = pl.multiple_of(i * block_q, block_q)
    k = k_ref[0, pl.ds(first, span), :]                       # [span, Dh]
    v = v_ref[0, pl.ds(first, span), :]
    # the query heads of this K/V head, stacked as rows: one product
    q = jnp.concatenate([q_ref[0, :, g * Dh:(g + 1) * Dh]
                         for g in range(group)], axis=0)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale    # [group bq, span]
    at = start_ref[b] + first
    seen = _band_seen(
        at + jax.lax.broadcasted_iota(jnp.int32, (block_q, span), 0),
        at - window + jax.lax.broadcasted_iota(jnp.int32, (block_q, span), 1),
        window)
    p, l = [], []
    for g in range(group):
        # a query sees the key at its own position: the max is a score's
        sg = jnp.where(seen, s[g * block_q:(g + 1) * block_q], NEG_INF)
        e = jnp.exp(sg - jnp.max(sg, axis=1, keepdims=True))
        l.append(jnp.sum(e, axis=1, keepdims=True))
        p.append(e.astype(v.dtype))
    o = jax.lax.dot_general(
        jnp.concatenate(p, axis=0), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) / jnp.concatenate(l, axis=0)
    for g in range(group):
        o_ref[0, :, g * Dh:(g + 1) * Dh] = o[
            g * block_q:(g + 1) * block_q].astype(o_ref.dtype)


def window_flash_attention_tpu(q, rows, ring, start, interpret: bool = False):
    """A chunk's sliding-window attention over what the rows' rings held
    before it, the scores never off the chip: ``q`` [B, T, H, Dh] at
    positions ``start + 0 .. T - 1``, ``rows`` [B, T, 2 KV Dh] the
    chunk's ``[K | V]`` of every K/V head, ``ring`` [B, W, 2 KV Dh]
    (row ``p mod W`` holds position ``p``, the last W under ``start``)
    -> [B, T, H, Dh].  T and W multiples of 128, Dh 128.

    The ring is put in the order of its positions on the way in (a row
    gather of W rows), so that the keys a block of queries can see are
    ONE run of ``W + block`` rows of ``[ring | chunk]`` and the mask
    needs no operand but ``start``.  A K/V head's rows stay in the fast
    memory while its query blocks pass."""
    B, T, H, Dh = q.shape
    W, KV = ring.shape[1], rows.shape[-1] // (2 * Dh)
    group, block_q = H // KV, 128
    order = (start[:, None] + jnp.arange(W, dtype=jnp.int32)[None]) % W
    kv = jnp.concatenate(
        [jnp.take_along_axis(ring, order[..., None], axis=1,
                             mode="promise_in_bounds").astype(rows.dtype),
         rows], axis=1)                                  # [B, W + T, 2 KV Dh]
    q_spec = pl.BlockSpec((1, block_q, group * Dh),
                          lambda b, h, i, st: (b, i, h))
    out = pl.pallas_call(
        functools.partial(_window_fwd_kernel, scale=Dh ** -0.5, window=W,
                          block_q=block_q, group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, KV, T // block_q),
            in_specs=[
                q_spec,
                pl.BlockSpec((1, W + T, Dh), lambda b, h, i, st: (b, 0, h)),
                pl.BlockSpec((1, W + T, Dh),
                             lambda b, h, i, st: (b, 0, KV + h)),
            ],
            out_specs=q_spec,
        ),
        out_shape=jax.ShapeDtypeStruct((B, T, H * Dh), q.dtype),
        interpret=interpret,
        name="dstpu_window_flash_fwd",
    )(start.astype(jnp.int32), q.reshape(B, T, H * Dh), kv, kv)
    return out.reshape(B, T, H, Dh)


# ------------------------------------------- the flash kernel's gradient
@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def attach(q, k, v, seg, out, lse, causal: bool, interpret: bool,
           heads: int, kv_heads: int):
    """``out``, with the flash gradient to ``q``, ``k`` and ``v``: the
    context and the log-sum (``[B H, 1, T]``) are the forward kernel's,
    made outside on operands cut from the gradient (``_flash_bhtd``)."""
    return out


def _attach_fwd(q, k, v, seg, out, lse, causal, interpret, heads, kv_heads):
    return out, (q, k, v, seg, out, lse)


def _attach_bwd(causal, interpret, heads, kv_heads, res, do):
    q, k, v, seg, out, lse = res
    (BH, T, D), S = q.shape, k.shape[1]
    path, why = flash_backward(T, S, D, heads, kv_heads, seg is not None,
                               q.dtype.itemsize)
    # for the build span this trace runs under (devprof listens): which
    # backward the step it makes ready runs, and why
    jax.monitoring.record_event(
        "/dstpu/build_word", flash_bwd=path,
        flash_bwd_why=why.replace(" ", "_"))
    if path == "fused":
        delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32),
                        axis=-1)[:, None, :]                # [BH, 1, T]
        dq, dk, dv = _flash_bwd_fused(q, k, v, lse, delta, do, causal=causal,
                                      interpret=interpret)
    else:
        block_q, block_k = _pick_blocks(T, S)
        dq, dk, dv = _flash_bwd_impl(
            q, k, v, seg, out, lse.reshape(BH, T, 1), do, causal=causal,
            block_q=block_q, block_k=block_k, heads=heads,
            kv_heads=kv_heads, interpret=interpret)
    # segment ids are integral: their cotangent is float0 (None when the
    # operand was None — the pytree structures must match); the context
    # and the log-sum were cut from the gradient where they were made:
    # None is a zero cotangent
    dseg = None if seg is None else np.zeros(seg.shape, jax.dtypes.float0)
    return dq, dk, dv, dseg, None, None


attach.defvjp(_attach_fwd, _attach_bwd)

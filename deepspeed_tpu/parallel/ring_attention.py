"""Ring attention over the ``seq`` mesh axis (long-context training).

Reference behavior: DeepSpeed's long-sequence path (DeepSpeed-Ulysses,
deepspeed/sequence/layer.py) plus the ring-attention literature the
reference ecosystem targets: each rank holds a sequence shard; K/V blocks
rotate around the ring while each rank accumulates its queries' attention
with an online (flash-style) softmax, so the full sequence never
materializes on one chip.

TPU design: the ring is a ``lax.ppermute`` over the ``seq`` axis inside a
``shard_map`` — XLA lowers it to ICI neighbor exchange, double-buffered by
the latency-hiding scheduler so the K/V hop overlaps each block's compute.
The online-softmax accumulator is the same (m, l, o) recurrence as the
pallas flash kernel (ops/attention_pallas.py); causality is enforced
per-block from ring positions so fully-masked blocks contribute zero.

Gradients: ``ppermute`` is linear with a transpose rule (the inverse
permutation), so ``jax.grad`` through this function yields the reverse
ring — backward needs no hand-written schedule.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.topology import MeshSpec

SEQ_AXIS = "seq"


def _repeat_kv(k, v, n_heads):
    kv = k.shape[2]
    if kv != n_heads:
        rep = n_heads // kv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return k, v


def ring_attention(q, k, v, axis_name: str = SEQ_AXIS, causal: bool = True,
                   scale: Optional[float] = None, segment_ids=None):
    """Blockwise ring attention.  MUST run inside a shard_map/manual context
    where ``axis_name`` is a manual mesh axis.

    q: [B, Tq, H, Dh], k/v: [B, Tk, KV, Dh] — the LOCAL sequence shards.
    segment_ids: optional [B, Tq] int32 LOCAL shard of the packed-layout
    ids; the key-side ids ride the ring with their K/V block, so
    cross-segment pairs mask out ring-wide.  Returns [B, Tq, H, Dh] in
    q.dtype.
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, Tq, H, Dh = q.shape
    k, v = _repeat_kv(k, v, H)
    Tk = k.shape[1]
    scale = scale if scale is not None else Dh ** -0.5

    qf = q.astype(jnp.float32) * scale
    o = jnp.zeros((B, Tq, H, Dh), jnp.float32)
    m = jnp.full((B, H, Tq), -jnp.inf, jnp.float32)   # running row max
    l = jnp.zeros((B, H, Tq), jnp.float32)            # running denominator

    # kv blocks rotate "up" the ring: after s hops, rank i holds block i-s.
    perm = [(i, (i + 1) % n) for i in range(n)]
    q_pos = idx * Tq + jnp.arange(Tq)
    seg_k0 = segment_ids if segment_ids is None else \
        jnp.asarray(segment_ids, jnp.int32)

    def step(carry, s):
        o, m, l, k_cur, v_cur, seg_cur = carry
        src = (idx - s) % n
        scores = jnp.einsum("bthd,bshd->bhts", qf, k_cur.astype(jnp.float32))
        mask = None
        if causal:
            k_pos = src * Tk + jnp.arange(Tk)
            mask = (q_pos[:, None] >= k_pos[None, :])[None]   # [1, Tq, Tk]
        if seg_cur is not None:
            same = seg_k0[:, :, None] == seg_cur[:, None, :]  # [B, Tq, Tk]
            mask = same if mask is None else mask & same
        if mask is not None:
            scores = jnp.where(mask[:, None], scores, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(scores - m_safe[..., None])          # masked rows → 0
        if mask is not None:
            p = jnp.where(mask[:, None], p, 0.0)
        alpha = jnp.exp(jnp.where(jnp.isfinite(m), m - m_safe, -jnp.inf))
        l = l * alpha + jnp.sum(p, axis=-1)
        o = o * alpha.transpose(0, 2, 1)[..., None] + jnp.einsum(
            "bhts,bshd->bthd", p, v_cur.astype(jnp.float32))
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        seg_nxt = (None if seg_cur is None else
                   jax.lax.ppermute(seg_cur, axis_name, perm))
        return (o, m_new, l, k_nxt, v_nxt, seg_nxt), None

    (o, m, l, _, _, _), _ = jax.lax.scan(
        step, (o, m, l, k, v, seg_k0), jnp.arange(n))
    l = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return (o / l).astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh: MeshSpec, causal: bool = True,
                           axis_name: str = SEQ_AXIS, segment_ids=None):
    """GSPMD entrypoint: wraps :func:`ring_attention` in a shard_map that
    manualizes ONLY the ``seq`` axis — batch (data) and head (model)
    shardings stay automatic, so ring attention composes with ZeRO and TP
    inside one jitted step.  ``segment_ids`` ([B, T] int32) shard along
    the sequence like q and rotate with the K/V blocks.
    """
    if mesh.size(axis_name) <= 1:
        from deepspeed_tpu.ops.attention import flash_attention

        return flash_attention(q, k, v, causal=causal,
                               segment_ids=segment_ids)
    spec = P(None, axis_name, None, None)
    in_specs, args = (spec, spec, spec), (q, k, v)
    if segment_ids is not None:
        in_specs += (P(None, axis_name),)
        args += (jnp.asarray(segment_ids, jnp.int32),)

    def wrapped(q, k, v, seg=None):
        return ring_attention(q, k, v, axis_name=axis_name, causal=causal,
                              segment_ids=seg)

    fn = jax.shard_map(wrapped, mesh=mesh.mesh, in_specs=in_specs,
                       out_specs=spec, axis_names={axis_name},
                       check_vma=False)
    return fn(*args)

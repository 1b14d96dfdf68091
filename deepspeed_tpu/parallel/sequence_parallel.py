"""DeepSpeed-Ulysses sequence parallelism over the ``seq`` mesh axis.

Reference behavior: deepspeed/sequence/layer.py (DistributedAttention):
activations are sequence-sharded; before attention an all-to-all swaps the
sharding from the sequence dim to the head dim (each rank gets the FULL
sequence for a SLICE of heads), full attention runs locally, and a second
all-to-all swaps back.  Communication is O(N/P) per rank vs all-gather's
O(N) — this is what lets the reference scale to million-token sequences.

TPU design: the two transposes are single ``lax.all_to_all`` ops over the
``seq`` axis inside a partially-manual shard_map (only ``seq`` manual;
``data``/``model`` axes stay under GSPMD, so Ulysses composes with ZeRO +
TP).  XLA lowers all-to-all onto the ICI torus natively.  Any attention
kernel runs in the middle — the pallas flash kernel by default — because
after the first swap attention is embarrassingly head-parallel.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.topology import MeshSpec

SEQ_AXIS = "seq"


def _default_attn(q, k, v, causal, segment_ids=None):
    from deepspeed_tpu.ops.attention import flash_attention

    return flash_attention(q, k, v, causal=causal, segment_ids=segment_ids)


def ulysses_attention(q, k, v, axis_name: str = SEQ_AXIS,
                      causal: bool = True,
                      attn_fn: Optional[Callable] = None,
                      segment_ids=None):
    """Head/sequence all-to-all attention.  MUST run inside a shard_map
    where ``axis_name`` is manual.

    q: [B, T_local, H, Dh]; k/v: [B, T_local, KV, Dh].
    Heads (and KV heads) must be divisible by the seq-axis size; KV heads
    are broadcast up if a GQA group doesn't divide.
    segment_ids: optional [B, T_local] int32 shard of the packed layout —
    after the all-to-all every rank holds the FULL sequence for its head
    slice, so the ids are all-gathered (tiny int32) and masking is local.
    """
    attn_fn = attn_fn or _default_attn
    sp = jax.lax.axis_size(axis_name)
    H, KV = q.shape[2], k.shape[2]
    if H % sp != 0:
        raise ValueError(f"n_heads {H} not divisible by seq parallelism {sp}")
    if KV % sp != 0:  # GQA group smaller than the ring: broadcast kv heads
        rep = H // KV
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)

    # seq-sharded -> head-sharded: [B, T/sp, H, Dh] -> [B, T, H/sp, Dh]
    swap = lambda x: jax.lax.all_to_all(x, axis_name, split_axis=2,
                                        concat_axis=1, tiled=True)
    qh, kh, vh = swap(q), swap(k), swap(v)
    seg_full = None
    if segment_ids is not None:
        seg_full = jax.lax.all_gather(
            jnp.asarray(segment_ids, jnp.int32), axis_name, axis=1,
            tiled=True)                                   # [B, T]
    # custom attn_fns keep their (q, k, v, causal) signature unless a
    # packed layout is actually in play
    out = (attn_fn(qh, kh, vh, causal) if seg_full is None
           else attn_fn(qh, kh, vh, causal, segment_ids=seg_full))
    # head-sharded -> seq-sharded
    return jax.lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)


def ulysses_attention_sharded(q, k, v, mesh: MeshSpec, causal: bool = True,
                              axis_name: str = SEQ_AXIS,
                              attn_fn: Optional[Callable] = None,
                              segment_ids=None):
    """GSPMD entrypoint: shard_map manualizing only ``seq`` (ZeRO/TP stay
    automatic), mirroring :func:`ring_attention_sharded`."""
    if mesh.size(axis_name) <= 1:
        fn1 = attn_fn or _default_attn
        return (fn1(q, k, v, causal) if segment_ids is None
                else fn1(q, k, v, causal, segment_ids=segment_ids))
    spec = P(None, axis_name, None, None)
    in_specs, args = (spec, spec, spec), (q, k, v)
    if segment_ids is not None:
        in_specs += (P(None, axis_name),)
        args += (jnp.asarray(segment_ids, jnp.int32),)

    def wrapped(q, k, v, seg=None):
        return ulysses_attention(q, k, v, axis_name=axis_name,
                                 causal=causal, attn_fn=attn_fn,
                                 segment_ids=seg)

    fn = jax.shard_map(wrapped, mesh=mesh.mesh, in_specs=in_specs,
                       out_specs=spec, axis_names={axis_name},
                       check_vma=False)
    return fn(*args)

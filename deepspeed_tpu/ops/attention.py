"""Attention kernels (ref: deepspeed/ops/transformer CUDA attention +
ops/transformer/inference).

``flash_attention`` is the training entrypoint: a Pallas TPU kernel
(block-tiled online-softmax, fwd+bwd custom VJP) with a jnp reference
for CPU runs and shapes the kernel's tiling does not cover.  The kernel
lands in :mod:`deepspeed_tpu.ops.attention_pallas`; this module owns
dispatch.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


def _reference(q, k, v, causal=True, segment_ids=None):
    B, T, H, Dh = q.shape
    KV = k.shape[2]
    if KV != H:
        rep = H // KV
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bthd,bshd->bhts", q, k,
                        preferred_element_type=jnp.float32) / np.sqrt(Dh)
    if causal:
        mask = jnp.tril(jnp.ones((T, k.shape[1]), bool))
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    if segment_ids is not None:
        same = segment_ids[:, None, :, None] == segment_ids[:, None, None, :]
        scores = jnp.where(same, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bshd->bthd", probs, v)


def _pallas_shapes_ok(q, k, segment_ids=None) -> bool:
    """The shapes the Pallas kernel's tiling covers (both lengths a
    multiple of 128 and at least 256, q, k and v of one head_dim, 64 or
    128; packed layouts are self-attention only).  Latent attention's
    widths (q/k of 128 + 64 with the 64 shared by all heads, v of 128,
    queries at an offset into the keys) are
    :func:`latent_flash_attention`'s, a kernel of their own."""
    T, S = q.shape[1], k.shape[1]
    return ((segment_ids is None or T == S)
            and T >= 256 and T % 128 == 0
            and S >= 256 and S % 128 == 0 and q.shape[-1] in (64, 128))


def flash_attention(q, k, v, causal: bool = True, segment_ids=None,
                    force_reference: bool = False, mesh=None):
    """[B,T,H,Dh] x [B,T,KV,Dh]^2 → [B,T,H,Dh].

    Dispatches to the Pallas TPU kernel when running on TPU with
    kernel-friendly shapes; otherwise the fused-softmax jnp reference
    (which XLA still fuses well).  ``force_reference``: callers whose
    operands are model-axis sharded without a mesh to split the call
    over (TP serving) must skip the pallas custom call — GSPMD cannot
    partition it.  ``mesh``: the :class:`~deepspeed_tpu.topology
    .MeshSpec` the caller's step runs under (training models pass the
    ambient one); the kernel then runs per shard, batch split over the
    token-replicating axes and heads over ``model``.  Operands those
    axes do not divide take the reference, which GSPMD partitions
    itself.
    """
    if jax.default_backend() != "tpu" or force_reference \
            or not _pallas_shapes_ok(q, k, segment_ids):
        return _reference(q, k, v, causal=causal, segment_ids=segment_ids)
    from deepspeed_tpu.ops.attention_pallas import flash_attention_tpu

    # one device, or already inside a shard_map (pipeline ticks,
    # Ulysses): the call is direct, as it always was
    if mesh is None or mesh.mesh.size == 1 \
            or jax.sharding.get_abstract_mesh().manual_axes:
        return flash_attention_tpu(q, k, v, causal=causal,
                                   segment_ids=segment_ids)

    # "Mosaic kernels cannot be automatically partitioned. Please wrap
    # the call in a shard_map" — and only a full-manual one lowers (no
    # axis_names=): nothing may be left for GSPMD to partition
    from deepspeed_tpu.topology import BATCH_AXES

    batch_axes = tuple(a for a in BATCH_AXES if mesh.size(a) > 1)
    tp = mesh.size("model")
    if q.shape[0] % int(np.prod([mesh.size(a) for a in batch_axes])) \
            or q.shape[2] % tp or k.shape[2] % tp:
        return _reference(q, k, v, causal=causal, segment_ids=segment_ids)
    spec = P(batch_axes or None, None, "model" if tp > 1 else None, None)
    args, in_specs = (q, k, v), (spec, spec, spec)
    if segment_ids is not None:
        args += (jnp.asarray(segment_ids, jnp.int32),)
        in_specs += (P(batch_axes or None, None),)

    def per_shard(q, k, v, seg=None):
        return flash_attention_tpu(q, k, v, causal=causal, segment_ids=seg)

    return jax.shard_map(per_shard, mesh=mesh.mesh, in_specs=in_specs,
                         out_specs=spec, check_vma=False)(*args)


def _latent_reference(qn, qr, kn, kr, v, start, scale):
    s = (jnp.einsum("bthd,bshd->bhts", qn, kn,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bthd,bsd->bhts", qr, kr,
                      preferred_element_type=jnp.float32)) * scale
    T, S = qn.shape[1], kn.shape[1]
    seen = jnp.arange(S)[None, None] <= (
        start[:, None] + jnp.arange(T)[None])[:, :, None]       # [B, T, S]
    s = jnp.where(seen[:, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhts,bshd->bthd", p, v)


def latent_flash_attention(qn, qr, kn, kr, v, start, scale: float,
                           force_reference: bool = False):
    """Latent attention in the per-head form, over expanded keys and
    values: ``qn`` [B, T, H, Dn] and ``qr`` [B, T, H, Dr] (rotated)
    against ``kn`` [B, S, H, Dn], ``kr`` [B, S, Dr] (one rotated key
    part for all heads) and ``v`` [B, S, H, Dv] -> [B, T, H, Dv].
    Query t of row b stands at position ``start[b] + t`` and sees the
    keys at or before it.

    On a TPU with both lengths a multiple of 128 the blocked Pallas
    kernel runs (``dstpu_latent_flash_fwd``: never an [H, T, S] score
    array, and ``kr`` is read once a block, not once a head); elsewhere
    the jnp reference, which materialises the scores."""
    T, S = qn.shape[1], kn.shape[1]
    if jax.default_backend() != "tpu" or force_reference \
            or T % 128 or S % 128:
        return _latent_reference(qn, qr, kn, kr, v, start, scale)
    from deepspeed_tpu.ops.attention_pallas import latent_flash_attention_tpu

    return latent_flash_attention_tpu(qn, qr, kn, kr, v, start, scale)


def window_reader(*, tokens: int, window: int, head_dim: int,
                  interpret: bool):
    """Which reader a sliding layer's chunk of ``tokens`` runs over its
    band, and why: ("pallas" | "xla", reason), the one answer the
    family's chunk and the engine's ``/statusz`` share (as
    ``kernels.paged_reader`` and ``state_stepper`` are for theirs; here,
    because ``models/`` imports nothing from ``inference/``), from the
    backend and the shapes alone.  On a TPU, where the chunk and the
    window are whole 128-row blocks and a head is one 128-lane tile, the
    band's scores stay on the chip (``dstpu_window_flash_fwd``:
    :func:`~deepspeed_tpu.ops.attention_pallas.
    window_flash_attention_tpu`); elsewhere XLA runs the band in blocks
    of the window, f32 scores through the memory.  A decode step
    (``tokens == 1``) reads its ring where it lies, in XLA."""
    for off, why in ((interpret, "interpret: no TPU backend"),
                     (tokens <= 0 or tokens % 128 or window % 128,
                      "chunk or window not whole 128-row blocks"),
                     (head_dim != 128, "a head is not one 128-lane tile")):
        if off:
            return "xla", why
    return "pallas", "a chunk's band in 128-row blocks on one device"

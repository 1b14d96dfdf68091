"""Hierarchical (two-level) + quantized collectives (ref: ZeRO++
hpZ/qgZ, arXiv:2306.10209; EQuARX quantized all-reduce on TPU,
arXiv:2506.17615).

The ``data`` axis of the mesh is factored into ``(inter, intra)``
sub-groups via ``axis_index_groups`` — no mesh rebuild, no second axis
name; the same ``shard_map`` body just addresses two nested rings:

* **intra group** — the ``hierarchy_size`` devices of one node
  (contiguous ranks ``n*k .. n*k+k-1``): fast links, cheap bytes.
* **inter group** — same intra-rank across all nodes (ranks ``j, k+j,
  2k+j, ...``): the slow tier every eliminated hop pays for.

Three schedules live here:

1. :func:`hierarchical_all_reduce` — gradient all-reduce as
   intra reduce-scatter → inter exchange (reduce-scatter + gather) →
   intra gather, every hop on the quantized wire (the EQuARX shape:
   both levels int8, exact bypass for verification).  Per-device wire
   bytes for W=8, k=2: ~1.75n vs flat f32's ~7n (4.0x), and only
   ~0.75n of it crosses inter-node links.
2. :func:`hpz_weight_gather` — qwZ weight all-gather where the inter
   hop moves ``inter`` int8 rows instead of ``world`` f32 rows, then
   fans out intra-node; the inter-gathered payload is the hpZ
   *secondary shard* and can be re-used (``secondary=``) to skip the
   inter hop entirely within a step.  Bit-exact vs the flat int8
   gather: quantization happens once, before any wire hop.
3. :func:`bucketed_reduce` — the reference's NCCL-bucket idiom via a
   ``lax.scan`` over fixed-size buckets, so XLA's latency-hiding
   scheduler can overlap bucket k's collective with bucket k+1's
   compute.  Buckets aligned to ``world * codec-unit`` make the
   per-bucket quantization grids equal the monolithic buffer's grids,
   so bucketing ships the identical int8 codes and scales as the
   single concatenate it replaces (grads agree to f32 rounding — the
   two compiled schedules may reassociate the final sums by an ulp;
   under ``codec="exact"`` on integer-valued data they are bit-equal).

Codec selection (``CommConfig.codec``): ``blockwise`` (v2 wire codec,
4096-element TPU-tile blocks from ops/quant.py), ``group`` (the legacy
flat 512-element grid), ``exact`` (f32 wire, bit-exact bypass).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.mesh import detect_hierarchy_size
from deepspeed_tpu.ops.quant import (
    BLOCK_ELEMS, INT_BOUNDS, block_pad, dequantize, quantize,
    quantized_all_gather, quantized_reduce_scatter)

__all__ = [
    "Hierarchy", "resolve_hierarchy", "codec_unit",
    "hierarchical_all_reduce", "hierarchical_all_reduce_tree",
    "hpz_weight_gather", "bucketed_reduce", "bucket_elems_for",
    "wire_bytes_per_device", "quantize_for_wire", "dequantize_from_wire",
    "quantize_for_wire_np",
]

# legacy flat grid (comm_compress._GROUP); kept as a codec so existing
# configs can reproduce pre-v2 numerics bit-for-bit
_GROUP_UNIT = 512

_CODEC_UNITS = {"blockwise": BLOCK_ELEMS, "group": _GROUP_UNIT, "exact": 1}


def codec_unit(codec: str) -> int:
    """Elements per quantization scale for a wire codec."""
    try:
        return _CODEC_UNITS[codec]
    except KeyError:
        raise ValueError(
            f"unknown wire codec {codec!r} (one of {sorted(_CODEC_UNITS)})")


# ------------------------------------------------------------ hierarchy
@dataclasses.dataclass(frozen=True)
class Hierarchy:
    """The (inter, intra) factoring of a flat collective axis.

    ``intra == 1`` or ``inter == 1`` degenerate to the flat schedule —
    every entrypoint below short-circuits them, so a Hierarchy is
    always safe to thread through even when it does nothing.
    """
    world: int
    intra: int

    def __post_init__(self):
        if self.world <= 0:
            raise ValueError(f"world must be positive, got {self.world}")
        if self.intra <= 0:
            raise ValueError(
                f"hierarchy_size must be positive, got {self.intra}")
        if self.world % self.intra:
            raise ValueError(
                f"hierarchy_size {self.intra} does not divide the data-"
                f"parallel world {self.world} — pick a divisor (nodes "
                "must be uniform)")

    @property
    def inter(self) -> int:
        return self.world // self.intra

    @property
    def flat(self) -> bool:
        return self.intra == 1 or self.inter == 1

    @functools.cached_property
    def intra_groups(self) -> Tuple[Tuple[int, ...], ...]:
        """Node n = contiguous ranks [n*k, (n+1)*k)."""
        k = self.intra
        return tuple(tuple(range(n * k, (n + 1) * k))
                     for n in range(self.inter))

    @functools.cached_property
    def inter_groups(self) -> Tuple[Tuple[int, ...], ...]:
        """Intra-rank j across all nodes: [j, k+j, 2k+j, ...]."""
        k = self.intra
        return tuple(tuple(j + n * k for n in range(self.inter))
                     for j in range(k))


def resolve_hierarchy(world: int, hierarchy_size: int = 0,
                      devices: Optional[Sequence] = None) -> Hierarchy:
    """CommConfig.hierarchy_size → a validated :class:`Hierarchy`.

    0 auto-detects from device topology (:func:`detect_hierarchy_size`
    — devices-per-process, 1 on single-process meshes); a non-divisor
    raises (uniform nodes are a schedule invariant, not a preference).
    When auto-detection proposes a split the world doesn't divide by
    (partial-node meshes), it falls back to flat instead of raising:
    only an EXPLICIT bad hierarchy_size is a config error.
    """
    if hierarchy_size == 0:
        k = detect_hierarchy_size(devices)
        if k <= 1 or world % k:
            return Hierarchy(world, 1)
        return Hierarchy(world, k)
    return Hierarchy(world, hierarchy_size)


# ------------------------------------------------- hierarchical all-reduce
def _pad_flat(flat: jnp.ndarray, unit: int) -> jnp.ndarray:
    n = flat.shape[0]
    pn = -(-n // unit) * unit
    if pn == n:
        return flat
    return jnp.concatenate([flat, jnp.zeros(pn - n, flat.dtype)])


def hierarchical_all_reduce(flat: jnp.ndarray, axis_name: str,
                            h: Hierarchy, *, bits: int = 8,
                            codec: str = "blockwise") -> jnp.ndarray:
    """Two-level all-reduce (MEAN over the full axis) of a flat buffer.

    Schedule (k = intra, m = inter): intra quantized reduce-scatter
    (a2a) → inter quantized reduce-scatter (a2a) → inter int8 gather →
    intra int8 gather.  Each device's wire traffic is ~(k-1)/k·n +
    2·(m-1)/m·n/k + (k-1)/k·n int8 bytes; only the two middle hops
    cross node boundaries.  ``codec="exact"`` runs the same schedule on
    the f32 wire (psum_scatter/all_gather) — bit-exact on data whose
    sums are exactly representable (the verification arm).

    ``flat`` must be 1D with ``flat.size % (world * codec_unit) == 0``
    — callers pad (:func:`_pad_flat` / :func:`bucket_elems_for` keep
    the alignment for you).
    """
    U = codec_unit(codec)
    W, k, m = h.world, h.intra, h.inter
    n = flat.shape[0]
    if n % (W * U):
        raise ValueError(
            f"buffer of {n} elements is not aligned to world*unit = "
            f"{W}*{U} — pad before calling")
    if h.flat:
        # degenerate hierarchy: one flat quantized RS + gather
        if codec == "exact":
            red = jax.lax.psum_scatter(flat, axis_name, tiled=True) / W
            return jax.lax.all_gather(red, axis_name, tiled=True)
        red = quantized_reduce_scatter(
            flat, axis_name, bits=bits, groups_per_shard=n // (W * U))
        return quantized_all_gather(
            red, axis_name, bits=bits, num_groups=red.shape[0] // U
        ).reshape(-1)

    if codec == "exact":
        # same two-level schedule, f32 wire: the bit-exact arm
        red = jax.lax.psum_scatter(
            flat, axis_name, tiled=True,
            axis_index_groups=[list(g) for g in h.intra_groups]) / k
        red = jax.lax.psum_scatter(
            red, axis_name, tiled=True,
            axis_index_groups=[list(g) for g in h.inter_groups]) / m
        red = jax.lax.all_gather(
            red, axis_name, tiled=True,
            axis_index_groups=[list(g) for g in h.inter_groups])
        return jax.lax.all_gather(
            red, axis_name, tiled=True,
            axis_index_groups=[list(g) for g in h.intra_groups])

    intra = [list(g) for g in h.intra_groups]
    inter = [list(g) for g in h.inter_groups]
    # 1) intra reduce-scatter: [n] -> [n/k], mean over the node
    red = quantized_reduce_scatter(
        flat, axis_name, bits=bits, groups_per_shard=n // (k * U),
        axis_index_groups=intra, group_size=k)
    # 2) inter reduce-scatter: [n/k] -> [n/(k*m)], global mean
    red = quantized_reduce_scatter(
        red, axis_name, bits=bits, groups_per_shard=n // (k * m * U),
        axis_index_groups=inter, group_size=m)
    # 3) inter int8 gather: back to the intra shard [n/k]
    red = quantized_all_gather(
        red, axis_name, bits=bits, num_groups=red.shape[0] // U,
        axis_index_groups=inter).reshape(-1)
    # 4) intra int8 gather: full [n] everywhere
    return quantized_all_gather(
        red, axis_name, bits=bits, num_groups=red.shape[0] // U,
        axis_index_groups=intra).reshape(-1)


# ------------------------------------------------------- bucketed overlap
def bucket_elems_for(bucket_mb: float, world: int, codec: str) -> int:
    """Bucket size in ELEMENTS, rounded up to ``world * codec_unit`` so
    per-bucket quantization grids coincide with the monolithic
    buffer's grids (bucketing preserves the wire codes exactly).  0 → 0
    (bucketing off, monolithic path)."""
    if bucket_mb <= 0:
        return 0
    unit = world * codec_unit(codec)
    raw = max(1, int(bucket_mb * (1 << 20)) // 4)      # f32 elements
    return -(-raw // unit) * unit


def bucketed_reduce(flat: jnp.ndarray, reduce_1d, bucket_elems: int
                    ) -> jnp.ndarray:
    """Apply ``reduce_1d`` (an aligned all-reduce of a 1D buffer) per
    fixed-size bucket via ``lax.scan``.

    The scan carries nothing — buckets are independent — so on TPU the
    latency-hiding scheduler is free to overlap bucket k's collective
    with bucket k+1's quantize/dequantize compute (the NCCL-bucket
    overlap, expressed in XLA scheduling rather than streams).  The
    scheduling upper bound on overlap efficiency is ``1 - 1/nbuckets``
    of the non-first-bucket comm hidden.  ``flat`` is padded up to a
    whole number of buckets internally and sliced back on return.
    """
    if bucket_elems <= 0 or flat.shape[0] <= bucket_elems:
        return reduce_1d(flat)
    padded = _pad_flat(flat, bucket_elems)
    nb = padded.shape[0] // bucket_elems
    bod = padded.reshape(nb, bucket_elems)

    def body(carry, bucket):
        return carry, reduce_1d(bucket)

    _, out = jax.lax.scan(body, 0, bod)
    return out.reshape(-1)[:flat.shape[0]]


# ------------------------------------------------- tree-level entrypoint
def hierarchical_all_reduce_tree(grads, axis_name: str, h: Hierarchy, *,
                                 bits: int = 8, codec: str = "blockwise",
                                 bucket_elems: int = 0):
    """Drop-in ``reduce_fn`` for ``comm_compress.local_grad_shardmap``:
    ravel the grad tree, (optionally) bucket it, run the two-level
    quantized all-reduce, and unflatten with each leaf RESTORED to its
    original dtype (bf16 grads come back bf16 — the flat path's
    widening bug does not exist here)."""
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    if not leaves:
        return grads
    flat = jnp.concatenate(
        [l.astype(jnp.float32).reshape(-1) for l in leaves])
    n = flat.shape[0]
    unit = h.world * codec_unit(codec)
    padded = _pad_flat(flat, unit)

    reduce_1d = functools.partial(hierarchical_all_reduce,
                                  axis_name=axis_name, h=h, bits=bits,
                                  codec=codec)
    red = bucketed_reduce(padded, reduce_1d, bucket_elems)

    out, off = [], 0
    for l in leaves:
        out.append(red[off:off + l.size].reshape(l.shape).astype(l.dtype))
        off += l.size
    return jax.tree_util.tree_unflatten(treedef, out)


# --------------------------------------------------- hpZ weight gather
def hpz_weight_gather(row: jnp.ndarray, axis_name: str, h: Hierarchy, *,
                      bits: int = 8, num_groups: int = 1,
                      secondary: Optional[Tuple] = None):
    """qwZ all-gather through the hierarchy: quantize ONCE, gather int8
    over the inter group ([inter, ...] — this payload is the hpZ
    secondary shard), gather that over the intra group, dequantize,
    and reorder to flat rank order.  Returns ``(gathered, secondary)``.

    Passing a previous call's ``secondary`` back in skips the inter
    hop entirely — the hpZ trade: after the first gather of a step,
    every node holds the full int8 weight spread across its intra
    group, so re-gathers are intra-node only.

    Bit-exact vs ``quantized_all_gather(row, axis)``: the int8 values
    and scales are produced before any wire hop on the same grid, so
    the dequantized result is identical element-for-element, rows in
    the same rank order.
    """
    if h.flat:
        return quantized_all_gather(row, axis_name, bits=bits,
                                    num_groups=num_groups), None
    inter = [list(g) for g in h.inter_groups]
    intra = [list(g) for g in h.intra_groups]
    if secondary is None:
        q, s, _ = quantize(row, bits=bits, num_groups=num_groups)
        qg = jax.lax.all_gather(q, axis_name, axis_index_groups=inter)
        sg = jax.lax.all_gather(s, axis_name, axis_index_groups=inter)
        secondary = (qg, sg)
    qg, sg = secondary
    qk = jax.lax.all_gather(qg, axis_name, axis_index_groups=intra)
    sk = jax.lax.all_gather(sg, axis_name, axis_index_groups=intra)
    # [k, m, ...] indexed [intra j][node n] -> dequant -> [m, k, ...]
    deq = jax.vmap(jax.vmap(
        lambda qq, ss: dequantize(qq, ss, bits=bits)))(qk, sk)
    deq = jnp.swapaxes(deq, 0, 1)
    # rank r = n*k + j lands at position r of the leading dim
    return deq.reshape((h.world,) + row.shape), secondary


# ------------------------------------------------------ wire accounting
def wire_bytes_per_device(n_elems: int, h: Hierarchy, *, bits: int = 8,
                          codec: str = "blockwise") -> Dict[str, Any]:
    """Analytic per-device wire bytes for ONE all-reduce of ``n_elems``
    f32 elements under each scheme — the numbers the ``comm_*``
    counters and COMM_BENCH stamp (deterministic: tree size is static,
    so this is device truth for payload bytes, not an estimate).

    int8 payload is 1 byte/elem regardless of ``bits`` (sub-8-bit
    rides an int8 container, as in ops/quant.py); each codec unit adds
    a 4-byte f32 scale.
    """
    W, k, m = h.world, h.intra, h.inter
    U = codec_unit(codec)
    per = 4.0 if codec == "exact" else 1.0 + 4.0 / U
    n = float(n_elems)
    flat_f32 = 2.0 * (W - 1) / W * 4.0 * n
    flat_q = 2.0 * (W - 1) / W * per * n
    if h.flat:
        hier_total, hier_inter = flat_q, flat_q
    else:
        intra_bytes = 2.0 * (k - 1) / k * per * n          # RS + AG
        inter_bytes = 2.0 * (m - 1) / m * per * (n / k)    # RS + AG
        hier_total = intra_bytes + inter_bytes
        hier_inter = inter_bytes
    if codec == "exact":
        int8_part, f32_part = 0.0, hier_total
    else:
        int8_part = hier_total / per           # 1 byte/elem payload
        f32_part = hier_total - int8_part      # the scales
    return {
        "elems": int(n_elems), "world": W, "intra": k, "inter": m,
        "codec": codec, "bits": int(bits),
        "flat_f32_bytes": flat_f32,
        "flat_quant_bytes": flat_q,
        "hier_quant_bytes": hier_total,
        "hier_quant_inter_bytes": hier_inter,
        "hier_int8_payload_bytes": int8_part,
        "hier_f32_payload_bytes": f32_part,
        "ratio_vs_f32": flat_f32 / hier_total if hier_total else 0.0,
        "inter_ratio_vs_f32": (flat_f32 / hier_inter) if hier_inter else 0.0,
    }


# --------------------------------------------- serving wire (H2D / TP)
def quantize_for_wire(x: jnp.ndarray, bits: int = 8):
    """Host-side pack of one weight leaf for quantized placement
    (TP replica upload, ZeRO-Inference layer broadcast): int8 payload
    in the LEAF'S OWN SHAPE (so the leaf's PartitionSpec applies to it
    unchanged) + f32 scales (tiny, replicated).  Block-count picks the
    v2 grid when the size divides ``BLOCK_ELEMS``, else one per-tensor
    scale — coarser, but the serving_rtol gate covers it.  Returns
    ``(q, scale, orig_dtype)``."""
    g = x.size // BLOCK_ELEMS if (x.size and x.size % BLOCK_ELEMS == 0) \
        else 1
    q, s, _ = quantize(jnp.asarray(x), bits=bits, num_groups=g)
    return q, s, x.dtype


def dequantize_from_wire(q: jnp.ndarray, scale: jnp.ndarray, dtype,
                         bits: int = 8) -> jnp.ndarray:
    """Device-side unpack of :func:`quantize_for_wire`."""
    return dequantize(q, scale, bits=bits, dtype=dtype)


def quantize_for_wire_np(x: np.ndarray, bits: int = 8
                         ) -> Tuple[np.ndarray, np.ndarray, Any]:
    """Numpy twin of :func:`quantize_for_wire` — the pack runs on the
    HOST so the H2D transfer itself carries int8 codes + f32 scales
    (quantizing a device-resident array would ship the full-precision
    leaf first and save nothing on the link).  Same grid and rounding
    as :func:`~deepspeed_tpu.ops.quant.quantize` symmetric mode, so
    :func:`dequantize_from_wire` unpacks it on device unchanged."""
    a = np.asarray(x)
    g = a.size // BLOCK_ELEMS if (a.size and a.size % BLOCK_ELEMS == 0) \
        else 1
    bound = INT_BOUNDS[bits]
    grouped = a.astype(np.float32).reshape(g, -1)
    scale = np.abs(grouped).max(axis=1) / bound
    scale = np.where(scale == 0, 1.0, scale).astype(np.float32)
    q = np.clip(np.round(grouped / scale[:, None]), -bound,
                bound).astype(np.int8)
    return q.reshape(a.shape), scale, a.dtype

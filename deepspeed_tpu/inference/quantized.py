"""Weight-only quantized inference (ref: deepspeed/inference
``init_inference(dtype=torch.int8)`` + module_inject's quantized kernel
variants, and the quantizer op family under deepspeed/ops/quantizer).

TPU design: weights live in HBM as int8 (+ per-group scales) — half the
bf16 residency, so a model twice the size fits one chip — and the
dequantize is traced INTO the jitted forward where XLA can fuse the
convert-and-scale with each weight's consumer.  The residency halving
is unconditional; the decode-bandwidth halving depends on XLA fusing
the dequant into the dot's operand read rather than materializing a
bf16 temp (to be pinned down with an on-chip microbench before any
speedup claim is made).
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.quant import dequantize, quantize


class QuantizedTensor(NamedTuple):
    """A group-quantized weight: int8 codes + per-group scales.

    Groups are contiguous runs along the LAST axis, so the scale is
    stored ``q.shape[:-1] + (groups_per_row,)`` — the same leading dims
    as the weight.  That makes the scale shard with the weight under
    tensor parallelism: the weight's own PartitionSpec applies to the
    scale directly (any axis the grouped shape can't honor falls back to
    replication — see :func:`shard_quantized`).
    """

    q: jnp.ndarray          # int8, original shape
    scale: jnp.ndarray      # f32, q.shape[:-1] + (groups_per_row,)

    @property
    def shape(self):
        return self.q.shape

    @property
    def dtype(self):        # for sharding/spec helpers that probe dtype
        return self.q.dtype


def _is_qt(x) -> bool:
    return isinstance(x, QuantizedTensor)


def _pick_groups(leaf, group_size: int) -> int:
    """Number of groups for ``leaf``: the widest divisor of the LAST dim
    that is ≤ ``group_size`` (so every group sits inside one row and the
    scale reshapes to ``leaf.shape[:-1] + (-1,)``).  A last dim with no
    usable divisor (e.g. prime) degrades to one group per row — wider
    than requested, so warn when it is much wider."""
    n = leaf.size
    last = leaf.shape[-1] if leaf.ndim else n
    gs = min(max(group_size, 1), last)
    while last % gs:
        gs -= 1
    if gs * 8 <= group_size:
        # degenerate factorization: per-element-ish groups would burn 4
        # scale bytes per weight byte — per-row groups cost less and
        # match the reference's row-granularity fallback
        gs = last
    if gs > 8 * group_size:
        from deepspeed_tpu.utils.logging import logger

        logger.warning(
            "int8 quantization of a %s-shaped weight uses groups of "
            "%d elements (requested %d) — expect elevated "
            "quantization error", leaf.shape, gs, group_size)
    return n // gs


def quantize_params(params: Any, *, bits: int = 8, group_size: int = 128,
                    min_ndim: int = 2, skip_paths=()) -> Any:
    """Quantize every floating leaf with ``ndim >= min_ndim`` (weights —
    unstacked norm gains and other vectors stay exact) to int8 groups.

    ``skip_paths``: leaf key names kept exact regardless of ndim — a
    STACKED tree's per-layer vectors ([L, d] norm gains, biases) pass
    the ndim gate looking like matrices, so a family's record must name
    them (the reference's weight-only quantization likewise touches only
    the matmul weights)."""
    if bits != 8:
        raise NotImplementedError("weight-only inference quant: int8 only")
    skip = set(skip_paths)

    def one(path, leaf):
        leaf = jnp.asarray(leaf)
        name = str(path[-1].key) if path and hasattr(path[-1], "key") \
            else ""
        if name in skip or leaf.ndim < min_ndim or \
                not jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf
        q, scale, _ = quantize(leaf, bits=8,
                               num_groups=_pick_groups(leaf, group_size))
        return QuantizedTensor(q=q, scale=scale.reshape(
            leaf.shape[:-1] + (-1,)))

    return jax.tree_util.tree_map_with_path(one, params)


def dequantize_params(params: Any, dtype=jnp.bfloat16) -> Any:
    """Inverse of :func:`quantize_params`; traced into the forward jit so
    the convert fuses into each weight's consuming op."""
    def one(leaf):
        if _is_qt(leaf):
            return dequantize(leaf.q, leaf.scale, dtype=dtype)
        return leaf

    return jax.tree.map(one, params, is_leaf=_is_qt)


def quantized_apply(apply_fn, dtype=jnp.bfloat16):
    """Wrap a pure ``apply_fn(params, *args)`` to accept quantized params."""
    def fn(qparams, *args, **kw):
        return apply_fn(dequantize_params(qparams, dtype), *args, **kw)

    return fn


def quantize_for_inference(params: Any, *apply_fns,
                           weight_dtype: str = "int8",
                           group_size: int = 128, dtype=jnp.bfloat16,
                           skip_paths=()):
    """One-stop weight-only quantization for an inference path: validates
    ``weight_dtype``, quantizes the params, and wraps every forward fn.
    Returns ``(qparams, wrapped_fn, ...)``.  Shared by
    :class:`~deepspeed_tpu.inference.engine.InferenceEngine` and the
    serving builders so validation and knobs cannot drift."""
    if weight_dtype != "int8":
        raise NotImplementedError(
            f"weight-only quantized inference supports 'int8' only, got "
            f"{weight_dtype!r}")
    qparams = quantize_params(params, group_size=group_size,
                              skip_paths=skip_paths)
    return (qparams, *[quantized_apply(f, dtype) for f in apply_fns])


def shard_quantized(qparams: Any, specs: Any, mesh) -> Any:
    """Place a (possibly partially) quantized param tree on ``mesh``.

    Exact leaves and int8 codes take the weight's own PartitionSpec; the
    per-row scale takes the SAME spec — its leading dims are the
    weight's — except any axis whose grouped extent the mesh can't
    divide evenly, which is replicated instead (scales are tiny, so a
    replicated axis costs ~nothing).  This is the composition the
    reference's module_inject performs when int8 kernels are injected
    into TP-sharded layers (ref: deepspeed/module_inject/
    replace_module.py + ops/quantizer).
    """
    from jax.sharding import PartitionSpec as P

    def _scale_spec(spec, scale):
        out = []
        for k, ax in enumerate(tuple(spec)[:scale.ndim]):
            names = (ax,) if isinstance(ax, str) else tuple(ax or ())
            w = 1
            for nm in names:
                w *= mesh.size(nm)
            out.append(ax if w > 1 and scale.shape[k] % w == 0 else None)
        return P(*out)

    def put(leaf, spec):
        if _is_qt(leaf):
            return QuantizedTensor(
                q=jax.device_put(leaf.q, mesh.sharding(spec)),
                scale=jax.device_put(
                    leaf.scale,
                    mesh.sharding(_scale_spec(spec, leaf.scale))))
        return jax.device_put(jnp.asarray(leaf), mesh.sharding(spec))

    return jax.tree.map(put, qparams, specs, is_leaf=_is_qt)


def quantization_error(params: Any, qparams: Any) -> float:
    """Max relative L2 error across quantized leaves (diagnostics)."""
    worst = 0.0
    for a, b in zip(jax.tree.leaves(params),
                    jax.tree.leaves(qparams, is_leaf=_is_qt)):
        if _is_qt(b):
            d = dequantize(b.q, b.scale, dtype=jnp.float32)
            num = float(jnp.linalg.norm(a.astype(jnp.float32) - d))
            den = float(jnp.linalg.norm(a.astype(jnp.float32))) or 1.0
            worst = max(worst, num / den)
    return worst

"""Inference engine (ref: deepspeed/inference/engine.py InferenceEngine).

The reference wraps a torch module, injects fused kernels
(module_inject) and shards weights across GPUs (``mp_size``).  Here the
engine jits the model's apply function over the mesh with TP shardings;
generation (KV cache, prefill/decode split, sampling) lands with the
model families — this core provides the forward path and the
``init_inference`` entrypoint contract.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax

from deepspeed_tpu import precision
from deepspeed_tpu.config import Config, PrecisionConfig
from deepspeed_tpu.topology import MeshSpec, default_mesh
from deepspeed_tpu.zero import SpecTree, param_shardings


class InferenceEngine:
    """Jitted forward over sharded params.

    ``apply_fn(params, *inputs)`` is the model's pure forward function.
    """

    def __init__(self, apply_fn: Callable, params: Any,
                 mesh: Optional[MeshSpec] = None,
                 param_specs: SpecTree = None,
                 dtype: str = "bfloat16", quant_group_size: int = 128):
        self.mesh = mesh or default_mesh()
        placed = None
        if dtype == "int8":
            # weight-only quantization (ref: init_inference(dtype=int8)):
            # int8 codes + group scales resident in HBM, dequant traced
            # into the forward so it fuses with each weight's consumer
            from deepspeed_tpu.inference.quantized import (
                quantize_for_inference, shard_quantized)
            from deepspeed_tpu.zero import resolve_specs

            # resolve TP specs against the ORIGINAL tree: after
            # quantization the leaves are (codes, scales) pairs
            specs = (None if param_specs is None
                     else resolve_specs(params, param_specs))
            params, apply_fn = quantize_for_inference(
                params, apply_fn, group_size=quant_group_size)
            if specs is not None:
                # int8 composes with TP: codes take the weight's spec,
                # per-row scales shard alongside (ref: module_inject's
                # int8 + mp_size injection)
                placed = shard_quantized(params, specs, self.mesh)
        else:
            pcfg = PrecisionConfig(dtype=dtype)
            params = precision.cast_for_compute(params, pcfg)
        self.apply_fn = apply_fn
        if placed is None:
            # reached with dtype != int8, or int8 + no specs (the int8 +
            # specs case produced `placed` above)
            shardings = param_shardings(params, self.mesh, stage=0,
                                        param_specs=param_specs)
            placed = jax.jit(lambda p: p, out_shardings=shardings)(params)
        self.params = placed

        def fwd(p, *inputs):
            # publish this engine's mesh at trace time (model code may read
            # current_mesh() for ring/ulysses/MoE sharded ops)
            from deepspeed_tpu import topology as _topo

            _topo.set_current_mesh(self.mesh)
            return apply_fn(p, *inputs)

        self._fwd = jax.jit(fwd)

    def __call__(self, *inputs):
        return self._fwd(self.params, *inputs)

    def forward(self, *inputs):
        return self(*inputs)


def init_inference(model: Any = None, *, apply_fn: Optional[Callable] = None,
                   params: Any = None, config: Any = None,
                   mesh: Optional[MeshSpec] = None,
                   param_specs: SpecTree = None,
                   dtype: str = "bfloat16", quant_group_size: int = 128,
                   **_compat) -> InferenceEngine:
    """ref: deepspeed.init_inference(model, config…) → engine.

    ``model`` may be an object with ``.apply``/``.params`` (flax-style) or
    pass ``apply_fn`` + ``params`` explicitly.
    """
    if isinstance(config, dict):
        config = Config.from_dict(config)
    if apply_fn is None:
        if model is None or not hasattr(model, "apply"):
            raise ValueError("provide apply_fn+params or a model with .apply")
        apply_fn = model.apply
        params = params if params is not None else getattr(model, "params", None)
    if params is None:
        raise ValueError("init_inference requires params")
    return InferenceEngine(apply_fn, params, mesh=mesh,
                           param_specs=param_specs, dtype=dtype,
                           quant_group_size=quant_group_size)


def serving_mesh_from_config(config: Any) -> Optional[MeshSpec]:
    """Resolve the serving TP mesh from a config ``mesh`` block.

    Serving shards params/KV over the ``model`` (TP) and ``expert``
    (EP) axes; the ``data`` axis is a training concept (one replica
    serves its whole batch), so a ``data: -1`` left at its default is
    read as 1 here and the engine spans exactly
    ``pipe*expert*seq*model`` devices from the front of
    ``jax.devices()`` — e.g. ``{"mesh": {"model": 2}}`` builds a
    2-device TP replica no matter how many chips the host exposes
    (the fleet hands later device slices to later replicas).  Returns
    None when every non-data axis is 1 (the single-device engine)."""
    mc = config.mesh
    sizes = {"pipe": mc.pipe, "data": mc.data, "expert": mc.expert,
             "seq": mc.seq, "model": mc.model}
    if sizes["data"] not in (1, -1):
        # a reused training config: data parallelism is meaningless for
        # one serving replica (the fleet is the data axis here), so an
        # explicit data>1 must not multiply the device demand 8x or
        # trip the device-count check on a small host
        from deepspeed_tpu.utils.logging import logger

        logger.warning(
            "serving mesh: ignoring mesh.data=%s — one serving replica "
            "has no data axis (replicate via the fleet instead)",
            sizes["data"])
    sizes["data"] = 1
    if all(int(v) <= 1 for v in sizes.values()):
        return None
    total = 1
    for v in sizes.values():
        total *= int(v)
    devs = jax.devices()
    if total > len(devs):
        raise ValueError(
            f"serving mesh {sizes} needs {total} devices, host exposes "
            f"{len(devs)}")
    return MeshSpec.build(sizes, devices=devs[:total])


_SERVING_BLOCKS_IF_ENABLED = (
    "zero_inference", "prefix_cache", "kv_tier", "speculative", "slo",
    "faults", "history", "incidents")


def init_serving(params, model_config, *, config: Any = None,
                 mesh: Optional[MeshSpec] = None, **kw):
    """Serving counterpart of :func:`init_inference` (ref: the reference
    serves through ``init_inference`` + DeepSpeed-MII's serve loop):
    build the continuous-batching engine for a model-family config,
    honoring a DeepSpeed-style JSON config.

    A ``mesh`` block in ``config`` builds a TP/EP-sharded serving
    replica (see :func:`serving_mesh_from_config` for how the axis
    sizes are read); an explicit ``mesh=`` kw still wins.

    A ``zero_inference`` block in ``config`` routes to the weight-
    streamed ZeRO-Inference engine
    (:mod:`deepspeed_tpu.inference.zero_inference`): layer weights live
    on a host/NVMe tier and stream double-buffered through a bounded
    HBM working set, so the served weight image may exceed HBM.  Its
    ``dtype`` field (e.g. ``int8``) overrides ``weight_dtype``.

    A ``prefix_cache`` block enables automatic prefix caching on the
    paged-KV path: full KV pages are content-addressed, prompts sharing
    a page-aligned prefix with earlier traffic skip that prefix's
    prefill compute, and freed pages stay warm until allocation
    pressure reclaims them (token-identical on/off).

    A ``speculative`` block enables draft-and-verify multi-token
    decoding (:mod:`deepspeed_tpu.inference.speculative`): each decode
    iteration drafts up to K cheap tokens per slot, verifies all K+1
    positions in one batched forward, and keeps the accepted span —
    greedy outputs token-identical on/off, and under ``zero_inference``
    one verify sweep amortizes one full layer-weight stream over the
    whole accepted span.

    Remaining ``kw`` (``max_batch``, ``page_size``, ``num_pages``,
    ``decode_chunk``, ``prefill_chunk``, ``weight_dtype``,
    ``prefix_cache``, ``admit_lookahead``, …) pass through to the
    builder (:func:`~deepspeed_tpu.inference.serving.serving_engine`).
    """
    from deepspeed_tpu.inference.serving import serving_engine

    if isinstance(config, dict):
        config = Config.from_dict(config)
    if mesh is None and config is not None:
        # `mesh` block → TP/EP-sharded serving replica (an explicit
        # mesh= kw still wins); see serving_mesh_from_config for the
        # serving reading of the axis sizes
        mesh = serving_mesh_from_config(config)
    if config is not None:
        # a config block → the builder's keyword of the same name (an
        # explicit keyword still wins).  These pass only when enabled
        # (kv_tier requires the prefix_cache block — the engine
        # validates; a model drafter instance rides the separate
        # drafter= kw; faults is a TEST facility — see CONFIG.md) ...
        for block in _SERVING_BLOCKS_IF_ENABLED:
            if getattr(config, block).enabled:
                kw.setdefault(block, getattr(config, block))
        # ... and these always: telemetry and tracing carry their own
        # enabled flag into the engine
        for block in ("telemetry", "tracing"):
            kw.setdefault(block, getattr(config, block))
    return serving_engine(params, model_config, mesh=mesh, **kw)

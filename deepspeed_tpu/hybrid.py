"""Hybrid train+generate engine for RLHF loops (ref:
deepspeed/runtime/hybrid_engine.py DeepSpeedHybridEngine).

The reference exists because torch-DeepSpeed has two incompatible worlds:
ZeRO-3 training keeps each parameter partitioned behind hooks, while fast
generation wants gathered weights laid out for the inference kernels.
DeepSpeedHybridEngine flips between them around every RLHF rollout —
gather partitions, re-shard to inference TP, run injected kernels, then
restore the training layout (``eval()``/``train()`` mode switching, weight
re-sharding, inference-cache management).

On TPU none of that machinery exists, by construction: master params live
in ZeRO/TP ``NamedSharding`` buffers, and BOTH compiled programs — the
train step and the prefill/decode pair — consume those same buffers.  XLA
inserts the stage-3 all-gathers at use inside generation exactly as it
does inside the training forward, overlapped with compute on ICI.  "Mode
switching" is therefore the identity: :meth:`HybridEngine.generate` is
just a second jit over the live ``engine.state.params``, with the cast to
the compute dtype traced into the program (no host-side copy, no
re-layout, no extra HBM residency beyond the KV cache).

Config parity: the ``hybrid_engine`` JSON block is accepted.  ``enabled``
and ``max_out_tokens`` are honored; ``inference_tp_size`` is validated
against the mesh's model axis (the TP layout is shared with training, so
it cannot differ); ``release_inference_cache`` / ``pin_parameters`` /
``tp_gather_partition_size`` describe machinery the TPU design deletes —
they are accepted and logged as no-ops, never silently dropped.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from deepspeed_tpu import precision
from deepspeed_tpu.inference.generation import generate_loop
from deepspeed_tpu.utils.logging import logger


class HybridEngine:
    """Wrap a :class:`~deepspeed_tpu.engine.TrainingEngine` with a
    generation path over the SAME sharded parameters.

    prefill_fn/decode_fn: ``(params, tokens, cache) -> (logits, cache)``
    with params in the COMPUTE dtype (the cast from the master dtype is
    traced in here).  alloc_cache: ``(batch, max_seq) -> cache``.

    Typical RLHF iteration (ref: DeepSpeed-Chat ppo_trainer)::

        rollout = hybrid.generate(prompts, max_new_tokens=..., temperature=1.0)
        ...score rollout, build PPO batch...
        loss = hybrid.train_batch(ppo_batch)     # delegates to the engine
    """

    def __init__(self, engine, prefill_fn: Callable, decode_fn: Callable,
                 alloc_cache: Callable, *, eos_token_id: Optional[int] = None,
                 max_out_tokens: Optional[int] = None):
        self.engine = engine
        self.eos = eos_token_id
        self.max_out_tokens = max_out_tokens
        if getattr(engine, "grad_comm_mode", None) == "qwz":
            raise ValueError(
                "hybrid_engine does not compose with zero_quantized_weights "
                "— the qwZ engine stores master params as one flat "
                "[world, chunk] buffer, not a model pytree; drop the qwZ "
                "flag for RLHF or export via engine.module_params()")
        if not hasattr(engine, "state"):
            raise ValueError(
                "hybrid_engine needs a TrainingEngine (live sharded "
                f"TrainState); got {type(engine).__name__} — the scheduled "
                "Infinity engine streams its state through host/NVMe and "
                "cannot serve rollouts from it")
        cdt = precision.compute_dtype(engine.config.precision)

        def cast(p):
            return jax.tree.map(
                lambda x: x.astype(cdt)
                if jnp.issubdtype(x.dtype, jnp.floating) else x, p)

        # donate the cache so decode updates pages/slots in place in HBM
        self._prefill = jax.jit(
            lambda p, t, c: prefill_fn(cast(p), t, c), donate_argnums=(2,))
        self._decode = jax.jit(
            lambda p, t, c: decode_fn(cast(p), t, c), donate_argnums=(2,))
        self._alloc = alloc_cache

    # ------------------------------------------------------------- training
    def train_batch(self, batch):
        return self.engine.train_batch(batch)

    def eval_batch(self, batch):
        return self.engine.eval_batch(batch)

    def __getattr__(self, name):
        # engine passthrough (step/backward/save_checkpoint/metrics/...);
        # 'engine' itself must miss cleanly or pickle/copy dunder probes
        # on a not-yet-initialized instance would recurse forever
        if name == "engine":
            raise AttributeError(name)
        return getattr(self.engine, name)

    # ------------------------------------------------------------- rollout
    def generate(self, tokens, max_new_tokens: int = 32,
                 temperature: float = 1.0, top_k: int = 0, top_p: float = 1.0,
                 rng: Optional[jax.Array] = None,
                 max_seq: Optional[int] = None):
        """tokens: [B, T] prompts → [B, T + max_new_tokens] rollouts,
        sampled from the CURRENT training params (no staleness — this
        reads ``engine.state.params`` live)."""
        if max_seq is None and self.max_out_tokens is not None:
            max_seq = self.max_out_tokens
        # overrun vs the cache budget raises inside generate_loop
        return generate_loop(
            self.engine.state.params, self._prefill, self._decode,
            self._alloc, tokens, max_new_tokens=max_new_tokens,
            temperature=temperature, top_k=top_k, top_p=top_p, rng=rng,
            max_seq=max_seq, eos=self.eos)


def _hybrid_block(config) -> dict:
    hb = dict((config.raw or {}).get("hybrid_engine", {}))
    if not hb.get("enabled", True):
        raise ValueError(
            "hybrid_engine.enabled is false in the config — remove the "
            "flag (or set it true) before building a HybridEngine")
    for key in ("release_inference_cache", "pin_parameters",
                "tp_gather_partition_size"):
        if key in hb:
            logger.info(
                "hybrid_engine.%s: accepted no-op — the TPU engine never "
                "re-lays-out weights between train and generate, so there "
                "is no cache to release or partition to gather", key)
    return hb


def llama_hybrid_engine(engine, cfg, *, eos_token_id: Optional[int] = None,
                        cache_dtype=jnp.bfloat16) -> HybridEngine:
    """Build a :class:`HybridEngine` over models/llama.py weights.

    ``engine`` must hold llama params (the pytree from
    :func:`~deepspeed_tpu.models.llama.init_params`); ``cfg`` is its
    :class:`~deepspeed_tpu.models.llama.LlamaConfig`.
    """
    hb = _hybrid_block(engine.config)
    tp = int(hb.get("inference_tp_size", 0) or 0)
    if tp and tp != engine.mesh.size("model"):
        raise ValueError(
            f"hybrid_engine.inference_tp_size={tp} differs from the mesh's "
            f"model axis ({engine.mesh.size('model')}); the TPU hybrid "
            "engine shares one TP layout between training and generation "
            "— set the mesh model axis instead")

    from deepspeed_tpu.inference.generation import cached_step_alloc

    step, alloc = cached_step_alloc(cfg, cache_dtype)
    return HybridEngine(
        engine, step, step, alloc, eos_token_id=eos_token_id,
        max_out_tokens=hb.get("max_out_tokens"))

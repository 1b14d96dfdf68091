"""What a decoder family is, stated once.

A family's file (``gpt2.py``, ``llama.py``, ``mixtral.py``) ends with its
``FAMILY = DecoderFamily(...)``: the pieces of one transformer layer and
the facts a serving build needs.  Everything that serves, streams, drafts
or generates (the ``inference`` package) reads the record through
:func:`decoder_family`; nothing here or in a family's file imports from
``inference``.  A new family is its own file plus one name in
``_FAMILY_MODULES``.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Callable, Optional, Tuple

import jax.numpy as jnp


def _no_check(cfg, mesh, max_seq) -> None:
    return None


@dataclasses.dataclass(frozen=True)
class DecoderFamily:
    """The hooks of one decoder family (an author's fields, not options).

    ``embed(params, tokens, start, cfg) -> (x, ctx)``: token (and
    learned position) embeddings, plus what every layer needs of the
    positions (``(cos, sin)`` under RoPE, ``()`` otherwise); ``start``
    is where ``tokens[:, 0]`` stands, see :func:`positions_from`.
    ``qkv(cfg, x, lp, *ctx) -> (q, k, v)``: ``[B, T, H, Dh]`` and
    ``[B, T, KV, Dh]``.  ``out(cfg, x, attn, lp) -> x``: the attention
    output projection and the FFN half, each with its residual;
    ``attn`` is ``[B, T, H * Dh]``.  ``head(params, x, cfg) -> logits``
    ``[B, T, V]`` f32.  Each hook names its own ``jax.named_scope``s.
    """

    config_type: type
    embed: Callable[..., Tuple[Any, tuple]]
    qkv: Callable[..., Tuple[Any, Any, Any]]
    out: Callable[..., Any]
    head: Callable[..., Any]
    # cfg -> PartitionSpec tree of the params, over the mesh's named axes
    param_specs: Callable[[Any], Any]
    # path fragments of the leaves weight-only quantization leaves exact
    quant_skip_paths: Tuple[str, ...]
    # the mesh axes whose size > 1 makes a serving build a sharded one
    shard_axes: Tuple[str, ...] = ("model",)
    # raises for a (cfg, mesh, max_seq) the family cannot serve
    check: Callable[[Any, Any, int], None] = _no_check
    # cfg -> rows of a learned position table; None: positions are free
    max_positions: Callable[[Any], Optional[int]] = lambda cfg: None
    # cfg -> (stem keys, head keys) of the params that stay on the device
    # under weight streaming, the stacked ``blocks`` streaming between
    # them; None: the family has no streamed split
    streamed_split: Optional[Callable[[Any], Tuple[tuple, tuple]]] = None

    @property
    def name(self) -> str:
        return self.config_type.__name__

    def sharded(self, mesh) -> bool:
        return mesh is not None and any(
            mesh.size(ax) > 1 for ax in self.shard_axes)


def positions_from(start, T: int):
    """Absolute positions of ``T`` tokens from ``start``: ``[T]`` from a
    scalar (one contiguous cache), ``[B, T]`` from per-row ``[B]`` offsets
    (ragged frontiers under continuous batching place each row at ITS
    length, not row 0's)."""
    if jnp.ndim(start) == 0:
        return start + jnp.arange(T, dtype=jnp.int32)
    return start[:, None] + jnp.arange(T, dtype=jnp.int32)[None]


# the registry: one module name a family
_FAMILY_MODULES = ("gpt2", "llama", "mixtral")


def decoder_families() -> Tuple[DecoderFamily, ...]:
    return tuple(
        importlib.import_module(f"deepspeed_tpu.models.{m}").FAMILY
        for m in _FAMILY_MODULES)


def decoder_family(cfg) -> DecoderFamily:
    """The record of ``cfg``'s family, by ``type(cfg)``."""
    fams = decoder_families()
    for fam in fams:
        if isinstance(cfg, fam.config_type):
            return fam
    raise TypeError(
        f"{type(cfg).__name__} is not a decoder family's config; "
        f"supported: {', '.join(f.name for f in fams)}")

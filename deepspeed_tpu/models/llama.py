"""Llama model family, TPU-first (flagship; SURVEY.md §2 #37).

Reference behavior: the DeepSpeed examples' Megatron-GPT / HF-Llama
training paths (ref: deepspeed/module_inject/containers/llama.py for the
module structure the reference injects into).

TPU-first design decisions:
- **Stacked layers + ``lax.scan``**: all transformer blocks' params are
  stacked on a leading ``[L, ...]`` axis and the forward is a scan over
  that axis.  One block gets compiled once (fast XLA compiles at depth),
  and the stacked layout is exactly what pipeline parallelism shards.
- **bf16 compute, f32 accumulation**: matmuls carry
  ``preferred_element_type=float32`` where accuracy matters (logits, att
  softmax) and bf16 elsewhere, keeping the MXU fed.
- **TP via spec tree**: ``param_specs()`` returns column-parallel
  (attn qkv, mlp in) / row-parallel (attn out, mlp out) PartitionSpecs
  over the ``model`` axis — XLA inserts the psum the Megatron pattern
  hand-codes.
- **GQA**: n_kv_heads <= n_heads with head-group broadcast.
- **Sequence axis ready**: activations carry a ``seq``-shardable layout;
  ring attention (``parallel/ring_attention.py``) plugs in via
  ``attn_impl="ring"``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.family import DecoderFamily, positions_from


@dataclasses.dataclass
class LlamaConfig:
    vocab_size: int = 32000
    dim: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 8
    ffn_dim: Optional[int] = None          # default 8/3 * dim rounded to 128
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    remat: str = "none"                    # none | full | save_dots
    loss_chunk: int = 0                    # >0: fused chunked-vocab CE
    # attn_impl="sparse": blocksparse attention from this dict (the
    # engine config's `sparse_attention` block — {"mode": ..., "block":
    # ..., ...}; see ops/sparse_attention.sparsity_config_from_dict)
    sparse_config: Optional[Dict[str, Any]] = None
    attn_impl: str = "auto"     # auto | flash | reference | ring | ulysses | sparse

    def __post_init__(self):
        if self.ffn_dim is None:
            self.ffn_dim = int(np.ceil(self.dim * 8 / 3 / 128) * 128)
        assert self.n_heads % self.n_kv_heads == 0
        assert self.dim % self.n_heads == 0

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @classmethod
    def llama3_8b(cls, **kw):
        return cls(vocab_size=128256, dim=4096, n_layers=32, n_heads=32,
                   n_kv_heads=8, ffn_dim=14336, rope_theta=500000.0, **kw)

    @classmethod
    def llama3_70b(cls, **kw):
        return cls(vocab_size=128256, dim=8192, n_layers=80, n_heads=64,
                   n_kv_heads=8, ffn_dim=28672, rope_theta=500000.0, **kw)

    @classmethod
    def tiny(cls, **kw):
        kw.setdefault("vocab_size", 256)
        kw.setdefault("dim", 64)
        kw.setdefault("n_layers", 2)
        kw.setdefault("n_heads", 4)
        kw.setdefault("n_kv_heads", 2)
        kw.setdefault("max_seq_len", 128)
        return cls(**kw)

    def flops_per_token(self) -> float:
        """Training FLOPs/token (fwd+bwd ≈ 6 * params + attention term)."""
        n = param_count(self)
        attn = 12 * self.n_layers * self.dim * self.max_seq_len  # qk^T + av
        return 6 * n + attn


def param_count(cfg: LlamaConfig) -> int:
    d, f, l = cfg.dim, cfg.ffn_dim, cfg.n_layers
    kvd = cfg.n_kv_heads * cfg.head_dim
    per_layer = (d * d) + (d * kvd) * 2 + (d * d) + (d * f) * 3 + 2 * d
    emb = cfg.vocab_size * d
    head = 0 if cfg.tie_embeddings else cfg.vocab_size * d
    return int(l * per_layer + emb + head + d)


# ---------------------------------------------------------------------- init
def init_params(rng: jax.Array, cfg: LlamaConfig,
                dtype=jnp.float32) -> Dict[str, Any]:
    k = jax.random.split(rng, 8)
    d, f, L = cfg.dim, cfg.ffn_dim, cfg.n_layers
    hd, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    s = lambda *sh: 1.0 / np.sqrt(sh[-2] if len(sh) > 1 else sh[-1])

    def w(key, *sh):
        return (jax.random.normal(key, sh) * s(*sh)).astype(dtype)

    params = {
        "embed": w(k[0], cfg.vocab_size, d),
        "blocks": {
            "attn_norm": jnp.ones((L, d), dtype),
            "wq": w(k[1], L, d, nh * hd),
            "wk": w(k[2], L, d, nkv * hd),
            "wv": w(k[3], L, d, nkv * hd),
            "wo": w(k[4], L, nh * hd, d),
            "mlp_norm": jnp.ones((L, d), dtype),
            "w1": w(k[5], L, d, f),   # gate
            "w3": w(k[6], L, d, f),   # up
            "w2": w(k[7], L, f, d),   # down
        },
        "final_norm": jnp.ones((d,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = w(jax.random.fold_in(rng, 99), d, cfg.vocab_size)
    return params


def param_specs(cfg: LlamaConfig, pipeline: bool = False) -> Dict[str, Any]:
    """Tensor-parallel shardings over the ``model`` axis (Megatron layout:
    column-parallel into the block, row-parallel out, psum inserted by XLA).
    Dim 0 of block leaves is the stacked layer axis → ``pipeline=True``
    shards it over the ``pipe`` axis (stage partitioning)."""
    col, row = P(None, None, "model"), P(None, "model", None)
    specs = {
        # feature-dim sharding: token gather stays local (vocab-dim sharding
        # makes XLA fall back to full rematerialization on the gather)
        "embed": P(None, "model"),
        "blocks": {
            "attn_norm": P(None, None),
            "wq": col, "wk": col, "wv": col, "wo": row,
            "mlp_norm": P(None, None),
            "w1": col, "w3": col, "w2": row,
        },
        "final_norm": P(None),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, "model")
    if pipeline:
        from deepspeed_tpu.parallel.pipeline import stage_spec

        specs["blocks"] = jax.tree.map(
            stage_spec, specs["blocks"],
            is_leaf=lambda x: x is None or isinstance(x, P))
    return specs


# ------------------------------------------------------------------- forward
def rms_norm(x, weight, eps):
    from deepspeed_tpu.ops.fused_ops import rms_norm as _rms

    return _rms(x, weight, eps)


def rope_tables(cfg: LlamaConfig, positions: jnp.ndarray):
    """positions: [T] (or [B, T] for per-sequence offsets) int32 →
    (cos, sin) [..., head_dim/2] in f32."""
    half = cfg.head_dim // 2
    freqs = cfg.rope_theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x, cos, sin):
    """x: [B, T, H, Dh]; rotate pairs (x1, x2) = (x[..., :half], x[..., half:]).

    cos/sin: [T, half] shared across the batch, or [B, T, half] per-sequence
    (paged decode with ragged frontiers)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:
        cos, sin = cos[None], sin[None]
    c = cos[:, :, None, :].astype(x.dtype)
    s = sin[:, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


_SPARSE_CACHE = {}


def _sparse_self_attention(cfg: LlamaConfig):
    """Per-config SparseSelfAttention (caches per-seqlen layouts so the
    O(H·nb²) host-side layout build does not rerun on every retrace)."""
    from deepspeed_tpu.ops.sparse_attention import (
        SparseSelfAttention, sparsity_config_from_dict)

    norm = tuple(sorted(
        (k, tuple(v) if isinstance(v, (list, tuple)) else v)
        for k, v in (cfg.sparse_config or {}).items()))
    key = (cfg.n_heads, norm)
    sa = _SPARSE_CACHE.get(key)
    if sa is None:
        sc = sparsity_config_from_dict(
            cfg.sparse_config or {}, cfg.n_heads,
            attention="unidirectional")               # causal LM default
        sa = _SPARSE_CACHE[key] = SparseSelfAttention(sc, causal=True)
    return sa


def _attention(q, k, v, cfg: LlamaConfig, segment_ids=None):
    """q: [B,T,H,Dh], k/v: [B,T,KV,Dh] → [B,T,H,Dh]."""
    impl = cfg.attn_impl
    if impl in ("ring", "ulysses"):
        from deepspeed_tpu.topology import current_mesh

        ms = current_mesh()
        if ms is not None and ms.size("seq") > 1:
            if impl == "ring":
                from deepspeed_tpu.parallel.ring_attention import (
                    ring_attention_sharded)

                return ring_attention_sharded(q, k, v, ms, causal=True,
                                              segment_ids=segment_ids)
            from deepspeed_tpu.parallel.sequence_parallel import (
                ulysses_attention_sharded)

            return ulysses_attention_sharded(q, k, v, ms, causal=True,
                                             segment_ids=segment_ids)
        impl = "auto"  # no seq axis in scope: plain attention
    if impl == "sparse":
        sa = _sparse_self_attention(cfg)   # cached per-config wrapper
        rep = cfg.n_heads // cfg.n_kv_heads
        kh = jnp.repeat(k, rep, axis=2) if rep > 1 else k
        vh = jnp.repeat(v, rep, axis=2) if rep > 1 else v
        out = sa(q.transpose(0, 2, 1, 3), kh.transpose(0, 2, 1, 3),
                 vh.transpose(0, 2, 1, 3), segment_ids=segment_ids)
        return out.transpose(0, 2, 1, 3)
    if impl in ("auto", "flash"):
        from deepspeed_tpu.ops.attention import flash_attention
        from deepspeed_tpu.topology import current_mesh

        # no except around this: a kernel the compiler refuses must
        # stop the trace, not turn into the reference unseen
        return flash_attention(q, k, v, causal=True,
                               segment_ids=segment_ids,
                               mesh=current_mesh())
    return reference_attention(q, k, v, causal=True, segment_ids=segment_ids)


def reference_attention(q, k, v, causal=True, segment_ids=None):
    """Plain jnp attention — the single numeric ground truth lives in
    ops/attention.py; re-exported here for model/test convenience."""
    from deepspeed_tpu.ops.attention import _reference

    return _reference(q, k, v, causal=causal, segment_ids=segment_ids)


def _qkv(cfg, x, lp, cos, sin):
    """Pre-norm + Q/K/V projections + RoPE → q [B, T, H, hd], k and v
    [B, T, KV, hd].  Shared by training's block and the family record
    (Mixtral's too) so the paths cannot drift."""
    B, T, _ = x.shape
    hd, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    with jax.named_scope("attn_qkv"):
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q = (h @ lp["wq"]).reshape(B, T, nh, hd)
        k = (h @ lp["wk"]).reshape(B, T, nkv, hd)
        v = (h @ lp["wv"]).reshape(B, T, nkv, hd)
        return apply_rope(q, cos, sin), apply_rope(k, cos, sin), v


def _out_ffn(cfg, x, attn, lp, tag=None):
    """Attention output projection + residual, then the SwiGLU FFN
    half.  ``attn``: [B, T, H*hd].  ``tag`` is training's
    ``checkpoint_name`` (remat)."""
    from deepspeed_tpu.ops.fused_ops import swiglu

    with jax.named_scope("attn_out"):
        x = x + attn @ lp["wo"]
    with jax.named_scope("mlp"):
        h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        mlp = swiglu(h, lp["w1"], lp["w3"])
        if tag is not None:
            mlp = tag(mlp, "mlp_out")
        return x + mlp @ lp["w2"]


def _head(params, x, cfg):
    """Final norm + LM head → logits [B, T, V] f32."""
    with jax.named_scope("final_norm"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    with jax.named_scope("lm_head"):
        return jnp.einsum("btd,dv->btv", x, lm_head(params, cfg),
                          preferred_element_type=jnp.float32)


def _block(cfg: LlamaConfig, x, layer_params, cos, sin, segment_ids):
    from jax.ad_checkpoint import checkpoint_name

    B, T, d = x.shape
    lp = layer_params
    q, k, v = _qkv(cfg, x, lp, cos, sin)
    with jax.named_scope("flash"):
        attn = _attention(q, k, v, cfg, segment_ids).reshape(
            B, T, cfg.n_heads * cfg.head_dim)
        attn = checkpoint_name(attn, "attn_out")  # remat.py save/offload tag
    return _out_ffn(cfg, x, attn, lp, tag=checkpoint_name)


def forward_hidden(params, tokens, cfg: LlamaConfig, positions=None,
                   segment_ids=None, n_micro: Optional[int] = None):
    """tokens: [B, T] int32 → final-norm hidden states [B, T, d] (the
    pre-LM-head activations; :func:`forward` adds the head projection,
    the chunked loss consumes these directly)."""
    from deepspeed_tpu import zero

    B, T = tokens.shape
    # under ZeRO-3 weights are gathered where they are used and the
    # activations stay on the batch axes (zero.py; the identity elsewhere)
    specs = param_specs(cfg)
    with jax.named_scope("embed"):
        embed = zero.gather_at_use(params["embed"], specs["embed"])
        x = zero.pin_to_batch(embed[tokens])  # [B, T, d]
        if positions is None:
            positions = jnp.arange(T, dtype=jnp.int32)
        cos, sin = rope_tables(cfg, positions)

    block = lambda x, lp: (_block(cfg, x, lp, cos, sin, segment_ids), None)
    from deepspeed_tpu.topology import current_mesh

    ms = current_mesh()
    if n_micro and ms is not None and ms.size("pipe") > 1:
        if segment_ids is not None:
            raise NotImplementedError(
                "packed segment_ids are not supported with "
                "pipeline-parallel microbatching: the block closure "
                "would capture the full-batch ids while pipelined_scan "
                "splits activations into microbatches — pipeline the "
                "batch without packing, or drop the pipe axis")
        from deepspeed_tpu.parallel.pipeline import pipelined_scan

        x = pipelined_scan(block, params["blocks"], x, n_micro, ms,
                           remat=cfg.remat)
    else:
        def block(x, lp):
            lp = zero.gather_at_use(lp, specs["blocks"], stacked=True)
            return zero.pin_to_batch(
                _block(cfg, x, lp, cos, sin, segment_ids)), None

        if cfg.remat != "none":
            from deepspeed_tpu.remat import policy as remat_policy

            block = jax.checkpoint(block, policy=remat_policy(cfg.remat))
        x, _ = jax.lax.scan(block, x, params["blocks"])

    with jax.named_scope("final_norm"):
        norm = zero.gather_at_use(params["final_norm"], specs["final_norm"])
        return rms_norm(x, norm, cfg.norm_eps)


def lm_head(params, cfg: LlamaConfig):
    return params["embed"].T if cfg.tie_embeddings else params["lm_head"]


def _lm_head_at_use(params, cfg: LlamaConfig):
    """:func:`lm_head` for a training forward: its leaf gathered where
    ZeRO-3 keeps it sharded (``zero.gather_at_use``).  The serving head
    calls :func:`lm_head` itself."""
    from deepspeed_tpu import zero

    key = "embed" if cfg.tie_embeddings else "lm_head"
    leaf = zero.gather_at_use(params[key], param_specs(cfg)[key])
    return lm_head({key: leaf}, cfg)


def forward(params, tokens, cfg: LlamaConfig, positions=None,
            segment_ids=None, n_micro: Optional[int] = None):
    """tokens: [B, T] int32 → logits [B, T, V] (f32).

    ``n_micro``: with a ``pipe`` axis in the ambient mesh, the block stack
    runs as a pipeline of n_micro microbatches (parallel/pipeline.py);
    embed/head stay under plain GSPMD on either side.
    """
    x = forward_hidden(params, tokens, cfg, positions=positions,
                       segment_ids=segment_ids, n_micro=n_micro)
    with jax.named_scope("lm_head"):
        return jnp.einsum("btd,dv->btv", x, _lm_head_at_use(params, cfg),
                          preferred_element_type=jnp.float32)


def layered_model(cfg: LlamaConfig, params):
    """Factor a llama param tree for the layer-streaming engine (ref:
    ZeRO-Infinity parameter offload, partitioned_param_swapper.py): stem
    = embedding, block = one transformer layer, head = final norm + LM
    head with the chunked fused loss.  See param_stream.LayeredModel."""
    from deepspeed_tpu.param_stream import LayeredModel

    if cfg.tie_embeddings:
        raise NotImplementedError(
            "layered streaming with tied embeddings would need the embed "
            "grad summed across stem and head — untie for now")

    def stem_fn(sp, batch):
        return sp["embed"][batch["tokens"][:, :-1]]

    def block_fn(lp, x):
        T = x.shape[1]
        cos, sin = rope_tables(cfg, jnp.arange(T, dtype=jnp.int32))
        return _block(cfg, x, lp, cos, sin, None)

    def head_fn(hp, x, batch):
        from deepspeed_tpu.ops.losses import chunked_lm_loss

        tokens = batch["tokens"]
        mask = batch.get("loss_mask")
        if mask is not None:
            mask = mask[:, 1:].astype(jnp.float32)
        x = rms_norm(x, hp["final_norm"], cfg.norm_eps)
        return chunked_lm_loss(x, hp["lm_head"], tokens[:, 1:], mask=mask,
                               chunk=cfg.loss_chunk or cfg.vocab_size)

    return LayeredModel(
        stem_fn=stem_fn, block_fn=block_fn, head_fn=head_fn,
        stem={"embed": params["embed"]}, blocks=params["blocks"],
        head={"final_norm": params["final_norm"],
              "lm_head": params["lm_head"]},
        n_layers=cfg.n_layers,
        assemble=lambda stem, blocks, head: {
            "embed": stem["embed"], "blocks": blocks,
            "final_norm": head["final_norm"],
            "lm_head": head["lm_head"]},
        # same split as the param factoring: TP specs (param_specs(cfg))
        # ride into the streaming engine per-layer
        factor_specs=lambda specs: (
            {"embed": specs["embed"]}, specs["blocks"],
            {"final_norm": specs["final_norm"],
             "lm_head": specs["lm_head"]}))


def layered_model_lazy(cfg: LlamaConfig, seed: int = 0,
                       dtype=jnp.bfloat16):
    """:func:`layered_model` for models whose FULL host image would not
    fit in RAM — the host-side analogue of ``zero.Init`` (ref:
    deepspeed.zero.Init partitioned construction): blocks are a
    per-layer init callable + stacked abstract spec, so the streaming
    engine materializes ONE layer at a time during tier ingest and peak
    host memory is the tier state plus a single layer, never the whole
    stacked tree."""
    d, f, L = cfg.dim, cfg.ffn_dim, cfg.n_layers
    hd, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    npdt = np.dtype(dtype)

    def nw(r, *sh):
        scale = 1.0 / np.sqrt(sh[-2] if len(sh) > 1 else sh[-1])
        return (r.standard_normal(sh, dtype=np.float32)
                * scale).astype(npdt)

    def blocks(l):
        r = np.random.default_rng((seed, l))
        return {
            "attn_norm": np.ones((d,), npdt),
            "wq": nw(r, d, nh * hd), "wk": nw(r, d, nkv * hd),
            "wv": nw(r, d, nkv * hd), "wo": nw(r, nh * hd, d),
            "mlp_norm": np.ones((d,), npdt),
            "w1": nw(r, d, f), "w3": nw(r, d, f), "w2": nw(r, f, d),
        }

    sds = jax.ShapeDtypeStruct
    blocks_spec = {
        "attn_norm": sds((L, d), dtype),
        "wq": sds((L, d, nh * hd), dtype),
        "wk": sds((L, d, nkv * hd), dtype),
        "wv": sds((L, d, nkv * hd), dtype),
        "wo": sds((L, nh * hd, d), dtype),
        "mlp_norm": sds((L, d), dtype),
        "w1": sds((L, d, f), dtype), "w3": sds((L, d, f), dtype),
        "w2": sds((L, f, d), dtype),
    }
    r0 = np.random.default_rng((seed, 1 << 30))
    lm = layered_model(cfg, {
        "embed": nw(r0, cfg.vocab_size, d),
        "blocks": blocks,
        "final_norm": np.ones((d,), npdt),
        "lm_head": nw(r0, d, cfg.vocab_size),
    })
    return dataclasses.replace(lm, blocks_spec=blocks_spec)


def packed_doc_mask(seg):
    """CE mask for a packed layout's [B, T+1] token-aligned segment ids:
    a document's last token must not predict the next document's first,
    and padding (id 0) targets mask out.  Shared by every family's
    loss_fn so the boundary semantics cannot drift."""
    return ((seg[:, :-1] == seg[:, 1:]) & (seg[:, :-1] > 0)
            ).astype(jnp.float32)


def loss_fn(cfg: LlamaConfig, n_micro: Optional[int] = None):
    """Causal-LM next-token cross entropy;
    batch = {tokens, (loss_mask), (segment_ids)}.

    ``segment_ids``: optional [B, T+1] int32 aligned with ``tokens``
    (NOT the [B, T] input window :func:`forward` takes — loss_fn slices
    them itself): packed-document attention isolation, with
    cross-document and padding (id 0) targets masked out of the CE.
    Not supported together with ``n_micro`` pipeline microbatching.

    ``n_micro``: pipeline-parallel microbatch count (see :func:`forward`);
    set it to ``gradient_accumulation_steps`` when ``pipe > 1`` — the
    engine then feeds the full batch in one call (DeepSpeed's
    PipelineEngine.train_batch contract, ref: runtime/pipe/engine.py).
    """

    def f(params, batch):
        from deepspeed_tpu.ops.losses import chunked_lm_loss

        tokens = batch["tokens"]
        targets = tokens[:, 1:]
        mask = batch.get("loss_mask")
        if mask is not None:
            mask = mask[:, 1:].astype(jnp.float32)
        seg = batch.get("segment_ids")
        if seg is not None:
            # ids align with tokens [B, T+1]; the forward consumes the
            # input slice, and the doc-boundary mask folds into the
            # loss mask
            doc = packed_doc_mask(seg)
            mask = doc if mask is None else mask * doc
            seg = seg[:, :-1]
        x = forward_hidden(params, tokens[:, :-1], cfg,
                           segment_ids=seg, n_micro=n_micro)
        # loss_chunk=0 → dense path inside chunked_lm_loss (chunk >= V);
        # >0 → fused head+CE, the [B,T,V] f32 logits never hit HBM
        return chunked_lm_loss(x, _lm_head_at_use(params, cfg), targets,
                               mask=mask,
                               chunk=cfg.loss_chunk or cfg.vocab_size)

    return f


def _embed(params, tokens, start, cfg):
    """Token embeddings and the RoPE tables from ``start`` on."""
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
        return x, rope_tables(cfg, positions_from(start, tokens.shape[1]))


def _streamed_split(cfg):
    """Embedding before the streamed blocks; final norm and LM head (the
    embedding again where it is tied) after them."""
    tied = getattr(cfg, "tie_embeddings", False)
    return ("embed",), ("final_norm", "embed" if tied else "lm_head")


# stacked [L, d] norm gains stay exact under weight-only quantization
FAMILY = DecoderFamily(
    config_type=LlamaConfig, embed=_embed, qkv=_qkv, out=_out_ffn,
    head=_head, param_specs=param_specs,
    quant_skip_paths=("attn_norm", "mlp_norm", "final_norm"),
    streamed_split=_streamed_split)

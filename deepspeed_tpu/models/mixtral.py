"""Mixtral-style MoE transformer (SURVEY.md §2 #37, MoE family).

Reference behavior: DeepSpeed's MoE training path — a GPT/Llama block whose
FFN is replaced by deepspeed.moe.layer.MoE (top-2 of N experts, capacity
factor, load-balance + z losses; ref: deepspeed/moe/layer.py,
sharded_moe.py) — as instantiated by Mixtral-8x7B-class configs.

TPU design mirrors models/llama.py: stacked layers + lax.scan, bf16-ready
matmuls, TP spec tree; the MoE FFN uses parallel/moe.py's einsum
dispatch/combine with the expert stack sharded over the ``expert`` axis.
Aux losses are carried out of the scan and added to the LM loss.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.config import MoEConfig
from deepspeed_tpu.models import llama as _llama
from deepspeed_tpu.parallel.moe import MoELayer, held_experts_ffn


@dataclasses.dataclass
class MixtralConfig:
    vocab_size: int = 32000
    dim: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 8
    ffn_dim: Optional[int] = None
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    z_loss_weight: float = 0.001
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    remat: str = "none"
    attn_impl: str = "auto"
    loss_chunk: int = 0                    # >0: fused chunked-vocab CE

    def __post_init__(self):
        if self.ffn_dim is None:
            self.ffn_dim = int(np.ceil(self.dim * 8 / 3 / 128) * 128)
        assert self.n_heads % self.n_kv_heads == 0
        assert self.dim % self.n_heads == 0

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    def moe_config(self) -> MoEConfig:
        return MoEConfig(enabled=True, num_experts=self.num_experts,
                         top_k=self.top_k,
                         capacity_factor=self.capacity_factor,
                         aux_loss_weight=self.aux_loss_weight,
                         z_loss_weight=self.z_loss_weight)

    def llama_view(self) -> _llama.LlamaConfig:
        """Attention/embedding hyperparams in LlamaConfig form (the
        attention path is shared with models/llama.py)."""
        return _llama.LlamaConfig(
            vocab_size=self.vocab_size, dim=self.dim, n_layers=self.n_layers,
            n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
            ffn_dim=self.ffn_dim, max_seq_len=self.max_seq_len,
            rope_theta=self.rope_theta, norm_eps=self.norm_eps,
            attn_impl=self.attn_impl)

    @classmethod
    def mixtral_8x7b(cls, **kw):
        return cls(vocab_size=32000, dim=4096, n_layers=32, n_heads=32,
                   n_kv_heads=8, ffn_dim=14336, num_experts=8, top_k=2,
                   rope_theta=1e6, **kw)

    @classmethod
    def tiny(cls, **kw):
        kw.setdefault("vocab_size", 256)
        kw.setdefault("dim", 32)
        kw.setdefault("n_layers", 2)
        kw.setdefault("n_heads", 4)
        kw.setdefault("n_kv_heads", 2)
        kw.setdefault("num_experts", 4)
        kw.setdefault("max_seq_len", 64)
        return cls(**kw)


def param_count(cfg: MixtralConfig) -> int:
    d, f, L, E = cfg.dim, cfg.ffn_dim, cfg.n_layers, cfg.num_experts
    kvd = cfg.n_kv_heads * cfg.head_dim
    attn = (d * d) + (d * kvd) * 2 + (d * d)
    moe = E * (d * f) * 3 + d * E          # experts + gate
    per_layer = attn + moe + 2 * d
    return int(L * per_layer + 2 * cfg.vocab_size * d + d)


def init_params(rng: jax.Array, cfg: MixtralConfig,
                dtype=jnp.float32) -> Dict[str, Any]:
    k = jax.random.split(rng, 10)
    d, f, L, E = cfg.dim, cfg.ffn_dim, cfg.n_layers, cfg.num_experts
    hd, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    s = lambda *sh: 1.0 / np.sqrt(sh[-2] if len(sh) > 1 else sh[-1])

    def w(key, *sh):
        return (jax.random.normal(key, sh) * s(*sh)).astype(dtype)

    return {
        "embed": w(k[0], cfg.vocab_size, d),
        "blocks": {
            "attn_norm": jnp.ones((L, d), dtype),
            "wq": w(k[1], L, d, nh * hd),
            "wk": w(k[2], L, d, nkv * hd),
            "wv": w(k[3], L, d, nkv * hd),
            "wo": w(k[4], L, nh * hd, d),
            "mlp_norm": jnp.ones((L, d), dtype),
            "gate": (jax.random.normal(k[5], (L, d, E)) * 0.02).astype(dtype),
            # expert FFNs stacked [L, E, ...]
            "w1": w(k[6], L, E, d, f),
            "w3": w(k[7], L, E, d, f),
            "w2": w(k[8], L, E, f, d),
        },
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": w(k[9], d, cfg.vocab_size),
    }


def param_specs(cfg: MixtralConfig) -> Dict[str, Any]:
    """TP over ``model`` for attention; experts sharded over ``expert``
    (dims: [L, E, in, out] → P(None, "expert", ...))."""
    col, row = P(None, None, "model"), P(None, "model", None)
    return {
        "embed": P(None, "model"),
        "blocks": {
            "attn_norm": P(None, None),
            "wq": col, "wk": col, "wv": col, "wo": row,
            "mlp_norm": P(None, None),
            "gate": P(None, None, None),
            "w1": P(None, "expert", None, "model"),
            "w3": P(None, "expert", None, "model"),
            "w2": P(None, "expert", "model", None),
        },
        "final_norm": P(None),
        "lm_head": P(None, "model"),
    }


def _attn_block(cfg: MixtralConfig, lcfg, x, lp, cos, sin,
                segment_ids=None):
    """The attention half of a Mixtral block (pre-norm attn + residual),
    shared by the training forward, the eval forward, and the layered
    streaming block so the four paths cannot drift."""
    from jax.ad_checkpoint import checkpoint_name

    B, T, _ = x.shape
    q, k, v = _llama._qkv(cfg, x, lp, cos, sin)
    with jax.named_scope("flash"):
        attn = _llama._attention(q, k, v, lcfg, segment_ids).reshape(
            B, T, cfg.n_heads * cfg.head_dim)
        attn = checkpoint_name(attn, "attn_out")  # remat.py save/offload tag
    with jax.named_scope("attn_out"):
        return x + attn @ lp["wo"]


def _moe_ffn(cfg: MixtralConfig, x, lp, mesh):
    """x: [B, T, d] → (y, aux) via top-k expert dispatch."""
    def expert_fn(p, h):
        from deepspeed_tpu.ops.fused_ops import swiglu

        return swiglu(h, p["w1"], p["w3"]) @ p["w2"]

    layer = MoELayer(cfg=cfg.moe_config(), expert_fn=expert_fn, mesh=mesh)
    eparams = {"w1": lp["w1"], "w3": lp["w3"], "w2": lp["w2"]}
    # the layer names its gate moe_router inside
    with jax.named_scope("moe_ffn"):
        return layer(lp["gate"], eparams, x)


def forward(params, tokens, cfg: MixtralConfig, positions=None,
            segment_ids=None):
    """tokens: [B, T] → (logits [B, T, V] f32, aux_losses dict).
    segment_ids: optional [B, T] int32 packed-document isolation (same
    contract as llama.forward)."""
    from deepspeed_tpu import zero
    from deepspeed_tpu.topology import current_mesh

    lcfg = cfg.llama_view()
    mesh = current_mesh()
    B, T = tokens.shape
    # under ZeRO-3 weights are gathered where they are used and the
    # activations stay on the batch axes (zero.py; the identity elsewhere)
    specs = param_specs(cfg)
    top = {k: params[k] for k in ("embed", "final_norm", "lm_head")}
    top = zero.gather_at_use(top, {k: specs[k] for k in top})
    x, cos, sin = _embed(top, tokens, lcfg, positions)
    x = zero.pin_to_batch(x)

    def block(carry, lp):
        from jax.ad_checkpoint import checkpoint_name

        x, aux_acc = carry
        lp = zero.gather_at_use(lp, specs["blocks"], stacked=True)
        x = _attn_block(cfg, lcfg, x, lp, cos, sin, segment_ids)
        with jax.named_scope("mlp"):
            h = _llama.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
            y, aux = _moe_ffn(cfg, h, lp, mesh)
        y = checkpoint_name(y, "mlp_out")
        x = zero.pin_to_batch(x + y)
        aux_acc = {
            "moe_aux_loss": aux_acc["moe_aux_loss"] + aux["moe_aux_loss"],
            "moe_z_loss": aux_acc["moe_z_loss"] + aux["moe_z_loss"],
            "moe_expert_load": aux_acc["moe_expert_load"]
            + aux["moe_expert_load"] / cfg.n_layers,
        }
        return (x, aux_acc), None

    blk = block
    if cfg.remat != "none":
        from deepspeed_tpu.remat import policy as remat_policy

        blk = jax.checkpoint(block, policy=remat_policy(cfg.remat))
    zero_aux = {"moe_aux_loss": jnp.float32(0.0),
                "moe_z_loss": jnp.float32(0.0),
                "moe_expert_load": jnp.zeros((cfg.num_experts,), jnp.float32)}
    (x, aux), _ = jax.lax.scan(blk, (x, zero_aux), params["blocks"])
    return _llama._head(top, x, lcfg), aux


def _embed(params, tokens, lcfg, positions=None):
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
        if positions is None:
            positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
        cos, sin = _llama.rope_tables(lcfg, positions)
        return x, cos, sin


def expert_layer(cfg: MixtralConfig, h, lp):
    """The inference path's FFN: capacity-free exact top-k (ref:
    DeepSpeed-MoE inference, deepspeed/moe/sharded_moe.py at eval).
    h [B, T, d] (normed) -> (y [B, T, d], rows [E] int32 routed to each
    expert).

    Training uses the capacity-limited dispatch (token drops are part of
    the reference's ``drop_tokens=True`` semantics under load); inference
    must not drop.  The router's choice goes to
    :func:`~deepspeed_tpu.parallel.moe.held_experts_ffn` with all the
    experts held: sorted and grouped, so that a row costs its ``top_k``
    experts, where the paged forward hands the stacks over whole
    (``lp["layer"]``: plain arrays on one device); every expert on every
    row, combined by the renormalised gate probabilities, at a decode
    step's few rows and wherever the weights arrive a layer at a time
    (sharded over a mesh, dequantised, streamed, scanned).
    """
    B, T, d = h.shape
    k = cfg.top_k
    hf = h.reshape(-1, d)
    with jax.named_scope("moe_router"):
        # router math in f32 like the training gate — bf16 logits could
        # flip a near-tied top-k choice and diverge from the trained
        # routing
        logits = hf.astype(jnp.float32) @ lp["gate"].astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        _, topi = jax.lax.top_k(logits, k)                          # [N, k]
        w = jnp.take_along_axis(probs, topi, axis=-1)
        if k > 1:
            # same renormalization as the training gate (top2gating)
            w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    with jax.named_scope("moe_ffn"):
        layer = lp.get("layer")
        y, rows = held_experts_ffn(hf, w, topi, lp["w1"], lp["w3"],
                                   lp["w2"], layer=layer,
                                   grouped=layer is not None,
                                   n_experts=lp["gate"].shape[-1])
    return y.reshape(B, T, d), rows


def forward_eval(params, tokens, cfg: MixtralConfig, positions=None):
    """Cache-free inference forward: the training attention path with the
    capacity-free exact top-k FFN (no token drops; the scanned layers'
    experts arrive as slices, so every expert evaluates every row).
    This is what kernel injection serves — the reference's eval-mode
    contract, where generation quality must not depend on router load
    balance."""
    lcfg = cfg.llama_view()
    x, cos, sin = _embed(params, tokens, lcfg, positions)

    def block(x, lp):
        x = _attn_block(cfg, lcfg, x, lp, cos, sin)
        with jax.named_scope("mlp"):
            h = _llama.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
            return x + expert_layer(cfg, h, lp)[0], None

    x, _ = jax.lax.scan(block, x, params["blocks"])
    return _llama._head(params, x, lcfg)


def layered_model(cfg: MixtralConfig, params):
    """Factor a Mixtral tree for the layer-streaming engine — MoE x
    parameter offload (ref: ZeRO-Infinity param swapping composed with
    deepspeed.moe; the expert stacks dominate MoE param bytes, so layer
    streaming is what lifts MoE past the HBM ceiling).  Each block
    returns (x, aux_scalar): the capacity-based training MoE's
    load-balance + z losses, which the engine adds to the total loss and
    back-propagates with cotangent 1 — identical routing gradients to
    the fused train step."""
    from deepspeed_tpu.param_stream import LayeredModel

    lcfg = cfg.llama_view()

    def stem_fn(sp, batch):
        return sp["embed"][batch["tokens"][:, :-1]]

    def block_fn(lp, x):
        T = x.shape[1]
        cos, sin = _llama.rope_tables(lcfg,
                                      jnp.arange(T, dtype=jnp.int32))
        x = _attn_block(cfg, lcfg, x, lp, cos, sin)
        h = _llama.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        y, aux = _moe_ffn(cfg, h, lp, mesh=None)
        return x + y, (aux["moe_aux_loss"]
                       + aux["moe_z_loss"]).astype(jnp.float32)

    def head_fn(hp, x, batch):
        from deepspeed_tpu.ops.losses import chunked_lm_loss

        x = _llama.rms_norm(x, hp["final_norm"], cfg.norm_eps)
        # loss_chunk matters MOST here: this engine's budget is a
        # 2-layer param working set, so the [B,T,V] dense logits would
        # dominate HBM at scale
        return chunked_lm_loss(x, hp["lm_head"], batch["tokens"][:, 1:],
                               chunk=cfg.loss_chunk or cfg.vocab_size)

    return LayeredModel(
        stem_fn=stem_fn, block_fn=block_fn, head_fn=head_fn,
        stem={"embed": params["embed"]}, blocks=params["blocks"],
        head={"final_norm": params["final_norm"],
              "lm_head": params["lm_head"]},
        n_layers=cfg.n_layers, block_has_aux=True,
        assemble=lambda stem, blocks, head: {
            "embed": stem["embed"], "blocks": blocks,
            "final_norm": head["final_norm"],
            "lm_head": head["lm_head"]})


def loss_fn(cfg: MixtralConfig):
    """Next-token CE + MoE aux losses; returns (loss, aux)."""

    def f(params, batch):
        tokens = batch["tokens"]
        seg = batch.get("segment_ids")     # [B, T+1], llama contract
        mask = batch.get("loss_mask")
        if mask is not None:
            mask = mask[:, 1:].astype(jnp.float32)
        if seg is not None:
            seg = jnp.asarray(seg, jnp.int32)
            doc = _llama.packed_doc_mask(seg)
            mask = doc if mask is None else mask * doc
        # NOTE: padding tokens (seg id 0) still feed the MoE router —
        # they contribute to the aux losses and consume expert capacity
        # (reference parity: the ref's gate has no padding awareness
        # either); heavy-tail-padded batches should trim T instead
        logits, aux = forward(params, tokens[:, :-1], cfg,
                              segment_ids=None if seg is None
                              else seg[:, :-1])
        targets = tokens[:, 1:]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        lm = (jnp.mean(nll) if mask is None
              else jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0))
        total = lm + aux["moe_aux_loss"] + aux["moe_z_loss"]
        return total, {"lm_loss": lm, **aux}

    return f


def _out_moe(cfg: MixtralConfig, x, attn, lp):
    """llama's attention output half, then the capacity-free top-k
    experts as the FFN (inference must not drop tokens) -> (x, rows
    routed to each expert)."""
    with jax.named_scope("attn_out"):
        x = x + attn @ lp["wo"]
    with jax.named_scope("mlp"):
        h = _llama.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        y, rows = expert_layer(cfg, h, lp)
        return x + y, rows


def _check(cfg: MixtralConfig, mesh, max_seq: int) -> None:
    if mesh is not None and cfg.num_experts % mesh.size("expert"):
        raise ValueError(
            f"num_experts {cfg.num_experts} not divisible by "
            f"expert-axis size {mesh.size('expert')}")


# llama's attention half with the MoE FFN (ref: DeepSpeed-MoE inference
# serves MoE models through the same engine).  Sharded serving: the stacked
# [L, E, ...] expert FFNs shard over the expert axis (XLA inserts the expert
# psum at the weighted combine), attention Megatron-style over model.  The
# router stays exact under weight-only quantization (int8 gate logits could
# flip a near-tied top-k choice) and so do the stacked norm gains.  On one
# device over plain arrays the paged loop hands the experts' stacks over
# whole, and the grouped product reads a layer of them in place.
FAMILY = dataclasses.replace(
    _llama.FAMILY, config_type=MixtralConfig, out=_out_moe,
    head=lambda params, x, cfg: _llama._head(params, x, cfg.llama_view()),
    param_specs=param_specs,
    quant_skip_paths=("gate",) + _llama.FAMILY.quant_skip_paths,
    shard_axes=("model", "expert"), check=_check,
    expert_rows=lambda cfg: (cfg.num_experts, cfg.top_k * cfg.n_layers),
    router=lambda cfg: (cfg.num_experts, cfg.top_k),
    whole_stacks=("w1", "w3", "w2"))

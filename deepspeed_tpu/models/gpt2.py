"""GPT-2 family (ref: the DeepSpeed Megatron-GPT2 example path; module
structure per deepspeed/module_inject/containers/gpt2.py).

Same stacked-layer scan design as :mod:`deepspeed_tpu.models.llama`;
differences: learned positional embeddings, LayerNorm (with bias), fused
QKV projection, GELU MLP, tied LM head.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.family import DecoderFamily, positions_from
from deepspeed_tpu.ops.fused_ops import layer_norm


@dataclasses.dataclass
class GPT2Config:
    vocab_size: int = 50257
    dim: int = 768
    n_layers: int = 12
    n_heads: int = 12
    max_seq_len: int = 1024
    norm_eps: float = 1e-5
    remat: str = "none"

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def n_kv_heads(self) -> int:
        return self.n_heads  # MHA — lets the shared cache builders apply

    @classmethod
    def gpt2_1_3b(cls, **kw):
        # "GPT-2 1.3B" config used by the reference's ZeRO-2 benchmark
        return cls(dim=2048, n_layers=24, n_heads=16, **kw)

    @classmethod
    def tiny(cls, **kw):
        kw.setdefault("vocab_size", 256)
        kw.setdefault("dim", 64)
        kw.setdefault("n_layers", 2)
        kw.setdefault("n_heads", 4)
        kw.setdefault("max_seq_len", 128)
        return cls(**kw)


def param_count(cfg: GPT2Config) -> int:
    d, L = cfg.dim, cfg.n_layers
    per_layer = (d * 3 * d + 3 * d) + (d * d + d) + \
        (d * 4 * d + 4 * d) + (4 * d * d + d) + 4 * d
    return int(L * per_layer + cfg.vocab_size * d
               + cfg.max_seq_len * d + 2 * d)


def init_params(rng: jax.Array, cfg: GPT2Config, dtype=jnp.float32) -> Dict[str, Any]:
    k = jax.random.split(rng, 6)
    d, L = cfg.dim, cfg.n_layers
    std = 0.02

    def w(key, *sh):
        return (jax.random.normal(key, sh) * std).astype(dtype)

    return {
        "wte": w(k[0], cfg.vocab_size, d),
        "wpe": w(k[1], cfg.max_seq_len, d),
        "blocks": {
            "ln1_w": jnp.ones((L, d), dtype), "ln1_b": jnp.zeros((L, d), dtype),
            "qkv_w": w(k[2], L, d, 3 * d), "qkv_b": jnp.zeros((L, 3 * d), dtype),
            "proj_w": w(k[3], L, d, d), "proj_b": jnp.zeros((L, d), dtype),
            "ln2_w": jnp.ones((L, d), dtype), "ln2_b": jnp.zeros((L, d), dtype),
            "fc_w": w(k[4], L, d, 4 * d), "fc_b": jnp.zeros((L, 4 * d), dtype),
            "out_w": w(k[5], L, 4 * d, d), "out_b": jnp.zeros((L, d), dtype),
        },
        "lnf_w": jnp.ones((d,), dtype), "lnf_b": jnp.zeros((d,), dtype),
    }


def param_specs(cfg: GPT2Config) -> Dict[str, Any]:
    col, row = P(None, None, "model"), P(None, "model", None)
    return {
        "wte": P(None, "model"), "wpe": P(),
        "blocks": {
            "ln1_w": P(), "ln1_b": P(),
            "qkv_w": col, "qkv_b": P(None, "model"),
            "proj_w": row, "proj_b": P(),
            "ln2_w": P(), "ln2_b": P(),
            "fc_w": col, "fc_b": P(None, "model"),
            "out_w": row, "out_b": P(),
        },
        "lnf_w": P(), "lnf_b": P(),
    }


def _block(cfg: GPT2Config, x, lp):
    from jax.ad_checkpoint import checkpoint_name

    from deepspeed_tpu.ops.attention import flash_attention
    from deepspeed_tpu.topology import current_mesh

    B, T, d = x.shape
    q, k, v = _qkv(cfg, x, lp)
    with jax.named_scope("flash"):
        attn = flash_attention(q, k, v, causal=True,
                               mesh=current_mesh()).reshape(B, T, d)
        attn = checkpoint_name(attn, "attn_out")  # remat.py save/offload tag
    return _out_mlp(cfg, x, attn, lp, tag=checkpoint_name)


def _qkv(cfg: GPT2Config, x, lp):
    """LayerNorm + fused QKV projection → q, k, v [B, T, H, hd]."""
    B, T, _ = x.shape
    nh, hd = cfg.n_heads, cfg.head_dim
    with jax.named_scope("attn_qkv"):
        h = layer_norm(x, lp["ln1_w"], lp["ln1_b"], cfg.norm_eps)
        qkv = h @ lp["qkv_w"] + lp["qkv_b"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        return (q.reshape(B, T, nh, hd), k.reshape(B, T, nh, hd),
                v.reshape(B, T, nh, hd))


def _out_mlp(cfg: GPT2Config, x, attn, lp, tag=None):
    """Attention output projection, then the GELU MLP, each with its
    residual.  ``tag`` is training's ``checkpoint_name`` (remat)."""
    with jax.named_scope("attn_out"):
        x = x + attn @ lp["proj_w"] + lp["proj_b"]
    with jax.named_scope("mlp"):
        h = layer_norm(x, lp["ln2_w"], lp["ln2_b"], cfg.norm_eps)
        h = jax.nn.gelu(h @ lp["fc_w"] + lp["fc_b"], approximate=True)
        if tag is not None:
            h = tag(h, "mlp_out")
        return x + h @ lp["out_w"] + lp["out_b"]


def _head(params, x, cfg: GPT2Config):
    with jax.named_scope("final_norm"):
        x = layer_norm(x, params["lnf_w"], params["lnf_b"], cfg.norm_eps)
    with jax.named_scope("lm_head"):
        return jnp.einsum("btd,vd->btv", x, params["wte"],
                          preferred_element_type=jnp.float32)


def forward(params, tokens, cfg: GPT2Config):
    from deepspeed_tpu import zero

    B, T = tokens.shape
    # under ZeRO-3 a layer's weights are gathered where they are used and
    # the activations stay on the batch axes (zero.py; both are the
    # identity anywhere else).  The serving hooks above are not touched.
    specs = param_specs(cfg)
    top = {k: params[k] for k in ("wte", "wpe", "lnf_w", "lnf_b")}
    top = zero.gather_at_use(top, {k: specs[k] for k in top})
    with jax.named_scope("embed"):
        x = zero.pin_to_batch(top["wte"][tokens] + top["wpe"][:T][None])

    def block(x, lp):
        lp = zero.gather_at_use(lp, specs["blocks"], stacked=True)
        return zero.pin_to_batch(_block(cfg, x, lp)), None

    if cfg.remat != "none":
        from deepspeed_tpu.remat import policy as remat_policy

        block = jax.checkpoint(block, policy=remat_policy(cfg.remat))
    x, _ = jax.lax.scan(block, x, params["blocks"])
    return _head(top, x, cfg)


def loss_fn(cfg: GPT2Config):
    def f(params, batch):
        if "segment_ids" in batch:
            raise NotImplementedError(
                "packed segment_ids: use the llama family — GPT-2's "
                "learned absolute positions don't reset per document, "
                "so silently accepting the key would train wrong")
        tokens = batch["tokens"]
        logits = forward(params, tokens[:, :-1], cfg)
        targets = tokens[:, 1:]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return jnp.mean(nll)

    return f


def _embed(params, tokens, start, cfg: GPT2Config):
    """Token + learned position embeddings from ``start`` on."""
    pos = positions_from(start, tokens.shape[1])
    with jax.named_scope("embed"):
        return params["wte"][tokens] + params["wpe"][pos], ()


def _check(cfg: GPT2Config, mesh, max_seq: int) -> None:
    if mesh is not None and mesh.size("expert") > 1:
        raise ValueError(
            "GPT-2 has no expert-parallel dimension — shard over the "
            "model axis instead")
    if max_seq > cfg.max_seq_len:
        # learned positions are HARD-bounded by the wpe table (unlike
        # RoPE); past it jax's clamping gather would silently reuse the
        # last position embedding
        raise ValueError(
            f"max_seq {max_seq} exceeds the learned position table "
            f"(cfg.max_seq_len={cfg.max_seq_len})")


# served like the llama family (ref: the reference's GPT-2 kernel-injection
# container, deepspeed/module_inject/containers/gpt2.py).  Only the matmul
# weights quantize: stacked biases/norm vectors and the (tiny,
# accuracy-critical) position table stay exact.  No streamed split.
FAMILY = DecoderFamily(
    config_type=GPT2Config, embed=_embed, qkv=_qkv, out=_out_mlp,
    head=_head, param_specs=param_specs,
    quant_skip_paths=("ln1_w", "ln1_b", "ln2_w", "ln2_b", "qkv_b",
                      "proj_b", "fc_b", "out_b", "lnf_w", "lnf_b", "wpe"),
    check=_check, max_positions=lambda cfg: cfg.max_seq_len)

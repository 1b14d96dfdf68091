"""openPangu-Ultra-MoE-style decoder (``model_type: pangu_ultra_moe``):
multi-head latent attention, sandwich norms, leading dense layers, then
expert layers with a sigmoid router, a shared expert and a *share* of
the routed experts.

One layer (``RMS`` = RMSNorm with its own gain)::

    x += RMS_post_attn(MLA(RMS_in(x)))
    x += RMS_post_mlp(F(RMS_pre_mlp(x)))

MLA on ``a = RMS_in(x)``: ``c_q = RMS(a W_qa)``, ``q = c_q W_qb`` -> H
heads of ``[q_nope | q_rope]``; ``[c_kv | k_r] = a W_kva``, ``c =
RMS(c_kv)``, ``k_rope = RoPE(k_r)`` (one for all heads); per head
``k_nope = c W_UK,h``, ``v = c W_UV,h``; ``score = (q_nope . k_nope +
RoPE(q_rope) . k_rope) / sqrt(Dn + Dr)``.  What a token leaves in the
cache is the row ``[c | k_rope]``; decode attends in the absorbed form
(``q~ = [q_nope W_UK^T | q_rope]`` against the rows, values the rows'
first C numbers, then ``W_UV``), prefill in the per-head form above: the
same numbers in exact arithmetic.

``F`` is a SwiGLU MLP in the ``n_dense_layers`` leading layers.  After
them: ``s = sigmoid(m W_g)`` over all ``n_routed_experts`` in f32, the
``top_k`` largest, ``w = routed_scaling_factor * s / sum(s)``,
``y = sum w_i E_i(m) + E_shared(m)``.  A rank of an expert-parallel
deployment holds ``experts_held = (first, count)`` of the experts and
computes their part only (:func:`~deepspeed_tpu.parallel.moe.
held_experts_ffn`); what the absent experts would add is left out, and
nothing stands in for the other ranks or their exchange.

Serving only: there is no ``loss_fn``.  The next-token-prediction module
(``num_nextn_predict_layers``) is not instantiated: it does not change
the model's next-token distribution.
"""

from __future__ import annotations

import dataclasses
import types
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models import llama as _llama
from deepspeed_tpu.models.family import (CacheRow, DecoderFamily,
                                         positions_from)
from deepspeed_tpu.parallel.moe import held_experts_ffn, sigmoid_topk_route


@dataclasses.dataclass
class PanguUltraMoEConfig:
    vocab_size: int = 153600
    dim: int = 7680
    n_layers: int = 61                 # dense + expert layers
    n_dense_layers: int = 3            # first_k_dense_replace
    n_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    ffn_dim: int = 18432               # the dense layers' SwiGLU
    moe_ffn_dim: int = 2048            # one expert's SwiGLU
    n_routed_experts: int = 256        # what the router scores
    # (first, count) of the routed experts whose weights are here
    experts_held: Tuple[int, int] = (0, 256)
    top_k: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    max_seq_len: int = 131072
    rope_theta: float = 25600000.0
    norm_eps: float = 1e-5

    def __post_init__(self):
        first, count = self.experts_held
        assert 0 <= first and first + count <= self.n_routed_experts
        assert 0 <= self.n_dense_layers <= self.n_layers
        assert self.qk_rope_dim % 2 == 0

    @property
    def n_expert_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def row_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_dim

    # the page pool's dims as the serving stack reads them off a config:
    # one "kv head" whose rows are the latent row as stored
    @property
    def n_kv_heads(self) -> int:
        return 1

    @property
    def head_dim(self) -> int:
        return _cache_row(self).pool_width

    @classmethod
    def tiny(cls, **kw):
        base = dict(vocab_size=256, dim=64, n_layers=3, n_dense_layers=1,
                    n_heads=4, q_lora_rank=32, kv_lora_rank=32,
                    qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
                    ffn_dim=96, moe_ffn_dim=32, n_routed_experts=16,
                    experts_held=(0, 4), top_k=4, max_seq_len=256)
        base.update(kw)
        return cls(**base)


def _cache_row(cfg) -> CacheRow:
    return CacheRow(1, cfg.row_width, cfg.kv_lora_rank, values_in_keys=True)


def _attn_shapes(cfg, L):
    d, H = cfg.dim, cfg.n_heads
    C, Dn, Dr, Dv = (cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.qk_rope_dim,
                     cfg.v_head_dim)
    return {
        "wq_a": (L, d, cfg.q_lora_rank),
        "wq_b": (L, cfg.q_lora_rank, H * (Dn + Dr)),
        "wkv_a": (L, d, C + Dr),
        # W_kvb in its two halves, so that neither form slices a weight
        "w_uk": (L, C, H * Dn), "w_uv": (L, C, H * Dv),
        "wo": (L, H * Dv, d),
    }


_NORMS = {"attn_norm": "dim", "post_attn_norm": "dim", "mlp_norm": "dim",
          "post_mlp_norm": "dim", "q_norm": "q_lora_rank",
          "kv_norm": "kv_lora_rank"}


def _stack_shapes(cfg, dense: bool):
    L = cfg.n_dense_layers if dense else cfg.n_expert_layers
    d = cfg.dim
    shapes = _attn_shapes(cfg, L)
    if dense:
        f = cfg.ffn_dim
        shapes.update(w1=(L, d, f), w3=(L, d, f), w2=(L, f, d))
    else:
        f, Eh = cfg.moe_ffn_dim, cfg.experts_held[1]
        fs = f * cfg.n_shared_experts
        shapes.update(gate=(L, d, cfg.n_routed_experts),
                      w1=(L, Eh, d, f), w3=(L, Eh, d, f), w2=(L, Eh, f, d),
                      sw1=(L, d, fs), sw3=(L, d, fs), sw2=(L, fs, d))
    return L, shapes


def init_params(rng: jax.Array, cfg: PanguUltraMoEConfig,
                dtype=jnp.float32) -> Dict[str, Any]:
    """Two stacks: ``dense_blocks`` ``[n_dense_layers, ...]`` and
    ``blocks`` ``[n_expert_layers, ...]`` with the held experts stacked
    ``[L, Eh, ...]`` and the router ``[L, d, n_routed_experts]``."""
    keys = iter(jax.random.split(rng, 40))

    def w(*sh):
        return (jax.random.normal(next(keys), sh)
                / np.sqrt(sh[-2])).astype(dtype)

    def stack(dense):
        L, shapes = _stack_shapes(cfg, dense)
        tree = {n: w(*sh) for n, sh in shapes.items()}
        tree.update({n: jnp.ones((L, getattr(cfg, width)), dtype)
                     for n, width in _NORMS.items()})
        return tree

    return {
        "embed": jax.random.normal(
            next(keys), (cfg.vocab_size, cfg.dim)).astype(dtype),
        "dense_blocks": stack(True), "blocks": stack(False),
        "final_norm": jnp.ones((cfg.dim,), dtype),
        "lm_head": w(cfg.dim, cfg.vocab_size),
    }


def param_specs(cfg: PanguUltraMoEConfig) -> Dict[str, Any]:
    """Every leaf replicated: the family serves on one device (its
    ``check`` refuses a model or expert axis)."""
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return jax.tree.map(lambda a: P(*(None,) * a.ndim), shapes)


def param_count(cfg: PanguUltraMoEConfig) -> int:
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return int(sum(np.prod(a.shape) for a in jax.tree.leaves(shapes)))


# -------------------------------------------------------------- the hooks
def _rope_tables(cfg, positions):
    return _llama.rope_tables(types.SimpleNamespace(
        head_dim=cfg.qk_rope_dim, rope_theta=cfg.rope_theta), positions)


def _embed(params, tokens, start, cfg):
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
        return x, _rope_tables(cfg, positions_from(start, tokens.shape[1]))


def mla_queries(cfg, q, cos, sin):
    """q [B, T, H * (Dn + Dr)] as heads [B, T, H, Dn + Dr], each head's
    rope part rotated."""
    Dn = cfg.qk_nope_dim
    q = q.reshape(q.shape[:2] + (cfg.n_heads, -1))
    return jnp.concatenate(
        [q[..., :Dn], _llama.apply_rope(q[..., Dn:], cos, sin)], -1)


def mla_row(cfg, a, lp, cos, sin):
    """What a token leaves in the cache, of the layer's normed input
    ``a`` [B, T, d]: the row [B, T, 1, C + Dr] = ``[RMS(c) | RoPE(k_r)]``."""
    C = cfg.kv_lora_rank
    kv = a @ lp["wkv_a"]
    c = _llama.rms_norm(kv[..., :C], lp["kv_norm"], cfg.norm_eps)
    k_rope = _llama.apply_rope(kv[:, :, None, C:], cos, sin)
    return jnp.concatenate([c[:, :, None], k_rope], -1)


def _qkv(cfg, x, lp, cos, sin):
    """-> (q [B, T, H, Dn + Dr] with its rope part rotated, the cache
    row [B, T, 1, C + Dr] = ``[c | k_rope]``, None)."""
    eps = cfg.norm_eps
    # the benchmark's vocabulary knows attn_qkv; the new words nest in it
    qkv = jax.named_scope("attn_qkv")
    with qkv:
        a = _llama.rms_norm(x, lp["attn_norm"], eps)
    with qkv, jax.named_scope("mla_q"):
        c_q = _llama.rms_norm(a @ lp["wq_a"], lp["q_norm"], eps)
        q = mla_queries(cfg, c_q @ lp["wq_b"], cos, sin)
    with qkv, jax.named_scope("mla_kv"):
        row = mla_row(cfg, a, lp, cos, sin)
    return q, row, None


def _latent(cfg, lp):
    C, H = cfg.kv_lora_rank, cfg.n_heads
    return (lp["w_uk"].reshape(C, H, -1), lp["w_uv"].reshape(C, H, -1),
            (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5)


def _attn_out(cfg, x, attn, lp):
    with jax.named_scope("attn_out"):
        return x + _llama.rms_norm(attn @ lp["wo"], lp["post_attn_norm"],
                                   cfg.norm_eps)


def _out_dense(cfg, x, attn, lp):
    """A leading layer's second half: SwiGLU between its two norms."""
    from deepspeed_tpu.ops.fused_ops import swiglu

    x = _attn_out(cfg, x, attn, lp)
    with jax.named_scope("mlp"):
        h = _llama.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        y = swiglu(h, lp["w1"], lp["w3"]) @ lp["w2"]
        return x + _llama.rms_norm(y, lp["post_mlp_norm"], cfg.norm_eps)


def expert_layer(cfg, h, lp, **choice):
    """h [B, T, d] (normed) -> (this rank's part of the routed sum plus
    the shared expert, rows [Eh] int32 routed to each held expert).
    ``choice``: what else the family's router chooses by (``bias``,
    ``groups``: :func:`~deepspeed_tpu.parallel.moe.sigmoid_topk_route`)."""
    from deepspeed_tpu.ops.fused_ops import swiglu

    B, T, d = h.shape
    hf = h.reshape(-1, d)
    w, experts = sigmoid_topk_route(
        hf, lp["gate"], cfg.top_k, cfg.routed_scaling_factor,
        cfg.norm_topk_prob, **choice)
    with jax.named_scope("moe_ffn"):
        y, rows = held_experts_ffn(hf, w, experts, lp["w1"], lp["w3"],
                                   lp["w2"], first=cfg.experts_held[0],
                                   layer=lp.get("layer"),
                                   n_experts=lp["gate"].shape[-1])
        with jax.named_scope("moe_shared"):
            y = y + swiglu(hf, lp["sw1"], lp["sw3"]) @ lp["sw2"]
    return y.reshape(B, T, d), rows


def _out_moe(cfg, x, attn, lp):
    x = _attn_out(cfg, x, attn, lp)
    with jax.named_scope("mlp"):
        h = _llama.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        y, rows = expert_layer(cfg, h, lp)
        return x + _llama.rms_norm(y, lp["post_mlp_norm"],
                                   cfg.norm_eps), rows


def _head(params, x, cfg):
    with jax.named_scope("final_norm"):
        x = _llama.rms_norm(x, params["final_norm"], cfg.norm_eps)
    with jax.named_scope("lm_head"):
        return jnp.einsum("btd,dv->btv", x, params["lm_head"],
                          preferred_element_type=jnp.float32)


def forward_eval(params, tokens, cfg: PanguUltraMoEConfig):
    """Cache-free forward in the published per-head form: tokens [B, T]
    -> logits [B, T, V] f32.  What the CPU tests hold the paged forward
    to; the attention is the blocked one a prefill runs."""
    from deepspeed_tpu.ops.attention import latent_flash_attention

    B, T = tokens.shape
    x, ctx = _embed(params, tokens, 0, cfg)
    Dn = cfg.qk_nope_dim
    C = cfg.kv_lora_rank

    def attend(x, lp):
        q, row, _ = _qkv(cfg, x, lp, *ctx)
        w_uk, w_uv, scale = _latent(cfg, lp)
        with jax.named_scope("flash"):
            c, k_rope = row[:, :, 0, :C], row[:, :, 0, C:]
            attn = latent_flash_attention(
                q[..., :Dn], q[..., Dn:],
                jnp.einsum("bsc,chd->bshd", c, w_uk), k_rope,
                jnp.einsum("bsc,chd->bshd", c, w_uv),
                jnp.zeros((B,), jnp.int32), scale)
        return attn.reshape(B, T, -1)

    def dense(x, lp):
        return _out_dense(cfg, x, attend(x, lp), lp), None

    def sparse(x, lp):
        return _out_moe(cfg, x, attend(x, lp), lp)[0], None

    x, _ = jax.lax.scan(dense, x, params["dense_blocks"])
    x, _ = jax.lax.scan(sparse, x, params["blocks"])
    return _head(params, x, cfg)


def _check(cfg: PanguUltraMoEConfig, mesh, max_seq: int) -> None:
    if mesh is not None and any(mesh.size(ax) > 1
                                for ax in ("model", "expert")):
        raise NotImplementedError(
            "PanguUltraMoEConfig cannot serve with a model or expert "
            "axis > 1: one latent row a token is shared by every head, "
            "and tensor parallelism over latent pages is not built")
    if max_seq > cfg.max_seq_len:
        raise ValueError(f"max_seq {max_seq} is past the model's "
                         f"max_seq_len {cfg.max_seq_len}")


_LATENT_PAGES = "pages hold one latent row a token, not per-head K and V"

# The router stays exact under weight-only quantization, and the norm
# gains.  Mechanisms that assume per-head K/V pages are refused by name.
FAMILY = DecoderFamily(
    config_type=PanguUltraMoEConfig, embed=_embed, qkv=_qkv, out=_out_moe,
    head=_head, param_specs=param_specs,
    quant_skip_paths=("gate",) + tuple(_NORMS) + ("final_norm",),
    shard_axes=("model", "expert"), check=_check,
    cache_row=_cache_row,
    lead=("dense_blocks", _out_dense), latent=_latent,
    expert_rows=lambda cfg: (cfg.experts_held[1],
                             cfg.top_k * cfg.n_expert_layers),
    router=lambda cfg: (cfg.n_routed_experts, cfg.top_k),
    whole_stacks=("w1", "w3", "w2"),
    refuses=(
        ("quantized_resident", "int8-resident " + _LATENT_PAGES),
        ("kv_tier", "a tier entry inherits per-head pages; " + _LATENT_PAGES),
        ("prefix_cache", "the prefix cache publishes per-head pages; "
         + _LATENT_PAGES),
        ("zero_inference", "weight streaming runs one stack of one layer "
         "kind; this family has a leading dense stack"),
        ("speculative", "the verify sweep reads a chunk program's logits "
         "at every position over per-head pages, and the next-token "
         "module that would draft is not instantiated"),
        ("contiguous_cache", "the contiguous-cache generators keep "
         "per-head K and V; serve through serving_engine"),
    ))

"""GPT-2 (Radford et al. 2019), plain: learned positions, pre-LayerNorm
with bias, fused QKV, tanh-GELU MLP of width 4d, tied output head.

Parameter names are those of the tree the system is given (``wte``,
``wpe``, ``blocks/*`` stacked over layers, ``lnf_*``).
"""

import jax
import jax.numpy as jnp

from benchmark.reference.common import causal_attention


def _layer_norm(x, w, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def logits(params, tokens, *, n_heads, eps, start=0, count=None):
    """tokens: [T] -> float32 logits [count, V] of the positions from
    ``start`` (all of them by default); position p predicts p + 1."""
    f32 = lambda a: a.astype(jnp.float32)
    T = tokens.shape[0]
    x = f32(params["wte"][tokens]) + f32(params["wpe"][:T])
    d = x.shape[-1]

    def block(x, lp):
        lp = jax.tree.map(f32, lp)
        h = _layer_norm(x, lp["ln1_w"], lp["ln1_b"], eps)
        q, k, v = jnp.split(h @ lp["qkv_w"] + lp["qkv_b"], 3, axis=-1)
        heads = lambda a: a.reshape(T, n_heads, d // n_heads)
        a = causal_attention(heads(q), heads(k), heads(v)).reshape(T, d)
        x = x + a @ lp["proj_w"] + lp["proj_b"]
        h = _layer_norm(x, lp["ln2_w"], lp["ln2_b"], eps)
        h = jax.nn.gelu(h @ lp["fc_w"] + lp["fc_b"], approximate=True)
        return x + h @ lp["out_w"] + lp["out_b"], None

    with jax.default_matmul_precision("highest"):
        x, _ = jax.lax.scan(block, x, params["blocks"])
        if count is not None:
            x = jax.lax.dynamic_slice_in_dim(x, start, count)
        x = _layer_norm(x, f32(params["lnf_w"]), f32(params["lnf_b"]), eps)
        return x @ f32(params["wte"]).T


def loss(params, tokens, *, n_heads, eps):
    """Mean next-token cross-entropy of tokens [B, T + 1]."""
    def one(row):
        lg = logits(params, row[:-1], n_heads=n_heads, eps=eps)
        logp = jax.nn.log_softmax(lg, axis=-1)
        return -jnp.take_along_axis(logp, row[1:, None], axis=-1)[:, 0]

    return jnp.mean(jax.lax.map(one, tokens))

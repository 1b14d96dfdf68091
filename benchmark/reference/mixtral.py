"""Mixtral (Jiang et al. 2024, arXiv:2401.04088), plain: RMSNorm,
grouped-query attention with rotary positions (half-split pairs, as the
published code), and a sparse FFN: softmax router over E experts, the
top k renormalised, each expert a SwiGLU of width f.  No capacity and no
dropped token.

Parameter names are those of the tree the system is given (``embed``,
``blocks/*`` stacked over layers with experts stacked ``[L, E, ...]``,
``final_norm``, ``lm_head``).  Experts are taken out of the stack and
upcast to float32 one at a time (a whole layer of them is 2.6 GiB in
bf16 alone), so that the served weights and this reference fit together.

Two passes, because a sparse model is discontinuous.  Where the k-th and
the (k+1)-th router logit of a position are closer than rounding,
another precision rightly picks the other expert, and from there on that
position's hidden state is another one.  ``keys_values`` runs the whole
sequence once and keeps every layer's K and V.  ``logits`` then runs a
stretch of positions against them, and may be told to ``swap`` the k-th
expert for the (k+1)-th at chosen positions of chosen layers: the other
answer that is just as right there.
"""

import jax
import jax.numpy as jnp

from benchmark.reference.common import causal_attention

EXPERT_WEIGHTS = ("w1", "w3", "w2")

f32 = lambda a: a.astype(jnp.float32)


def _layer(blocks, at):
    """Layer ``at`` of the stacked tree, its experts left in the stack."""
    return {n: a if n in EXPERT_WEIGHTS else a[at] for n, a in blocks.items()}


def _rms_norm(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(x, lp, pos, cached, *, n_heads, n_kv_heads, rope_theta, eps):
    """x: [N, d] at positions ``pos`` -> (x after attention, K, V of
    these positions).  ``cached`` is None (attend among these positions)
    or a layer's (K, V) over the whole sequence, in which these
    positions' rows are replaced by what is computed here."""
    N, d = x.shape
    hd = d // n_heads
    h = _rms_norm(x, f32(lp["attn_norm"]), eps)
    q = _rope((h @ f32(lp["wq"])).reshape(N, n_heads, hd), pos, rope_theta)
    k = _rope((h @ f32(lp["wk"])).reshape(N, n_kv_heads, hd), pos, rope_theta)
    v = (h @ f32(lp["wv"])).reshape(N, n_kv_heads, hd)
    keys, values = k, v
    if cached is not None:
        keys = jax.lax.dynamic_update_slice_in_dim(cached[0], k, pos[0], 0)
        values = jax.lax.dynamic_update_slice_in_dim(cached[1], v, pos[0], 0)
    a = causal_attention(q, keys, values, pos[0])
    return x + a.reshape(N, d) @ f32(lp["wo"]), k, v


def _sparse_ffn(x, lp, at, swap, *, top_k, eps):
    """-> (x after the FFN of layer ``at``, router margin [N]).  The margin is the gap
    between the k-th and the (k+1)-th router logit as a share of the
    largest router logit; where ``swap`` is set the (k+1)-th expert
    takes the k-th's place."""
    h = _rms_norm(x, f32(lp["mlp_norm"]), eps)
    route = h @ f32(lp["gate"])                                    # [N, E]
    top, idx = jax.lax.top_k(route, top_k + 1)
    margin = (top[:, top_k - 1] - top[:, top_k]) / jnp.abs(route).max(-1)
    last = lambda a: jnp.where(swap, a[:, top_k], a[:, top_k - 1])[:, None]
    top = jnp.concatenate([top[:, :top_k - 1], last(top)], -1)
    idx = jnp.concatenate([idx[:, :top_k - 1], last(idx)], -1)
    w = jax.nn.softmax(top, axis=-1)                  # = renormalised top k

    def expert(y, e):
        w1, w3, w2 = (f32(lp[n][at, e]) for n in EXPERT_WEIGHTS)
        share = jnp.sum(jnp.where(idx == e, w, 0.0), -1)
        out = (jax.nn.silu(h @ w1) * (h @ w3)) @ w2
        return y + share[:, None] * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        jnp.arange(lp["w1"].shape[1]))
    return x + y, margin


def keys_values(params, tokens, *, top_k, **attn):
    """tokens: [T] -> every layer's (K, V), each [L, T, KV, D], of the
    whole sequence as the router's own choice gives them."""
    pos = jnp.arange(tokens.shape[0])
    stay = jnp.zeros(tokens.shape, bool)

    def block(x, at):
        lp = _layer(params["blocks"], at)
        x, k, v = _attention(x, lp, pos, None, **attn)
        x, _ = _sparse_ffn(x, lp, at, stay, top_k=top_k, eps=attn["eps"])
        return x, (k, v)

    with jax.default_matmul_precision("highest"):
        _, cache = jax.lax.scan(block, f32(params["embed"][tokens]),
                                jnp.arange(params["blocks"]["gate"].shape[0]))
    return cache


def logits(params, tokens, cache, start, count, swap, *, top_k, **attn):
    """-> (float32 logits [count, V], router margins [L, count]) of the
    ``count`` positions from ``start``, run against ``cache`` (what
    ``keys_values`` returned) with their own rows computed anew.
    ``swap``: [L, count] booleans."""
    pos = start + jnp.arange(count)
    x = f32(params["embed"][jax.lax.dynamic_slice_in_dim(tokens, start,
                                                         count)])

    def block(x, layer):
        at, k, v, swap_here = layer
        lp = _layer(params["blocks"], at)
        x, _, _ = _attention(x, lp, pos, (k, v), **attn)
        return _sparse_ffn(x, lp, at, swap_here, top_k=top_k,
                           eps=attn["eps"])

    with jax.default_matmul_precision("highest"):
        x, margins = jax.lax.scan(
            block, x, (jnp.arange(swap.shape[0]), *cache, swap))
        x = _rms_norm(x, f32(params["final_norm"]), attn["eps"])
        return x @ f32(params["lm_head"]), margins

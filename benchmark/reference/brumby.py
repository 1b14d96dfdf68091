"""Plain float32 forward of a power-retention decoder (``model_type:
brumby``), from the DEFINITION: a layer's mixer is computed as attention
is, every query against every earlier key, and never through the expanded
state the program serves.  Imports nothing from the program's models.

A layer (``N`` = RMSNorm with a gain), per token::

    h = x + W_o Ret(N(x));   y = h + W_2 (SiLU(W_1 N(h)) * W_3 N(h))

On ``a = N(x)``: ``q = RoPE(N_head(a W_q))``, ``k = RoPE(N_head(a
W_k))`` (rotate-half over all of a head's lanes), ``v = a W_v``, ``log g
= logsigmoid(a W_g + b_g)`` one number a K/V head, ``G`` its running
sum; query head h reads K/V head ``h // (H / KV)``::

    w_ts = (q_t . k_s)^2 exp(G_t - G_s),  s <= t
    Ret_t = sum_s w_ts v_s / (sum_s w_ts + eps)

in blocks of ``QUERY_BLOCK`` queries against all the keys (a block's
weights are [H, block, T] float32: 17,408 tokens fit), the layers in a
``lax.scan`` over the stacked weights, each cast to float32 as it is
used.  Every product at ``jax.default_matmul_precision("highest")``,
which the caller sets.

Departures from the published layer, each an assumption the
configuration lists (``assumed``): the degree is 2; the gate is one
log-sigmoid a K/V head with a bias; the normaliser is the plain sum of
the weights plus ``eps``; no scale on ``q . k`` (a constant one cancels
in the quotient); the published layer's switch to a K/V form under some
length is an implementation's economy with these numbers and is not
modelled.
"""

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256
TOKEN_BLOCK = 2048      # rows of the SwiGLU at a time


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    """x [T, heads, Dh]: pairs (i, i + Dh/2) rotated by position."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _blocks(n, block):
    """The largest block within ``block`` that divides n."""
    return max(b for b in range(1, min(n, block) + 1) if n % b == 0)


def retention(q, k, v, G, eps):
    """The definition: q [T, H, Dh], k, v [T, KV, Dh], G [T, KV] the
    running sum of log g -> [T, H, Dh]."""
    T, H, Dh = q.shape
    KV = k.shape[1]
    b = _blocks(T, QUERY_BLOCK)
    s = jnp.arange(T)

    def block(args):
        qb, Gb, t = args                    # [b, KV, n, Dh], [b, KV], [b]
        decay = jnp.exp(jnp.where(
            s[None] <= t[:, None], Gb.T[:, :, None] - G.T[:, None, :],
            -jnp.inf))                                      # [KV, b, T]
        w = jnp.einsum("tkni,ski->knts", qb, k) ** 2 * decay[:, None]
        return jnp.einsum("knts,ski->tkni", w, v) / (
            w.sum(-1).transpose(2, 0, 1)[..., None] + eps)

    out = jax.lax.map(block, (
        q.reshape(T // b, b, KV, H // KV, Dh), G.reshape(T // b, b, KV),
        s.reshape(T // b, b)))
    return out.reshape(T, H, Dh)


def _layer(x, lp, positions, head_dim, theta, eps, ret_eps):
    """One layer on x [T, d] -> (y, (k, v, G)): its keys, values and
    running log-gates ride out for a check that wants the state they
    define."""
    lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
    T = x.shape[0]
    a = _norm(x, lp["attn_norm"], eps)
    heads = lambda y: y.reshape(T, -1, head_dim)
    q = _rope(_norm(heads(a @ lp["wq"]), lp["q_norm"], eps), positions, theta)
    k = _rope(_norm(heads(a @ lp["wk"]), lp["k_norm"], eps), positions, theta)
    v = heads(a @ lp["wv"])
    G = jnp.cumsum(jax.nn.log_sigmoid(a @ lp["w_g"] + lp["b_g"]), axis=0)
    h = x + retention(q, k, v, G, ret_eps).reshape(T, -1) @ lp["wo"]
    rows = _blocks(T, TOKEN_BLOCK)

    def ffn(hb):
        m = _norm(hb, lp["mlp_norm"], eps)
        return hb + (jax.nn.silu(m @ lp["w1"]) * (m @ lp["w3"])) @ lp["w2"]

    y = jax.lax.map(ffn, h.reshape(T // rows, rows, -1)).reshape(T, -1)
    return y, (k, v, G)


def hidden(params, tokens, *, head_dim, rope_theta, eps, ret_eps,
           keep=False):
    """tokens [T] from position 0 -> (x [T, d] before the final norm,
    None or every layer's (k [L, T, KV, Dh], v, G [L, T, KV]))."""
    x = params["embed"][tokens].astype(jnp.float32)
    positions = jnp.arange(tokens.shape[0])

    def one(x, lp):
        y, kvg = _layer(x, lp, positions, head_dim, rope_theta, eps, ret_eps)
        return y, kvg if keep else None

    return jax.lax.scan(one, x, params["ret_blocks"])


def logits(params, tokens, start, count, **kw):
    """tokens [T] -> logits [count, V] of the positions from ``start``."""
    x, _ = hidden(params, tokens, **kw)
    x = jax.lax.dynamic_slice_in_dim(x, start, count)
    x = _norm(x, params["final_norm"].astype(jnp.float32), kw["eps"])
    return x @ params["lm_head"].astype(jnp.float32)

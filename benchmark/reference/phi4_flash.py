"""Phi-4-mini-flash (``model_type: phi4flash``; SambaY, arXiv:2507.06607,
with differential attention, arXiv:2410.05258), plain: float32, every
layer on every position, Mamba-1 a token at a time, differential
attention as four plain softmax products on heads of the published
width, the window as a mask, no cache, no chunks, no kernels.

With ``LN(x) = (x - mean) / sqrt(var + eps) * g + b``, no position
encoding anywhere::

    x = E[token]
    layer l:  h = x + Mix_l(LN(x));  y = h + (SiLU(g) * u) W_2,  [g | u] = LN(h) W_1
    logits = LN(x) E^T                           (the head is the embedding)

``kinds`` says what ``Mix_l`` is, a layer:

* ``"mamba"``: ``[u | z] = a W_in``; ``c = SiLU(conv(u) + b)`` (depthwise,
  causal, the last tap on the current token); ``[r | B | C] = c W_x``;
  ``D_t = softplus(r W_dt + b_dt)``; ``A = -exp(A_log)`` [channels,
  states]; ``S_0 = 0``, ``S_t = exp(D_t A) S_(t-1) + (D_t c_t) B_t^T``;
  ``o_t = S_t C_t + D c_t``; ``Mix = (o * SiLU(z)) W_out``.  The LAST such
  layer's ``o`` is the memory ``M``.
* ``"gmu"``: ``Mix = (SiLU(a W_g) * M) W_o``, ``M`` of the same token.
* ``"window"``, ``"full"``: ``[q | k | v] = a W_qkv + b``; adjacent heads
  are a pair, ``(q1, q2)`` of the queries over ``(k1, k2)``, ``(v1, v2)``
  of K/V pair ``pair // 2``; ``P_i = softmax(q_i k_i^T / sqrt(head))``
  over the keys at or before the query (a window layer: the last
  ``window`` of them, the query's own among them); ``o = [P_1 v1 | P_1
  v2] - lambda [P_2 v1 | P_2 v2]``, ``lambda = exp(lq1 . lk1) - exp(lq2 .
  lk2) + lambda_init``; ``Mix = (RMS(o) * subln * (1 - lambda_init)) W_o
  + b_o``, the norm over a pair's ``2 head`` numbers.
* ``"cross"``: the same with ``q = a W_q + b`` alone, over the ``k`` and
  ``v`` of the ``"full"`` layer.

Departures from the published description, none of the arithmetic: the
parameter names and shapes are those of the tree the system is given
(``A_log`` is kept ``[d_inner / 128, d_state, 128]``, channels last, and is
turned back here; ``lam0`` holds ``lambda_init`` of each attention
layer's depth); queries are taken in blocks so that a block's scores fit;
layers of one kind pair run in a scan.  One pass over the whole sequence;
what a caller wants of it is sliced before the head.
"""

import jax
import jax.numpy as jnp

Q_BLOCK = 512

f32 = lambda a: a.astype(jnp.float32)


def _layer_norm(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * f32(g) + f32(b)


def _mlp(x, lp, eps):
    h = _layer_norm(x, lp["mlp_norm_g"], lp["mlp_norm_b"], eps) \
        @ f32(lp["w_gu"])
    f = h.shape[-1] // 2
    return (jax.nn.silu(h[:, :f]) * h[:, f:]) @ f32(lp["w_down"])


def recurrence(c, dt, A, B, C, S):
    """Mamba-1's, a token at a time: c, dt [T, channels], A [channels,
    states], B, C [T, states], S [channels, states] -> (o [T, channels]
    without the skip, S after all T)."""
    def step(S, t):
        c, dt, B, C = t
        S = jnp.exp(dt[:, None] * A) * S + (dt * c)[:, None] * B[None, :]
        return S, S @ C

    S, o = jax.lax.scan(step, S, (c, dt, B, C))
    return o, S


def _mamba(x, lp, *, d_state, dt_rank, eps):
    """x: [T, d] -> (the mixer's output [T, d], the scan's output ``o``
    [T, channels] with the skip and before the gate, the state [channels,
    states] after all T tokens), from zero state and zero rows."""
    T = x.shape[0]
    a = _layer_norm(x, lp["attn_norm_g"], lp["attn_norm_b"], eps)
    uz = a @ f32(lp["w_in"])
    di = uz.shape[-1] // 2
    u, z = uz[:, :di], uz[:, di:]
    w = f32(lp["conv_w"])
    taps = w.shape[0]
    seen = jnp.concatenate([jnp.zeros((taps - 1, di)), u])
    c = jax.nn.silu(sum(seen[i:i + T] * w[i] for i in range(taps))
                    + f32(lp["conv_b"]))
    rbc = c @ f32(lp["w_x"])
    r, B, C = (rbc[:, :dt_rank], rbc[:, dt_rank:dt_rank + d_state],
               rbc[:, dt_rank + d_state:])
    dt = jax.nn.softplus(r @ f32(lp["w_dt"]) + f32(lp["dt_bias"]))
    A = -jnp.exp(f32(lp["A_log"])).transpose(0, 2, 1).reshape(di, d_state)
    o, S = recurrence(c, dt, A, B, C, jnp.zeros((di, d_state)))
    o = o + f32(lp["D"]) * c
    return (o * jax.nn.silu(z)) @ f32(lp["w_out"]), o, S


def _softmax_product(q, k, vs, window):
    """q [T, P, Dh] over k [T, P / 2, Dh] (a K/V pair serves two pairs of
    queries) -> ``P v`` for each v of ``vs`` [T, P / 2, Dh], [T, P, Dh]
    each: plain causal softmax of ``q k^T / sqrt(Dh)``, the last
    ``window`` keys (None: all)."""
    T, P, Dh = q.shape
    blk = Q_BLOCK if T % Q_BLOCK == 0 else T
    qb = q.reshape(T // blk, blk, P // 2, 2, Dh)
    key_pos = jnp.arange(T)

    def one(args):
        qi, first = args
        s = jnp.einsum("qkgd,tkd->kgqt", qi, k) / jnp.sqrt(float(Dh))
        pos = (first + jnp.arange(blk))[:, None]
        seen = key_pos[None, :] <= pos
        if window is not None:
            seen &= key_pos[None, :] > pos - window
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return tuple(jnp.einsum("kgqt,tkd->qkgd", p, v) for v in vs)

    outs = jax.lax.map(one, (qb, jnp.arange(T // blk) * blk))
    return tuple(o.reshape(T, P, Dh) for o in outs)


def _differential(q, k, v, lp, *, head_dim, window, eps):
    """q [T, heads x head], k, v [T, kv heads x head] -> the mixer's
    output [T, d]: the four products, their difference, the norm, W_o."""
    T = q.shape[0]
    heads = lambda y: y.reshape(T, -1, head_dim)
    q, k, v = heads(q), heads(k), heads(v)
    q1, q2 = q[:, 0::2], q[:, 1::2]
    k1, k2 = k[:, 0::2], k[:, 1::2]
    v1, v2 = v[:, 0::2], v[:, 1::2]
    a11, a12 = _softmax_product(q1, k1, (v1, v2), window)
    a21, a22 = _softmax_product(q2, k2, (v1, v2), window)
    lam0 = f32(lp["lam0"])
    lam = jnp.exp(jnp.sum(f32(lp["lq1"]) * f32(lp["lk1"]))) \
        - jnp.exp(jnp.sum(f32(lp["lq2"]) * f32(lp["lk2"]))) + lam0
    o = jnp.concatenate([a11, a12], -1) - lam * jnp.concatenate([a21, a22],
                                                                -1)
    o = o / jnp.sqrt((o * o).mean(-1, keepdims=True) + eps) \
        * f32(lp["subln"]) * (1.0 - lam0)
    return o.reshape(T, -1) @ f32(lp["wo"]) + f32(lp["bo"])


def _attention(x, lp, *, head_dim, window, eps):
    """A layer with keys of its own -> (the mixer's output, its k, v)."""
    a = _layer_norm(x, lp["attn_norm_g"], lp["attn_norm_b"], eps)
    qkv = a @ f32(lp["wqkv"]) + f32(lp["bqkv"])
    nq = lp["wo"].shape[0]
    nk = (qkv.shape[-1] - nq) // 2
    q, k, v = qkv[:, :nq], qkv[:, nq:nq + nk], qkv[:, nq + nk:]
    return _differential(q, k, v, lp, head_dim=head_dim, window=window,
                         eps=eps), k, v


def _cross(x, lp, k, v, *, head_dim, eps):
    a = _layer_norm(x, lp["attn_norm_g"], lp["attn_norm_b"], eps)
    q = a @ f32(lp["wq"]) + f32(lp["bq"])
    return _differential(q, k, v, lp, head_dim=head_dim, window=None,
                         eps=eps)


def _gmu(x, lp, M, eps):
    a = _layer_norm(x, lp["attn_norm_g"], lp["attn_norm_b"], eps)
    return (jax.nn.silu(a @ f32(lp["w_g"])) * M) @ f32(lp["w_o"])


def hidden(params, tokens, *, kinds, head_dim, window, d_state, dt_rank,
           eps):
    """tokens: [T] -> (the last layer's output [T, d], the Mamba-1
    layers' states after all T tokens, stacked in the model's order
    [L_mamba, channels, states]).  ``kinds``: the model's layers in
    order; they must be ``(mamba, window) x n, mamba, full, (gmu, cross)
    x m``."""
    n, m = kinds.count("window"), kinds.count("cross")
    assert kinds == ("mamba", "window") * n + ("mamba", "full") \
        + ("gmu", "cross") * m, kinds
    mkw = dict(d_state=d_state, dt_rank=dt_rank, eps=eps)
    at = lambda stack, i: jax.tree.map(lambda a: a[i], stack)
    half = lambda x, y, lp: (x + y) + _mlp(x + y, lp, eps)

    def self_period(x, stacks):
        mp, wp = stacks
        y, _, S = _mamba(x, mp, **mkw)
        x = half(x, y, mp)
        y, _, _ = _attention(x, wp, head_dim=head_dim, window=window,
                             eps=eps)
        return half(x, y, wp), S

    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"][tokens])
        mamba = params["mamba_blocks"]
        x, states = jax.lax.scan(
            self_period, x,
            (jax.tree.map(lambda a: a[:n], mamba), params["win_blocks"]))
        mp, fp = at(mamba, n), at(params["blocks"], 0)
        y, M, S = _mamba(x, mp, **mkw)
        x = half(x, y, mp)
        y, k, v = _attention(x, fp, head_dim=head_dim, window=None, eps=eps)
        x = half(x, y, fp)

        def cross_period(x, stacks):
            gp, cp = stacks
            x = half(x, _gmu(x, gp, M, eps), gp)
            return half(x, _cross(x, cp, k, v, head_dim=head_dim, eps=eps),
                        cp), None

        x, _ = jax.lax.scan(cross_period, x, (params["gmu_blocks"],
                                              params["cross_blocks"]))
        return x, jnp.concatenate([states, S[None]])


def logits(params, tokens, start, count, **kw):
    """-> float32 logits [count, V] of the ``count`` positions from
    ``start``; position p predicts p + 1.  The head runs on those rows
    alone."""
    x, _ = hidden(params, tokens, **kw)
    with jax.default_matmul_precision("highest"):
        x = jax.lax.dynamic_slice_in_dim(x, start, count)
        x = _layer_norm(x, params["final_norm_g"], params["final_norm_b"],
                        kw["eps"])
        return x @ f32(params["embed"]).T


def forward(params, tokens, **kw):
    """The whole forward, once: tokens [T] -> logits [T, V].  What the
    CPU tests hold the system to."""
    return logits(params, tokens, 0, tokens.shape[0], **kw)

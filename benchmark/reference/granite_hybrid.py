"""Granite-4.0-H (``model_type: granitemoehybrid``), plain: float32, the
Mamba-2 layers' recurrence token by token, no cache, no chunks, no
kernels.

With ``N`` the RMSNorm ``x / sqrt(mean x^2 + eps) * w``::

    x = embedding_multiplier * E[token]
    layer l:  h = x + residual_multiplier * Mixer_l(N(x))
              y = h + residual_multiplier * MLP(N(h))
    logits = N(x) E^T / logits_scaling          (the head is the embedding)

``Mixer_l`` is attention where the period says "attention", else Mamba-2.
MLP on ``m``: ``[g | u] = m W_gu``, ``(SiLU(g) * u) W_down``; no biases.

Attention on ``a``: ``[q | k | v] = a W_qkv``, no bias, no
position encoding of any kind, causal softmax of ``attention_multiplier
* q k^T`` (heads grouped over the KV heads), ``W_o``.

Mamba-2 on ``a`` (``H`` heads of ``P`` channels, one group, state
``N``): ``[z | xBC | dt] = a [W_in | W_dt]`` (the published matrix's
columns, the last block stored apart); the channels of ``xBC`` pass a
depthwise causal convolution (``taps`` taps, the last on the current
token, WITH bias) and SiLU, and split into ``x`` [H, P], ``B`` [N], ``C``
[N], which all heads share; ``D_t = softplus(dt + dt_bias)``, ``A =
-exp(A_log)``, one a head.  For each head, ``S_0 = 0`` [P, N]::

    S_t = exp(D_t A) S_(t-1) + D_t x_t B_t^T;   o_t = S_t C_t + D x_t

``y = N(o_t * SiLU(z_t))`` over all ``H P`` channels (the gate BEFORE the
norm), then ``W_out``.

Parameter names are those of the tree the system is given
(``ssm_blocks/*`` the Mamba-2 layers and ``blocks/*`` the attention
layers, each stacked in the model's order).  One pass over the whole
sequence; what a caller wants of it is sliced before the head.
"""

import jax
import jax.numpy as jnp

Q_BLOCK = 512

f32 = lambda a: a.astype(jnp.float32)


def _rms_norm(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * f32(w)


def _attention(x, lp, *, head_dim, scale, eps):
    """x: [T, d] -> the mixer's output [T, d]."""
    T = x.shape[0]
    a = _rms_norm(x, lp["attn_norm"], eps)
    heads = lambda y: y.reshape(T, -1, head_dim)
    qkv = heads(a @ f32(lp["wqkv"]))
    H = lp["wo"].shape[0] // head_dim
    KV = (qkv.shape[1] - H) // 2
    q, k, v = qkv[:, :H], qkv[:, H:H + KV], qkv[:, H + KV:]
    blk = Q_BLOCK if T % Q_BLOCK == 0 else T
    qb = q.reshape(T // blk, blk, KV, -1, head_dim)
    key_pos = jnp.arange(T)

    def one(args):
        qi, first = args
        s = scale * jnp.einsum("qkgd,tkd->kgqt", qi, k)
        seen = key_pos[None, :] <= (first + jnp.arange(blk))[:, None]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v)

    o = jax.lax.map(one, (qb, jnp.arange(T // blk) * blk))
    return o.reshape(T, -1) @ f32(lp["wo"])


def recurrence(x, dt, A, B, C, S):
    """The state-space recurrence, a token at a time: x [T, H, P], dt
    [T, H], A [H], B, C [T, N], S [H, P, N] -> (o [T, H, P] without the
    skip, S after all T)."""
    def step(S, t):
        x, dt, B, C = t
        S = jnp.exp(dt * A)[:, None, None] * S \
            + (dt[:, None] * x)[:, :, None] * B[None, None, :]
        return S, jnp.einsum("hpn,n->hp", S, C)

    S, o = jax.lax.scan(step, S, (x, dt, B, C))
    return o, S


def _mamba(x, lp, *, heads, state, eps):
    """x: [T, d] -> (the mixer's output [T, d], the state [H, P, N] after
    all T tokens), from zero state and zero convolution rows."""
    T = x.shape[0]
    a = _rms_norm(x, lp["attn_norm"], eps)
    zx, dt = a @ f32(lp["w_in"]), a @ f32(lp["w_dt"])
    inner = (zx.shape[-1] - 2 * state) // 2
    z, xBC = zx[:, :inner], zx[:, inner:]
    w = f32(lp["conv_w"])
    taps = w.shape[0]
    seen = jnp.concatenate([jnp.zeros((taps - 1, xBC.shape[1])), xBC])
    y = jax.nn.silu(sum(seen[i:i + T] * w[i] for i in range(taps))
                    + f32(lp["conv_b"]))
    xs = y[:, :inner].reshape(T, heads, -1)
    B, C = y[:, inner:inner + state], y[:, inner + state:]
    dt = jax.nn.softplus(dt + f32(lp["dt_bias"]))
    o, S = recurrence(xs, dt, -jnp.exp(f32(lp["A_log"])), B, C,
                      jnp.zeros((heads, xs.shape[-1], state)))
    o = o + f32(lp["D"])[:, None] * xs
    g = _rms_norm(o.reshape(T, -1) * jax.nn.silu(z), lp["ssm_norm"], eps)
    return g @ f32(lp["w_out"]), S


def _mlp(x, lp, eps):
    h = _rms_norm(x, lp["mlp_norm"], eps) @ f32(lp["w_gu"])
    f = h.shape[-1] // 2
    return (jax.nn.silu(h[:, :f]) * h[:, f:]) @ f32(lp["w_down"])


def hidden(params, tokens, *, period, head_dim, heads, state, emb_mult,
           res_mult, attn_scale, eps):
    """tokens: [T] -> (the last layer's output [T, d], the Mamba-2
    layers' states after all T tokens, stacked in the model's order
    [L_ssm, H, P, N]).  ``period``: "mamba" or "attention", a layer of
    one period; the model is whole periods, one after another."""
    n_ssm = period.count("mamba")
    a_period = lambda stack, n: jax.tree.map(
        lambda a: a.reshape((-1, n) + a.shape[1:]), stack)

    def one(x, stacks):
        ssm, att = stacks
        i_ssm = i_att = 0
        states = []
        for kind in period:
            if kind == "attention":
                lp = jax.tree.map(lambda a: a[i_att], att)
                y = _attention(x, lp, head_dim=head_dim, scale=attn_scale,
                               eps=eps)
                i_att += 1
            else:
                lp = jax.tree.map(lambda a: a[i_ssm], ssm)
                y, S = _mamba(x, lp, heads=heads, state=state, eps=eps)
                states.append(S)
                i_ssm += 1
            h = x + res_mult * y
            x = h + res_mult * _mlp(h, lp, eps)
        return x, jnp.stack(states)

    with jax.default_matmul_precision("highest"):
        x, states = jax.lax.scan(
            one, emb_mult * f32(params["embed"][tokens]),
            (a_period(params["ssm_blocks"], n_ssm),
             a_period(params["blocks"], len(period) - n_ssm)))
        return x, states.reshape((-1,) + states.shape[2:])


def logits(params, tokens, start, count, *, logits_scale, **kw):
    """-> float32 logits [count, V] of the ``count`` positions from
    ``start``; position p predicts p + 1."""
    x, _ = hidden(params, tokens, **kw)
    with jax.default_matmul_precision("highest"):
        x = jax.lax.dynamic_slice_in_dim(x, start, count)
        x = _rms_norm(x, params["final_norm"], kw["eps"])
        return x @ f32(params["embed"]).T / logits_scale


def forward(params, tokens, **kw):
    """The whole forward, once: tokens [T] -> logits [T, V].  What the
    CPU tests hold the system to."""
    return logits(params, tokens, 0, tokens.shape[0], **kw)

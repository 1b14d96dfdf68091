"""Qwen3-Next (``model_type: qwen3_next``), plain: float32, the linear
layers' recurrence token by token, no cache, no chunks, no kernels.

Layer ``l`` of ``L`` (``N`` = the zero-centred RMSNorm ``x / sqrt(mean
x^2 + eps) * (1 + w)``): ``h = x + Mixer_l(N(x))``, ``y = h + MoE(N(h))``;
``Mixer_l`` is gated attention where ``(l + 1) % interval == 0``, else
Gated DeltaNet.  Final ``N``, untied head.

Gated attention on ``a = N(x)``: ``q = N_head(a W_q)``, ``k = N_head(a
W_k)``, ``v = a W_v``; RoPE (rotate-half, no scaling) on the first
``rotary`` numbers of a head; causal softmax at ``head^-1/2``; ``out =
(attn * sigmoid(a W_g)) W_o``.  No biases.

Gated DeltaNet on ``a``: ``[q, k, v, z] = a W_qkvz``, ``[b, a'] = a
W_ba``; the channels of ``[q, k, v]`` pass a depthwise causal convolution
(``taps`` taps, the last on the current token, no bias) and SiLU;
``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a' + dt_bias)``; q, k
L2-normalised a head (``x / sqrt(sum x^2 + 1e-6)``), q scaled by
``Dk^-1/2``; q/k head ``j // (Hv / Hk)`` serves value head ``j``.  For
each value head, ``S_0 = 0`` [Dk, Dv]::

    S' = e^g_t S_(t-1);  u_t = beta_t (v_t - S'^T k_t);
    S_t = S' + k_t u_t^T;  o_t = S_t^T q_t

``y = (o_t / sqrt(mean o_t^2 + eps) * w) * SiLU(z_t)`` over a head's Dv
numbers, then ``W_out``.

MoE on ``m``: ``p = softmax(m W_r)`` over all the experts the router
has, the ``top_k`` largest divided by their sum, ``y = sum p_e
SwiGLU_e(m)`` over the experts HELD (``first .. first + Eh``, what the
tree's stacks hold: one rank's share of an expert-parallel deployment,
what the absent experts would add left out) ``+ sigmoid(m w_s)
SwiGLU_shared(m)``.

Parameter names are those of the tree the system is given
(``gdn_blocks/*`` the linear layers and ``blocks/*`` the attention
layers, each stacked in the model's order, experts ``[L, Eh, ...]``).
Two passes, as ``reference/pangu_ultra_moe.py`` and for its reason:
``carry`` runs the whole sequence and keeps what a later stretch needs of
each layer (an attention layer's keys and values; a linear layer's state
and convolution rows as they stand before position ``start``); ``logits``
runs a stretch of positions from there and may ``swap`` the k-th expert
for the (k+1)-th at chosen positions of chosen layers.
"""

import jax
import jax.numpy as jnp

from benchmark.reference.common import causal_attention

EXPERT_WEIGHTS = ("w1", "w3", "w2")

f32 = lambda a: a.astype(jnp.float32)


def _layer(blocks, at):
    """Layer ``at`` of a stack, its experts left in the stack."""
    return {n: a if n in EXPERT_WEIGHTS else a[at]
            for n, a in blocks.items()}


def _norm1p(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * (1 + f32(w))


def _rope(x, pos, theta):
    """x: [N, H, R] at positions ``pos`` [N]; halves paired."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(x, lp, pos, cached, *, head_dim, rotary, rope_theta, eps):
    """x: [N, d] at positions ``pos`` -> (the mixer's output, this
    stretch's (k, v)).  ``cached``: None, or the layer's (k, v) over the
    whole sequence, in which this stretch's rows are replaced."""
    N = x.shape[0]
    a = _norm1p(x, lp["attn_norm"], eps)
    heads = lambda y: y.reshape(N, -1, head_dim)
    rot = lambda t: jnp.concatenate(
        [_rope(t[..., :rotary], pos, rope_theta), t[..., rotary:]], -1)
    q = rot(_norm1p(heads(a @ f32(lp["wq"])), lp["q_norm"], eps))
    k = rot(_norm1p(heads(a @ f32(lp["wk"])), lp["k_norm"], eps))
    v = heads(a @ f32(lp["wv"]))
    keys, values = k, v
    if cached is not None:
        keys = jax.lax.dynamic_update_slice_in_dim(cached[0], k, pos[0], 0)
        values = jax.lax.dynamic_update_slice_in_dim(cached[1], v, pos[0], 0)
    o = causal_attention(q, keys, values, pos[0]).reshape(N, -1)
    return (o * jax.nn.sigmoid(a @ f32(lp["wg"]))) @ f32(lp["wo"]), (k, v)


def recurrence(q, k, v, g, beta, S, snap_at):
    """The gated delta rule, a token at a time: q, k [N, H, Dk], v [N,
    H, Dv], g, beta [N, H], S [H, Dk, Dv] -> (o [N, H, Dv], S after all
    N, S as it stood before token ``snap_at``)."""
    def step(carry, t):
        S, snap = carry
        q, k, v, g, beta, i = t
        snap = jnp.where(i == snap_at, S, snap)
        S = jnp.exp(g)[:, None, None] * S
        u = beta[:, None] * (v - jnp.einsum("hkv,hk->hv", S, k))
        S = S + k[:, :, None] * u[:, None, :]
        return (S, snap), jnp.einsum("hkv,hk->hv", S, q)

    (S, snap), o = jax.lax.scan(step, (S, S),
                                (q, k, v, g, beta, jnp.arange(q.shape[0])))
    return o, S, jnp.where(snap_at >= q.shape[0], S, snap)


def _delta_net(x, lp, held, snap_at, *, k_heads, v_heads, k_dim, v_dim,
               eps):
    """x: [N, d] -> (the mixer's output, (convolution rows, S) as they
    stand before token ``snap_at`` of this stretch).  ``held``: the
    (rows [taps - 1, channels], S) the stretch starts from."""
    N = x.shape[0]
    Kd, Vd = k_heads * k_dim, v_heads * v_dim
    rows, S = held
    taps = rows.shape[0] + 1
    a = _norm1p(x, lp["attn_norm"], eps)
    qkvz = a @ f32(lp["w_qkvz"])
    mixed, z = qkvz[:, :2 * Kd + Vd], qkvz[:, 2 * Kd + Vd:]
    ba = a @ f32(lp["w_ba"])
    beta = jax.nn.sigmoid(ba[:, :v_heads])
    g = -jnp.exp(f32(lp["A_log"])) * jax.nn.softplus(
        ba[:, v_heads:] + f32(lp["dt_bias"]))
    seen = jnp.concatenate([rows, mixed])
    w = f32(lp["conv_w"])
    y = jax.nn.silu(sum(seen[i:i + N] * w[i] for i in range(taps)))
    l2 = lambda t: t / jnp.sqrt((t * t).sum(-1, keepdims=True) + 1e-6)
    each = v_heads // k_heads
    q = jnp.repeat(l2(y[:, :Kd].reshape(N, k_heads, k_dim)), each, 1) \
        * k_dim ** -0.5
    k = jnp.repeat(l2(y[:, Kd:2 * Kd].reshape(N, k_heads, k_dim)), each, 1)
    v = y[:, 2 * Kd:].reshape(N, v_heads, v_dim)
    o, _, snap = recurrence(q, k, v, g, beta, S, snap_at)
    o = o / jnp.sqrt((o * o).mean(-1, keepdims=True) + eps) \
        * f32(lp["gdn_norm"])
    y = (o.reshape(N, -1) * jax.nn.silu(z)) @ f32(lp["w_out"])
    return y, (jax.lax.dynamic_slice_in_dim(seen, snap_at, taps - 1), snap)


def _swiglu(h, w1, w3, w2):
    return (jax.nn.silu(h @ f32(w1)) * (h @ f32(w3))) @ f32(w2)


def route(h, gate, top_k, normalize, swap=None):
    """-> (weights [N, k], experts [N, k], margin [N]): softmax over all
    the experts, the top k (the (k+1)-th in the k-th's place where
    ``swap``), divided by their sum.  The margin is the gap between the
    k-th and the (k+1)-th router logit as a share of the largest
    logit's magnitude."""
    z = h @ f32(gate)
    p = jax.nn.softmax(z, axis=-1)
    top, idx = jax.lax.top_k(z, top_k + 1)
    margin = (top[:, top_k - 1] - top[:, top_k]) / jnp.abs(z).max(-1)
    if swap is None:
        idx = idx[:, :top_k]
    else:
        idx = jnp.concatenate(
            [idx[:, :top_k - 1],
             jnp.where(swap, idx[:, top_k], idx[:, top_k - 1])[:, None]], -1)
    w = jnp.take_along_axis(p, idx, axis=-1)
    if normalize:
        w = w / w.sum(-1, keepdims=True)
    return w, idx, margin


def held_part(h, lp, at, w, idx, first):
    """What the experts held contribute: sum over them of the router's
    weight (zero where it did not choose the expert) times the expert."""
    def expert(y, e):
        share = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)
        out = _swiglu(h, *(lp[n][at, e] for n in EXPERT_WEIGHTS))
        return y + share[:, None] * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        jnp.arange(lp["w1"].shape[1]))
    return y


def _ffn(x, lp, at, swap, *, top_k, first, normalize, eps):
    h = _norm1p(x, lp["mlp_norm"], eps)
    w, idx, margin = route(h, lp["gate"], top_k, normalize, swap)
    y = held_part(h, lp, at, w, idx, first) \
        + jax.nn.sigmoid(h @ f32(lp["shared_gate"])) \
        * _swiglu(h, lp["sw1"], lp["sw3"], lp["sw2"])
    return x + y, margin


_MOE = ("top_k", "first", "normalize")
_ATTN = ("head_dim", "rotary", "rope_theta")
_GDN = ("k_heads", "v_heads", "k_dim", "v_dim")


def _split(kw):
    pick = lambda names: dict({n: kw[n] for n in names}, eps=kw["eps"])
    return pick(_ATTN), pick(_GDN), pick(_MOE)


def _zero_state(params, gdn):
    lin = params["gdn_blocks"]
    return (jnp.zeros((lin["conv_w"].shape[1] - 1, lin["conv_w"].shape[2])),
            jnp.zeros((gdn["v_heads"], gdn["k_dim"], gdn["v_dim"])))


def _walk(params, x, pos, interval, held, swap, snap_at, kw):
    """Every layer in the model's order over the stretch ``x`` at
    ``pos``.  ``held``: a layer each, None (the stretch is the whole
    sequence) or what ``carry`` kept of it.  -> (x, what each layer
    leaves: an attention layer its (k, v), a linear one its (rows, S)
    before ``snap_at``; the routers' margins [L, N])."""
    attn, gdn, moe = _split(kw)
    n_layers = (params["blocks"]["wq"].shape[0] * interval)
    left, margins = [], []
    for l in range(n_layers):
        full = (l + 1) % interval == 0
        stack = params["blocks" if full else "gdn_blocks"]
        at = l // interval if full else l - l // interval
        lp = _layer(stack, at)
        if full:
            y, keep = _attention(x, lp, pos, held and held[l], **attn)
        else:
            y, keep = _delta_net(
                x, lp, held[l] if held else _zero_state(params, gdn),
                snap_at, **gdn)
        x, margin = _ffn(x + y, lp, at, None if swap is None else swap[l],
                         **moe)
        left.append(keep)
        margins.append(margin)
    return x, left, jnp.stack(margins)


def carry(params, tokens, start, *, interval, **kw):
    """tokens: [T] -> what a stretch that begins at ``start`` needs of
    each layer, as the router's own choice gives it: a list, a layer
    each, of (k, v) [T, KV, D] or (rows, S) before ``start``."""
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"][tokens])
        _, left, _ = _walk(params, x, jnp.arange(tokens.shape[0]), interval,
                           None, None, start, kw)
    return left


def logits(params, tokens, held, start, count, swap, *, interval, **kw):
    """-> (float32 logits [count, V], router margins [L, count]) of the
    ``count`` positions from ``start``, run from ``held`` (what ``carry``
    returned for this ``start``) with their own rows computed anew.
    ``swap``: [L, count] booleans."""
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"][jax.lax.dynamic_slice_in_dim(
            tokens, start, count)])
        x, _, margins = _walk(params, x, start + jnp.arange(count),
                              interval, held, swap, count, kw)
        x = _norm1p(x, params["final_norm"], kw["eps"])
        return x @ f32(params["lm_head"]), margins


def state_after(params, tokens, count, *, interval, **kw):
    """The (rows, S) of every linear layer, stacked in the model's
    order, after the first ``count`` tokens of ``tokens``."""
    left = carry(params, tokens, count, interval=interval, **kw)
    lin = [keep for l, keep in enumerate(left) if (l + 1) % interval]
    return tuple(jnp.stack(part) for part in zip(*lin))


def forward(params, tokens, **kw):
    """The whole forward, once: tokens [T] -> logits [T, V].  What the
    CPU tests hold the system to."""
    n_layers = params["blocks"]["wq"].shape[0] * kw["interval"]
    none = jnp.zeros((n_layers, tokens.shape[0]), bool)
    return logits(params, tokens, None, 0, tokens.shape[0], none, **kw)[0]

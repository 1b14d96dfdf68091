"""Laguna (``model_type: laguna``), plain: float32, whole sequences, an
explicit mask a layer kind, no cache, no ring, no chunks, no kernels.

``RMS(x) = x / sqrt(mean x^2 + eps) * w``; no bias anywhere.  ``x =
E[token]``; layer ``l``: ``h = x + Attn_l(RMS(x))``, ``y = h +
FFN_l(RMS(h))``; ``logits = RMS(x) W_head``.

``Attn_l`` on ``a = RMS(x)``: ``q, k, v = a W_q, a W_k, a W_v`` in heads
of ``head_dim`` (as many query heads as ``W_q`` has, over the K/V heads
``W_k`` has); q and k rotated, halves paired, by the layer kind's
table; softmax of ``q k^T / sqrt(head_dim)`` under the kind's mask: a
full layer's query at ``p`` sees the keys at ``0 .. p``, a sliding
layer's the keys at ``p - window + 1 .. p``; ``g = sigmoid(a W_g)``, one
number a head, multiplies the head's output; ``W_o``.

Tables.  Full: the first ``R`` numbers of a head (``rotary``) turn by
``inv_i = (f_i / factor) r_i + f_i (1 - r_i)``, ``f_i = theta^(-2i/R)``,
``r_i = clip((i - lo) / (hi - lo), 0, 1)``, ``lo = floor(c(beta_fast))``,
``hi = ceil(c(beta_slow))``, ``c(n) = R ln(original / (2 pi n)) / (2 ln
theta)``, clipped to 0 .. R - 1; ``cos`` and ``sin`` both times
``attention_factor``; the other numbers of the head pass.  Sliding: the
whole head, ``inv_i = theta_s^(-2i/head_dim)``, no factor.

``FFN_0``: ``(SiLU(x W_1) * (x W_3)) W_2``.  The others: ``s = sigmoid(m
W_r)`` over all the experts the router has, the top k, ``w = scale * s /
sum(s)``, ``y = sum w_e E_e(m) + E_shared(m)`` over the experts HELD
(``first .. first + Eh``, what the tree's stacks hold): one rank's share
of an expert-parallel deployment; what the absent experts would add is
left out.

Parameter names are those of the tree the system is given:
``lead_blocks/*`` (layer 0), then periods of ``period`` kinds, a sliding
layer the next of ``win_blocks/*`` and a full one the next of
``blocks/*``, experts stacked ``[L, Eh, ...]``.  Two passes, as
``reference/pangu_ultra_moe.py`` and for its reason: ``keys_values`` runs
the whole sequence and keeps every layer's ``(k, v)``; ``logits`` runs a
stretch of positions against them and may ``swap`` the k-th expert for
the (k+1)-th at chosen positions of chosen expert layers.
"""

import math

import jax
import jax.numpy as jnp

EXPERT_WEIGHTS = ("w1", "w3", "w2")
Q_BLOCK = 256       # queries a block, one K/V head's query heads a pass

f32 = lambda a: a.astype(jnp.float32)


def _layer(blocks, at):
    """Layer ``at`` of a stack, its experts left in the stack."""
    return {n: a if n in EXPERT_WEIGHTS and a.ndim == 4 else a[at]
            for n, a in blocks.items()}


def _rms_norm(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * f32(w)


def yarn_inv_freq(R, theta, factor, original, beta_fast, beta_slow):
    """The full layers' ``R / 2`` frequencies, the formula above."""
    c = lambda n: R * math.log(original / (2 * math.pi * n)) \
        / (2 * math.log(theta))
    lo = max(math.floor(c(beta_fast)), 0)
    hi = min(math.ceil(c(beta_slow)), R - 1)
    if lo == hi:
        hi += 0.001
    out = []
    for i in range(R // 2):
        f = theta ** (-2.0 * i / R)
        r = min(max((i - lo) / (hi - lo), 0.0), 1.0)
        out.append(f / factor * r + f * (1.0 - r))
    return jnp.asarray(out, jnp.float32)


def _turn(x, pos, inv_freq, gain=1.0):
    """x: [N, H, D] at positions ``pos`` [N]: its first ``2 *
    len(inv_freq)`` numbers rotated, halves paired, ``cos`` and ``sin``
    times ``gain``; the rest pass."""
    half = inv_freq.shape[0]
    ang = pos.astype(jnp.float32)[:, None, None] * inv_freq
    cos, sin = gain * jnp.cos(ang), gain * jnp.sin(ang)
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def mask(kind, qpos, kpos, window):
    """[N, T] booleans: which keys a layer of ``kind`` lets each query
    see."""
    seen = kpos[None, :] <= qpos[:, None]
    if kind == "sliding":
        seen &= kpos[None, :] > qpos[:, None] - window
    return seen


def _attend(q, k, v, kind, first, window):
    """q: [N, H, D] at positions first..first+N; k, v [T, KV, D] of the
    whole sequence -> [N, H, D].  The mask is the whole ``[N, T]`` one;
    the scores go through a K/V head's query heads and ``Q_BLOCK``
    queries at a time, so that those of a 32k-token sequence are 0.3 GB
    and not 300."""
    N, H, D = q.shape
    T, KV = k.shape[:2]
    blk = Q_BLOCK if N % Q_BLOCK == 0 else N
    seen = mask(kind, first + jnp.arange(N), jnp.arange(T), window)

    def group(args):
        qg, kg, vg = args                   # [N, G, D], [T, D], [T, D]

        def block(args):
            qb, see = args                  # [blk, G, D], [blk, T]
            s = jnp.einsum("qgd,td->gqt", qb, kg) / jnp.sqrt(jnp.float32(D))
            p = jax.nn.softmax(jnp.where(see[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("gqt,td->qgd", p, vg)

        out = jax.lax.map(block, (qg.reshape(N // blk, blk, H // KV, D),
                                  seen.reshape(N // blk, blk, T)))
        return out.reshape(N, H // KV, D)

    out = jax.lax.map(group, (
        jnp.moveaxis(q.reshape(N, KV, H // KV, D), 1, 0),
        jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(N, H, D)


def _attention(x, lp, kind, pos, cached, *, head_dim, window, rotary,
               theta_full, yarn, attention_factor, theta_sliding, eps):
    """x: [N, d] at positions ``pos`` -> (x after attention, this
    stretch's (k, v)).  ``cached``: None, or the layer's (k, v) over the
    whole sequence, in which this stretch's rows are replaced."""
    N = x.shape[0]
    a = _rms_norm(x, lp["attn_norm"], eps)
    heads = lambda y: y.reshape(N, -1, head_dim)
    if kind == "sliding":
        half = head_dim // 2
        freqs = theta_sliding ** (
            -jnp.arange(half, dtype=jnp.float32) / half)
        turn = lambda t: _turn(t, pos, freqs)
    else:
        freqs = yarn_inv_freq(rotary, theta_full, *yarn)
        turn = lambda t: _turn(t, pos, freqs, attention_factor)
    q = turn(jnp.einsum("nd,hkd->nhk", a, f32(lp["wq"])))    # both kept
    k = turn(jnp.einsum("nd,hkd->nhk", a, f32(lp["wk"])))    # [H, D, d]
    v = heads(a @ f32(lp["wv"]))
    keys, values = k, v
    if cached is not None:
        keys = jax.lax.dynamic_update_slice_in_dim(cached[0], k, pos[0], 0)
        values = jax.lax.dynamic_update_slice_in_dim(cached[1], v, pos[0], 0)
    o = _attend(q, keys, values, kind, pos[0], window)
    g = jax.nn.sigmoid(a @ f32(lp["wg"]))                    # [N, H]
    return x + (o * g[..., None]).reshape(N, -1) @ f32(lp["wo"]), (k, v)


def _swiglu(h, w1, w3, w2):
    return (jax.nn.silu(h @ f32(w1)) * (h @ f32(w3))) @ f32(w2)


def route(h, gate, top_k, scale, normalize, swap=None):
    """-> (weights [N, k], experts [N, k], margin [N]): sigmoid scores
    over all the experts, the top k (the (k+1)-th in the k-th's place
    where ``swap``), divided by their sum and multiplied by ``scale``.
    The margin is the gap between the k-th and the (k+1)-th router logit
    as a share of the largest logit's magnitude."""
    z = h @ f32(gate)
    top, idx = jax.lax.top_k(z, top_k + 1)
    margin = (top[:, top_k - 1] - top[:, top_k]) / jnp.abs(z).max(-1)
    if swap is None:
        top, idx = top[:, :top_k], idx[:, :top_k]
    else:
        last = lambda a: jnp.where(swap, a[:, top_k],
                                   a[:, top_k - 1])[:, None]
        top = jnp.concatenate([top[:, :top_k - 1], last(top)], -1)
        idx = jnp.concatenate([idx[:, :top_k - 1], last(idx)], -1)
    s = jax.nn.sigmoid(top)
    if normalize:
        s = s / (s.sum(-1, keepdims=True) + 1e-20)
    return s * scale, idx, margin


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


def expert_ffn(x, lp, at, swap, *, top_k, first, scale, normalize, eps,
               shared=True):
    """-> (x + the held experts' part (+ the shared expert), margins)."""
    h = _rms_norm(x, lp["mlp_norm"], eps)
    w, idx, margin = route(h, lp["gate"], top_k, scale, normalize, swap)
    y = held_part(h, lp, at, w, idx, first)
    if shared:
        y = y + _swiglu(h, lp["sw1"], lp["sw3"], lp["sw2"])
    return x + y, margin


def _dense_ffn(x, lp, eps):
    h = _rms_norm(x, lp["mlp_norm"], eps)
    return x + _swiglu(h, lp["w1"], lp["w3"], lp["w2"])


_MOE = ("top_k", "first", "scale", "normalize")


def _split(kw):
    moe = dict({k: kw[k] for k in _MOE}, eps=kw["eps"])
    attn = {k: v for k, v in kw.items() if k not in _MOE and k != "period"}
    return attn, moe


def layers(params, period):
    """The model's layers in order: (kind, stack's name, index in it)."""
    out, at = [("full", "lead_blocks", 0)], {"sliding": 0, "full": 0}
    n = params["win_blocks"]["wq"].shape[0] // period.count("sliding")
    for _ in range(n):
        for kind in period:
            out.append((kind, "win_blocks" if kind == "sliding"
                        else "blocks", at[kind]))
            at[kind] += 1
    return out


def _walk(params, x, pos, cache, swap, kw):
    """Every layer over the stretch ``x`` at ``pos``.  ``cache``: None
    (the stretch is the whole sequence) or a layer each its (k, v).  ->
    (x, each layer's (k, v) of the stretch, the routers' margins
    [expert layers, N])."""
    attn, moe = _split(kw)
    left, margins = [], []
    for l, (kind, name, at) in enumerate(layers(params, kw["period"])):
        lp = _layer(params[name], at)
        x, kv = _attention(x, lp, kind, pos, cache and cache[l], **attn)
        left.append(kv)
        if name == "lead_blocks":
            x = _dense_ffn(x, lp, kw["eps"])
            continue
        x, margin = expert_ffn(x, lp, at,
                               None if swap is None else swap[l - 1], **moe)
        margins.append(margin)
    return x, left, jnp.stack(margins)


def keys_values(params, tokens, **kw):
    """tokens: [T] -> every layer's (k, v) [T, KV, D] of the whole
    sequence, in the model's order, as the router's own choice gives
    them."""
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"][tokens])
        return _walk(params, x, jnp.arange(tokens.shape[0]), None, None,
                     kw)[1]


def logits(params, tokens, cache, start, count, swap, **kw):
    """-> (float32 logits [count, V], router margins [expert layers,
    count]) of the ``count`` positions from ``start``, run against
    ``cache`` (what ``keys_values`` returned; None: ``start`` is 0 and
    ``count`` the whole sequence) with their own rows computed anew.
    ``swap``: [expert layers, count] booleans."""
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"][jax.lax.dynamic_slice_in_dim(
            tokens, start, count)])
        x, _, margins = _walk(params, x, start + jnp.arange(count), cache,
                              swap, kw)
        x = _rms_norm(x, params["final_norm"], kw["eps"])
        return x @ f32(params["lm_head"]), margins


def forward(params, tokens, **kw):
    """The whole forward, once: tokens [T] -> logits [T, V].  What the
    CPU tests hold the system to."""
    return logits(params, tokens, None, 0, tokens.shape[0], None, **kw)[0]

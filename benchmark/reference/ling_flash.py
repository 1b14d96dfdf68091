"""Ling-3.0-flash-VL's language model, plain: float32, the KDA layers'
recurrence a token at a time (no chunks), latent attention in its
unabsorbed per-head form over the whole sequence, no cache, no kernels.

Layer ``l`` of ``L`` (``RMS`` = RMSNorm with its own gain, eps inside
the root): ``h = x + Mixer_l(RMS(x))``, ``y = h + F_l(RMS(h))``.
``Mixer_l`` is latent attention where ``(l + 1) % group == 0``, else Kimi
Delta Attention; ``F_l`` a SwiGLU in the first ``dense`` layers, else the
expert layer.  Final ``RMS``, untied head.

KDA on ``a`` (arXiv:2510.26692, H heads of Dk = Dv): ``[q, k, v] = a
W_qkv``; the channels pass a depthwise causal convolution (``taps``
taps, the last on the current token, no bias) and SiLU; q, k
L2-normalised a head (``x / sqrt(sum x^2 + 1e-6)``), q scaled by
``Dk^-1/2``; ``g = lower * sigmoid(exp(A_log) (a W_f + b_f))``, a number
a key channel in ``(lower, 0)``; ``beta = sigmoid(a w_b)`` a head.  For
each head, ``S_0 = 0`` [Dk, Dv]::

    S' = Diag(e^g_t) S_(t-1);  u_t = beta_t (v_t - S'^T k_t);
    S_t = S' + k_t u_t^T;  o_t = S_t^T q_t

``y = (o_t / sqrt(mean o_t^2 + eps) * w) sigmoid(a w_g)`` (the gate one
number a head), then ``W_out``.

Latent attention on ``a``: ``q = a W_q`` in heads of ``[nope | rope]``;
``[c_kv | k_r] = a W_kva``, ``c = RMS(c_kv)``; per head ``k_nope = c
W_UK``, ``v = c W_UV``; ``k_rope = RoPE(k_r)``, one for all heads; scores
``(q_nope . k_nope + RoPE(q_rope) . k_rope) / sqrt(Dn + Dr)``, causal;
``out = (attn * sigmoid(a w_g)) W_o``, the gate one number a head.  RoPE
pairs the halves of its 64 numbers, no scaling.

The expert layer on ``m``: ``s = sigmoid(m W_r)`` over all the experts
the router has; the choice is by ``s + b``: the experts lie in ``groups``
groups of consecutive experts, a group's score is the sum of its two
largest, the ``keep`` best groups stay and the ``top_k`` largest inside
them are chosen; ``w = scale * s / sum(s)`` over the chosen; ``y = sum
w_e SwiGLU_e(m)`` over the experts HELD (``first .. first + Eh``, what the
tree's stacks hold: one rank's share of an expert-parallel deployment,
what the absent experts would add left out) ``+ SwiGLU_shared(m)``.

Departures from the published description: none in the mathematics; the
published ``q_proj``/``k_proj``/``v_proj`` stand side by side as
``w_qkv``, the gate's ``f_proj`` as stored, ``[outputs, d]`` (``w_f``),
and ``kv_b_proj`` as its halves ``w_uk``/``w_uv`` (the same numbers),
the vision tower and the next-token module are absent, and the
SwiGLU clamps (``expert_swiglu_limit_list``) are 0 = none on every layer
a configuration here keeps.

Parameter names are those of the tree the system is given
(``lead_blocks/*`` the leading dense KDA layers, ``kda_blocks/*`` the
other KDA layers, ``blocks/*`` the attention layers, each stacked in the
model's order, experts ``[L, Eh, ...]``).  Two passes, as
``reference/qwen3_next.py`` and for its reason: ``carry`` runs the whole
sequence (a KDA layer ``M_BLOCK`` rows at a time) and keeps what a later
stretch needs of each layer (an attention layer's ``(c, k_rope)``; a KDA
layer's state and convolution rows as they stand before position
``start``); ``logits`` runs a stretch of positions
from there and may ``swap`` the k-th expert for the (k+1)-th, or the
last kept group for the next, at chosen positions of chosen layers.
Both walk the layers as a scan of one period (:func:`_walk`): a program
holds a layer of each kind, not the model's depth of them (unrolled, a
run's check wrote over 140 MB of programs into the chip machine's 192
MiB compile cache and put the serving programs out: PR 51's review).
"""

import jax
import jax.numpy as jnp

from benchmark.reference.pangu_ultra_moe import (_attend, _layer, _rms_norm,
                                                 _rope, _swiglu, held_part)

f32 = lambda a: a.astype(jnp.float32)

# rows of a KDA layer at once: the check pads its longest request
# (16,384 tokens) to 32,768 rows, where a layer's float32 projections,
# taps and gates would be 6-7 GiB beside 9.3 GiB of weights
M_BLOCK = 4096


def recurrence(q, k, v, g, beta, S, snap_at):
    """The channel-gated delta rule, a token at a time: q, k, g [N, H,
    Dk], v [N, H, Dv], beta [N, H], S [H, Dk, Dv] -> (o [N, H, Dv], S
    after all N, S as it stood before token ``snap_at``)."""
    def step(carry, t):
        S, snap = carry
        q, k, v, g, beta, i = t
        snap = jnp.where(i == snap_at, S, snap)
        S = jnp.exp(g)[:, :, None] * S
        u = beta[:, None] * (v - jnp.einsum("hkv,hk->hv", S, k))
        S = S + k[:, :, None] * u[:, None, :]
        return (S, snap), jnp.einsum("hkv,hk->hv", S, q)

    (S, snap), o = jax.lax.scan(step, (S, S),
                                (q, k, v, g, beta, jnp.arange(q.shape[0])))
    return o, S, jnp.where(snap_at >= q.shape[0], S, snap)


def kda(x, lp, held, snap_at, *, heads, lower, eps):
    """x: [N, d] -> (the mixer's output, (convolution rows, S) as they
    stand before token ``snap_at`` of this stretch, and as the stretch
    leaves them).  ``held``: the (rows [taps - 1, channels], S) the
    stretch starts from."""
    N = x.shape[0]
    rows, S = held
    taps = rows.shape[0] + 1
    a = _rms_norm(x, lp["attn_norm"], eps)
    seen = jnp.concatenate([rows, a @ f32(lp["w_qkv"])])
    w = f32(lp["conv_w"])
    y = jax.nn.silu(sum(seen[i:i + N] * w[i] for i in range(taps)))
    q, k, v = y.reshape(N, 3, heads, -1).swapaxes(0, 1)
    l2 = lambda t: t / jnp.sqrt((t * t).sum(-1, keepdims=True) + 1e-6)
    D = q.shape[-1]
    sharp = jnp.exp(f32(lp["A_log"]))[:, None]
    g = lower * jax.nn.sigmoid(
        sharp * (a @ f32(lp["w_f"]).T + f32(lp["b_f"])).reshape(N, heads, D))
    beta = jax.nn.sigmoid(a @ f32(lp["w_b"]))
    o, S, snap = recurrence(l2(q) * D ** -0.5, l2(k), v, g, beta, S, snap_at)
    o = o / jnp.sqrt((o * o).mean(-1, keepdims=True) + eps) \
        * f32(lp["o_norm"])
    o = o * jax.nn.sigmoid(a @ f32(lp["w_g"]))[..., None]
    return o.reshape(N, -1) @ f32(lp["w_out"]), (
        jax.lax.dynamic_slice_in_dim(seen, snap_at, taps - 1), snap), (
        seen[N:], S)


def kda_in_blocks(x, lp, held, snap_at, **kw):
    """:func:`kda` over a long stretch ``M_BLOCK`` rows at a time, each
    block starting from what the one before left: the same recurrence
    over the same rows, and what a layer holds at once is bounded
    whatever the sequence's length (whole blocks: the check pads to a
    power of two; a shorter or ragged stretch goes through at once).
    -> (the mixer's output, (rows, S) before token ``snap_at``)."""
    N = x.shape[0]
    if N <= M_BLOCK or N % M_BLOCK:
        return kda(x, lp, held, snap_at, **kw)[:2]

    def block(carry, at):
        held, snap = carry
        xb, lo = at
        y, before, held = kda(xb, lp, held, snap_at - lo, **kw)
        # the last block that starts at or before ``snap_at`` holds it
        # (or, past the stretch's end, what the last block leaves)
        snap = jax.tree.map(lambda new, old: jnp.where(snap_at >= lo, new,
                                                       old), before, snap)
        return (held, snap), y

    (_, snap), y = jax.lax.scan(
        block, (held, held),
        (x.reshape(-1, M_BLOCK, x.shape[1]), jnp.arange(0, N, M_BLOCK)))
    return y.reshape(N, -1), snap


def _attention(x, lp, pos, cached, *, heads, nope, rope_theta, eps):
    """x: [N, d] at positions ``pos`` -> (the mixer's output, this
    stretch's (c, k_rope)).  ``cached``: None, or the layer's (c,
    k_rope) over the whole sequence, in which this stretch's rows are
    replaced."""
    N = x.shape[0]
    C = lp["w_uk"].shape[0]
    a = _rms_norm(x, lp["attn_norm"], eps)
    q = (a @ f32(lp["wq"])).reshape(N, heads, -1)
    kv = a @ f32(lp["wkv_a"])
    c = _rms_norm(kv[:, :C], lp["kv_norm"], eps)
    k_rope = _rope(kv[:, C:], pos, rope_theta)
    rows, ropes = c, k_rope
    if cached is not None:
        rows = jax.lax.dynamic_update_slice_in_dim(cached[0], c, pos[0], 0)
        ropes = jax.lax.dynamic_update_slice_in_dim(cached[1], k_rope,
                                                    pos[0], 0)
    o = _attend(q[..., :nope], _rope(q[..., nope:], pos, rope_theta), rows,
                ropes, f32(lp["w_uk"]).reshape(C, heads, -1),
                f32(lp["w_uv"]).reshape(C, heads, -1), pos[0])
    o = o * jax.nn.sigmoid(a @ f32(lp["w_g"]))[..., None]
    return o.reshape(N, -1) @ f32(lp["wo"]), (c, k_rope)


def _logit_gap(a, b, s, z):
    """The gap between two choice scores in the router logit's units
    (over the sigmoid's slope at ``s``), as a share of the largest
    logit's magnitude."""
    return (a - b) / (s * (1 - s)) / jnp.abs(z).max(-1)


def route(h, gate, bias, top_k, groups, scale, normalize, swap=None):
    """-> (weights [N, k], experts [N, k], margins [2, N]): sigmoid
    scores over all the experts; by ``s + bias`` the ``keep`` best of
    ``n`` groups (``groups``; a group's score the sum of its two
    largest) and the top k inside them; the chosen scores divided by
    their sum and multiplied by ``scale``.  ``swap`` [N] int: 1 puts the
    (k+1)-th expert in the k-th's place, 2 the next group in the last
    kept one's.  The margins: of the k-th expert over the (k+1)-th, and
    of the last kept group over the next, both in logit units at the
    k-th expert's score."""
    z = h @ f32(gate)
    s = jax.nn.sigmoid(z)
    n, keep = groups
    by_group = (s + f32(bias)).reshape(s.shape[0], n, -1)
    best, order = jax.lax.top_k(
        jax.lax.top_k(by_group, 2)[0].sum(-1), keep + 1)
    if swap is not None:
        order = order.at[:, keep - 1].set(jnp.where(
            swap == 2, order[:, keep], order[:, keep - 1]))
    kept = jnp.any(order[:, :keep, None] == jnp.arange(n), axis=1)
    choice = jnp.where(kept[..., None], by_group, -jnp.inf).reshape(s.shape)
    top, idx = jax.lax.top_k(choice, top_k + 1)
    s_k = jnp.take_along_axis(s, idx[:, top_k - 1:top_k], 1)[:, 0]
    margins = jnp.stack([
        _logit_gap(top[:, top_k - 1], top[:, top_k], s_k, z),
        _logit_gap(best[:, keep - 1], best[:, keep], s_k, z)])
    if swap is not None:
        idx = idx.at[:, top_k - 1].set(jnp.where(
            swap == 1, idx[:, top_k], idx[:, top_k - 1]))
    idx = idx[:, :top_k]
    w = jnp.take_along_axis(s, idx, axis=-1)
    if normalize:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * scale, idx, margins


def expert_ffn(x, lp, at, swap, *, top_k, groups, first, scale, normalize,
               eps):
    h = _rms_norm(x, lp["mlp_norm"], eps)
    w, idx, margins = route(h, lp["gate"], lp["gate_bias"], top_k, groups,
                            scale, normalize, swap)
    y = held_part(h, lp, at, w, idx, first) \
        + _swiglu(h, lp["sw1"], lp["sw3"], lp["sw2"])
    return x + y, margins


def _dense_ffn(x, lp, eps):
    return x + _swiglu(_rms_norm(x, lp["mlp_norm"], eps), lp["w1"],
                       lp["w3"], lp["w2"])


_MOE = ("top_k", "groups", "first", "scale", "normalize")
_KDA = ("heads", "lower")
_MLA = ("heads", "nope", "rope_theta")


def _split(kw):
    pick = lambda names: dict({n: kw[n] for n in names}, eps=kw["eps"])
    return pick(_KDA), pick(_MLA), pick(_MOE)


def _zero_state(params, heads):
    lin = params["kda_blocks"]
    D = lin["w_out"].shape[1] // heads
    return (jnp.zeros((lin["conv_w"].shape[1] - 1, lin["conv_w"].shape[2])),
            jnp.zeros((heads, D, D)))


def n_layers(params, group):
    return params["blocks"]["wq"].shape[0] * group


# what :func:`kda` reads of a layer: the same leaves in both KDA stacks
_MIXER = ("attn_norm", "w_qkv", "conv_w", "A_log", "w_f", "b_f", "w_b",
          "w_g", "o_norm", "w_out")


def _walk(params, x, pos, group, held, swap, snap_at, kw):
    """Every layer in the model's order over the stretch ``x`` at
    ``pos``, as ONE period's program scanned over the periods (a period:
    ``group - 1`` KDA layers, themselves a scan of one layer, then the
    attention layer), so that what is compiled is a layer of each kind
    and not the model's depth of them.  KDA layer ``n`` of the model is
    ``lead_blocks[n]`` with the dense SwiGLU while ``n < dense``, else
    ``kda_blocks[n - dense]`` with the expert layer: the mixer's weights
    are chosen, the mixer is one.  ``held``: None (the stretch is the
    whole sequence) or what ``carry`` kept.  ``swap``: [L, N].  -> (x,
    what the layers leave: ``kda`` the (rows, S) before ``snap_at`` of
    the KDA layers ``[periods, group - 1, ...]``, ``mla`` the attention
    layers' (c, k_rope) ``[periods, N, ...]``; the routers' margins [L,
    2, N], a dense layer's infinite)."""
    kda_kw, mla_kw, moe = _split(kw)
    lead, lin, full = (params[n] for n in ("lead_blocks", "kda_blocks",
                                           "blocks"))
    dense, periods, N = lead["w_qkv"].shape[0], full["wq"].shape[0], \
        x.shape[0]
    if held is None:
        zero = _zero_state(params, kw["heads"])
        held = {"kda": jax.tree.map(lambda a: jnp.broadcast_to(
            a, (periods, group - 1) + a.shape), zero)}
    pick = lambda stack, at: {n: stack[n][at] for n in _MIXER}

    def expert(x, stack, at, swap):
        return expert_ffn(x, _layer(stack, at), at, swap, **moe)

    def linear(x, layer):
        n, before, swap = layer
        at = n - dense
        lp = pick(lin, at) if not dense else jax.lax.cond(
            n < dense, lambda: pick(lead, n), lambda: pick(lin, at))
        y, keep = kda_in_blocks(x, lp, before, snap_at, **kda_kw)
        sparse = lambda x: expert(x, lin, at, swap)
        x, margin = sparse(x + y) if not dense else jax.lax.cond(
            n < dense,
            lambda x: (_dense_ffn(x, _layer(lead, n), kw["eps"]),
                       jnp.full((2, N), jnp.inf)), sparse, x + y)
        return x, (keep, margin)

    def period(x, xs):
        p, before, swap = xs
        x, (kept, margins) = jax.lax.scan(
            linear, x, (p * (group - 1) + jnp.arange(group - 1),
                        before["kda"], swap[:-1]))
        lp = _layer(full, p)
        y, keep = _attention(x, lp, pos, before.get("mla"), **mla_kw)
        x, margin = expert(x + y, full, p, swap[-1])
        return x, ({"kda": kept, "mla": keep},
                   jnp.concatenate([margins, margin[None]]))

    x, (left, margins) = jax.lax.scan(
        period, x, (jnp.arange(periods), held,
                    swap.reshape(periods, group, N)))
    return x, left, margins.reshape(periods * group, 2, N)


def carry(params, tokens, start, *, group, **kw):
    """tokens: [T] -> what a stretch that begins at ``start`` needs of
    each layer, as the router's own choice gives it: ``mla`` the
    attention layers' (c [P, T, C], k_rope [P, T, Dr]), ``kda`` the KDA
    layers' (rows, S) ``[P, group - 1, ...]`` before ``start``."""
    T = tokens.shape[0]
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"][tokens])
        _, left, _ = _walk(
            params, x, jnp.arange(T), group, None,
            jnp.zeros((n_layers(params, group), T), jnp.int32), start, kw)
    return left


def logits(params, tokens, held, start, count, swap, *, group, **kw):
    """-> (float32 logits [count, V], router margins [L, 2, count]) of
    the ``count`` positions from ``start``, run from ``held`` (what
    ``carry`` returned for this ``start``) with their own rows computed
    anew.  ``swap``: [L, count] of 0, 1 (the expert) or 2 (the group)."""
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"][jax.lax.dynamic_slice_in_dim(
            tokens, start, count)])
        x, _, margins = _walk(params, x, start + jnp.arange(count), group,
                              held, swap, count, kw)
        x = _rms_norm(x, params["final_norm"], kw["eps"])
        return x @ f32(params["lm_head"]), margins


def state_after(params, tokens, count, *, group, **kw):
    """The (rows, S) of every KDA layer, stacked in the model's order,
    after the first ``count`` tokens of ``tokens``."""
    left = carry(params, tokens, count, group=group, **kw)
    return jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]),
                        left["kda"])


def forward(params, tokens, **kw):
    """The whole forward, once: tokens [T] -> logits [T, V].  What the
    CPU tests hold the system to."""
    none = jnp.zeros((n_layers(params, kw["group"]), tokens.shape[0]),
                     jnp.int32)
    return logits(params, tokens, None, 0, tokens.shape[0], none, **kw)[0]

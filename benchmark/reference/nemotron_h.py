"""Nemotron-H (``model_type: nemotron_h``), plain: float32, the Mamba-2
layers' recurrence token by token, no pages, no chunks, no kernels.

Every layer is one of three things alone, by its letter in ``pattern``
(``N`` = the RMSNorm ``x / sqrt(mean x^2 + eps) * w``)::

    x_0 = E[token];   x_(l+1) = x_l + F_l(N_l(x_l));   logits = N_f(x_L) W_head

``M``, Mamba-2 on ``a = N(x)`` (``H`` heads of ``P`` channels in ``G``
groups, state ``S``): ``[z | xBC] = a W_in``, ``dt = a W_dt`` (the
published matrix's columns, the last block stored apart); the channels
of ``xBC`` pass a depthwise causal convolution (``taps`` taps, the last
on the current token, WITH bias) and SiLU, and split into ``x`` [H, P],
``B`` [G, S], ``C`` [G, S]; head ``h`` reads group ``h // (H / G)``;
``D_t = softplus(dt + dt_bias)``, no clamp; ``A = -exp(A_log)``, one a
head.  For each head, ``S_0 = 0`` [P, S]::

    S_t = exp(D_t A) S_(t-1) + D_t x_t B_t^T;   o_t = S_t C_t + D x_t

``y = N_group(o_t * SiLU(z_t))``: the gate BEFORE the norm, the norm
over each group's ``H P / G`` channels; then ``W_out``.

``*``, attention on ``a``: ``[q | k | v] = a W_qkv``, no bias, no
position encoding of any kind, causal softmax of ``q k^T / sqrt(head)``
(heads grouped over the KV heads), ``W_o``.

``E``, on ``a``: ``s = sigmoid(a W_g)`` over all the experts the router
has; the ``top_k`` largest of ``s + b`` (``b`` the selection bias);
``w_j = scale * s_j / (sum of the chosen s + 1e-20)``; ``y = sum_j w_j
W_down,j relu(W_up,j a)^2`` over the experts HELD (``first .. first +
Eh``, what the tree's stacks hold: one rank's share of an expert-parallel
deployment, what the absent experts would add left out) ``+ W_sdown
relu(W_sup a)^2``.  The tree stores an expert's ``ffn`` columns with
zeros behind them; this file reads the first ``ffn`` alone.

Parameter names are those of the tree the system is given
(``ssm_blocks/*`` the ``M`` layers, ``blocks/*`` the ``*`` layers,
``moe_blocks/*`` the ``E`` layers, each stacked in the model's order).
Two passes, as ``reference/qwen3_next.py`` and for its reason: ``carry``
runs the whole sequence and keeps what a later stretch needs of each
layer (a ``*`` layer's keys and values; an ``M`` layer's state and
convolution rows as they stand before position ``start``); ``logits``
runs a stretch of positions from there and may ``swap`` the k-th expert
for the (k+1)-th at chosen positions of chosen ``E`` layers.  Each layer
is a jitted function of its own (52 of them written out in one program
compile for minutes); a caller may jit the whole all the same.
"""

import functools

import jax
import jax.numpy as jnp

Q_BLOCK = 256
# Rows of a stretch a Mamba-2 layer is given at once.  Its projections,
# the convolution's rows and a HEAD's B and C stand in float32 for every
# row it is given: 5.4 GiB at the 32,768 rows the check pads a
# 16,384-token prompt with its answer to, beside 9.9 GiB of weights on a
# 15.75 GiB chip.  The recurrence is a token at a time either way.
M_BLOCK = 4096

f32 = lambda a: a.astype(jnp.float32)


def _rms_norm(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * f32(w)


def _relu2(a):
    return jnp.square(jnp.maximum(a, 0.0))


def _highest(fn):
    @functools.wraps(fn)
    def run(*args, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kw)

    return run


@functools.partial(jax.jit, static_argnames=("head_dim", "eps"))
@_highest
def _attention(x, stack, at, pos, cached, *, head_dim, eps):
    """x: [N, d] at positions ``pos`` -> (the layer's output, its (k, v)
    [T, KV, D]).  ``cached``: None (the stretch is the whole sequence) or
    the sequence's (k, v), this stretch's rows of which are computed
    anew."""
    lp = jax.tree.map(lambda a: a[at], stack)
    N = x.shape[0]
    a = _rms_norm(x, lp["attn_norm"], eps)
    qkv = (a @ f32(lp["wqkv"])).reshape(N, -1, head_dim)
    H = lp["wo"].shape[0] // head_dim
    KV = (qkv.shape[1] - H) // 2
    q, k, v = qkv[:, :H], qkv[:, H:H + KV], qkv[:, H + KV:]
    if cached is not None:
        k = jax.lax.dynamic_update_slice_in_dim(cached[0], k, pos[0], 0)
        v = jax.lax.dynamic_update_slice_in_dim(cached[1], v, pos[0], 0)
    blk = Q_BLOCK if N % Q_BLOCK == 0 else N
    qb = q.reshape(N // blk, blk, KV, H // KV, head_dim)
    key_pos = jnp.arange(k.shape[0])

    def one(args):
        qi, first = args
        s = jnp.einsum("qkgd,tkd->kgqt", qi, k) / jnp.sqrt(
            jnp.float32(head_dim))
        seen = key_pos[None, :] <= (first + jnp.arange(blk))[:, None]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v)

    o = jax.lax.map(one, (qb, pos[0] + jnp.arange(N // blk) * blk))
    return o.reshape(N, -1) @ f32(lp["wo"]), (k, v)


def recurrence(x, dt, A, B, C, S, snap_at):
    """The state-space recurrence, a token at a time: x [N, H, P], dt
    [N, H], A [H], B, C [N, H, S] (a head's: its group's), S [H, P, S]
    -> (o [N, H, P] without the skip, S after all N, S as it stood
    before token ``snap_at``)."""
    def step(carry, t):
        S, snap = carry
        x, dt, B, C, i = t
        snap = jnp.where(i == snap_at, S, snap)
        S = jnp.exp(dt * A)[:, None, None] * S \
            + (dt[:, None] * x)[:, :, None] * B[:, None, :]
        return (S, snap), jnp.einsum("hps,hs->hp", S, C)

    (S, snap), o = jax.lax.scan(
        step, (S, S), (x, dt, B, C, jnp.arange(x.shape[0])))
    return o, S, jnp.where(snap_at >= x.shape[0], S, snap)


@functools.partial(jax.jit,
                   static_argnames=("heads", "groups", "state", "eps"))
@_highest
def _mamba(x, stack, at, held, snap_at, *, heads, groups, state, eps):
    """x: [N, d] -> (the layer's output, (convolution rows, S) as they
    stand before token ``snap_at`` of this stretch, the same after all
    of it).  ``held``: the (rows [taps - 1, channels], S [H, P, S]) the
    stretch starts from."""
    lp = jax.tree.map(lambda a: a[at], stack)
    N = x.shape[0]
    rows, S = held
    taps = rows.shape[0] + 1
    a = _rms_norm(x, lp["attn_norm"], eps)
    zx, dt = a @ f32(lp["w_in"]), a @ f32(lp["w_dt"])
    inner = lp["w_out"].shape[0]
    z, xBC = zx[:, :inner], zx[:, inner:]
    seen = jnp.concatenate([rows, xBC])
    w = f32(lp["conv_w"])
    y = jax.nn.silu(sum(seen[i:i + N] * w[i] for i in range(taps))
                    + f32(lp["conv_b"]))
    xs = y[:, :inner].reshape(N, heads, -1)
    a_head = lambda t: jnp.repeat(t.reshape(N, groups, state),
                                  heads // groups, axis=1)
    B = a_head(y[:, inner:inner + groups * state])
    C = a_head(y[:, inner + groups * state:])
    dt = jax.nn.softplus(dt + f32(lp["dt_bias"]))
    o, S, snap = recurrence(xs, dt, -jnp.exp(f32(lp["A_log"])), B, C, S,
                            snap_at)
    o = o + f32(lp["D"])[:, None] * xs
    g = (o.reshape(N, -1) * jax.nn.silu(z)).reshape(N, groups, -1)
    g = g / jnp.sqrt((g * g).mean(-1, keepdims=True) + eps)
    y = (g.reshape(N, -1) * f32(lp["ssm_norm"])) @ f32(lp["w_out"])
    return (y, (jax.lax.dynamic_slice_in_dim(seen, snap_at, taps - 1), snap),
            (seen[N:], S))


def _mamba_in_blocks(x, stack, at, held, snap_at, **ssm):
    """:func:`_mamba` over a stretch of any length, ``M_BLOCK`` rows at
    a time, each block starting from what the one before left: the same
    recurrence over the same rows, and what a layer holds at once is
    bounded whatever the sequence's length.  -> (the layer's output,
    (rows, S) before token ``snap_at``)."""
    ys, snap = [], None
    for lo in range(0, x.shape[0], M_BLOCK):
        y, before, held = _mamba(x[lo:lo + M_BLOCK], stack, at, held,
                                 snap_at - lo, **ssm)
        ys.append(y)
        # the last block that starts at or before ``snap_at`` holds it
        # (or, past the stretch's end, what the last block leaves)
        snap = before if snap is None else jax.tree.map(
            lambda new, old: jnp.where(snap_at >= lo, new, old),
            before, snap)
    return jnp.concatenate(ys), snap


def route(h, gate, bias, top_k, scale, normalize, swap=None):
    """-> (weights [N, k], experts [N, k], margin [N]): sigmoid scores
    over all the experts, the top k of ``s + bias`` (the (k+1)-th in the
    k-th's place where ``swap``), weighted by ``s`` alone, divided by
    their sum and multiplied by ``scale``.  The margin is the gap
    between the k-th and the (k+1)-th ``s + bias`` over a quarter of the
    largest router logit's magnitude: a score moves by at most a quarter
    of what its logit moves by."""
    z = h @ f32(gate)
    s = jax.nn.sigmoid(z)
    top, idx = jax.lax.top_k(s + f32(bias), top_k + 1)
    margin = (top[:, top_k - 1] - top[:, top_k]) / (0.25 * jnp.abs(z).max(-1))
    if swap is None:
        idx = idx[:, :top_k]
    else:
        idx = jnp.concatenate([idx[:, :top_k - 1], jnp.where(
            swap, idx[:, top_k], idx[:, top_k - 1])[:, None]], -1)
    s = jnp.take_along_axis(s, idx, axis=-1)
    if normalize:
        s = s / (s.sum(-1, keepdims=True) + 1e-20)
    return s * scale, idx, margin


def held_part(h, lp, w, idx, first, ffn):
    """What the experts held contribute: sum over them of the router's
    weight (zero where it did not choose the expert) times the expert, at
    its first ``ffn`` columns."""
    def expert(y, e):
        share = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)
        out = _relu2(h @ f32(lp["w_up"][e][:, :ffn])) \
            @ f32(lp["w_down"][e][:ffn])
        return y + share[:, None] * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        jnp.arange(lp["w_up"].shape[0]))
    return y


@functools.partial(jax.jit, static_argnames=(
    "top_k", "first", "scale", "normalize", "ffn", "eps", "shared"))
@_highest
def _experts(x, stack, at, swap, *, top_k, first, scale, normalize, ffn,
             eps, shared=True):
    """-> (the held experts' part (+ the shared expert), margins)."""
    lp = jax.tree.map(lambda a: a[at], stack)
    h = _rms_norm(x, lp["mlp_norm"], eps)
    w, idx, margin = route(h, lp["gate"], lp["gate_bias"], top_k, scale,
                           normalize, swap)
    y = held_part(h, lp, w, idx, first, ffn)
    if shared:
        y = y + _relu2(h @ f32(lp["sw_up"])) @ f32(lp["sw_down"])
    return y, margin


_MOE = ("top_k", "first", "scale", "normalize", "ffn")
_SSM = ("heads", "groups", "state")


def _split(kw):
    pick = lambda names: dict({n: kw[n] for n in names}, eps=kw["eps"])
    return pick(("head_dim",)), pick(_SSM), pick(_MOE)


def _zero_state(params, ssm):
    m = params["ssm_blocks"]
    return (jnp.zeros((m["conv_w"].shape[1] - 1, m["conv_w"].shape[2])),
            jnp.zeros((ssm["heads"], m["w_out"].shape[1] // ssm["heads"],
                       ssm["state"])))


def _walk(params, x, pos, held, swap, snap_at, kw):
    """Every layer in the model's order over the stretch ``x`` at
    ``pos``.  ``held``: a layer each, None (the stretch is the whole
    sequence) or what ``carry`` kept of it.  -> (x, what each layer
    leaves: a ``*`` layer its (k, v), an ``M`` layer its (rows, S)
    before ``snap_at``, an ``E`` layer None; the routers' margins
    [E layers, N])."""
    attn, ssm, moe = _split(kw)
    at = dict.fromkeys("M*E", 0)
    left, margins = [], []
    for l, letter in enumerate(kw["pattern"]):
        i = at[letter]
        at[letter] += 1
        keep = None
        if letter == "*":
            y, keep = _attention(x, params["blocks"], i, pos,
                                 held and held[l], **attn)
        elif letter == "M":
            y, keep = _mamba_in_blocks(
                x, params["ssm_blocks"], i,
                held[l] if held else _zero_state(params, ssm), snap_at,
                **ssm)
        else:
            y, margin = _experts(x, params["moe_blocks"], i,
                                 None if swap is None else swap[i], **moe)
            margins.append(margin)
        x = x + y
        left.append(keep)
    return x, left, jnp.stack(margins)


def carry(params, tokens, start, **kw):
    """tokens: [T] -> what a stretch that begins at ``start`` needs of
    each layer, as the router's own choice gives it: a list, a layer
    each, of (k, v) [T, KV, D], (rows, S) before ``start``, or None."""
    x = f32(params["embed"][tokens])
    _, left, _ = _walk(params, x, jnp.arange(tokens.shape[0]), None, None,
                       start, kw)
    return left


def logits(params, tokens, held, start, count, swap, **kw):
    """-> (float32 logits [count, V], router margins [E layers, count])
    of the ``count`` positions from ``start``, run from ``held`` (what
    ``carry`` returned for this ``start``) with their own rows computed
    anew.  ``swap``: [E layers, count] booleans."""
    x = f32(params["embed"][jax.lax.dynamic_slice_in_dim(
        tokens, start, count)])
    x, _, margins = _walk(params, x, start + jnp.arange(count), held, swap,
                          count, kw)
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, params["final_norm"], kw["eps"])
        return x @ f32(params["lm_head"]), margins


def state_after(params, tokens, count, **kw):
    """The (rows, S) of every ``M`` layer, stacked in the model's order,
    after the first ``count`` tokens of ``tokens``."""
    left = carry(params, tokens, count, **kw)
    ssm = [keep for keep, letter in zip(left, kw["pattern"])
           if letter == "M"]
    return tuple(jnp.stack(part) for part in zip(*ssm))


def forward(params, tokens, **kw):
    """The whole forward, once: tokens [T] -> logits [T, V].  What the
    CPU tests hold the system to."""
    none = jnp.zeros((kw["pattern"].count("E"), tokens.shape[0]), bool)
    return logits(params, tokens, None, 0, tokens.shape[0], none, **kw)[0]

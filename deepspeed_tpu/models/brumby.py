"""Brumby-style decoder (``model_type: brumby``): Qwen3's dense skeleton
with every attention layer replaced by **power retention** of degree 2,
so no layer attends over pages: the family has no pool layer at all.

A layer, per token (``N`` = RMSNorm with a gain, no bias anywhere)::

    h = x + W_o Ret(N(x));   y = h + W_2 (SiLU(W_1 N(h)) * W_3 N(h))

On ``a = N(x)``: ``q = RoPE(N_head(a W_q))`` (H heads), ``k =
RoPE(N_head(a W_k))``, ``v = a W_v`` (KV heads; query head h reads K/V
head ``h // (H / KV)``), ``log g = logsigmoid(a W_g + b_g)`` one number
a K/V head in float32, ``G_t`` its running sum.  The definition (what
``benchmark/reference/brumby.py`` computes)::

    w_ts = (q_t . k_s)^2 exp(G_t - G_s),  s <= t
    Ret_t = sum_s w_ts v_s / (sum_s w_ts + eps)

What is served is the same thing as a recurrence.  With ``phi(a) .
phi(b) = (a . b)^2`` a K/V head keeps ``S = sum_s decay phi(k_s) v_s^T``
and the normaliser ``z = sum_s decay phi(k_s)``, float32 from zero::

    S <- g S + phi(k) v^T;  z <- g z + phi(k);  Ret = phi(q)^T S / (phi(q) . z + eps)

**The layout of phi** (:func:`phi`): the products ``a_i a_j`` by the
lanes' rotations, ``phi(a)[d, i] = w_d a_i a_(i - d mod Dh)`` for ``d =
0 .. Dh/2``, ``w_0 = w_(Dh/2) = 1`` and ``2^(1/2)`` between (every
unordered pair {i, j} stands once at its circular distance, the
distance ``Dh/2`` twice at weight 1): ``Dh/2 + 1`` rows of ``Dh`` lanes,
65 x 128 = 8,320 numbers for the exact 8,256, and a row of it is one lane
rotation and two products of what the chip holds in a register.  The
normaliser is the state of a constant value channel: ``v`` is extended
to ``[v | 1 | 0 x 7]`` (a whole (8, 128) tile of rows), so ``z`` is row
``Dh`` of the same buffer and the same rule moves it.  A K/V head's
state is ``[(Dh/2 + 1) (Dh + 8), Dh]``: for each rotation d a tile
``[Dh + 8, Dh]`` whose row r and lane i hold ``sum decay v_ext[r]
phi(k)[d, i]``.

A decode step is :func:`ret_step` (the rule on a slot's state where it
lies: :func:`ret_rule_in_place` in ``dstpu_state_step``); a prompt chunk
the same in blocks of ``ret_block`` tokens (:func:`ret_chunk_rule` in
XLA, :func:`ret_block_rule` a block on the chip), the state carried on.
Serving only.
"""

from __future__ import annotations

import dataclasses
import functools
import types
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models import llama as _llama
from deepspeed_tpu.models.family import (DecoderFamily, Recurrent, SlotState,
                                         StateRow, chunk_state,
                                         positions_from, step_state)
from deepspeed_tpu.models.llama import rms_norm
from deepspeed_tpu.models.qwen3_next import _mm3, _pair

# rows a state tile keeps behind a head's Dh value rows: the normaliser's
# and zeros, a whole sublane tile
_EXTRA = 8
# phi's weight on a pair of two different lanes, which stands once for
# both orders: without it phi(a) . phi(b) is not (a . b)^2
OFF_DIAGONAL = float(np.sqrt(2.0))
_NT, _TN = (((1,), (1,)), ((), ())), (((0,), (0,)), ((), ()))


@dataclasses.dataclass
class BrumbyConfig:
    vocab_size: int = 151936
    dim: int = 5120
    n_layers: int = 40
    n_heads: int = 40
    n_kv_heads: int = 8
    head_dim: int = 128
    ffn_dim: int = 17408
    max_seq_len: int = 32768
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-6
    # the quotient's: Ret = num / (den + ret_eps)
    ret_eps: float = 1e-6
    # tokens a block of the chunked rule: the program's choice, not the
    # model's (any block gives the recurrence's numbers)
    ret_block: int = 128

    def __post_init__(self):
        assert self.n_heads % self.n_kv_heads == 0
        assert self.head_dim % 2 == 0
        assert self.queries_per_state <= _EXTRA

    @property
    def queries_per_state(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def rotations(self) -> int:
        return self.head_dim // 2 + 1

    @property
    def state_shape(self):
        """A slot's state a layer: [K/V heads, rotations x (Dh + 8), Dh]."""
        return (self.n_kv_heads,
                self.rotations * (self.head_dim + _EXTRA), self.head_dim)

    @classmethod
    def tiny(cls, **kw):
        base = dict(vocab_size=256, dim=64, n_layers=2, n_heads=10,
                    n_kv_heads=2, head_dim=16, ffn_dim=96, max_seq_len=512,
                    ret_block=8)
        base.update(kw)
        return cls(**base)


def _sections(cfg):
    """Every layer, then an empty section that is the tail: behind the
    last layer a prompt chunk's last real row alone pays the final norm
    and the head (1,024 rows of 151,936 logits are 1.6 TFLOP a chunk)."""
    return (((True,), cfg.n_layers), ((), 0))


def _state_row(cfg) -> StateRow:
    return StateRow(cfg.n_layers, None, cfg.state_shape)


# ------------------------------------------------------------- parameters
def _shapes(cfg):
    L, d, f = cfg.n_layers, cfg.dim, cfg.ffn_dim
    Hd, Kd = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    return {"wq": (L, d, Hd), "wk": (L, d, Kd), "wv": (L, d, Kd),
            "wo": (L, Hd, d), "w_g": (L, d, cfg.n_kv_heads),
            "w1": (L, d, f), "w3": (L, d, f), "w2": (L, f, d)}


_GAINS = {"attn_norm": "dim", "mlp_norm": "dim", "q_norm": "head_dim",
          "k_norm": "head_dim"}
_EXACT = ("attn_norm", "mlp_norm", "q_norm", "k_norm", "final_norm", "w_g",
          "b_g")
# half-lives of the seeded gates, tokens: a head a layer is drawn between
HALF_LIVES = (32.0, 32768.0)


def init_params(rng: jax.Array, cfg: BrumbyConfig,
                dtype=jnp.float32) -> Dict[str, Any]:
    """One stack, ``ret_blocks`` ``[n_layers, ...]``: there is no
    ``blocks``, the pool's stack, because there is no pool.  Gains are
    drawn about 1, so that a norm left out shows.  ``b_g`` (float32) is
    ``log(h / ln 2)`` for a half-life h drawn log-uniformly in
    ``HALF_LIVES`` a K/V head a layer: ``logsigmoid(b) ~ -e^-b``, so a
    head's state halves in about h tokens (``a W_g`` moves that by a
    factor e either way): heads that remember a paragraph and heads that
    remember the document.  At ``b_g = 0`` every state would forget in two
    tokens and no check would see the carry."""
    keys = iter(jax.random.split(rng, 32))

    def w(*sh):
        return (jax.random.normal(next(keys), sh)
                / np.sqrt(sh[-2])).astype(dtype)

    def gain(*sh):
        return (1.0 + 0.1 * jax.random.normal(next(keys), sh)).astype(dtype)

    L = cfg.n_layers
    stack = {n: w(*sh) for n, sh in _shapes(cfg).items()}
    stack.update({n: gain(L, getattr(cfg, width))
                  for n, width in _GAINS.items()})
    lo, hi = np.log(HALF_LIVES[0]), np.log(HALF_LIVES[1])
    stack["b_g"] = (jax.random.uniform(
        next(keys), (L, cfg.n_kv_heads), minval=lo, maxval=hi)
        - np.log(np.log(2.0))).astype(jnp.float32)
    return {
        "embed": jax.random.normal(
            next(keys), (cfg.vocab_size, cfg.dim)).astype(dtype),
        "ret_blocks": stack,
        "final_norm": gain(cfg.dim),
        "lm_head": w(cfg.dim, cfg.vocab_size),
    }


def param_specs(cfg: BrumbyConfig) -> Dict[str, Any]:
    """Every leaf replicated: the family serves on one device."""
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return jax.tree.map(lambda a: P(*(None,) * a.ndim), shapes)


def param_count(cfg: BrumbyConfig) -> int:
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return int(sum(np.prod(a.shape) for a in jax.tree.leaves(shapes)))


# ---------------------------------------------------------------- the rule
def phi_weights(head_dim: int) -> np.ndarray:
    """``w_d`` of :func:`phi`, [Dh/2 + 1]."""
    w = np.full((head_dim // 2 + 1,), OFF_DIAGONAL, np.float32)
    w[0] = w[-1] = 1.0
    return w


def phi(a):
    """a [..., Dh] -> [..., Dh/2 + 1, Dh]: ``w_d a_i a_(i - d)``, so that
    ``sum(phi(a) * phi(b)) = (a . b)^2`` (to rounding)."""
    w = phi_weights(a.shape[-1])
    return jnp.stack([w[d] * a * jnp.roll(a, d, axis=-1)
                      for d in range(len(w))], axis=-2)


def _extended(v):
    """v [..., Dh] -> [..., Dh + 8]: ``[v | 1 | 0 x 7]``, the value
    channels with the normaliser's constant one behind them."""
    unit = np.zeros((_EXTRA,), np.float32)
    unit[0] = 1.0
    return jnp.concatenate(
        [v, jnp.broadcast_to(unit, v.shape[:-1] + (_EXTRA,))], axis=-1)


_hi = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)


def ret_rule(S, q, k, v, g, eps: float):
    """One token of the recurrence over the last two dimensions: S [...,
    rotations x (Dh + 8), Dh], q [..., n, Dh] (the query heads that read
    this state, zero rows behind them), k, v [..., 1, Dh], g [..., 1, 1],
    f32 -> (o [..., n, Dh], S): the state decays and takes ``v_ext
    phi(k)``, then every query reads it and divides by its reading of the
    normaliser's row.  ``k = 0, g = 1`` leaves S bit for bit."""
    Dh = k.shape[-1]
    R = Dh + _EXTRA
    tiles = S.reshape(S.shape[:-2] + (-1, R, Dh))           # [..., d, r, i]
    tiles = jnp.asarray(g)[..., None] * tiles \
        + _extended(v)[..., 0, None, :, None] * phi(k)[..., 0, :, None, :]
    r = _hi("...ndi,...dri->...nr", phi(q), tiles)
    return r[..., :Dh] / (r[..., Dh:Dh + 1] + eps), tiles.reshape(S.shape)


def ret_rule_in_place(S_ref, q, k, v, g, eps: float, n: int):
    """:func:`ret_rule` on a head's state where it lies in the kernel's
    memory (``family.step_state``'s ``in_place``): S_ref [rotations x (Dh
    + 8), Dh] a reference, q [8, Dh] whose first ``n`` rows are real, k,
    v [1, Dh], g a scalar -> o [8, Dh].  A rotation's tile at a time: it
    decays, takes ``v_ext phi(k)[d]`` (one lane rotation makes the row),
    goes back, and each query adds its products with it; the sums over
    the lanes, the turn of a column into a row and the quotient come
    once, at the end.  No factor is a matrix product: the state is read
    five times by what a register holds."""
    from jax.experimental.pallas import tpu as pltpu

    Dh, f32 = k.shape[-1], jnp.float32
    R, half = Dh + _EXTRA, Dh // 2
    eye = (jax.lax.broadcasted_iota(jnp.int32, (Dh, Dh), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (Dh, Dh), 1))
    # v down the rows, the normaliser's one behind it
    v_ext = jnp.concatenate([
        jnp.sum(jnp.where(eye, v, 0.0), axis=1, keepdims=True),
        (jax.lax.broadcasted_iota(jnp.int32, (_EXTRA, 1), 0) == 0
         ).astype(f32)], axis=0)                             # [R, 1]
    kb = jnp.broadcast_to(k, (8, Dh))

    def tile(d, acc):
        w = jnp.where((d == 0) | (d == half), 1.0, OFF_DIAGONAL).astype(f32)
        pk = w * kb * pltpu.roll(kb, d, 1)
        pq = w * q * pltpu.roll(q, d, 1)
        rows = pl_rows(d * R, R)
        # float32 whatever the state is kept in; rounded on its way out
        S = g * S_ref[rows, :].astype(f32) + v_ext * pk[0:1, :]
        S_ref[rows, :] = S.astype(S_ref.dtype)
        return tuple(a + S * pq[h:h + 1, :] for h, a in enumerate(acc))

    acc = jax.lax.fori_loop(0, half + 1, tile,
                            tuple(jnp.zeros((R, Dh), f32) for _ in range(n)))
    head = jax.lax.broadcasted_iota(jnp.int32, (8, Dh), 0)
    o = jnp.zeros((8, Dh), f32)
    for h, a in enumerate(acc):
        r = jnp.sum(a, axis=1, keepdims=True)                # [R, 1]
        num = jnp.sum(jnp.where(eye, r[:Dh], 0.0), axis=0, keepdims=True)
        o = jnp.where(head == h, num / (r[Dh:Dh + 1] + eps), o)
    return o


def pl_rows(at, n: int):
    """``n`` rows of a reference from ``at``, a multiple of 8."""
    from jax.experimental import pallas as pl

    return pl.ds(pl.multiple_of(at, 8), n)


def ret_step(cfg, q, k, v, g, S):
    """One token of the recurrence, every row and K/V head at once: q
    [B, H, Dh], k, v [B, KV, Dh], g [B, KV], all f32, S [B, KV, ...] or
    the carried buffer it is a layer of (``family.step_state``) -> (o [B,
    H, Dh], S as it came).  The queries of a state head go over as a
    whole tile of 8 rows, zeros behind the real ones."""
    B, H, Dh = q.shape
    KV, n = cfg.n_kv_heads, cfg.queries_per_state
    q = jnp.pad(q.reshape(B, KV, n, Dh), ((0, 0), (0, 0), (0, 8 - n), (0, 0)))
    o, S = step_state(
        functools.partial(ret_rule, eps=cfg.ret_eps), S, q, k[..., None, :],
        v[..., None, :], g[..., None, None],
        in_place=functools.partial(ret_rule_in_place, eps=cfg.ret_eps, n=n))
    return o[:, :, :n].reshape(B, H, Dh), S


def ret_chunk_rule(cfg, q, k, v, logg, S):
    """The recurrence of :func:`ret_step` over T tokens in blocks of
    ``ret_block``, in XLA: q [B, T, H, Dh], k, v [B, T, KV, Dh], logg [B,
    T, KV] (0, with k = 0, at a token that moves nothing), S [B, KV, ...],
    f32 -> (o [B, T, H, Dh], S).  Inside a block, with ``c`` the running
    sum of log g: ``P = (Q K^T)^2 e^(c_t - c_s)`` (s <= t) against the
    block's own ``v_ext``, ``(phi(Q) e^c) S`` against what the block found,
    and the block leaves ``e^c_C S + sum_s e^(c_C - c_s) v_ext phi(k_s)``
    (the decay enters once: phi is quadratic in k).  No factor is above
    1."""
    B, T, H, Dh = q.shape
    KV, n, C = cfg.n_kv_heads, cfg.queries_per_state, cfg.ret_block
    R = Dh + _EXTRA
    pad = -T % C
    if pad:
        q, k, v, logg = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),)
                                 * (a.ndim - 2)) for a in (q, k, v, logg))
    N = (T + pad) // C
    # [B, T, ...] -> [N, B, C, ...]: the scan runs over blocks
    blk = lambda a: jnp.moveaxis(a.reshape((B, N, C) + a.shape[2:]), 1, 0)
    q, k, v = blk(q.reshape(B, -1, KV, n, Dh)), blk(k), blk(_extended(v))
    c = jnp.cumsum(blk(logg), axis=2)                        # [N, B, C, KV]
    causal = (np.arange(C)[:, None] >= np.arange(C)[None])[..., None]

    def one(S, b):
        q, k, v, c = b
        tiles = S.reshape(B, KV, -1, R, Dh)
        decay = jnp.exp(jnp.where(
            causal, c[:, :, None] - c[:, None, :], -jnp.inf))  # [B, t, s, KV]
        P = _hi("btkni,bski->btskn", q, k) ** 2 * decay[..., None]
        r = _hi("btskn,bskr->btknr", P, v) + _hi(
            "btkndi,bkdri->btknr", phi(q) * jnp.exp(c)[..., None, None, None],
            tiles)
        last = c[:, -1]                                      # [B, KV]
        tiles = jnp.exp(last)[..., None, None, None] * tiles + _hi(
            "bskr,bskdi->bkdri", v,
            phi(k) * jnp.exp(last[:, None] - c)[..., None, None])
        return tiles.reshape(S.shape), r

    S, r = jax.lax.scan(one, S, (q, k, v, c))
    with jax.named_scope("ret_quotient"):
        o = r[..., :Dh] / (r[..., Dh:Dh + 1] + cfg.ret_eps)
    o = jnp.moveaxis(o, 0, 1).reshape(B, N * C, H, Dh)
    return o[:, :T], S


def ret_block_rule(S_ref, q, k, v, col, lane, eps: float, n: int):
    """One block of C tokens of :func:`ret_chunk_rule` on a grid step's K/V
    heads' states where they lie (the family's ``Recurrent.block``, what
    ``dstpu_state_chunk`` runs a block, in place; one head a step at the
    published widths): S_ref [h, rotations x (Dh + 8), Dh] a reference,
    q [h, C, n Dh] a head's n queries side by side, k, v [h, C, Dh],
    ``col`` [h, C, 1] the running sum c of log g down the block and
    ``lane`` [h, 1, C] across it -> o [h, C, n Dh]."""
    return jnp.stack([
        _ret_block_head(S_ref.at[h], q[h], k[h], v[h], col[h], lane[h], eps,
                        n) for h in range(S_ref.shape[0])])


def _ret_block_head(S_ref, Q, K, V, c, across, eps: float, n: int):
    """:func:`ret_block_rule` on one head: S_ref [rotations x (Dh + 8),
    Dh], Q [C, n Dh], K, V [C, Dh], c [C, 1], across [1, C] -> o [C, n Dh].
    The block's own ``[C, C]`` matrices first; then a rotation's tile at a
    time: ``phi(Q)[d]`` and ``phi(K)[d]`` are made from the block's rows
    by one lane rotation, the queries read the tile (rows of values
    against lanes: a product over the lanes of both), the normaliser's
    row by a sum, and the tile decays and takes ``V^T (phi(K)[d] e^(c_C -
    c))``; every product three bf16 passes in f32 (``qwen3_next._mm3``)."""
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    C, Dh = K.shape
    R, half = Dh + _EXTRA, Dh // 2
    mm = lambda a, b, dims: _mm3(_pair(a), _pair(b), dims)
    t = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    s = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    decay = jnp.where(t >= s, jnp.exp(jnp.minimum(c - across, 0.0)), 0.0)
    ec = jnp.exp(c)                                          # [C, 1]
    # c_C down the block: c over the lanes' last column, spread
    last = jnp.sum(jnp.where(s == C - 1, across, 0.0), axis=1, keepdims=True)
    out = jnp.exp(last - c)                                  # [C, 1]
    gamma = jnp.exp(last[0:1])                               # [1, 1]
    Qs = [Q[:, h * Dh:(h + 1) * Dh] for h in range(n)]
    num, den = [], []
    for Qh in Qs:
        P = mm(Qh, K, _NT)
        P = P * P * decay
        num.append(mm(P, V, (((1,), (0,)), ((), ()))))
        den.append(jnp.sum(P, axis=1, keepdims=True))
    first = jax.lax.broadcasted_iota(jnp.int32, (_EXTRA, Dh), 0) == 0

    def tile(d, carry):
        num, den = carry
        w = jnp.where((d == 0) | (d == half), 1.0, OFF_DIAGONAL).astype(f32)
        main = S_ref[pl_rows(d * R, Dh), :]               # [Dh, Dh]
        z = S_ref[pl_rows(d * R + Dh, _EXTRA), :]         # row 0 of 8
        mp = _pair(main)
        pqs = [w * Qh * pltpu.roll(Qh, d, 1) * ec for Qh in Qs]
        num = tuple(a + _mm3(_pair(pq), mp, _NT) for a, pq in zip(num, pqs))
        den = tuple(a + jnp.sum(pq * z[0:1], axis=1, keepdims=True)
                    for a, pq in zip(den, pqs))
        pk = w * K * pltpu.roll(K, d, 1) * out               # [C, Dh]
        S_ref[pl_rows(d * R, Dh), :] = gamma * main + mm(V, pk, _TN)
        S_ref[pl_rows(d * R + Dh, _EXTRA), :] = jnp.where(
            first, gamma * z + jnp.sum(pk, axis=0, keepdims=True), 0.0)
        return num, den

    num, den = jax.lax.fori_loop(0, half + 1, tile, (tuple(num), tuple(den)))
    return jnp.concatenate([a / (b + eps) for a, b in zip(num, den)],
                           axis=1)


def ret_chunk_kernel(cfg, q, k, v, logg, S: SlotState):
    """:func:`ret_chunk_rule` where the build runs a chunk's state on the
    chip: the running sums made here, the blocks :func:`ret_block_rule`'s,
    a K/V head's n queries one operand of n Dh lanes (an operand of MORE
    heads than the state: they ride side by side in its head's tile)."""
    B, T, H, Dh = q.shape
    KV, n, C = cfg.n_kv_heads, cfg.queries_per_state, cfg.ret_block
    pad = -T % C
    if pad:
        q, k, v, logg = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),)
                                 * (a.ndim - 2)) for a in (q, k, v, logg))
    c = jnp.cumsum(logg.reshape(B, -1, C, KV), axis=2).reshape(logg.shape)
    o, S = chunk_state(
        functools.partial(ret_block_rule, eps=cfg.ret_eps, n=n), S,
        (q.reshape(B, T + pad, KV, n * Dh), k, v), c[..., None],
        c[..., None], C, in_place=True)
    return o.reshape(B, T + pad, H, Dh)[:, :T], S


def ret_mix(cfg, x, lp, state, valid, start=None, ctx=()):
    """The retention mixer (the family's ``Recurrent.mix``): ``x`` [B, T,
    d] -> (y [B, T, d] before the residual, the rows' new (None, S): the
    layer keeps no rows).  ``valid`` [B]: tokens at or past it move
    nothing (their k is 0 and their gate 1).  ``ctx``: the positions'
    ``(cos, sin)``.  A chunk's ``SlotState`` runs on the chip."""
    B, T, _ = x.shape
    Dh, f32 = cfg.head_dim, jnp.float32
    _, S = state
    # the benchmark's vocabulary has attention's words; ours nest in them
    with jax.named_scope("attn_qkv"), jax.named_scope("ret_proj"):
        a = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        heads = lambda y: y.reshape(B, T, -1, Dh)
        q = _llama.apply_rope(
            rms_norm(heads(a @ lp["wq"]), lp["q_norm"], cfg.norm_eps), *ctx)
        k = _llama.apply_rope(
            rms_norm(heads(a @ lp["wk"]), lp["k_norm"], cfg.norm_eps), *ctx)
        v = heads(a @ lp["wv"])
    with jax.named_scope("attn_qkv"), jax.named_scope("ret_gate"):
        real = (jnp.arange(T)[None] < valid[:, None])[..., None]
        logg = jnp.where(real, jax.nn.log_sigmoid(
            jnp.einsum("btd,dh->bth", a, lp["w_g"],
                       preferred_element_type=f32)
            + lp["b_g"].astype(f32)), 0.0)
        k = jnp.where(real[..., None], k, 0)
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    if T == 1:
        with jax.named_scope("kv_attend"), jax.named_scope("ret_step"):
            o, S = ret_step(cfg, q[:, 0], k[:, 0], v[:, 0],
                            jnp.exp(logg[:, 0]), S)
            o = o[:, None]
    else:
        with jax.named_scope("kv_attend"), jax.named_scope("ret_chunk"):
            if isinstance(S, SlotState):
                o, S = ret_chunk_kernel(cfg, q, k, v, logg, S)
            else:       # f32 whatever the state is kept in
                o, S = ret_chunk_rule(cfg, q, k, v, logg, S.astype(f32))
    with jax.named_scope("attn_out"):
        return o.astype(x.dtype).reshape(B, T, -1) @ lp["wo"], (None, S)


# -------------------------------------------------------------- the hooks
def _embed(params, tokens, start, cfg):
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
        return x, _llama.rope_tables(
            types.SimpleNamespace(head_dim=cfg.head_dim,
                                  rope_theta=cfg.rope_theta),
            positions_from(start, tokens.shape[1]))


def _ret_out(cfg, x, y, lp):
    from deepspeed_tpu.ops.fused_ops import swiglu

    x = x + y
    with jax.named_scope("mlp"):
        h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        return x + swiglu(h, lp["w1"], lp["w3"]) @ lp["w2"]


def _head(params, x, cfg):
    with jax.named_scope("final_norm"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    with jax.named_scope("lm_head"):
        return jnp.einsum("btd,dv->btv", x, params["lm_head"],
                          preferred_element_type=jnp.float32)


def _check(cfg: BrumbyConfig, mesh, max_seq: int) -> None:
    if max_seq > cfg.max_seq_len:
        raise ValueError(f"max_seq {max_seq} is past the model's "
                         f"max_seq_len {cfg.max_seq_len}")


_STATE = ("a layer's whole past is one state a slot (a third of a GiB at "
          "the published widths), not rows a token: ")

# Every layer keeps its state a slot and none attends over pages: ``qkv``
# and ``out`` name nothing, the period has no pool layer.  What would need
# a snapshot of a slot's state, its rollback or its split is refused by
# name.
FAMILY = DecoderFamily(
    config_type=BrumbyConfig, embed=_embed, qkv=None, out=None, head=_head,
    param_specs=param_specs, quant_skip_paths=_EXACT, check=_check,
    recurrent=Recurrent(key="ret_blocks", period=lambda cfg: (True,),
                        sections=_sections, tail=1, mix=ret_mix,
                        out=_ret_out, state_row=_state_row,
                        write_scope="ret_write", block=ret_block_rule),
    refuses=(
        ("prefix_cache", _STATE + "there are no pages to share, and no "
         "snapshot of the state at a shared prefix's end is kept"),
        ("kv_tier", _STATE + "a tier entry holds pages, and this family "
         "writes none"),
        ("quantized_resident", _STATE + "int8-resident pages come with "
         "kv_tier, and there is no page"),
        ("speculative", _STATE + "rejected draft tokens would have moved "
         "it, and no rollback is built"),
        ("tensor_parallel", _STATE + "the state kernels are one device's, "
         "and the state is not sharded over its K/V heads"),
        ("zero_inference", "weight streaming runs a stack of pool layers "
         "a program; this family has none"),
        ("contiguous_cache", "the contiguous-cache generators keep "
         "per-head K and V alone; serve through serving_engine"),
    ))

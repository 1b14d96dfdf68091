"""Phi-4-mini-flash-style decoder (``model_type: phi4flash``): SambaY, a
decoder-hybrid-decoder (arXiv:2507.06607) with differential attention
(arXiv:2410.05258).  A **self-decoder** that every token passes, Mamba-1
mixers on its even layers and attention on its odd ones, every attention
layer but the last over a sliding window; then a **cross-decoder** that
keeps no cache of its own (YOCO, arXiv:2405.05254): gated memory units
(GMU) on its even layers, which gate the LAST Mamba-1 layer's scan output
``M`` of the same token, and on its odd layers attention with a query of
its own over the keys and values the self-decoder's one full-attention
layer wrote.

With ``LN(x) = (x - mean) / sqrt(var + eps) * g + b`` (in f32) and no
position encoding anywhere::

    x = E[token]
    h = x + Mix_l(LN(x));   y = h + W_2 (SiLU(g) * u),  [g | u] = W_1 LN(h)
    logits = LN(x) E^T                                  (the head is tied)

``Mix_l`` on ``a = LN(x)``, by the layer's kind (:func:`layer_kinds`):

* **Mamba-1** (arXiv:2312.00752): ``[u | z] = a W_in``; ``c = SiLU(conv(u)
  + b)`` (causal, depthwise, ``d_conv`` taps); ``[r | B | C] = c W_x``;
  ``D_t = softplus(r W_dt + b_dt)`` a channel; ``A = -exp(A_log)`` a
  (channel, state) pair; ``S_t = exp(D_t A) S_(t-1) + D_t c_t B_t^T``, float32
  from zero; ``o_t = S_t C_t + D c_t``; ``Mix = (o * SiLU(z)) W_out``.  The
  last Mamba-1 layer also hands ``M_t = o_t`` on.
* **GMU**: ``Mix = (SiLU(a W_1') * M_t) W_2'``: element-wise on the same
  token's ``M``, no state and no history.
* **Differential attention**: adjacent heads are a pair, ``(q1, q2)`` of
  the queries over ``(k1, k2)``, ``(v1, v2)`` of the K/V heads they share;
  ``P_i = softmax(q_i k_i^T / sqrt(head))``, causal (a window layer's query
  at ``p`` sees ``p - window + 1 .. p``); ``o = P_1 [v1 | v2] - lambda P_2
  [v1 | v2]``, ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``,
  ``lambda_init = 0.8 - 0.6 exp(-0.3 l)`` at layer ``l``; ``Mix = (RMS(o)
  (1 - lambda_init)) W_o + b_o``, the norm over a pair's ``2 head``
  numbers.  A cross layer has ``W_q`` alone and reads the full layer's
  rows.

What is stored is a pair a K/V head: ``K = [k1 | k2]``, ``V = [v1 | v2]``
(``n_kv_heads / 2`` heads of ``2 head``: the published bytes a token, no
padding), read by queries ``[q1 | 0]`` and ``[0 | q2]`` (``n_heads`` heads
of ``2 head``): the zero lanes add exact zeros to a score, and every
reader the repo has computes ``P_i [v1 | v2]`` as it stands.  The shared
readers scale scores by ``(2 head)^-1/2``; the ``sqrt 2`` that differs from
the model's by goes into q before it is rounded.

What a slot keeps: the Mamba-1 layers ``d_conv - 1`` rows of ``u`` and the
state, kept ``[d_inner / 128, d_state, 128]`` (channels on the lanes); a
window layer a ring of ``sliding_window`` rows ``[K | V]`` (the window
family's, :mod:`deepspeed_tpu.models.laguna`, whose functions step and
chunk it); the one full layer rows in the page pool, a layer deep; a GMU
and a cross layer nothing.  Two per-slot kinds in one family:
``family.Recurrent.also``.

Layers behind the self-decoder are needed at a row's last real token and
nowhere else (their outputs at other prompt positions feed nothing), so
a prompt chunk pays the self-decoder alone: ``family.Recurrent.tail``.

Serving only: there is no ``loss_fn``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.family import (CacheRow, CarriedRows,
                                         DecoderFamily, PoolReader,
                                         Recurrent, SlotRows, StateRow,
                                         step_state)
from deepspeed_tpu.models.laguna import (window_chunk, window_reader,
                                         window_step)

_LANES = 128


@dataclasses.dataclass
class Phi4FlashConfig:
    vocab_size: int = 200064
    dim: int = 2560
    n_layers: int = 32
    n_heads: int = 40
    n_kv_heads: int = 20
    ffn_dim: int = 10240
    mb_per_layer: int = 2              # every second layer is not attention
    sliding_window: int = 512
    d_inner: int = 5120
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 160
    norm_eps: float = 1e-5
    max_seq_len: int = 262144
    # tokens a trip of the chunk's scan takes, unrolled
    scan_block: int = 16

    def __post_init__(self):
        assert self.mb_per_layer == 2, "Mamba-1 and attention alternate"
        assert self.n_layers % 4 == 0 and self.n_layers >= 8
        assert self.dim % self.n_heads == 0
        assert self.n_heads % self.n_kv_heads == 0 and self.n_kv_heads % 2 == 0
        assert self.n_heads // self.n_kv_heads == 2, \
            "two pairs of queries over a pair of K/V heads"
        assert self.d_inner % _LANES == 0

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def n_self_layers(self) -> int:
        """The self-decoder: half the depth and the two layers that hand
        the memory and the keys on."""
        return self.n_layers // 2 + 2

    @property
    def n_mamba_layers(self) -> int:
        return self.n_self_layers // 2

    @property
    def n_sliding_layers(self) -> int:
        return self.n_self_layers // 2 - 1

    @property
    def n_heads_sliding(self) -> int:
        """(a window layer's heads, under the window family's name)"""
        return self.n_heads

    @property
    def n_cross_layers(self) -> int:
        """GMU layers, and as many cross-attention layers."""
        return (self.n_layers - self.n_self_layers) // 2

    @property
    def state_heads(self) -> int:
        return self.d_inner // _LANES

    @property
    def pairs(self) -> "Paired":
        """The attention as the caches and the readers see it."""
        return Paired(self.n_kv_heads // 2, 2 * self.head_dim,
                      self.sliding_window)

    @classmethod
    def tiny(cls, **kw):
        """Every kind in the published order: three (Mamba-1, window)
        periods, (Mamba-1, full), two (GMU, cross); a window the contexts
        pass several times over; two 128-channel groups of state."""
        base = dict(vocab_size=256, dim=128, n_layers=12, n_heads=8,
                    n_kv_heads=4, ffn_dim=256, sliding_window=8,
                    d_inner=256, d_state=16, dt_rank=8, max_seq_len=512,
                    scan_block=4)
        base.update(kw)
        return cls(**base)


class Paired(NamedTuple):
    """What the window family's functions ask a config for."""

    n_kv_heads: int
    head_dim: int
    sliding_window: int


def layer_kinds(cfg) -> Tuple[str, ...]:
    """The model's layers in order: "mamba", "window", "full", "gmu" or
    "cross"."""
    n = cfg.n_self_layers
    return tuple(
        ("mamba" if l % 2 == 0 else "window" if l < n - 1 else "full")
        if l < n else ("gmu" if l % 2 == 0 else "cross")
        for l in range(cfg.n_layers))


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def _sections(cfg):
    return (((True, "win_blocks"), cfg.n_sliding_layers),
            ((True, False), 1),
            ((None, "cross_blocks"), cfg.n_cross_layers))


def _state_row(cfg) -> StateRow:
    """A Mamba-1 layer's: ``d_conv - 1`` rows of ``u`` and the state,
    channels on the lanes; beside it a window layer's ring."""
    return SlotRows(
        StateRow(cfg.n_mamba_layers, (cfg.d_conv - 1, cfg.d_inner),
                 (cfg.state_heads, cfg.d_state, _LANES)),
        _ring_row(cfg))


def _ring_row(cfg) -> StateRow:
    p = cfg.pairs
    return StateRow(cfg.n_sliding_layers,
                    (p.sliding_window, 2 * p.n_kv_heads * p.head_dim), None)


# ------------------------------------------------------------- parameters
_NORMS = ("attn_norm_g", "attn_norm_b", "mlp_norm_g", "mlp_norm_b")
_LAMBDAS = ("lq1", "lk1", "lq2", "lk2")
# leaves that stay exact under weight-only quantization; ``lam0`` is no
# parameter: ``lambda_init`` of the layer's depth, stated a layer
_EXACT = _NORMS + _LAMBDAS + ("subln", "lam0", "bqkv", "bq", "bo", "A_log",
                              "dt_bias", "D", "conv_w", "conv_b",
                              "final_norm_g", "final_norm_b")
_STACKS = {"mamba": "mamba_blocks", "window": "win_blocks", "full": "blocks",
           "gmu": "gmu_blocks", "cross": "cross_blocks"}


def _stack_shapes(cfg, kind: str):
    d, f, di = cfg.dim, cfg.ffn_dim, cfg.d_inner
    q = cfg.n_heads * cfg.head_dim
    kv = cfg.n_kv_heads * cfg.head_dim
    L = layer_kinds(cfg).count(kind)
    if kind == "mamba":
        shapes = {"w_in": (L, d, 2 * di),
                  "w_x": (L, di, cfg.dt_rank + 2 * cfg.d_state),
                  "w_dt": (L, cfg.dt_rank, di), "w_out": (L, di, d)}
    elif kind == "gmu":
        shapes = {"w_g": (L, d, di), "w_o": (L, di, d)}
    elif kind == "cross":
        shapes = {"wq": (L, d, q), "wo": (L, q, d)}
    else:
        shapes = {"wqkv": (L, d, q + 2 * kv), "wo": (L, q, d)}
    shapes.update(w_gu=(L, d, 2 * f), w_down=(L, f, d))
    return L, shapes


def init_params(rng: jax.Array, cfg: Phi4FlashConfig,
                dtype=jnp.float32) -> Dict[str, Any]:
    """Five stacks, one a kind (``blocks`` is the full layer: the page
    pool's), each with its layers' SwiGLU halves, and the embedding, which
    is the head too: rows of about unit norm, so that the logits have
    about unit variance and the residual stream is what the layers
    computed.  Every matrix at the fan-in scale; gains drawn about 1 and
    biases about 0, so that a norm or a bias left out shows.  ``A_log``
    is the published layer's (A = 1 .. ``d_state`` a channel), kept
    ``[heads, d_state, 128]`` as the state is; the step ``D_t`` falls in
    1e-3 .. 1e-1 before what the token adds, so that channels forget in
    a token and in thousands."""
    keys = iter(jax.random.split(rng, 128))
    nk = lambda: next(keys)

    def w(*sh):
        return (jax.random.normal(nk(), sh) / np.sqrt(sh[-2])).astype(dtype)

    def gain(*sh):
        return (1.0 + 0.1 * jax.random.normal(nk(), sh)).astype(dtype)

    def bias(*sh):
        return (0.1 * jax.random.normal(nk(), sh)).astype(dtype)

    kinds = layer_kinds(cfg)

    def stack(kind):
        L, shapes = _stack_shapes(cfg, kind)
        tree = {n: w(*sh) for n, sh in shapes.items()}
        tree.update(attn_norm_g=gain(L, cfg.dim), attn_norm_b=bias(L, cfg.dim),
                    mlp_norm_g=gain(L, cfg.dim), mlp_norm_b=bias(L, cfg.dim))
        if kind == "mamba":
            di, N = cfg.d_inner, cfg.d_state
            dt = jnp.exp(jax.random.uniform(
                nk(), (L, di), minval=np.log(1e-3), maxval=np.log(1e-1)))
            tree.update(
                conv_w=(jax.random.normal(nk(), (L, cfg.d_conv, di))
                        / np.sqrt(cfg.d_conv)).astype(dtype),
                conv_b=bias(L, di),
                w_dt=(0.5 * tree["w_dt"].astype(jnp.float32)).astype(dtype),
                dt_bias=(dt + jnp.log(-jnp.expm1(-dt))).astype(jnp.float32),
                A_log=jnp.broadcast_to(
                    jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[:, None],
                    (L, cfg.state_heads, N, _LANES)),
                D=jnp.ones((L, di), jnp.float32))
        elif kind != "gmu":
            q = cfg.n_heads * cfg.head_dim
            at = [l for l, k in enumerate(kinds) if k == kind]
            tree.update(
                {n: (0.1 * jax.random.normal(nk(), (L, cfg.head_dim))
                     ).astype(jnp.float32) for n in _LAMBDAS},
                subln=gain(L, 2 * cfg.head_dim), bo=bias(L, cfg.dim),
                lam0=jnp.asarray([lambda_init(l) for l in at], jnp.float32))
            if kind == "cross":
                tree["bq"] = bias(L, q)
            else:
                tree["bqkv"] = bias(L, q + 2 * cfg.n_kv_heads * cfg.head_dim)
        return tree

    return {
        "embed": (jax.random.normal(nk(), (cfg.vocab_size, cfg.dim))
                  / np.sqrt(cfg.dim)).astype(dtype),
        **{key: stack(kind) for kind, key in _STACKS.items()},
        "final_norm_g": gain(cfg.dim), "final_norm_b": bias(cfg.dim),
    }


def param_specs(cfg: Phi4FlashConfig) -> Dict[str, Any]:
    """Every leaf replicated: the family serves on one device."""
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return jax.tree.map(lambda a: P(*(None,) * a.ndim), shapes)


def param_count(cfg: Phi4FlashConfig) -> int:
    """The embedding once (it is the head too); ``lam0`` is a constant of
    the depth, not a parameter."""
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return int(sum(np.prod(a.shape) for path, a in
                   jax.tree_util.tree_leaves_with_path(shapes)
                   if "lam0" not in jax.tree_util.keystr(path)))


# ------------------------------------------------------------ the pieces
def layer_norm(x, g, b, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32)
            + b.astype(jnp.float32)).astype(x.dtype)


def _mlp_half(cfg, x, lp):
    with jax.named_scope("mlp"):
        h = layer_norm(x, lp["mlp_norm_g"], lp["mlp_norm_b"],
                       cfg.norm_eps) @ lp["w_gu"]
        g, u = h[..., :cfg.ffn_dim], h[..., cfg.ffn_dim:]
        act = (jax.nn.silu(g.astype(jnp.float32))
               * u.astype(jnp.float32)).astype(x.dtype)
        return x + act @ lp["w_down"]


# ------------------------------------------------------------ Mamba-1
def mamba_rule(S, A, dt, dtx, Bm, Cm):
    """One token of the recurrence over the last two dimensions: S, A
    [..., N, C] (``A`` the layer's tile, the slots'), dt, dtx [..., 1, C]
    (``D_t`` and ``D_t c_t``), Bm, Cm [..., N, 1], f32 -> (o [..., 1, C]
    without the ``D c`` skip, S).  The decay is a (channel, state) pair's:
    neither a row, a column nor a scalar of S.  ``dt = 0`` leaves S as it
    was, bit for bit."""
    S = jnp.exp(dt * A) * S + dtx * Bm
    return jnp.sum(S * Cm, axis=-2, keepdims=True), S


def mamba_step(c, dt, A, Bm, Cm, S):
    """One token, every row at once: c, dt [B, H, C] (the convolution's
    output and the step, a channel), A [H, N, C], Bm, Cm [B, N] (shared by
    the heads), f32, S [B, H, N, C] or the carried buffer it is a layer
    of (``family.step_state``) -> (o [B, H, C], S as it came)."""
    row = lambda v: v[:, :, None, :]
    col = lambda v: v[:, None, :, None]
    o, S = step_state(mamba_rule, S, A[None], row(dt), row(dt * c),
                      col(Bm), col(Cm))
    return o[:, :, 0], S


def mamba_chunk_scan(c, dt, A, Bm, Cm, S, block: int):
    """:func:`mamba_rule` over T tokens: c, dt [B, T, H, C], A [H, N, C],
    Bm, Cm [B, T, N], S [B, H, N, C], f32 -> (o [B, T, H, C], S).  A
    decay a (channel, state) pair has no matrix form, so the tokens run
    in order: a scan over blocks of ``block`` tokens, a block's tokens
    unrolled in the body (one fused pass a block, the state a value of
    it).  T is padded to whole blocks with tokens that move nothing (dt
    = 0)."""
    B, T = c.shape[:2]
    block = min(block, T)
    pad = -T % block
    if pad:
        c, dt, Bm, Cm = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),)
                                 * (a.ndim - 2)) for a in (c, dt, Bm, Cm))
    nb = (T + pad) // block
    blk = lambda a: jnp.moveaxis(a.reshape((B, nb, block) + a.shape[2:]),
                                 1, 0)

    def one(S, b):
        dt, dtx, Bm, Cm = b
        out = []
        for i in range(block):
            o, S = mamba_rule(S, A[None], dt[:, i, :, None, :],
                              dtx[:, i, :, None, :], Bm[:, i, None, :, None],
                              Cm[:, i, None, :, None])
            out.append(o[:, :, 0])
        return S, jnp.stack(out, axis=1)                 # [B, block, H, C]

    S, o = jax.lax.scan(one, S, (blk(dt), blk(dt * c), blk(Bm), blk(Cm)))
    o = jnp.moveaxis(o, 0, 1).reshape((B, nb * block) + o.shape[3:])
    return o[:, :T], S


def mamba_mix(cfg, x, lp, state, valid, start=None, ctx=()):
    """The Mamba-1 mixer (the family's ``Recurrent.mix``): ``x`` [B, T,
    d] -> ((y [B, T, d] before the residual, the scan's output ``o`` [B,
    T, d_inner] with the skip and before the gate: the memory, which the
    seam hands on), the rows' new (conv, S)).  ``valid`` [B]: tokens at or
    past it move neither S (their step is 0: decay 1, write 0) nor the
    convolution's rows, which are the ``d_conv - 1`` inputs that end at
    the last real token."""
    B, T, _ = x.shape
    di, N, R, taps = cfg.d_inner, cfg.d_state, cfg.dt_rank, cfg.d_conv
    H, f32 = cfg.state_heads, jnp.float32
    conv, S = state
    with jax.named_scope("attn_qkv"), jax.named_scope("mamba_proj"):
        a = layer_norm(x, lp["attn_norm_g"], lp["attn_norm_b"], cfg.norm_eps)
        uz = a @ lp["w_in"]
        u, z = uz[..., :di], uz[..., di:]
    with jax.named_scope("attn_qkv"), jax.named_scope("mamba_conv"):
        seen = jnp.concatenate([conv.astype(u.dtype), u], axis=1)
        w = lp["conv_w"].astype(f32)
        c = sum(seen[:, i:i + T].astype(f32) * w[i] for i in range(taps))
        c = jax.nn.silu(c + lp["conv_b"].astype(f32))        # [B, T, di]
        conv = jax.vmap(lambda rows, n: jax.lax.dynamic_slice_in_dim(
            rows, n, taps - 1))(seen, valid).astype(conv.dtype)
    with jax.named_scope("attn_qkv"), jax.named_scope("mamba_proj"):
        rbc = jnp.einsum("bti,ir->btr", c.astype(x.dtype), lp["w_x"],
                         preferred_element_type=f32)
        r, Bm, Cm = rbc[..., :R], rbc[..., R:R + N], rbc[..., R + N:]
        real = (jnp.arange(T)[None] < valid[:, None])[..., None]
        dt = jnp.where(real, jax.nn.softplus(
            jnp.einsum("btr,ri->bti", r.astype(x.dtype), lp["w_dt"],
                       preferred_element_type=f32)
            + lp["dt_bias"].astype(f32)), 0.0)               # [B, T, di]
        A = -jnp.exp(lp["A_log"].astype(f32))                # [H, N, C]
    heads = lambda v: v.reshape(v.shape[:-1] + (H, _LANES))
    if T == 1:
        with jax.named_scope("kv_attend"), jax.named_scope("mamba_step"):
            o, S = mamba_step(heads(c[:, 0]), heads(dt[:, 0]), A, Bm[:, 0],
                              Cm[:, 0], S)
            o = o[:, None]
    else:
        with jax.named_scope("kv_attend"), jax.named_scope("mamba_scan"):
            # f32 whatever the state is kept in
            o, S = mamba_chunk_scan(heads(c), heads(dt), A, Bm, Cm,
                                    S.astype(f32), cfg.scan_block)
    with jax.named_scope("attn_out"), jax.named_scope("mamba_gate"):
        o = o.reshape(B, T, di) + lp["D"].astype(f32) * c
        y = (o * jax.nn.silu(z.astype(f32))).astype(x.dtype) @ lp["w_out"]
        return (y, o.astype(x.dtype)), (conv, S)


def _mamba_out(cfg, x, y, lp):
    return _mlp_half(cfg, x + y, lp)


def gmu_layer(cfg, x, lp):
    """A gated memory unit and its SwiGLU half (the family's
    ``Recurrent.ffn``: it touches no cache): ``lp["memory"]`` [B, T,
    d_inner] is the last Mamba-1 layer's ``o`` of the same tokens."""
    with jax.named_scope("gmu"):
        a = layer_norm(x, lp["attn_norm_g"], lp["attn_norm_b"], cfg.norm_eps)
        g = jax.nn.silu((a @ lp["w_g"]).astype(jnp.float32))
        y = (g * lp["memory"].astype(jnp.float32)).astype(x.dtype)
        x = x + y @ lp["w_o"]
    return _mlp_half(cfg, x, lp)


# ------------------------------------------------ differential attention
def _paired_q(cfg, q):
    """q [B, T, n_heads x head] -> [B, T, n_heads, 2 head]: a pair's
    ``[q1 | 0]`` and ``[0 | q2]``, scaled for readers that divide scores
    by ``sqrt(2 head)``."""
    B, T, _ = q.shape
    Dh = cfg.head_dim
    pair = (q * math.sqrt(2.0)).reshape(B, T, -1, 1, 2 * Dh)    # [q1 | q2]
    first = jnp.arange(2 * Dh) < Dh
    return jnp.where(jnp.stack([first, ~first]), pair, 0).reshape(
        B, T, cfg.n_heads, 2 * Dh)


def _qkv(cfg, x, lp):
    """A layer's that keeps keys of its own (the full layer's ``qkv``
    hook; a window layer's too): (q [B, T, n_heads, 2 head], k, v [B, T,
    n_kv_heads / 2, 2 head])."""
    B, T, _ = x.shape
    p = cfg.pairs
    with jax.named_scope("attn_qkv"):
        a = layer_norm(x, lp["attn_norm_g"], lp["attn_norm_b"], cfg.norm_eps)
        qkv = jnp.einsum("btd,dh->bth", a, lp["wqkv"],
                         preferred_element_type=jnp.float32) \
            + lp["bqkv"].astype(jnp.float32)
        nq, nk = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
        rows = lambda y: y.astype(x.dtype).reshape(B, T, p.n_kv_heads,
                                                   p.head_dim)
        return _paired_q(cfg, qkv[..., :nq]).astype(x.dtype), \
            rows(qkv[..., nq:nq + nk]), rows(qkv[..., nq + nk:])


def _cross_q(cfg, x, lp):
    """A cross layer's query (its ``PoolReader.q``): it has no keys."""
    with jax.named_scope("attn_qkv"), jax.named_scope("yoco_q"):
        a = layer_norm(x, lp["attn_norm_g"], lp["attn_norm_b"], cfg.norm_eps)
        q = jnp.einsum("btd,dh->bth", a, lp["wq"],
                       preferred_element_type=jnp.float32) \
            + lp["bq"].astype(jnp.float32)
        return _paired_q(cfg, q).astype(x.dtype)


def diff_combine(cfg, attn, lp):
    """attn [B, T, n_heads x 2 head], a pair's ``P_1 [v1 | v2]`` and ``P_2
    [v1 | v2]`` side by side -> [B, T, n_heads x head]: their difference
    under the layer's ``lambda``, normed over a pair's numbers."""
    B, T, _ = attn.shape
    f32 = jnp.float32
    a = attn.astype(f32).reshape(B, T, cfg.n_heads // 2, 2, 2 * cfg.head_dim)
    dot = lambda q, k: jnp.exp(jnp.sum(lp[q].astype(f32)
                                       * lp[k].astype(f32)))
    lam0 = lp["lam0"].astype(f32)
    lam = dot("lq1", "lk1") - dot("lq2", "lk2") + lam0
    o = a[..., 0, :] - lam * a[..., 1, :]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.norm_eps) \
        * lp["subln"].astype(f32) * (1.0 - lam0)
    return o.reshape(B, T, -1)


def _attn_out(cfg, x, attn, lp):
    with jax.named_scope("attn_out"):
        with jax.named_scope("diff_combine"):
            o = diff_combine(cfg, attn, lp).astype(x.dtype)
        x = x + o @ lp["wo"] + lp["bo"]
    return _mlp_half(cfg, x, lp)


def win_mix(cfg, x, lp, state, valid, start, ctx):
    """A window layer's attention (a ``Recurrent.mix`` of the family's
    second per-slot kind): the window family's ring, step and chunk
    (:mod:`deepspeed_tpu.models.laguna`) under this family's heads."""
    B, T, _ = x.shape
    rings, _ = state
    q, k, v = _qkv(cfg, x, lp)
    if isinstance(rings, CarriedRows):
        row = jnp.concatenate([k.reshape(B, -1), v.reshape(B, -1)], -1)
        o, rings = window_step(cfg.pairs, q[:, 0], row, rings, start,
                               valid > 0)
        o = o[:, None]
    else:
        o, rings = window_chunk(cfg.pairs, q, k, v, rings, start, valid)
    return o.reshape(B, T, -1), (rings, None)


def _window_reader(cfg, tokens: int, interpret: bool) -> Tuple[str, str]:
    return window_reader(cfg.pairs, tokens, interpret)


# -------------------------------------------------------------- the hooks
def _embed(params, tokens, start, cfg):
    """No positions anywhere: ``ctx`` is empty."""
    with jax.named_scope("embed"):
        return params["embed"][tokens], ()


def _head(params, x, cfg):
    """The tied head: the embedding's rows."""
    with jax.named_scope("final_norm"):
        x = layer_norm(x, params["final_norm_g"], params["final_norm_b"],
                       cfg.norm_eps)
    with jax.named_scope("lm_head"):
        return jnp.einsum("btd,vd->btv", x, params["embed"],
                          preferred_element_type=jnp.float32)


def _check(cfg: Phi4FlashConfig, mesh, max_seq: int) -> None:
    if mesh is not None and any(mesh.size(ax) > 1
                                for ax in ("model", "expert")):
        raise NotImplementedError(
            "Phi4FlashConfig cannot serve with a model or expert axis > 1: "
            "a slot's Mamba-1 state and its window layers' rings are not "
            "sharded")
    if max_seq > cfg.max_seq_len:
        raise ValueError(f"max_seq {max_seq} is past the model's "
                         f"max_seq_len {cfg.max_seq_len}")


_SLOT = ("a slot keeps a Mamba-1 state and rings of its last rows, not "
         "pages a token: ")

# What would need a snapshot of a slot's state, rings and memory at a token
# other than its last, or their rollback, is refused by name.
FAMILY = DecoderFamily(
    config_type=Phi4FlashConfig, embed=_embed, qkv=_qkv, out=_attn_out,
    head=_head, param_specs=param_specs, quant_skip_paths=_EXACT,
    shard_axes=("model", "expert"), check=_check,
    cache_row=lambda cfg: CacheRow(cfg.pairs.n_kv_heads, cfg.pairs.head_dim,
                                   cfg.pairs.head_dim),
    recurrent=Recurrent(
        key="mamba_blocks", period=lambda cfg: (True, False), mix=mamba_mix,
        out=_mamba_out, state_row=_state_row, write_scope="mamba_write",
        chunk_reader=_window_reader, sections=_sections,
        ffn=("gmu_blocks", gmu_layer),
        hands_on=lambda cfg: cfg.d_inner, tail=1,
        also=(Recurrent(
            key="win_blocks", period=lambda cfg: (), mix=win_mix,
            out=_attn_out, state_row=_ring_row, write_scope="win_write",
            rows_in_place=True),),
        readers=(PoolReader("cross_blocks", _cross_q, _attn_out, 0,
                            "yoco_read"),)),
    refuses=(
        ("prefix_cache", _SLOT + "a shared prefix's pages say nothing of "
         "the state, the rings and the memory at its end, and no snapshot "
         "of them is kept"),
        ("kv_tier", _SLOT + "a tier entry holds pages, and a prompt resumed "
         "from them would start its self-decoder from nothing"),
        ("quantized_resident", _SLOT + "int8-resident pages come with "
         "kv_tier"),
        ("speculative", _SLOT + "rejected draft tokens would have moved "
         "them, no rollback is built, and a continuation program gives the "
         "logits of a row's last token alone"),
        ("zero_inference", "weight streaming runs one stack of one layer "
         "kind; this family has five"),
        ("contiguous_cache", "the contiguous-cache generators keep "
         "per-head K and V of every layer; serve through serving_engine"),
    ))

"""Granite-4.0-H-style decoder (``model_type: granitemoehybrid``): layers
in periods stated by ``layer_types``, most of them a Mamba-2 mixer (a
state-space recurrence over a per-slot state), some a grouped-query
softmax attention over the page pool with no position encoding at all,
every layer's FFN a dense SwiGLU, and four scalar multipliers.

With ``N`` the RMSNorm ``x / sqrt(mean x^2 + eps) * w`` (in f32)::

    x = embedding_multiplier * E[token]
    h = x + residual_multiplier * Mixer_l(N(x))
    y = h + residual_multiplier * MLP(N(h))
    logits = N(x) E^T / logits_scaling

MLP: ``[g | u] = a W_in``, ``(SiLU(g) * u) W_out``, no biases.

Attention on ``a = N(x)``: ``[q | k | v] = a W_qkv``, no bias, no
rotation, causal softmax of ``attention_multiplier * q k^T`` (a stated
scale, not ``head_dim^-1/2``), ``W_o``.

Mamba-2 on ``a`` (``H`` heads of ``P`` channels in ``G`` groups, state
``N``; this model has one group, ``nemotron_h``, which runs the same
functions, eight): ``[z | xBC | dt] = a [W_in | W_dt]``; ``xBC`` passes a
depthwise causal convolution of ``conv_kernel`` taps with bias, then
SiLU, and splits into ``x`` [H, P], ``B`` [G, N], ``C`` [G, N] (head
``h`` reads group ``h // (H / G)``'s); ``D_t = softplus(dt + dt_bias)``
and ``A = -exp(A_log)``, one a head, f32.  A head keeps ``S`` [P, N] in
float32, from zero::

    S_t = exp(D_t A) S_(t-1) + D_t x_t B_t^T;   o_t = S_t C_t + D x_t

then ``y = N_group(o_t * SiLU(z_t))`` (the gate before the norm, which
runs over each group's ``H P / G`` channels) and ``W_out``.  A decode step is that
recurrence (:func:`ssm_step`); a prompt chunk computes the same in
blocks of ``ssm_block`` tokens (:func:`ssm_chunk_scan`), the state
carried from block to block and from chunk to chunk.  What a slot keeps
a layer is the last ``conv_kernel - 1`` inputs of the convolution and
``S``: :class:`~deepspeed_tpu.models.family.StateRow`.

Serving only.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.family import (CacheRow, DecoderFamily, Recurrent,
                                         StateRow, step_state)

_PUBLISHED_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


@dataclasses.dataclass
class GraniteHybridConfig:
    vocab_size: int = 100352
    dim: int = 2048
    n_layers: int = 40
    # one period of layer kinds ("mamba" | "attention"); the model is
    # whole periods
    period: Tuple[str, ...] = _PUBLISHED_PERIOD
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 64
    ffn_dim: int = 8192
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 1
    conv_kernel: int = 4
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    max_seq_len: int = 131072
    norm_eps: float = 1e-5
    # tokens a block of the chunked scan (the published
    # ``mamba_chunk_size``): the program's choice, not the model's (any
    # block gives the recurrence's numbers)
    ssm_block: int = 256

    def __post_init__(self):
        self.period = tuple(self.period)
        assert set(self.period) <= {"mamba", "attention"}
        assert "mamba" in self.period and "attention" in self.period
        assert self.n_layers % len(self.period) == 0, \
            "the model is whole periods"
        assert self.n_heads % self.n_kv_heads == 0
        assert self.ssm_heads % self.ssm_groups == 0

    @classmethod
    def from_layer_types(cls, layer_types, **kw):
        """``layer_types`` (a kind a layer, as published) cut to its
        shortest period."""
        kinds = tuple(layer_types)
        n = next(n for n in range(1, len(kinds) + 1)
                 if len(kinds) % n == 0
                 and kinds == kinds[:n] * (len(kinds) // n))
        return cls(n_layers=len(kinds), period=kinds[:n], **kw)

    @property
    def n_ssm_layers(self) -> int:
        return self.n_layers // len(self.period) * self.period.count("mamba")

    @property
    def n_attn_layers(self) -> int:
        return self.n_layers - self.n_ssm_layers

    @property
    def kv_width(self) -> int:
        """Numbers a head's K or V row takes in the page pool: whole
        128-lane tiles, zeros behind ``head_dim`` (the TPU lays 64
        numbers out in 128 lanes whatever is declared, and the Mosaic
        decode kernel slices pages in whole tiles)."""
        return -(-self.head_dim // 128) * 128

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_channels(self) -> int:
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @classmethod
    def tiny(cls, **kw):
        base = dict(vocab_size=256, dim=64, n_layers=8,
                    period=("mamba", "mamba", "attention", "mamba"),
                    n_heads=4, n_kv_heads=2, head_dim=16, ffn_dim=128,
                    ssm_heads=4, ssm_head_dim=16, ssm_state=16,
                    attention_multiplier=0.125, max_seq_len=512,
                    ssm_block=8)
        base.update(kw)
        return cls(**base)


def _period(cfg) -> Tuple[bool, ...]:
    return tuple(kind == "mamba" for kind in cfg.period)


def _state_row(cfg) -> StateRow:
    return StateRow(cfg.n_ssm_layers,
                    (cfg.conv_kernel - 1, cfg.conv_channels),
                    (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state))


# ------------------------------------------------------------- parameters
def _stack_shapes(cfg, ssm: bool):
    d, f = cfg.dim, cfg.ffn_dim
    if ssm:
        L = cfg.n_ssm_layers
        # the published in_proj's columns [z | xBC | dt], the dt block
        # apart: 8,512 columns are not whole 128-lane tiles, and the
        # chip then keeps the stack rows-minor and the programs re-lay
        # all of it, 1.17 GB, once a step (AOT for a v5e, PR 42)
        shapes = {"w_in": (L, d, cfg.ssm_inner + cfg.conv_channels),
                  "w_dt": (L, d, cfg.ssm_heads),
                  "w_out": (L, cfg.ssm_inner, d)}
    else:
        L = cfg.n_attn_layers
        H, KV, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        shapes = {"wqkv": (L, d, (H + 2 * KV) * Dh), "wo": (L, H * Dh, d)}
    shapes.update(w_gu=(L, d, 2 * f), w_down=(L, f, d))
    return L, shapes


# The projections whose output enters the residual stream are drawn 32
# times the fan-in scale.  The multipliers are the published model's: a
# token enters as 12 E[token] and the tied head reads E^T / 8, so with
# every sublayer's output of unit scale a token's own embedding decides
# its successor (a logit of 35 for repeating it against a spread of 1:
# every served answer was one token repeated, v5e, PR 42) and a fault in
# any layer moves no token.  At 32 the stream is what the layers
# computed (the token's own logit stands 2 above the spread's 1).
_TO_RESIDUAL, _OUT_GAIN = ("w_out", "wo", "w_down"), 32.0
_EXACT = ("attn_norm", "mlp_norm", "ssm_norm", "final_norm", "A_log",
          "dt_bias", "D", "conv_w", "conv_b")


def init_params(rng: jax.Array, cfg: GraniteHybridConfig,
                dtype=jnp.float32) -> Dict[str, Any]:
    """Two stacks: ``ssm_blocks`` ``[n_ssm_layers, ...]`` and ``blocks``
    ``[n_attn_layers, ...]`` (the attention layers: the page pool's),
    each with its layers' MLP halves; the head is the embedding, drawn
    so that the logits have about unit variance, and the projections
    into the residual stream are drawn ``_OUT_GAIN`` times the fan-in
    scale (see there).  Gains are drawn about 1, so that a norm left out
    shows; ``A_log``,
    ``dt_bias`` and ``D`` as the published layer initialises them (A in
    1..16, a step of 1e-3..1e-1, D = 1), which gives heads that forget
    in tens of tokens and heads that remember thousands."""
    keys = iter(jax.random.split(rng, 32))

    def w(*sh):
        return (jax.random.normal(next(keys), sh)
                / np.sqrt(sh[-2])).astype(dtype)

    def w_res(*sh):
        return (jax.random.normal(next(keys), sh)
                * (_OUT_GAIN / np.sqrt(sh[-2]))).astype(dtype)

    def gain(*sh):
        return (1.0 + 0.1 * jax.random.normal(next(keys), sh)).astype(dtype)

    def stack(ssm):
        L, shapes = _stack_shapes(cfg, ssm)
        tree = {n: (w_res if n in _TO_RESIDUAL else w)(*sh)
                for n, sh in shapes.items()}
        tree.update(attn_norm=gain(L, cfg.dim), mlp_norm=gain(L, cfg.dim))
        if ssm:
            H = cfg.ssm_heads
            u = lambda lo, hi: jax.random.uniform(next(keys), (L, H),
                                                  minval=lo, maxval=hi)
            dt = jnp.exp(u(np.log(1e-3), np.log(1e-1)))
            tree.update(
                conv_w=(jax.random.normal(
                    next(keys), (L, cfg.conv_kernel, cfg.conv_channels))
                    / np.sqrt(cfg.conv_kernel)).astype(dtype),
                conv_b=(0.1 * jax.random.normal(
                    next(keys), (L, cfg.conv_channels))).astype(dtype),
                A_log=jnp.log(u(1.0, 16.0)).astype(jnp.float32),
                dt_bias=(dt + jnp.log(-jnp.expm1(-dt))).astype(jnp.float32),
                D=jnp.ones((L, H), jnp.float32),
                ssm_norm=gain(L, cfg.ssm_inner))
        return tree

    return {
        # rows of the tied head: logits of about unit variance
        "embed": (jax.random.normal(next(keys), (cfg.vocab_size, cfg.dim))
                  * cfg.logits_scaling / np.sqrt(cfg.dim)).astype(dtype),
        "ssm_blocks": stack(True), "blocks": stack(False),
        "final_norm": gain(cfg.dim),
    }


def param_specs(cfg: GraniteHybridConfig) -> Dict[str, Any]:
    """Every leaf replicated: the family serves on one device."""
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return jax.tree.map(lambda a: P(*(None,) * a.ndim), shapes)


def param_count(cfg: GraniteHybridConfig) -> int:
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return int(sum(np.prod(a.shape) for a in jax.tree.leaves(shapes)))


# ------------------------------------------------------------ the pieces
def rms_norm(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _residual(cfg, x, y):
    """``x + residual_multiplier * y``, rounded once."""
    return (x.astype(jnp.float32) + cfg.residual_multiplier
            * y.astype(jnp.float32)).astype(x.dtype)


_mm = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)


def ssm_rule(S, dtx, decay, Bm, Cm):
    """One token of the recurrence over the last two dimensions: S [...,
    P, N], dtx [..., P, 1], decay [..., 1, 1], Bm, Cm [..., 1, N], f32 ->
    (o [..., P, 1] without the ``D x`` skip, S).  Two passes over S, the
    reduction and the update, not products: ``o = S_new C = e^(dt A) (S
    C) + (B . C) dt x``."""
    SC = jnp.sum(S * Cm, axis=-1, keepdims=True)
    BC = jnp.sum(Bm * Cm, axis=-1, keepdims=True)
    return decay * SC + BC * dtx, decay * S + dtx * Bm


def ssm_step(x, dt, A, Bm, Cm, S):
    """One token of the recurrence, every row and head at once: x [B, H,
    P], dt [B, H], A [H], Bm, Cm [B, N] (one group: shared by the heads)
    or [B, G, N] (a group's, handed to each of its heads), all f32, S
    [B, H, P, N] or the carried buffer it is a layer of
    (``family.step_state``) -> (o [B, H, P] without the ``D x`` skip, S
    as it came): :func:`ssm_rule` on ``dt x`` and ``e^(dt A)``.  A row
    with ``dt = 0`` leaves its state as it was, bit for bit."""
    decay = jnp.exp(dt * A)[..., None, None]                 # [B, H, 1, 1]
    dtx = (dt[..., None] * x)[..., None]                     # [B, H, P, 1]
    a_head = lambda v: v[:, None, None, :] if v.ndim == 2 else jnp.repeat(
        v, x.shape[1] // v.shape[1], axis=1)[:, :, None, :]
    o, S = step_state(ssm_rule, S, dtx, decay, a_head(Bm), a_head(Cm))
    return o[..., 0], S


def ssm_chunk_scan(x, dt, A, Bm, Cm, S, block: int):
    """The recurrence of :func:`ssm_step` over T tokens in blocks: x [B,
    T, H, P], dt [B, T, H], A [H], Bm, Cm [B, T, N] (one group) or [B, T,
    G, N], S [B, H, P, N], f32 -> (o [B, T, H, P], S).  With groups the
    ``C . B`` products are a group's, made once and handed to each of
    its heads.  Inside a block, with ``L`` the running sum
    of ``dt A``: ``o_i = e^L_i S C_i + sum_(j <= i) e^(L_i - L_j) (C_i .
    B_j) dt_j x_j``, and the block leaves ``e^L_last S + sum_j e^(L_last
    - L_j) dt_j x_j B_j^T``.  No factor is above 1.  T is padded to
    whole blocks with tokens that move nothing (dt = 0)."""
    B, T, H, Pd = x.shape
    block = min(block, T)
    pad = -T % block
    if pad:
        x, dt, Bm, Cm = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),)
                                 * (a.ndim - 2)) for a in (x, dt, Bm, Cm))
    N, C = (T + pad) // block, block
    # [B, T, ...] -> [N, B, C, ...]: the scan runs over blocks
    blk = lambda a: jnp.moveaxis(a.reshape((B, N, C) + a.shape[2:]), 1, 0)
    dtx, Bm, Cm = blk(x * dt[..., None]), blk(Bm), blk(Cm)
    L = jnp.cumsum(jnp.moveaxis(blk(dt * A), 2, 3), axis=-1)  # [N, B, H, C]
    i, j = np.arange(C)[:, None], np.arange(C)[None]
    decay = jnp.exp(jnp.where(i >= j, L[..., :, None] - L[..., None, :],
                              -jnp.inf))                     # j <= i, else 0
    if Bm.ndim == 5:                    # [N, B, C, G, N]: a group's
        each, g = H // Bm.shape[3], "h"
        M = jnp.repeat(_mm("nbigs,nbjgs->nbgij", Cm, Bm), each, axis=2)
        Bm, Cm = jnp.repeat(Bm, each, axis=3), jnp.repeat(Cm, each, axis=3)
    else:
        M, g = _mm("nbis,nbjs->nbij", Cm, Bm)[:, :, None], ""
    M = M * decay                                            # [N, B, H, C, C]
    intra = _mm("nbhij,nbjhp->nbihp", M, dtx)
    q_in = jnp.exp(L)                                        # [N, B, H, C]
    k_out = jnp.exp(L[..., -1:] - L)
    last = q_in[..., -1][..., None, None]

    def one(S, b):
        intra, Cm, Bm, dtx, q_in, k_out, last = b
        o = intra + _mm(f"bi{g}s,bhps,bhi->bihp", Cm, S, q_in)
        return last * S + _mm(f"bjhp,bj{g}s,bhj->bhps", dtx, Bm, k_out), o

    S, o = jax.lax.scan(one, S, (intra, Cm, Bm, dtx, q_in, k_out, last),
                        unroll=True)
    return jnp.moveaxis(o, 0, 1).reshape(B, N * C, H, Pd)[:, :T], S


def _gated_norm(o, z, w, eps):
    """``N(o * SiLU(z))`` over the last axis (all the inner channels, or
    one group's where the caller has set the groups apart): the gate is
    applied BEFORE the norm."""
    g = o * jax.nn.silu(z.astype(jnp.float32))
    return g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def ssm_mix(cfg, x, lp, state, valid, start=None, ctx=()):
    """The Mamba-2 mixer (the family's ``Recurrent.mix``): ``x`` [B, T,
    d] -> (y [B, T, d] before the residual, the rows' new (conv, S)).
    ``valid`` [B]: tokens at or past it move neither S (their dt is 0:
    decay 1, write 0) nor the convolution's rows, which are the
    ``conv_kernel - 1`` inputs that end at the last real token.  ``start``
    and ``ctx`` (the seam hands every mixer where its rows stand and what
    ``embed`` made of the positions) are not used: the mixer has no
    positions."""
    B, T, _ = x.shape
    H, Pd, N, taps = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                      cfg.conv_kernel)
    G = cfg.ssm_groups
    inner, f32 = cfg.ssm_inner, jnp.float32
    conv, S = state
    # the benchmark's vocabulary has attention's words; ours nest in them
    with jax.named_scope("attn_qkv"), jax.named_scope("ssm_proj"):
        a = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        zx = a @ lp["w_in"]
        z, xBC = zx[..., :inner], zx[..., inner:]
        real = (jnp.arange(T)[None] < valid[:, None])[..., None]
        dt = jnp.where(real, jax.nn.softplus(
            jnp.einsum("btd,dh->bth", a, lp["w_dt"],
                       preferred_element_type=f32)
            + lp["dt_bias"].astype(f32)), 0.0)               # [B, T, H]
        A = -jnp.exp(lp["A_log"].astype(f32))
    with jax.named_scope("attn_qkv"), jax.named_scope("ssm_conv"):
        seen = jnp.concatenate([conv.astype(xBC.dtype), xBC], axis=1)
        w = lp["conv_w"].astype(f32)
        y = sum(seen[:, i:i + T].astype(f32) * w[i] for i in range(taps))
        y = jax.nn.silu(y + lp["conv_b"].astype(f32))
        conv = jax.vmap(lambda rows, n: jax.lax.dynamic_slice_in_dim(
            rows, n, taps - 1))(seen, valid).astype(conv.dtype)
        xs = y[..., :inner].reshape(B, T, H, Pd)
        Bm, Cm = y[..., inner:inner + G * N], y[..., inner + G * N:]
        if G > 1:                       # a group's, [B, T, G, N]
            Bm, Cm = Bm.reshape(B, T, G, N), Cm.reshape(B, T, G, N)
    if T == 1:
        with jax.named_scope("kv_attend"), jax.named_scope("ssm_step"):
            o, S = ssm_step(xs[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], S)
            o = o[:, None]
    else:
        with jax.named_scope("kv_attend"), jax.named_scope("ssm_scan"):
            # f32 whatever the state is kept in
            o, S = ssm_chunk_scan(xs, dt, A, Bm, Cm, S.astype(f32),
                                  cfg.ssm_block)
    with jax.named_scope("attn_out"), jax.named_scope("ssm_gate_norm"):
        o = (o + lp["D"].astype(f32)[:, None] * xs).reshape(B, T, inner)
        # the norm runs over each group's channels
        apart = lambda a: a.reshape(a.shape[:-1] + (G, -1)) if G > 1 else a
        o = _gated_norm(apart(o), apart(z), apart(lp["ssm_norm"]),
                        cfg.norm_eps).reshape(B, T, inner)
        return o.astype(x.dtype) @ lp["w_out"], (conv, S)


def _mlp_half(cfg, x, lp):
    with jax.named_scope("mlp"):
        h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps) @ lp["w_gu"]
        g, u = h[..., :cfg.ffn_dim], h[..., cfg.ffn_dim:]
        act = (jax.nn.silu(g.astype(jnp.float32))
               * u.astype(jnp.float32)).astype(x.dtype)
        return _residual(cfg, x, act @ lp["w_down"])


# -------------------------------------------------------------- the hooks
def _embed(params, tokens, start, cfg):
    """No positions anywhere: ``ctx`` is empty."""
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
        return (cfg.embedding_multiplier
                * x.astype(jnp.float32)).astype(x.dtype), ()


def _qkv(cfg, x, lp):
    """An attention layer's (q [B, T, H, W], k, v [B, T, KV, W]), not
    rotated, each head's ``head_dim`` numbers with zeros behind up to the
    pool's row width W (``kv_width``).  The shared attention step scales
    scores by ``W^-1/2``; what the stated ``attention_multiplier``
    differs from that by goes into q before it is rounded."""
    B, T, _ = x.shape
    with jax.named_scope("attn_qkv"):
        a = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        qkv = jnp.einsum("btd,dh->bth", a, lp["wqkv"],
                         preferred_element_type=jnp.float32)
        nq = cfg.n_heads * cfg.head_dim
        nk = cfg.n_kv_heads * cfg.head_dim
        heads = lambda y: jnp.pad(
            y.astype(x.dtype).reshape(B, T, -1, cfg.head_dim),
            ((0, 0),) * 3 + ((0, cfg.kv_width - cfg.head_dim),))
        q = qkv[..., :nq] * (cfg.attention_multiplier * cfg.kv_width ** 0.5)
        return heads(q), heads(qkv[..., nq:nq + nk]), \
            heads(qkv[..., nq + nk:])


def _out(cfg, x, attn, lp):
    B, T, _ = x.shape
    with jax.named_scope("attn_out"):
        attn = attn.reshape(B, T, cfg.n_heads, -1)[..., :cfg.head_dim]
        x = _residual(cfg, x, attn.reshape(B, T, -1) @ lp["wo"])
    return _mlp_half(cfg, x, lp)


def _ssm_out(cfg, x, y, lp):
    return _mlp_half(cfg, _residual(cfg, x, y), lp)


def _head(params, x, cfg):
    """The tied head: the embedding's rows, scaled down."""
    with jax.named_scope("final_norm"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    with jax.named_scope("lm_head"):
        return jnp.einsum("btd,vd->btv", x, params["embed"],
                          preferred_element_type=jnp.float32) \
            / cfg.logits_scaling


def _check(cfg: GraniteHybridConfig, mesh, max_seq: int) -> None:
    if mesh is not None and mesh.size("model") > 1:
        raise NotImplementedError(
            "GraniteHybridConfig cannot serve with a model axis > 1: the "
            "per-slot recurrent state is not sharded")
    if max_seq > cfg.max_seq_len:
        raise ValueError(f"max_seq {max_seq} is past the model's "
                         f"max_seq_len {cfg.max_seq_len}")


_STATE = ("a recurrent layer's state is one matrix a slot, not rows a "
          "token: ")

# What would need a snapshot of a slot's state at a token other than its
# last, or its rollback, is refused by name.
FAMILY = DecoderFamily(
    config_type=GraniteHybridConfig, embed=_embed, qkv=_qkv, out=_out,
    head=_head, param_specs=param_specs, quant_skip_paths=_EXACT,
    check=_check,
    cache_row=lambda cfg: CacheRow(cfg.n_kv_heads, cfg.kv_width,
                                   cfg.kv_width, head_width=cfg.head_dim),
    recurrent=Recurrent(key="ssm_blocks", period=_period, mix=ssm_mix,
                        out=_ssm_out, state_row=_state_row,
                        write_scope="ssm_write"),
    refuses=(
        ("prefix_cache", _STATE + "a shared prefix's pages say nothing of "
         "the state at its end, and no snapshot of it is kept"),
        ("kv_tier", _STATE + "a tier entry holds pages, and a prompt "
         "resumed from them would start its recurrent layers from zero"),
        ("quantized_resident", _STATE + "int8-resident pages come with "
         "kv_tier"),
        ("speculative", _STATE + "rejected draft tokens would have moved "
         "it, and no rollback is built"),
        ("zero_inference", "weight streaming runs one stack of one layer "
         "kind; this family's layers come in periods of two kinds"),
        ("contiguous_cache", "the contiguous-cache generators keep "
         "per-head K and V alone; serve through serving_engine"),
    ))

"""Qwen3-Next-style decoder (``model_type: qwen3_next``): layers in
periods of ``full_attention_interval``, all but the last of a period a
Gated DeltaNet (a linear-attention recurrence over a per-slot state),
the last a gated softmax attention over the page pool, and every
layer's FFN sparse.

A layer (``N`` = the zero-centred RMSNorm ``x / sqrt(mean x^2 + eps) *
(1 + w)``)::

    h = x + Mixer(N(x));   y = h + MoE(N(h))

Gated attention on ``a = N(x)``: ``q = N_head(a W_q)``, ``k = N_head(a
W_k)``, ``v = a W_v``, RoPE (rotate-half) on the first
``partial_rotary_factor`` of a head's numbers, causal softmax at scale
``head_dim^-1/2``, ``out = (attn * sigmoid(a W_g)) W_o``.  ``W_q`` and
``W_g`` are the two halves of the published ``q_proj``, stored apart.

Gated DeltaNet on ``a``: ``[q, k, v, z] = a W_qkvz``, ``[b, a'] = a
W_ba``; ``[q, k, v]`` pass a depthwise causal convolution of
``conv_kernel`` taps and SiLU; ``beta = sigmoid(b)``, ``g = -exp(A_log)
softplus(a' + dt_bias)``; q and k are L2-normalised a head, q scaled by
``Dk^-1/2``; a q/k head serves ``Hv / Hk`` value heads, each keeping ``S``
[Dk, Dv] in float32, from zero::

    S' = e^g S;  u = beta (v - S'^T k);  S = S' + k u^T;  o = S^T q

then ``y = (RMS_head(o) w) * SiLU(z)`` and ``W_out``.  A decode step is
that recurrence (:func:`gdn_step`); a prompt chunk computes the same in
blocks of ``gdn_block`` tokens (the WY/UT form: :func:`gdn_chunk_rule` in
XLA, :func:`gdn_block_rule` a block on the chip), the state carried on.
A slot keeps a layer's last ``conv_kernel - 1`` inputs of the convolution and
``S``: :class:`~deepspeed_tpu.models.family.StateRow`.

MoE: ``p = softmax(m W_r)`` over all the experts in f32, the ``top_k``
largest divided by their sum, ``y = sum p_e E_e(m) + sigmoid(m w_s)
E_shared(m)``; a rank holds ``experts_held`` of them, as
:mod:`~deepspeed_tpu.models.pangu_ultra_moe` does.
Serving only.  The next-token-prediction module is not instantiated.
"""

from __future__ import annotations

import dataclasses
import functools
import types
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models import llama as _llama
from deepspeed_tpu.models.family import (DecoderFamily, Recurrent, SlotState,
                                         StateRow, chunk_state,
                                         positions_from, step_state)
from deepspeed_tpu.parallel.moe import held_experts_ffn, softmax_topk_route


@dataclasses.dataclass
class Qwen3NextConfig:
    vocab_size: int = 151936
    dim: int = 2048
    n_layers: int = 48
    full_attention_interval: int = 4   # the last layer of each period
    n_heads: int = 16
    n_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    lin_k_heads: int = 16
    lin_v_heads: int = 32
    lin_k_dim: int = 128
    lin_v_dim: int = 128
    conv_kernel: int = 4
    moe_ffn_dim: int = 512
    shared_ffn_dim: int = 512
    n_routed_experts: int = 512        # what the router scores
    # (first, count) of the routed experts whose weights are here
    experts_held: Tuple[int, int] = (0, 512)
    top_k: int = 10
    norm_topk_prob: bool = True
    max_seq_len: int = 262144
    rope_theta: float = 10000000.0
    norm_eps: float = 1e-6
    # tokens a block of the chunked rule: the program's choice, not the
    # model's (any block gives the recurrence's numbers)
    gdn_block: int = 64

    def __post_init__(self):
        first, count = self.experts_held
        assert 0 <= first and first + count <= self.n_routed_experts
        assert self.n_layers % self.full_attention_interval == 0, \
            "the model is whole periods"
        assert self.lin_v_heads % self.lin_k_heads == 0
        assert self.rotary_dim % 2 == 0

    @property
    def n_full_layers(self) -> int:
        return self.n_layers // self.full_attention_interval

    @property
    def n_lin_layers(self) -> int:
        return self.n_layers - self.n_full_layers

    @property
    def n_expert_layers(self) -> int:
        return self.n_layers

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def conv_channels(self) -> int:
        return (2 * self.lin_k_heads * self.lin_k_dim
                + self.lin_v_heads * self.lin_v_dim)

    @classmethod
    def tiny(cls, **kw):
        base = dict(vocab_size=256, dim=64, n_layers=4, n_heads=4,
                    n_kv_heads=2, head_dim=16, lin_k_heads=2, lin_v_heads=4,
                    lin_k_dim=16, lin_v_dim=16, moe_ffn_dim=32,
                    shared_ffn_dim=32, n_routed_experts=16,
                    experts_held=(0, 8), top_k=4, max_seq_len=512,
                    gdn_block=8)
        base.update(kw)
        return cls(**base)


def _period(cfg) -> Tuple[bool, ...]:
    return (True,) * (cfg.full_attention_interval - 1) + (False,)


def _state_row(cfg) -> StateRow:
    return StateRow(cfg.n_lin_layers,
                    (cfg.conv_kernel - 1, cfg.conv_channels),
                    (cfg.lin_v_heads, cfg.lin_k_dim, cfg.lin_v_dim))


# ------------------------------------------------------------- parameters
def _moe_shapes(cfg, L):
    d, f, fs = cfg.dim, cfg.moe_ffn_dim, cfg.shared_ffn_dim
    Eh = cfg.experts_held[1]
    return {"gate": (L, d, cfg.n_routed_experts),
            "w1": (L, Eh, d, f), "w3": (L, Eh, d, f), "w2": (L, Eh, f, d),
            "sw1": (L, d, fs), "sw3": (L, d, fs), "sw2": (L, fs, d),
            "shared_gate": (L, d, 1)}


def _stack_shapes(cfg, linear: bool):
    d = cfg.dim
    if linear:
        L = cfg.n_lin_layers
        Kd, Vd = cfg.lin_k_heads * cfg.lin_k_dim, \
            cfg.lin_v_heads * cfg.lin_v_dim
        shapes = {"w_qkvz": (L, d, 2 * Kd + 2 * Vd),
                  "w_ba": (L, d, 2 * cfg.lin_v_heads),
                  "w_out": (L, Vd, d)}
    else:
        L = cfg.n_full_layers
        H, KV, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        shapes = {"wq": (L, d, H * Dh), "wg": (L, d, H * Dh),
                  "wk": (L, d, KV * Dh), "wv": (L, d, KV * Dh),
                  "wo": (L, H * Dh, d)}
    shapes.update(_moe_shapes(cfg, L))
    return L, shapes


# zero-centred gains (applied as 1 + w), by stack
_NORMS = {True: {"attn_norm": "dim", "mlp_norm": "dim"},
          False: {"attn_norm": "dim", "mlp_norm": "dim",
                  "q_norm": "head_dim", "k_norm": "head_dim"}}
_EXACT = ("gate", "shared_gate", "attn_norm", "mlp_norm", "q_norm",
          "k_norm", "gdn_norm", "final_norm", "A_log", "dt_bias", "conv_w")


def init_params(rng: jax.Array, cfg: Qwen3NextConfig,
                dtype=jnp.float32) -> Dict[str, Any]:
    """Two stacks: ``gdn_blocks`` ``[n_lin_layers, ...]`` and ``blocks``
    ``[n_full_layers, ...]`` (the attention layers: the page pool's),
    each with its layers' expert halves, the held experts stacked
    ``[L, Eh, ...]``.  Gains are drawn about their neutral value, so
    that a norm applied in the wrong form shows; ``A_log`` and
    ``dt_bias`` as the published layer initialises them (A in 1..16,
    a step of 1e-3..1e-1), which gives heads that remember a few tokens
    and heads that remember thousands."""
    keys = iter(jax.random.split(rng, 64))

    def w(*sh):
        return (jax.random.normal(next(keys), sh)
                / np.sqrt(sh[-2])).astype(dtype)

    def gain(about, *sh):
        return (about + 0.1 * jax.random.normal(next(keys), sh)).astype(dtype)

    def stack(linear):
        L, shapes = _stack_shapes(cfg, linear)
        tree = {n: w(*sh) for n, sh in shapes.items()}
        tree.update({n: gain(0.0, L, getattr(cfg, width))
                     for n, width in _NORMS[linear].items()})
        if linear:
            Hv = cfg.lin_v_heads
            u = lambda lo, hi: jax.random.uniform(next(keys), (L, Hv),
                                                  minval=lo, maxval=hi)
            dt = jnp.exp(u(np.log(1e-3), np.log(1e-1)))
            tree.update(
                conv_w=(jax.random.normal(
                    next(keys), (L, cfg.conv_kernel, cfg.conv_channels))
                    / np.sqrt(cfg.conv_kernel)).astype(dtype),
                A_log=jnp.log(u(1.0, 16.0)).astype(jnp.float32),
                dt_bias=(dt + jnp.log(-jnp.expm1(-dt))).astype(jnp.float32),
                gdn_norm=gain(1.0, L, cfg.lin_v_dim))
        return tree

    return {
        "embed": jax.random.normal(
            next(keys), (cfg.vocab_size, cfg.dim)).astype(dtype),
        "gdn_blocks": stack(True), "blocks": stack(False),
        "final_norm": gain(0.0, cfg.dim),
        "lm_head": w(cfg.dim, cfg.vocab_size),
    }


def param_specs(cfg: Qwen3NextConfig) -> Dict[str, Any]:
    """Every leaf replicated: the family serves on one device."""
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return jax.tree.map(lambda a: P(*(None,) * a.ndim), shapes)


def param_count(cfg: Qwen3NextConfig) -> int:
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return int(sum(np.prod(a.shape) for a in jax.tree.leaves(shapes)))


# ------------------------------------------------------------ the pieces
def norm1p(x, w, eps):
    """The zero-centred RMSNorm, in f32: ``(1 + w)`` is not a bf16
    number where ``w`` is."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


_mm = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)


def gdn_rule(S, q, k, v, decay, beta):
    """One token of the recurrence over the last two dimensions: S [...,
    Dk, Dv], q, k [..., Dk, 1], v [..., 1, Dv], decay, beta [..., 1, 1],
    f32 -> (o [..., 1, Dv], S).  Two passes over S, the two reductions
    and the update, not products: ``o = S_new^T q = e^g S^T q + (k . q)
    u``."""
    Sk = jnp.sum(S * k, axis=-2, keepdims=True) * decay      # S'^T k
    Sq = jnp.sum(S * q, axis=-2, keepdims=True) * decay
    u = beta * (v - Sk)
    return Sq + jnp.sum(k * q, -2, keepdims=True) * u, decay * S + k * u


def gdn_step(q, k, v, g, beta, S):
    """One token of the recurrence, every row and head at once: q, k
    [B, H, Dk], v [B, H, Dv], g, beta [B, H], all f32, S [B, H, Dk, Dv]
    or the carried buffer it is a layer of (``family.step_state``) -> (o
    [B, H, Dv], S as it came): :func:`gdn_rule` on ``e^g``.  A row with
    ``beta = g = 0`` leaves its state as it was, bit for bit."""
    o, S = step_state(gdn_rule, S, q[..., None], k[..., None],
                      v[..., None, :], jnp.exp(g)[..., None, None],
                      beta[..., None, None])
    return o[..., 0, :], S


def gdn_chunk_rule(q, k, v, g, beta, S, block: int):
    """The recurrence of :func:`gdn_step` over T tokens in blocks: q, k
    [B, T, H, Dk], v [B, T, H, Dv], g, beta [B, T, H], S [B, H, Dk, Dv],
    f32 -> (o [B, T, H, Dv], S).  Inside a block of C tokens, with ``c``
    the running sum of g and ``M[i, j] = beta_i e^(c_i - c_j) k_i . k_j``
    (j < i), the u of all its tokens solve ``(I + M) U = beta (V -
    e^c K S)``; ``(I + M)^-1`` is the product of ``I + (-M)^(2^n)``
    (M is strictly lower: its C-th power is zero).  Then ``o_i = e^c_i
    S^T q_i + sum_(j <= i) e^(c_i - c_j) (k_j . q_i) u_j`` and the
    block leaves ``e^c_C S + sum_j e^(c_C - c_j) k_j u_j^T``.  No factor
    is above 1.  T is padded to whole blocks with tokens that move
    nothing (beta = g = 0)."""
    B, T, H, Dk = q.shape
    pad = -T % block
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),)
                                    * (a.ndim - 2))
                            for a in (q, k, v, g, beta))
    N, C = (T + pad) // block, block
    # [B, T, H, ...] -> [N, B, H, C, ...]: the scan runs over blocks
    blk = lambda a: jnp.moveaxis(
        a.reshape((B, N, C) + a.shape[2:]), (1, 2), (0, 3))
    q, k, v = blk(q), blk(k), blk(v)
    g, beta = blk(g[..., None])[..., 0], blk(beta[..., None])[..., 0]
    c = jnp.cumsum(g, axis=-1)                               # [N, B, H, C]
    i, j = np.arange(C)[:, None], np.arange(C)[None]
    decay = jnp.exp(jnp.where(i >= j, c[..., :, None] - c[..., None, :],
                              -jnp.inf))                     # j <= i, else 0
    kb = k * beta[..., None]
    X = -jnp.where(i > j, _mm("nbhid,nbhjd->nbhij", kb, k) * decay, 0.0)
    inv = jnp.eye(C, dtype=X.dtype) + X
    for _ in range(max(0, (C - 1).bit_length() - 1)):
        X = _mm("nbhij,nbhjk->nbhik", X, X)
        inv = inv + _mm("nbhij,nbhjk->nbhik", inv, X)
    value = _mm("nbhij,nbhjd->nbhid", inv, v * beta[..., None])
    k_cum = _mm("nbhij,nbhjd->nbhid", inv, kb * jnp.exp(c)[..., None])
    qk = _mm("nbhid,nbhjd->nbhij", q, k) * decay             # j <= i
    q_in = q * jnp.exp(c)[..., None]
    k_out = k * jnp.exp(c[..., -1:] - c)[..., None]
    last = jnp.exp(c[..., -1])[..., None, None]

    def one(S, b):
        value, k_cum, qk, q_in, k_out, last = b
        u = value - _mm("bhik,bhkd->bhid", k_cum, S)
        o = _mm("bhik,bhkd->bhid", q_in, S) + _mm("bhij,bhjd->bhid", qk, u)
        return last * S + _mm("bhik,bhid->bhkd", k_out, u), o

    S, o = jax.lax.scan(one, S, (value, k_cum, qk, q_in, k_out, last),
                        unroll=True)
    o = jnp.moveaxis(o, (0, 3), (1, 2)).reshape(B, N * C, H, -1)
    return o[:, :T], S


def gdn_mix(cfg, x, lp, state, valid, start=None, ctx=()):
    """The Gated DeltaNet mixer (the family's ``Recurrent.mix``): ``x``
    [B, T, d] -> (y [B, T, d] before the residual, the rows' new (conv,
    S)).  ``valid`` [B]: tokens at or past it move neither S (their beta
    and g are 0) nor the convolution's rows, which are the
    ``conv_kernel - 1`` inputs that end at the last real token.  ``start``
    and ``ctx`` (where the rows stand, what ``embed`` made of the positions)
    are not used: the mixer has none.  A chunk's ``SlotState`` runs on chip."""
    B, T, _ = x.shape
    Hk, Hv, Dk, Dv = (cfg.lin_k_heads, cfg.lin_v_heads, cfg.lin_k_dim,
                      cfg.lin_v_dim)
    Kd, taps = Hk * Dk, cfg.conv_kernel
    f32 = jnp.float32
    conv, S = state
    # the benchmark's vocabulary has attention's words; ours nest in them
    with jax.named_scope("attn_qkv"), jax.named_scope("gdn_proj"):
        a = norm1p(x, lp["attn_norm"], cfg.norm_eps)
        qkvz = a @ lp["w_qkvz"]
        mixed, z = qkvz[..., :cfg.conv_channels], \
            qkvz[..., cfg.conv_channels:]
        ba = jnp.einsum("btd,dh->bth", a, lp["w_ba"],
                        preferred_element_type=f32)
        real = (jnp.arange(T)[None] < valid[:, None])[..., None]
        beta = jnp.where(real, jax.nn.sigmoid(ba[..., :Hv]), 0.0)
        g = jnp.where(real, -jnp.exp(lp["A_log"].astype(f32))
                      * jax.nn.softplus(ba[..., Hv:]
                                        + lp["dt_bias"].astype(f32)), 0.0)
    with jax.named_scope("attn_qkv"), jax.named_scope("gdn_conv"):
        seen = jnp.concatenate([conv.astype(mixed.dtype), mixed], axis=1)
        w = lp["conv_w"].astype(f32)
        y = sum(seen[:, i:i + T].astype(f32) * w[i] for i in range(taps))
        y = jax.nn.silu(y)
        conv = jax.vmap(lambda rows, n: jax.lax.dynamic_slice_in_dim(
            rows, n, taps - 1))(seen, valid).astype(conv.dtype)
        q = _l2norm(y[..., :Kd].reshape(B, T, Hk, Dk)) * Dk ** -0.5
        k = _l2norm(y[..., Kd:2 * Kd].reshape(B, T, Hk, Dk))
        v = y[..., 2 * Kd:].reshape(B, T, Hv, Dv)
        if not isinstance(S, SlotState):    # the kernel reads a key head
            q, k = (jnp.repeat(t, Hv // Hk, axis=2) for t in (q, k))
    if T == 1:
        with jax.named_scope("kv_attend"), jax.named_scope("gdn_step"):
            o, S = gdn_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                            S)
            o = o[:, None]
    else:
        with jax.named_scope("kv_attend"), jax.named_scope("gdn_scan"):
            if isinstance(S, SlotState):
                o, S = gdn_chunk_kernel(q, k, v, g, beta, S, cfg.gdn_block)
            else:       # f32 whatever the state is kept in
                o, S = gdn_chunk_rule(q, k, v, g, beta, S.astype(f32),
                                      cfg.gdn_block)
    with jax.named_scope("attn_out"), jax.named_scope("gdn_gate_norm"):
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                              + cfg.norm_eps) * lp["gdn_norm"].astype(f32)
        o = o.reshape(B, T, -1) * jax.nn.silu(z.astype(f32))
        return o.astype(x.dtype) @ lp["w_out"], (conv, S)


def _pair(a):
    """``a`` f32 as two bf16 numbers an entry, ``hi + lo``: 16 bits of
    its mantissa."""
    hi = a.astype(jnp.bfloat16)
    return hi, (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)


# [h, m, k] x [h, k, n], x [h, n, k] and [h, k, m] x [h, k, n], a head each
_NN, _NT, _TN = ((((2,), (1,)), ((0,), (0,))), (((2,), (2,)), ((0,), (0,))),
                 (((1,), (1,)), ((0,), (0,))))


def _mm3(a, b, dims=_NN):
    """The product of two :func:`_pair` operands, a head each, in three
    bf16 passes (``hi hi + hi lo + lo hi``) accumulated in f32: an error
    of ~2^-16 a product, where a bf16 operand's is 2^-8 (PERF.md 6, PR
    50)."""
    dot = lambda x, y: jax.lax.dot_general(
        x, y, dims, preferred_element_type=jnp.float32)
    return dot(a[0], b[0]) + (dot(a[0], b[1]) + dot(a[1], b[0]))


def _unit_lower_inverse(M, i, j, leaf: int = 16):
    """``(I + M)^-1`` of strictly lower-triangular M [h, C, C] (``i``,
    ``j`` [1, C, C] the row and column numbers), by blocks and exact: the
    ``leaf``-wide diagonal blocks D first, all at once (``D^leaf = 0``:
    the product of ``I + (-D)^(2^n)``), then ``[[A, 0], [B, D]]^-1 =
    [[A^-1, 0], [-D^-1 B A^-1, D^-1]]`` a doubling of the block, each
    level two products of masked [C, C] matrices: a product costs the
    chip's matrix unit its latency, whatever its operands' size, and
    nothing is sliced or put together."""
    C = M.shape[-1]
    leaf = min(leaf, C)
    if C % leaf or (C // leaf) & (C // leaf - 1) or leaf & (leaf - 1):
        raise ValueError(f"a block of {C} tokens is not a power of two")
    X = jnp.where(i // leaf == j // leaf, -M, 0.0)
    inv = jnp.where(i == j, 1.0, 0.0) + X
    for _ in range(leaf.bit_length() - 2):
        Xp = _pair(X)
        X = _mm3(Xp, Xp)
        inv = inv + _mm3(_pair(inv), _pair(X))
    while leaf < C:
        below = (i // (2 * leaf) == j // (2 * leaf)) & (i // leaf != j // leaf)
        ip = _pair(inv)
        inv = inv - _mm3(_pair(_mm3(ip, _pair(jnp.where(below, M, 0.0)))), ip)
        leaf *= 2
    return inv


def gdn_block_rule(S, q, k, v, col, lane):
    """One block of C tokens of :func:`gdn_chunk_rule` on h value heads
    at once (the family's ``Recurrent.block``, what ``dstpu_state_chunk``
    runs a block: :func:`~deepspeed_tpu.inference.kernels.state_chunk`):
    S [h, Dk, Dv], q, k [h or fewer, C, Dk] (a key head serves as many
    value heads as it takes), v [h, C, Dv], ``col`` [h, C, 2] the running
    sum ``c`` of g inside the block and beta down the block, ``lane`` [h,
    1, C] ``c`` across it -> (o [h, C, Dv], S).  The same algebra with the
    solve last: ``u = (I + M)^-1 beta (v - e^c k S)``, ``o = e^c q S + (q
    k^T decay) u``, ``S <- e^c_C S + k^T (e^(c_C - c) u)``; every product
    three bf16 passes in f32 (:func:`_mm3`), the heads' side by side (their
    chains are independent: the chip runs one while another waits), no
    factor above 1, and a block whose beta and g are 0 leaves S bit for
    bit."""
    h, C = S.shape[0], q.shape[1]
    c, beta = col[..., 0:1], col[..., 1:2]
    i = jax.lax.broadcasted_iota(jnp.int32, (1, C, C), 1)
    j = jax.lax.broadcasted_iota(jnp.int32, (1, C, C), 2)
    decay = jnp.where(i >= j, jnp.exp(jnp.minimum(c - lane, 0.0)), 0.0)
    # a key head's products once, then a copy a value head it serves
    wide = lambda t: t if t.shape[0] == h else jnp.repeat(
        t, h // t.shape[0], axis=0)
    kp, qp = _pair(k), _pair(q)
    kk, qk = wide(_mm3(kp, kp, _NT)), wide(_mm3(qp, kp, _NT))
    kp, qp = tuple(map(wide, kp)), tuple(map(wide, qp))
    inv = _unit_lower_inverse(jnp.where(i > j, beta * decay * kk, 0.0), i, j)
    ec, Sp = jnp.exp(c), _pair(S)
    u = _mm3(_pair(inv), _pair(beta * (v - ec * _mm3(kp, Sp))))
    o = ec * _mm3(qp, Sp) + _mm3(_pair(qk * decay), _pair(u))
    # c_C as a row: c over the lanes, its last row summed out with zeros
    # (the chip spreads one number one way at a time)
    over = jnp.broadcast_to(c, u.shape)
    last = jnp.sum(jnp.where(jax.lax.broadcasted_iota(
        jnp.int32, u.shape, 1) == C - 1, over, 0.0), axis=1, keepdims=True)
    return o, jnp.exp(last) * S + _mm3(kp, _pair(jnp.exp(last - over) * u),
                                       _TN)


def gdn_chunk_kernel(q, k, v, g, beta, S: SlotState, block: int):
    """:func:`gdn_chunk_rule` where the build runs a chunk's state on the
    chip: q, k [B, T, Hk, Dk] (a key head is not repeated: it serves ``Hv
    // Hk`` value heads where it lies), v [B, T, Hv, Dv], g, beta [B, T,
    Hv], f32 -> (o [B, T, Hv, Dv], S [B, Hv, Dk, Dv]): the running sums
    made here, the blocks :func:`gdn_block_rule`'s."""
    B, T, H = g.shape
    pad = -T % block
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),)
                                    * (a.ndim - 2))
                            for a in (q, k, v, g, beta))
    c = jnp.cumsum(g.reshape(B, -1, block, H), axis=2).reshape(g.shape)
    o, S = chunk_state(gdn_block_rule, S, (q, k, v),
                       jnp.stack([c, beta], axis=-1), c[..., None], block)
    return o[:, :T], S


def expert_layer(cfg, h, lp):
    """h [B, T, d] (normed) -> (this rank's part of the routed sum plus
    the gated shared expert, rows [Eh] int32 routed to each held
    expert)."""
    from deepspeed_tpu.ops.fused_ops import swiglu

    B, T, d = h.shape
    hf = h.reshape(-1, d)
    w, experts = softmax_topk_route(hf, lp["gate"], cfg.top_k,
                                    cfg.norm_topk_prob)
    with jax.named_scope("moe_ffn"):
        y, rows = held_experts_ffn(hf, w, experts, lp["w1"], lp["w3"],
                                   lp["w2"], first=cfg.experts_held[0],
                                   layer=lp.get("layer"),
                                   n_experts=lp["gate"].shape[-1])
        with jax.named_scope("moe_shared"):
            open_ = jax.nn.sigmoid(jnp.einsum(
                "nd,do->no", hf, lp["shared_gate"],
                preferred_element_type=jnp.float32))
            shared = swiglu(hf, lp["sw1"], lp["sw3"]) @ lp["sw2"]
            y = y + (open_ * shared.astype(jnp.float32)).astype(y.dtype)
    return y.reshape(B, T, d), rows


def _ffn_half(cfg, x, lp):
    with jax.named_scope("mlp"):
        y, rows = expert_layer(cfg, norm1p(x, lp["mlp_norm"], cfg.norm_eps),
                               lp)
        return x + y, rows


# -------------------------------------------------------------- the hooks
def _embed(params, tokens, start, cfg):
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
        return x, _llama.rope_tables(
            types.SimpleNamespace(head_dim=cfg.rotary_dim,
                                  rope_theta=cfg.rope_theta),
            positions_from(start, tokens.shape[1]))


def _qkv(cfg, x, lp, cos, sin):
    """An attention layer's (q [B, T, H, Dh], k, v [B, T, KV, Dh]): q
    and k normed a head, their first ``rotary_dim`` numbers rotated."""
    B, T, _ = x.shape
    R, eps = cfg.rotary_dim, cfg.norm_eps
    with jax.named_scope("attn_qkv"):
        a = norm1p(x, lp["attn_norm"], eps)
        heads = lambda y: y.reshape(B, T, -1, cfg.head_dim)
        rot = lambda t: jnp.concatenate(
            [_llama.apply_rope(t[..., :R], cos, sin), t[..., R:]], -1)
        q = rot(norm1p(heads(a @ lp["wq"]), lp["q_norm"], eps))
        k = rot(norm1p(heads(a @ lp["wk"]), lp["k_norm"], eps))
        return q, k, heads(a @ lp["wv"])


def _out(cfg, x, attn, lp):
    """An attention layer's second half: the output gate (from the
    layer's normed input, which the projection in ``qkv`` read too),
    ``W_o``, the residual, the sparse FFN."""
    with jax.named_scope("attn_out"):
        with jax.named_scope("attn_gate"):
            gate = norm1p(x, lp["attn_norm"], cfg.norm_eps) @ lp["wg"]
            attn = (attn.astype(jnp.float32) * jax.nn.sigmoid(
                gate.astype(jnp.float32))).astype(attn.dtype)
        x = x + attn @ lp["wo"]
    return _ffn_half(cfg, x, lp)


def _gdn_out(cfg, x, y, lp):
    return _ffn_half(cfg, x + y, lp)


def _head(params, x, cfg):
    with jax.named_scope("final_norm"):
        x = norm1p(x, params["final_norm"], cfg.norm_eps)
    with jax.named_scope("lm_head"):
        return jnp.einsum("btd,dv->btv", x, params["lm_head"],
                          preferred_element_type=jnp.float32)


def _check(cfg: Qwen3NextConfig, mesh, max_seq: int) -> None:
    if mesh is not None and any(mesh.size(ax) > 1
                                for ax in ("model", "expert")):
        raise NotImplementedError(
            "Qwen3NextConfig cannot serve with a model or expert axis "
            "> 1: the per-slot recurrent state is not sharded, and the "
            "held experts' grouped product is one device's")
    if max_seq > cfg.max_seq_len:
        raise ValueError(f"max_seq {max_seq} is past the model's "
                         f"max_seq_len {cfg.max_seq_len}")


_STATE = ("a recurrent layer's state is one matrix a slot, not rows a "
          "token: ")

# What would need a snapshot of a slot's state at a token other than its
# last, or its rollback, is refused by name.
FAMILY = DecoderFamily(
    config_type=Qwen3NextConfig, embed=_embed, qkv=_qkv, out=_out,
    head=_head, param_specs=param_specs, quant_skip_paths=_EXACT,
    shard_axes=("model", "expert"), check=_check,
    expert_rows=lambda cfg: (cfg.experts_held[1],
                             cfg.top_k * cfg.n_expert_layers),
    router=lambda cfg: (cfg.n_routed_experts, cfg.top_k),
    whole_stacks=("w1", "w3", "w2"),
    recurrent=Recurrent(key="gdn_blocks", period=_period, mix=gdn_mix,
                        out=_gdn_out, state_row=_state_row,
                        write_scope="gdn_write", block=gdn_block_rule),
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

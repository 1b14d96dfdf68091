"""Ling-3.0-flash-style decoder (the language model of inclusionAI's
Ling-3.0-flash-VL): layers in periods of ``layer_group_size``, all but
the last of a period a Kimi Delta Attention (KDA: a delta rule whose
decay is a number a KEY CHANNEL, over a per-slot state), the last a
multi-head latent attention over one-row-a-token pages, the first
``n_dense_layers`` layers with a dense SwiGLU and the others with a
group-limited sigmoid router over a share of the routed experts plus a
shared expert.

A layer (``RMS`` = RMSNorm with its own gain)::

    h = x + Mixer(RMS(x));   y = h + F(RMS(h))

KDA on ``a = RMS(x)`` (arXiv:2510.26692; H heads of ``Dk = Dv =
kda_head_dim``): ``[q, k, v] = SiLU(conv(a W_qkv))`` (depthwise, causal,
``conv_kernel`` taps, no bias), q and k L2-normalised a head, q scaled by
``Dk^-1/2``; ``g = L sigmoid(e^A_h (a W_f + b_f))`` a key channel, in
``(L, 0)`` with ``L = kda_lower_bound``; ``beta = sigmoid(a w_b)`` a
head.  A head keeps ``S`` [Dk, Dv] in float32, from zero::

    S' = Diag(e^g) S;  u = beta (v - S'^T k);  S = S' + k u^T;  o = S^T q

then ``y = (RMS_head(o) * sigmoid(a w_g)) W_o``, the gate one number a
head.  A decode step is that recurrence (:func:`kda_rule`); a prompt chunk
computes the same in blocks of ``kda_block`` tokens
(:func:`kda_block_rule`: in XLA under a scan, or a block of
``dstpu_state_chunk`` on the chip), the state carried on.

MLA as :mod:`~deepspeed_tpu.models.pangu_ultra_moe`'s, whose projections
these are, but ``q = a W_q`` (no low rank) and the head-wise sigmoid gate
before ``W_o``.  The router: ``s = sigmoid(m W_r)`` over all the experts
in f32; by ``s + b`` the ``topk_group`` best of ``n_group`` groups (a
group's score the sum of its two largest), the ``top_k`` largest inside
them; ``w = routed_scaling_factor * s / sum(s)``.  A rank holds
``experts_held``, as its siblings do.

Serving only.  The vision tower and the next-token module are not
instantiated: this is the language model under text.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models import llama as _llama
from deepspeed_tpu.models import pangu_ultra_moe as _pangu
from deepspeed_tpu.models.family import (DecoderFamily, Recurrent, SlotState,
                                         StateRow, chunk_state, step_state)
from deepspeed_tpu.models.qwen3_next import (_NN, _NT, _TN, _l2norm, _mm3,
                                             _pair, _unit_lower_inverse)


@dataclasses.dataclass
class LingFlashConfig:
    vocab_size: int = 157184
    dim: int = 2560
    n_layers: int = 42
    n_dense_layers: int = 2            # first_k_dense_replace
    layer_group_size: int = 6          # the last layer of each period attends
    n_heads: int = 32                  # of a KDA layer and of an MLA layer
    kda_head_dim: int = 128            # Dk = Dv
    conv_kernel: int = 4
    kda_lower_bound: float = -5.0
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    ffn_dim: int = 6144                # the dense layers' SwiGLU
    moe_ffn_dim: int = 768             # one expert's SwiGLU
    shared_ffn_dim: int = 768
    n_routed_experts: int = 512        # what the router scores
    # (first, count) of the routed experts whose weights are here
    experts_held: Tuple[int, int] = (0, 512)
    top_k: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    max_seq_len: int = 131072
    rope_theta: float = 6000000.0
    norm_eps: float = 1e-6
    # tokens a block of the chunked rule: the program's choice, not the
    # model's (any block gives the recurrence's numbers)
    kda_block: int = 64

    def __post_init__(self):
        first, count = self.experts_held
        assert 0 <= first and first + count <= self.n_routed_experts
        assert self.n_layers % self.layer_group_size == 0, \
            "the model is whole periods"
        assert 0 <= self.n_dense_layers < self.layer_group_size, \
            "the leading dense layers are KDA layers of the first period"
        assert self.n_routed_experts % self.n_group == 0
        assert self.qk_rope_dim % 2 == 0

    @property
    def n_mla_layers(self) -> int:
        return self.n_layers // self.layer_group_size

    @property
    def n_kda_layers(self) -> int:
        return self.n_layers - self.n_mla_layers

    @property
    def n_expert_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def conv_channels(self) -> int:
        return 3 * self.n_heads * self.kda_head_dim

    @property
    def row_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_dim

    # the page pool's dims as the serving stack reads them off a config:
    # one "kv head" whose rows are the latent row as stored
    @property
    def n_kv_heads(self) -> int:
        return 1

    @property
    def head_dim(self) -> int:
        return _pangu._cache_row(self).pool_width

    @classmethod
    def tiny(cls, **kw):
        base = dict(vocab_size=256, dim=64, n_layers=6, n_dense_layers=1,
                    layer_group_size=3, n_heads=4, kda_head_dim=16,
                    kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
                    v_head_dim=16, ffn_dim=96, moe_ffn_dim=32,
                    shared_ffn_dim=32, n_routed_experts=16,
                    experts_held=(0, 4), top_k=4, n_group=4, topk_group=2,
                    max_seq_len=512, kda_block=8)
        base.update(kw)
        return cls(**base)


def _sections(cfg) -> tuple:
    """Behind the leading dense layers: the rest of the first period,
    then the whole periods."""
    G, lead = cfg.layer_group_size, cfg.n_dense_layers
    period, periods = (True,) * (G - 1) + (False,), cfg.n_layers // G
    if not lead:
        return ((period, periods),)
    return ((period[lead:], 1),) + ((period, periods - 1),) * (periods > 1)


def _state_row(cfg) -> StateRow:
    return StateRow(cfg.n_kda_layers,
                    (cfg.conv_kernel - 1, cfg.conv_channels),
                    (cfg.n_heads, cfg.kda_head_dim, cfg.kda_head_dim))


# ------------------------------------------------------------- parameters
def _kda_shapes(cfg, L):
    d, H, D = cfg.dim, cfg.n_heads, cfg.kda_head_dim
    return {"w_qkv": (L, d, 3 * H * D), "w_b": (L, d, H), "w_g": (L, d, H),
            "w_out": (L, H * D, d)}


def _mla_shapes(cfg, L):
    d, H, C = cfg.dim, cfg.n_heads, cfg.kv_lora_rank
    return {"wq": (L, d, H * (cfg.qk_nope_dim + cfg.qk_rope_dim)),
            "wkv_a": (L, d, C + cfg.qk_rope_dim),
            # W_kvb in its two halves, so that neither form slices a weight
            "w_uk": (L, C, H * cfg.qk_nope_dim),
            "w_uv": (L, C, H * cfg.v_head_dim),
            "w_g": (L, d, H), "wo": (L, H * cfg.v_head_dim, d)}


def _ffn_shapes(cfg, L, dense: bool):
    d = cfg.dim
    if dense:
        f = cfg.ffn_dim
        return {"w1": (L, d, f), "w3": (L, d, f), "w2": (L, f, d)}
    f, fs, Eh = cfg.moe_ffn_dim, cfg.shared_ffn_dim, cfg.experts_held[1]
    return {"gate": (L, d, cfg.n_routed_experts),
            "w1": (L, Eh, d, f), "w3": (L, Eh, d, f), "w2": (L, Eh, f, d),
            "sw1": (L, d, fs), "sw3": (L, d, fs), "sw2": (L, fs, d)}


# RMSNorm gains (and what else stays exact under weight-only quantization)
_NORMS = {True: {"attn_norm": "dim", "mlp_norm": "dim",
                 "o_norm": "kda_head_dim"},
          False: {"attn_norm": "dim", "mlp_norm": "dim",
                  "kv_norm": "kv_lora_rank"}}
_EXACT = ("gate", "gate_bias", "attn_norm", "mlp_norm", "o_norm", "kv_norm",
          "final_norm", "A_log", "b_f", "conv_w")


def init_params(rng: jax.Array, cfg: LingFlashConfig,
                dtype=jnp.float32) -> Dict[str, Any]:
    """Three stacks: ``lead_blocks`` ``[n_dense_layers, ...]`` (a KDA
    mixer and a dense SwiGLU), ``kda_blocks`` (the other KDA layers) and
    ``blocks`` ``[n_mla_layers, ...]`` (the page pool's), the last two
    with their layers' expert halves, the held experts stacked ``[L, Eh,
    ...]``.  Gains are drawn about 1, so that a norm left out shows.  The
    gate: ``A_log`` a head in log(1/4 .. 2) and ``b_f`` a channel so that
    ``e^A b_f`` lies in -10 .. -2: channels that forget in two tokens
    beside channels that remember thousands, the sharper heads reaching
    the lower bound on some tokens.  The router's bias is drawn small:
    it moves choices that are close, as a trained one does."""
    keys = iter(jax.random.split(rng, 96))

    def w(*sh):
        return (jax.random.normal(next(keys), sh)
                / np.sqrt(sh[-2])).astype(dtype)

    def gain(*sh):
        return (1.0 + 0.1 * jax.random.normal(next(keys), sh)).astype(dtype)

    def stack(L, kda: bool, dense: bool):
        shapes = dict((_kda_shapes if kda else _mla_shapes)(cfg, L),
                      **_ffn_shapes(cfg, L, dense))
        tree = {n: w(*sh) for n, sh in shapes.items()}
        tree.update({n: gain(L, getattr(cfg, width))
                     for n, width in _NORMS[kda].items()})
        if not dense:
            tree["gate_bias"] = 0.02 * jax.random.normal(
                next(keys), (L, cfg.n_routed_experts), jnp.float32)
        if kda:
            H, D = cfg.n_heads, cfg.kda_head_dim
            # the gate's projection is read in float32 and wanted
            # [outputs, d] by both programs: held any other way each
            # copies the stack whole, 188 MB a run (AOT, v5e, PR 51)
            tree["w_f"] = jnp.swapaxes(w(L, cfg.dim, H * D), 1, 2)
            u = lambda lo, hi, *sh: jax.random.uniform(
                next(keys), (L,) + sh, minval=lo, maxval=hi)
            A = u(np.log(0.25), np.log(2.0), H)
            tree.update(
                conv_w=(jax.random.normal(
                    next(keys), (L, cfg.conv_kernel, cfg.conv_channels))
                    / np.sqrt(cfg.conv_kernel)).astype(dtype),
                A_log=A.astype(jnp.float32),
                b_f=(-u(2.0, 10.0, H, D) / jnp.exp(A)[..., None]).reshape(
                    L, H * D).astype(jnp.float32))
        return tree

    n_lead = cfg.n_dense_layers
    return {
        "embed": jax.random.normal(
            next(keys), (cfg.vocab_size, cfg.dim)).astype(dtype),
        "lead_blocks": stack(n_lead, True, True),
        "kda_blocks": stack(cfg.n_kda_layers - n_lead, True, False),
        "blocks": stack(cfg.n_mla_layers, False, False),
        "final_norm": gain(cfg.dim),
        "lm_head": w(cfg.dim, cfg.vocab_size),
    }


def param_specs(cfg: LingFlashConfig) -> Dict[str, Any]:
    """Every leaf replicated: the family serves on one device."""
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return jax.tree.map(lambda a: P(*(None,) * a.ndim), shapes)


def param_count(cfg: LingFlashConfig) -> int:
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return int(sum(np.prod(a.shape) for a in jax.tree.leaves(shapes)))


# ---------------------------------------------------- the delta rule's two
def kda_rule(S, q, k, v, decay, beta):
    """One token of the recurrence over the last two dimensions: S [...,
    Dk, Dv], q, k, decay [..., Dk, 1] (``e^g``, a number a key channel: a
    row of S each), v [..., 1, Dv], beta [..., 1, 1], f32 -> (o [..., 1,
    Dv], S).  The decayed state once, the two reductions and the update,
    not products: ``o = S_new^T q = S'^T q + (k . q) u``.  ``beta = 0``
    and ``decay = 1`` leave S bit for bit."""
    S = decay * S
    u = beta * (v - jnp.sum(S * k, axis=-2, keepdims=True))
    o = jnp.sum(S * q, axis=-2, keepdims=True)
    return o + jnp.sum(k * q, -2, keepdims=True) * u, S + k * u


def kda_step(q, k, v, g, beta, S):
    """One token, every row and head at once: q, k, g [B, H, Dk], v [B,
    H, Dv], beta [B, H], all f32, S [B, H, Dk, Dv] or the carried buffer
    it is a layer of (``family.step_state``) -> (o [B, H, Dv], S as it
    came): :func:`kda_rule` on ``e^g``."""
    o, S = step_state(kda_rule, S, q[..., None], k[..., None],
                      v[..., None, :], jnp.exp(g)[..., None],
                      beta[..., None, None])
    return o[..., 0, :], S


# tokens of a block that share one reference of the running decay, and the
# largest exponent either factor of a pair then sees: 8 tokens either side
# of the reference at no more than |kda_lower_bound| = 5 a token
_SUB, _FACTOR_CAP = 16, 40.0


def _exact(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _three_pass(a, b, dims=_NN):
    return _mm3(_pair(a), _pair(b), dims)


def kda_block_rule(S, q, k, v, c, col, lane=None, *, dot=_three_pass):
    """One block of C tokens of the recurrence on h heads at once (the
    family's ``Recurrent.block``): S [h, Dk, Dv], q, k [h, C, Dk], v [h,
    C, Dv], ``c`` [h, C, Dk] the running sum of g inside the block, a key
    channel, ``col`` [h, C, 1] beta down the block (``lane`` is not
    used) -> (o [h, C, Dv], S).  The WY form of ``qwen3_next.
    gdn_block_rule`` with the decay inside the products: between tokens
    ``j <= i`` it is ``sum_d a_i[d] b_j[d] e^(c_i[d] - c_j[d])``, which no
    scalar matrix factors.  Rows in strips of ``_SUB`` tokens, each
    against every column: ``(a_i e^(c_i - r)) . (b_j e^(r - c_j))`` with
    ``r`` the strip's middle token's ``c``, so that for ``j <= i`` neither
    factor passes ``e^40`` (g is at least ``kda_lower_bound`` a token) and
    their product is the decay itself, at most 1; a column behind the
    strip is masked, its factor capped.  Then, as there, ``u = (I +
    M)^-1 beta (v - (k e^c) S)``, ``o = (q e^c) S + QK u``, ``S <- Diag(
    e^c_C) S + (k e^(c_C - c))^T u``.  ``dot``: the products' arithmetic
    (three bf16 passes in f32 in a kernel's body; exact in XLA).  A block
    whose beta and g are 0 leaves S bit for bit."""
    C = q.shape[1]
    beta, sub = col, min(_SUB, C)
    i = jax.lax.broadcasted_iota(jnp.int32, (1, C, C), 1)
    j = jax.lax.broadcasted_iota(jnp.int32, (1, C, C), 2)
    kk, qk = [], []
    for at in range(0, C, sub):
        r = c[:, at + sub // 2:at + sub // 2 + 1]
        rows = jnp.exp(c[:, at:at + sub] - r)
        cols = k * jnp.exp(jnp.minimum(r - c, _FACTOR_CAP))
        kk.append(dot(k[:, at:at + sub] * rows, cols, _NT))
        qk.append(dot(q[:, at:at + sub] * rows, cols, _NT))
    kk, qk = (jnp.concatenate(t, axis=1) if len(t) > 1 else t[0]
              for t in (kk, qk))
    inv = _unit_lower_inverse(jnp.where(i > j, beta * kk, 0.0), i, j)
    ec = jnp.exp(c)
    u = dot(inv, beta * (v - dot(k * ec, S)))
    o = dot(q * ec, S) + dot(jnp.where(i >= j, qk, 0.0), u)
    last = c[:, C - 1:C]                                     # [h, 1, Dk]
    # e^c_C down S's rows: the row's numbers on a diagonal, summed across
    # (one number and zeros: exact; the chip turns nothing)
    Dk = S.shape[1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (1, Dk, Dk), 1)
           == jax.lax.broadcasted_iota(jnp.int32, (1, Dk, Dk), 2))
    down = jnp.sum(jnp.where(eye, jnp.exp(last), 0.0), axis=2, keepdims=True)
    return o, down * S + dot(k * jnp.exp(last - c), u, _TN)


def kda_chunk(q, k, v, g, beta, S, block: int):
    """The recurrence over T tokens in blocks: q, k, g [B, T, H, Dk], v
    [B, T, H, Dv], beta [B, T, H], f32, S the rows' state [B, H, Dk, Dv]
    or the :class:`~deepspeed_tpu.models.family.SlotState` that carries
    it on the chip -> (o [B, T, H, Dv], S [B, H, Dk, Dv]): the running
    sums made here, the blocks :func:`kda_block_rule`'s, through
    ``dstpu_state_chunk`` or, elsewhere, a scan in exact products.  T is
    padded to whole blocks with tokens that move nothing (beta = g =
    0)."""
    B, T, H, Dk = q.shape
    pad = -T % block
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),)
                                    * (a.ndim - 2))
                            for a in (q, k, v, g, beta))
    N = (T + pad) // block
    c = jnp.cumsum(g.reshape(B, N, block, H, Dk), axis=2).reshape(g.shape)
    if isinstance(S, SlotState):
        col = beta[..., None]
        o, S = chunk_state(kda_block_rule, S, (q, k, v, c), col, col, block)
        return o[:, :T], S
    # [B, T, H, w] -> [N, B * H, block, w]: a block's heads side by side
    blk = lambda a: a.reshape(B, N, block, H, -1).transpose(
        1, 0, 3, 2, 4).reshape(N, B * H, block, -1)

    def one(S, tiles):
        o, S = kda_block_rule(S, *tiles, dot=_exact)
        return S, o

    S, o = jax.lax.scan(
        one, S.astype(jnp.float32).reshape(B * H, Dk, -1),
        tuple(map(blk, (q, k, v, c, beta[..., None]))))
    o = o.reshape(N, B, H, block, -1).transpose(1, 0, 3, 2, 4)
    return o.reshape(B, N * block, H, -1)[:, :T], S.reshape(B, H, Dk, -1)


def kda_mix(cfg, x, lp, state, valid, start=None, ctx=()):
    """The KDA mixer (the family's ``Recurrent.mix``): ``x`` [B, T, d] ->
    (y [B, T, d] before the residual, the rows' new (conv, S)).  ``valid``
    [B]: tokens at or past it move neither S (their beta and g are 0) nor
    the convolution's rows, which are the ``conv_kernel - 1`` inputs that
    end at the last real token.  ``start`` and ``ctx`` are not used: the
    mixer has no positions."""
    B, T, _ = x.shape
    H, D, taps = cfg.n_heads, cfg.kda_head_dim, cfg.conv_kernel
    f32 = jnp.float32
    conv, S = state
    # the benchmark's vocabulary has attention's words; ours nest in them
    with jax.named_scope("attn_qkv"), jax.named_scope("kda_proj"):
        a = _llama.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        mixed = a @ lp["w_qkv"]
        project = lambda w: jnp.einsum("btd,dh->bth", a, w,
                                       preferred_element_type=f32)
        real = (jnp.arange(T)[None] < valid[:, None])[..., None]
        beta = jnp.where(real, jax.nn.sigmoid(project(lp["w_b"])), 0.0)
        sharp = jnp.repeat(jnp.exp(lp["A_log"].astype(f32)), D)
        g = jnp.where(real, cfg.kda_lower_bound * jax.nn.sigmoid(
            sharp * (jnp.einsum("btd,hd->bth", a, lp["w_f"],
                                preferred_element_type=f32)
                     + lp["b_f"].astype(f32))), 0.0)
        open_ = jax.nn.sigmoid(project(lp["w_g"]))            # [B, T, H]
    with jax.named_scope("attn_qkv"), jax.named_scope("kda_conv"):
        seen = jnp.concatenate([conv.astype(mixed.dtype), mixed], axis=1)
        w = lp["conv_w"].astype(f32)
        y = sum(seen[:, i:i + T].astype(f32) * w[i] for i in range(taps))
        y = jax.nn.silu(y)
        conv = jax.vmap(lambda rows, n: jax.lax.dynamic_slice_in_dim(
            rows, n, taps - 1))(seen, valid).astype(conv.dtype)
        q, k, v, g = (t.reshape(B, T, H, D) for t in (
            y[..., :H * D], y[..., H * D:2 * H * D], y[..., 2 * H * D:], g))
        q, k = _l2norm(q) * D ** -0.5, _l2norm(k)
    if T == 1:
        with jax.named_scope("kv_attend"), jax.named_scope("kda_step"):
            o, S = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                            S)
            o = o[:, None]
    else:
        with jax.named_scope("kv_attend"), jax.named_scope("kda_scan"):
            o, S = kda_chunk(q, k, v, g, beta, S, cfg.kda_block)
    with jax.named_scope("attn_out"), jax.named_scope("kda_gate_norm"):
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                              + cfg.norm_eps) * lp["o_norm"].astype(f32)
        o = (o * open_[..., None]).reshape(B, T, -1)
        return o.astype(x.dtype) @ lp["w_out"], (conv, S)


# -------------------------------------------------------------- the hooks
def _qkv(cfg, x, lp, cos, sin):
    """An MLA layer's (q [B, T, H, Dn + Dr] with its rope part rotated,
    the cache row [B, T, 1, C + Dr], None): openPangu's, the queries
    projected at full rank."""
    qkv = jax.named_scope("attn_qkv")
    with qkv:
        a = _llama.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    with qkv, jax.named_scope("mla_q"):
        q = _pangu.mla_queries(cfg, a @ lp["wq"], cos, sin)
    with qkv, jax.named_scope("mla_kv"):
        row = _pangu.mla_row(cfg, a, lp, cos, sin)
    return q, row, None


def expert_layer(cfg, h, lp):
    """h [B, T, d] (normed) -> (this rank's part of the routed sum plus
    the shared expert, rows [Eh] int32 routed to each held expert): the
    group limit on ``s + b`` over all the experts, before the held share
    is taken."""
    return _pangu.expert_layer(cfg, h, lp, bias=lp["gate_bias"],
                               groups=(cfg.n_group, cfg.topk_group))


def _ffn_moe(cfg, x, lp):
    with jax.named_scope("mlp"):
        y, rows = expert_layer(
            cfg, _llama.rms_norm(x, lp["mlp_norm"], cfg.norm_eps), lp)
        return x + y, rows


def _ffn_dense(cfg, x, lp):
    from deepspeed_tpu.ops.fused_ops import swiglu

    with jax.named_scope("mlp"):
        h = _llama.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        return x + swiglu(h, lp["w1"], lp["w3"]) @ lp["w2"]


def _out(cfg, x, attn, lp):
    """An MLA layer's second half: the head-wise gate (from the layer's
    normed input, which ``qkv`` read too), ``W_o``, the residual, the
    sparse FFN."""
    B, T, _ = x.shape
    with jax.named_scope("attn_out"):
        with jax.named_scope("attn_gate"):
            open_ = jax.nn.sigmoid(jnp.einsum(
                "btd,dh->bth",
                _llama.rms_norm(x, lp["attn_norm"], cfg.norm_eps),
                lp["w_g"], preferred_element_type=jnp.float32))
            attn = (attn.reshape(B, T, cfg.n_heads, -1).astype(jnp.float32)
                    * open_[..., None]).reshape(B, T, -1).astype(attn.dtype)
        x = x + attn @ lp["wo"]
    return _ffn_moe(cfg, x, lp)


def _kda_out(cfg, x, y, lp):
    return _ffn_moe(cfg, x + y, lp)


def _kda_out_dense(cfg, x, y, lp):
    return _ffn_dense(cfg, x + y, lp)


def _check(cfg: LingFlashConfig, mesh, max_seq: int) -> None:
    if mesh is not None and any(mesh.size(ax) > 1
                                for ax in ("model", "expert")):
        raise NotImplementedError(
            "LingFlashConfig cannot serve with a model or expert axis > "
            "1: the per-slot recurrent state is not sharded, one latent "
            "row a token is shared by every head, and the held experts' "
            "grouped product is one device's")
    if max_seq > cfg.max_seq_len:
        raise ValueError(f"max_seq {max_seq} is past the model's "
                         f"max_seq_len {cfg.max_seq_len}")


_STATE = ("a KDA layer's state is one matrix a slot, not rows a token: ")

# What would need a snapshot of a slot's state at a token other than its
# last, or its rollback, is refused by name; so is what assumes per-head
# K/V pages.
FAMILY = DecoderFamily(
    config_type=LingFlashConfig, embed=_pangu._embed, qkv=_qkv, out=_out,
    head=_pangu._head, param_specs=param_specs, quant_skip_paths=_EXACT,
    shard_axes=("model", "expert"), check=_check,
    cache_row=_pangu._cache_row, latent=_pangu._latent,
    expert_rows=lambda cfg: (cfg.experts_held[1],
                             cfg.top_k * cfg.n_expert_layers),
    router=lambda cfg: (cfg.n_routed_experts, cfg.top_k),
    whole_stacks=("w1", "w3", "w2"),
    recurrent=Recurrent(key="kda_blocks",
                        period=lambda cfg: _sections(cfg)[-1][0],
                        mix=kda_mix, out=_kda_out, state_row=_state_row,
                        write_scope="kda_write", sections=_sections,
                        block=kda_block_rule,
                        lead=("lead_blocks", _kda_out_dense)),
    refuses=(
        ("prefix_cache", _STATE + "a shared prefix's pages say nothing of "
         "the state at its end, and no snapshot of it is kept"),
        ("kv_tier", _STATE + "a tier entry holds pages, and a prompt "
         "resumed from them would start its KDA layers from zero"),
        ("quantized_resident", "int8-resident pages hold per-head K and "
         "V, not one latent row a token, and come with kv_tier"),
        ("speculative", _STATE + "rejected draft tokens would have moved "
         "it, no rollback is built, and the next-token module that would "
         "draft is not instantiated"),
        ("zero_inference", "weight streaming runs one stack of one layer "
         "kind; this family's layers come in periods of two kinds behind "
         "a leading dense stack"),
        ("contiguous_cache", "the contiguous-cache generators keep "
         "per-head K and V alone; serve through serving_engine"),
    ))

"""Laguna-style decoder (``model_type: laguna``): full and sliding-window
attention in periods stated by ``layer_types``, another count of query
heads and another RoPE table a kind over the same K/V heads, a per-head
output gate, a leading dense layer, then expert layers with a sigmoid
router, a shared expert and a *share* of the routed experts.

With ``RMS(x) = x / sqrt(mean x^2 + eps) * w`` (in f32), no bias
anywhere::

    x = E[token]
    h = x + Attn_l(RMS(x));   y = h + FFN_l(RMS(h))
    logits = RMS(x) W_head

``Attn_l`` on ``a = RMS(x)``: ``q, k, v = a W_q, a W_k, a W_v`` (``H_l``
query heads, 48 on a full layer and 72 on a sliding one, over 8 K/V
heads of 128); q and k rotated (half-split) by the layer kind's table;
causal softmax of ``q k^T / sqrt(head)``, a sliding layer's query at
``p`` seeing the keys at ``p - window + 1 .. p``; ``g = sigmoid(a
W_g)``, one number a head, multiplies the head's output before ``W_o``.

The tables (:func:`rope_tables`): a full layer rotates the first
``partial_rotary_factor`` of a head by YaRN frequencies (the fast ones
left alone, the slow ones divided by ``factor``, a ramp between), with
``cos`` and ``sin`` both multiplied by ``attention_factor``; a sliding
layer rotates the whole head by plain frequencies of its own base.

``FFN_0`` is a SwiGLU; the others ``s = sigmoid(m W_r)`` over all
``n_routed_experts`` in f32, the ``top_k`` largest, ``w =
routed_scaling_factor * s / sum(s)``, ``y = sum w_e E_e(m) +
E_shared(m)``.  A rank of an expert-parallel deployment holds
``experts_held = (first, count)`` of the experts and computes their part
only (:func:`~deepspeed_tpu.parallel.moe.held_experts_ffn`); what the
absent experts would add is left out.

What a sliding layer keeps is a ring a slot, not pages: row ``p mod
window`` holds ``[rotated K | V]`` of position ``p``
(:class:`~deepspeed_tpu.models.family.StateRow` with no float32 state),
so the page pool holds the full layers alone.  The order of a ring's
rows does not matter to a softmax: a read needs which rows are live, a
write touches one row.

The layers are ``F | S S S F | S S S F ...``: layer 0 (full attention,
dense) is the family's ``lead`` and the rest whole periods, which the
published 48 layers cut to ``1 + 4 n`` are (the published model ends
three sliding layers into a period; serving all 48 would take a
trailing part-period, which is not built).

Serving only: there is no ``loss_fn``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.family import (CarriedRows, DecoderFamily,
                                         Recurrent, StateRow, positions_from)
from deepspeed_tpu.parallel.moe import held_experts_ffn, sigmoid_topk_route

_NEG = -1e30


@dataclasses.dataclass
class LagunaConfig:
    vocab_size: int = 100352
    dim: int = 3072
    n_layers: int = 48                 # the lead and the periods' layers
    # one period of layer kinds behind the leading layer
    period: Tuple[str, ...] = ("sliding", "sliding", "sliding", "full")
    n_kv_heads: int = 8
    head_dim: int = 128
    n_heads_full: int = 48
    n_heads_sliding: int = 72
    sliding_window: int = 512
    ffn_dim: int = 12288               # layer 0's SwiGLU
    moe_ffn_dim: int = 1024            # one expert's SwiGLU
    shared_ffn_dim: int = 1024
    n_routed_experts: int = 256        # what the router scores
    # (first, count) of the routed experts whose weights are here
    experts_held: Tuple[int, int] = (0, 256)
    top_k: int = 10
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    # a full layer's table: YaRN over the first part of a head
    rope_theta_full: float = 500000.0
    rotary_full: float = 0.5
    yarn_factor: float = 128.0
    yarn_original_max: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    attention_factor: float = 1.4852030263919618
    # a sliding layer's: the whole head, plain
    rope_theta_sliding: float = 10000.0
    max_seq_len: int = 1048576
    norm_eps: float = 1e-6

    def __post_init__(self):
        self.period = tuple(self.period)
        assert set(self.period) == {"sliding", "full"}
        assert (self.n_layers - 1) % len(self.period) == 0, \
            "the model is a leading layer and whole periods"
        first, count = self.experts_held
        assert 0 <= first and first + count <= self.n_routed_experts
        for heads in (self.n_heads_full, self.n_heads_sliding):
            assert heads % self.n_kv_heads == 0
        assert self.rotary_dim_full % 2 == 0 and self.head_dim % 2 == 0

    @property
    def n_periods(self) -> int:
        return (self.n_layers - 1) // len(self.period)

    @property
    def n_sliding_layers(self) -> int:
        return self.n_periods * self.period.count("sliding")

    @property
    def n_full_layers(self) -> int:
        """The pool's layers: the lead and the periods' full layers."""
        return self.n_layers - self.n_sliding_layers

    @property
    def n_expert_layers(self) -> int:
        return self.n_layers - 1

    @property
    def rotary_dim_full(self) -> int:
        return int(self.head_dim * self.rotary_full)

    @classmethod
    def tiny(cls, **kw):
        """Two periods behind the dense lead, at the published shape:
        two head counts over the same K/V heads, a window the contexts
        pass several times over, a share of the experts."""
        base = dict(vocab_size=256, dim=64, n_layers=9, n_kv_heads=3,
                    head_dim=16, n_heads_full=6, n_heads_sliding=9,
                    sliding_window=8, ffn_dim=128, moe_ffn_dim=32,
                    shared_ffn_dim=32, n_routed_experts=8,
                    experts_held=(0, 8), top_k=3, yarn_factor=8.0,
                    yarn_original_max=16, max_seq_len=512)
        base.update(kw)
        return cls(**base)


def _period(cfg) -> Tuple[bool, ...]:
    return tuple(kind == "sliding" for kind in cfg.period)


def _state_row(cfg) -> StateRow:
    """A ring of ``sliding_window`` rows ``[K | V]`` a sliding layer, in
    the cache's dtype, and no float32 state."""
    return StateRow(cfg.n_sliding_layers,
                    (cfg.sliding_window,
                     2 * cfg.n_kv_heads * cfg.head_dim), None)


# ------------------------------------------------------------- parameters
_NORMS = ("attn_norm", "mlp_norm")


def _stack_shapes(cfg, kind: str):
    """kind: "lead" (layer 0: full attention, dense), "full" or
    "sliding" (the periods' layers: sparse)."""
    d, Dh = cfg.dim, cfg.head_dim
    H = cfg.n_heads_sliding if kind == "sliding" else cfg.n_heads_full
    L = {"lead": 1, "full": cfg.n_full_layers - 1,
         "sliding": cfg.n_sliding_layers}[kind]
    kv = cfg.n_kv_heads * Dh
    # W_q and W_k are kept [heads, head, d], the contraction last: the
    # chip lays the rotated projections out heads-major (their consumers
    # take the heads apart), and held [d, heads x head] every program
    # kept a transposed copy of the whole stacks beside them (0.65 GiB;
    # AOT for a v5e, PR 44); held [heads x head, d] a layer's slice was
    # copied out before the product took it apart into heads (54 MiB a
    # sliding layer a step, 3% of the device's time; v5e, PR 44)
    shapes = {"wq": (L, H, Dh, d), "wk": (L, cfg.n_kv_heads, Dh, d),
              "wv": (L, d, kv), "wg": (L, d, H), "wo": (L, H * Dh, d)}
    if kind == "lead":
        f = cfg.ffn_dim
        shapes.update(w1=(L, d, f), w3=(L, d, f), w2=(L, f, d))
    else:
        f, fs, Eh = cfg.moe_ffn_dim, cfg.shared_ffn_dim, cfg.experts_held[1]
        shapes.update(gate=(L, d, cfg.n_routed_experts),
                      w1=(L, Eh, d, f), w3=(L, Eh, d, f), w2=(L, Eh, f, d),
                      sw1=(L, d, fs), sw3=(L, d, fs), sw2=(L, fs, d))
    return L, shapes


def init_params(rng: jax.Array, cfg: LagunaConfig,
                dtype=jnp.float32) -> Dict[str, Any]:
    """Three stacks: ``lead_blocks`` (layer 0), ``blocks`` (the periods'
    full layers: the page pool's, behind the lead) and ``win_blocks``
    (the sliding layers), experts stacked ``[L, Eh, ...]`` and the
    router ``[L, d, n_routed_experts]``.  Every matrix at the fan-in
    scale; gains drawn about 1, so that a norm left out shows."""
    keys = iter(jax.random.split(rng, 64))

    def w(*sh, fan_in=-2):
        return (jax.random.normal(next(keys), sh)
                / np.sqrt(sh[fan_in])).astype(dtype)

    def gain(*sh):
        return (1.0 + 0.1 * jax.random.normal(next(keys), sh)).astype(dtype)

    def stack(kind):
        L, shapes = _stack_shapes(cfg, kind)
        tree = {n: w(*sh, fan_in=-1 if n in ("wq", "wk") else -2)
                for n, sh in shapes.items()}
        tree.update({n: gain(L, cfg.dim) for n in _NORMS})
        return tree

    return {
        "embed": jax.random.normal(
            next(keys), (cfg.vocab_size, cfg.dim)).astype(dtype),
        "lead_blocks": stack("lead"), "blocks": stack("full"),
        "win_blocks": stack("sliding"),
        "final_norm": gain(cfg.dim),
        "lm_head": w(cfg.dim, cfg.vocab_size),
    }


def param_specs(cfg: LagunaConfig) -> Dict[str, Any]:
    """Every leaf replicated: the family serves on one device (its
    ``check`` refuses a model or expert axis)."""
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return jax.tree.map(lambda a: P(*(None,) * a.ndim), shapes)


def param_count(cfg: LagunaConfig) -> int:
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return int(sum(np.prod(a.shape) for a in jax.tree.leaves(shapes)))


# ------------------------------------------------------------ the pieces
def rms_norm(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def yarn_inv_freq(cfg) -> np.ndarray:
    """A full layer's ``R / 2`` frequencies (``R`` = ``rotary_dim_full``):
    ``inv_i = (f_i / factor) r_i + f_i (1 - r_i)`` with ``f_i =
    theta^(-2i/R)`` and the ramp ``r_i = clip((i - lo) / (hi - lo), 0,
    1)``: the fast frequencies (more than ``beta_fast`` turns over the
    original context) are left alone, the slow ones (fewer than
    ``beta_slow``) divided by ``factor``.  ``lo = floor(c(beta_fast))``,
    ``hi = ceil(c(beta_slow))``, ``c(n) = R ln(original / (2 pi n)) / (2
    ln theta)``, both clipped to 0 .. R - 1."""
    R, base = cfg.rotary_dim_full, cfg.rope_theta_full

    def turns_at(n):
        return R * math.log(cfg.yarn_original_max / (2 * math.pi * n)) \
            / (2 * math.log(base))

    lo = max(math.floor(turns_at(cfg.yarn_beta_fast)), 0)
    hi = min(math.ceil(turns_at(cfg.yarn_beta_slow)), R - 1)
    if lo == hi:
        hi += 0.001
    i = np.arange(R // 2, dtype=np.float64)
    f = base ** (-2.0 * i / R)
    ramp = np.clip((i - lo) / (hi - lo), 0.0, 1.0)
    return (f / cfg.yarn_factor * ramp + f * (1.0 - ramp)).astype(np.float32)


def rope_tables(cfg, positions):
    """positions [T] or [B, T] -> ``(cos_f, sin_f, cos_s, sin_s)``, f32:
    a full layer's ``[..., rotary_dim_full / 2]``, both multiplied by
    ``attention_factor``, and a sliding layer's ``[..., head_dim / 2]``
    at its own base."""
    p = positions.astype(jnp.float32)[..., None]
    full = p * jnp.asarray(yarn_inv_freq(cfg))
    half = cfg.head_dim // 2
    plain = p * jnp.asarray(
        (cfg.rope_theta_sliding
         ** (-np.arange(half, dtype=np.float64) / half)).astype(np.float32))
    af = jnp.float32(cfg.attention_factor)
    return (af * jnp.cos(full), af * jnp.sin(full),
            jnp.cos(plain), jnp.sin(plain))


def _rotate(x, cos, sin):
    """x [B, T, H, D] f32: its first ``2 * cos.shape[-1]`` numbers
    rotated, halves paired; the rest pass as they are."""
    if cos.ndim == 2:
        cos, sin = cos[None], sin[None]
    c, s = cos[:, :, None, :], sin[:, :, None, :]
    half = cos.shape[-1]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], -1)


def _project(cfg, x, lp, cos, sin):
    """-> (q [B, T, H, Dh], k [B, T, KV, Dh], both rotated in f32, v [B,
    T, KV, Dh]).  The products come out in the activations' dtype: asked
    for in float32 the chip kept a transposed copy of each projection's
    whole stack beside it (0.65 GiB; AOT for a v5e, PR 44)."""
    B, T, _ = x.shape
    a = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    turn = lambda w: _rotate(
        jnp.einsum("btd,hkd->bthk", a, w).astype(jnp.float32),
        cos, sin).astype(x.dtype)
    return turn(lp["wq"]), turn(lp["wk"]), \
        (a @ lp["wv"]).reshape(B, T, -1, cfg.head_dim)


def _gated_out(cfg, x, attn, lp):
    """The per-head gate (from the layer's normed input, which the
    projections read too), ``W_o``, the residual."""
    B, T, _ = x.shape
    with jax.named_scope("attn_out"):
        with jax.named_scope("attn_gate"):
            a = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
            g = jax.nn.sigmoid((a @ lp["wg"]).astype(jnp.float32))
            attn = (attn.reshape(B, T, g.shape[-1], -1).astype(jnp.float32)
                    * g[..., None]).astype(x.dtype).reshape(B, T, -1)
        return x + attn @ lp["wo"]


def expert_layer(cfg, h, lp):
    """h [B, T, d] (normed) -> (this rank's part of the routed sum plus
    the shared expert, rows [Eh] int32 routed to each held expert)."""
    from deepspeed_tpu.ops.fused_ops import swiglu

    B, T, d = h.shape
    hf = h.reshape(-1, d)
    w, experts = sigmoid_topk_route(
        hf, lp["gate"], cfg.top_k, cfg.routed_scaling_factor,
        cfg.norm_topk_prob)
    with jax.named_scope("moe_ffn"):
        y, rows = held_experts_ffn(hf, w, experts, lp["w1"], lp["w3"],
                                   lp["w2"], first=cfg.experts_held[0],
                                   layer=lp.get("layer"),
                                   n_experts=lp["gate"].shape[-1])
        with jax.named_scope("moe_shared"):
            y = y + swiglu(hf, lp["sw1"], lp["sw3"]) @ lp["sw2"]
    return y.reshape(B, T, d), rows


def _sparse_half(cfg, x, lp):
    with jax.named_scope("mlp"):
        y, rows = expert_layer(
            cfg, rms_norm(x, lp["mlp_norm"], cfg.norm_eps), lp)
        return x + y, rows


# ------------------------------------------------- the window's attention
def _by_kv_head(cfg, q, lanes, seen):
    """Grouped-query attention of ``q`` [..., M, H, Dh] over rows
    [..., S, 2 KV Dh] (a row ``[K | V]`` of every K/V head), of which
    ``lanes(first, count)`` gives those lanes, where ``seen`` [..., M,
    S] -> [..., M, H, Dh]: softmax in f32.

    A K/V head at a time, each a product batched over the leading
    dimensions alone, on lane-aligned slices of the operands as they
    lie.  As one product batched over the K/V heads too, the chip
    wanted q with the K/V heads major, took that layout back through the
    rotation into the projection, and kept a transposed copy of
    ``W_q``'s whole stack beside it and of a layer's slice of it a
    layer (0.5 GiB and 54 MiB a layer a step; AOT for a v5e, PR 44)."""
    KV, Dh = cfg.n_kv_heads, cfg.head_dim
    M, H = q.shape[-3:-1]
    G = H // KV
    lead = q.shape[:-3]
    out = []
    for h in range(KV):
        qh = q[..., h * G:(h + 1) * G, :].reshape(lead + (M * G, Dh))
        kh, vh = lanes(h * Dh, Dh), lanes((KV + h) * Dh, Dh)
        s = jnp.einsum("...md,...sd->...ms", qh, kh.astype(q.dtype),
                       preferred_element_type=jnp.float32) * Dh ** -0.5
        see = jnp.broadcast_to(seen[..., :, None, :], lead + (
            M, G, seen.shape[-1])).reshape(lead + (M * G, -1))
        p = jax.nn.softmax(jnp.where(see, s, _NEG), axis=-1)
        o = jnp.einsum("...ms,...sd->...md", p.astype(q.dtype),
                       vh.astype(q.dtype),
                       preferred_element_type=jnp.float32)
        out.append(o.astype(q.dtype).reshape(lead + (M, G, Dh)))
    return jnp.concatenate(out, axis=-2)


def window_step(cfg, q, row, rings: CarriedRows, pos, live):
    """One token a slot over the slots' rings WHERE THEY LIE: q [B, H,
    Dh], row [B, 2 KV Dh] (this token's ``[K | V]``), ``rings.buffer``
    [layers, B, W, 2 KV Dh], pos [B] where the token stands, live [B]
    bool -> (o [B, H, Dh], the rings).  A live slot's row goes to ring
    row ``pos mod W`` (another's goes nowhere); after it the ring is the
    positions ``max(0, pos - W + 1) .. pos``, so ring row ``j`` is seen
    where ``j <= pos``."""
    B, H, Dh = q.shape
    W = cfg.sliding_window
    buffer, layer = rings
    with jax.named_scope("kv_write"), jax.named_scope("win_write"):
        at = jnp.stack([jnp.broadcast_to(layer, (B,)).astype(jnp.int32),
                        jnp.where(live, jnp.arange(B, dtype=jnp.int32),
                                  buffer.shape[1]),
                        (pos % W).astype(jnp.int32)], axis=-1)
        buffer = buffer.at[at[:, 0], at[:, 1], at[:, 2]].set(
            row.astype(buffer.dtype), mode="drop")
    with jax.named_scope("kv_attend"), jax.named_scope("win_attend"):
        # a head's lanes of the layer's rings, each read where it lies
        # by the product that needs it: sliced out whole first, the
        # layer's 192 MiB were written once more and read twice
        lanes = lambda lo, n: jax.lax.dynamic_slice(
            buffer, (layer, 0, 0, lo), (1, B, W, n))[0]
        seen = jnp.arange(W)[None] <= pos[:, None]              # [B, W]
        o = _by_kv_head(cfg, q[:, None], lanes, seen[:, None])
    return o.reshape(B, H, Dh), CarriedRows(buffer, layer)


def window_reader(cfg, tokens: int, interpret: bool) -> Tuple[str, str]:
    """Which reader a chunk of ``tokens`` runs over its band, and why
    (the family's ``Recurrent.chunk_reader``: what ``window_chunk``
    asks and ``/statusz`` shows)."""
    from deepspeed_tpu.ops.attention import window_reader as reader

    return reader(tokens=tokens, window=cfg.sliding_window,
                  head_dim=cfg.head_dim, interpret=interpret)


def _band_in_blocks(cfg, q, rows, ring, start):
    """The band as XLA runs it: queries in blocks of W, each against its
    own block of keys and the block before it (the first block's is the
    ring), ``[W, 2 W]`` f32 scores a block a head, not ``[T, T + W]``."""
    B, T, H, Dh = q.shape
    W = cfg.sliding_window
    nb = -(-T // W)
    pad = nb * W - T
    j = jnp.arange(W, dtype=jnp.int32)[None]
    last = start[:, None] - 1
    hist_pos = last - (last - j) % W                              # [B, W]
    grow = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),)
                             * (a.ndim - 2))
    blocks = lambda a: a.reshape((B, nb, W) + a.shape[2:])
    # block b's keys: rows b W .. (b + 2) W of [ring | chunk]
    pairs = lambda a: jnp.concatenate(
        [blocks(a[:, :nb * W]), blocks(a[:, W:])], axis=2)
    kvb = pairs(jnp.concatenate([ring.astype(rows.dtype), grow(rows)],
                                axis=1))                  # [B, nb, 2W, ..]
    pos = start[:, None] + jnp.arange(nb * W, dtype=jnp.int32)[None]
    kpos = pairs(jnp.concatenate([hist_pos, pos], axis=1))[:, :, None]
    qpos = blocks(pos)[..., None]                         # [B, nb, W, 1]
    seen = (kpos <= qpos) & (kpos > qpos - W) & (kpos >= 0)
    o = _by_kv_head(cfg, blocks(grow(q)),
                    lambda lo, n: kvb[..., lo:lo + n], seen)
    return o.reshape(B, nb * W, H, Dh)[:, :T]


def window_chunk(cfg, q, k, v, ring, start, valid):
    """T tokens a row under the band, over what the row's ring held
    before them: q [B, T, H, Dh], k, v [B, T, KV, Dh] at positions
    ``start + 0 .. T - 1``, ring [B, W, 2 KV Dh] -> (o [B, T, H, Dh],
    the ring with the last ``min(valid, W)`` real tokens written).

    Ring row ``j`` holds the largest position under ``start`` congruent
    to ``j``, if there is one at or past 0: a first chunk sees nothing
    of what the slot held.  On a TPU, at whole 128-row blocks and heads
    of 128 (:func:`window_reader`), the band's scores stay on the chip
    (``dstpu_window_flash_fwd``); elsewhere XLA runs it in blocks of W
    (:func:`_band_in_blocks` over :func:`_by_kv_head`, which
    ``window_step`` shares: the same arithmetic, the kernel's
    reference)."""
    B, T, H, Dh = q.shape
    W = cfg.sliding_window
    with jax.named_scope("kv_attend"), jax.named_scope("win_attend"):
        rows = jnp.concatenate([k.reshape(B, T, -1), v.reshape(B, T, -1)],
                               axis=-1)                   # [B, T, 2 KV Dh]
        if window_reader(cfg, T, jax.default_backend() != "tpu")[0] \
                == "pallas":
            from deepspeed_tpu.ops.attention_pallas import (
                window_flash_attention_tpu)

            o = window_flash_attention_tpu(q, rows, ring, start)
        else:
            o = _band_in_blocks(cfg, q, rows, ring, start)
    with jax.named_scope("kv_write"), jax.named_scope("win_write"):
        # ring row j takes the last real token congruent to j, if the
        # chunk has one
        j = jnp.arange(W, dtype=jnp.int32)[None]
        end = start[:, None] + valid[:, None] - 1                # [B, 1]
        take = end - (end - j) % W - start[:, None]              # [B, W]
        new = jnp.take_along_axis(
            rows, jnp.clip(take, 0, T - 1)[..., None], axis=1)
        ring = jnp.where((take >= 0)[..., None], new.astype(ring.dtype),
                         ring)
    return o, ring


def win_mix(cfg, x, lp, state, valid, start, ctx):
    """A sliding layer's attention (the family's ``Recurrent.mix``):
    ``x`` [B, T, d] -> (the heads' outputs [B, T, H Dh], which the
    layer's ``out`` gates and projects, the rows' rings and no state).  ``valid`` [B]:
    tokens at or past it move no ring row, a row with none moves
    nothing.  ``state[0]`` is the rows' rings [B, W, 2 KV Dh], or in a
    decode step over every slot the carried buffer and the layer
    (``family.CarriedRows``), updated where it lies."""
    B, T, _ = x.shape
    rings, _ = state
    with jax.named_scope("attn_qkv"):
        q, k, v = _project(cfg, x, lp, *ctx[2:])
    if isinstance(rings, CarriedRows):
        row = jnp.concatenate([k.reshape(B, -1), v.reshape(B, -1)], -1)
        o, rings = window_step(cfg, q[:, 0], row, rings, start, valid > 0)
        o = o[:, None]
    else:
        o, rings = window_chunk(cfg, q, k, v, rings, start, valid)
    return o.reshape(B, T, -1), (rings, None)


# -------------------------------------------------------------- the hooks
def _embed(params, tokens, start, cfg):
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
        return x, rope_tables(cfg, positions_from(start, tokens.shape[1]))


def _qkv(cfg, x, lp, cos_f, sin_f, cos_s, sin_s):
    """A full layer's (the lead's, too)."""
    with jax.named_scope("attn_qkv"):
        return _project(cfg, x, lp, cos_f, sin_f)


def _out(cfg, x, attn, lp):
    return _sparse_half(cfg, _gated_out(cfg, x, attn, lp), lp)


def _out_lead(cfg, x, attn, lp):
    """Layer 0's second half: a dense SwiGLU."""
    from deepspeed_tpu.ops.fused_ops import swiglu

    x = _gated_out(cfg, x, attn, lp)
    with jax.named_scope("mlp"):
        h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        return x + swiglu(h, lp["w1"], lp["w3"]) @ lp["w2"]


def _win_out(cfg, x, y, lp):
    return _sparse_half(cfg, _gated_out(cfg, x, y, lp), lp)


def _head(params, x, cfg):
    with jax.named_scope("final_norm"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    with jax.named_scope("lm_head"):
        return jnp.einsum("btd,dv->btv", x, params["lm_head"],
                          preferred_element_type=jnp.float32)


def _check(cfg: LagunaConfig, mesh, max_seq: int) -> None:
    if mesh is not None and any(mesh.size(ax) > 1
                                for ax in ("model", "expert")):
        raise NotImplementedError(
            "LagunaConfig cannot serve with a model or expert axis > 1: "
            "a sliding layer's per-slot ring is not sharded")
    if max_seq > cfg.max_seq_len:
        raise ValueError(f"max_seq {max_seq} is past the model's "
                         f"max_seq_len {cfg.max_seq_len}")


_RING = ("a sliding layer keeps a ring of its last rows a slot, not pages "
         "a token: ")

# The router stays exact under weight-only quantization, and the norm
# gains.  What would need a snapshot of a slot's rings at a token other
# than its last, or their rollback, is refused by name.
FAMILY = DecoderFamily(
    config_type=LagunaConfig, embed=_embed, qkv=_qkv, out=_out, head=_head,
    param_specs=param_specs,
    quant_skip_paths=("gate", "wg") + _NORMS + ("final_norm",),
    shard_axes=("model", "expert"), check=_check,
    lead=("lead_blocks", _out_lead),
    expert_rows=lambda cfg: (cfg.experts_held[1],
                             cfg.top_k * cfg.n_expert_layers),
    router=lambda cfg: (cfg.n_routed_experts, cfg.top_k),
    whole_stacks=("w1", "w3", "w2"),
    recurrent=Recurrent(key="win_blocks", period=_period, mix=win_mix,
                        out=_win_out, state_row=_state_row,
                        write_scope="win_write", rows_in_place=True,
                        chunk_reader=window_reader),
    refuses=(
        ("prefix_cache", _RING + "a shared prefix's pages say nothing of "
         "the rings at its end, and no snapshot of them is kept"),
        ("kv_tier", _RING + "a tier entry holds pages, and a prompt "
         "resumed from them would start its sliding layers with nothing "
         "in view"),
        ("quantized_resident", _RING + "int8-resident pages come with "
         "kv_tier"),
        ("speculative", _RING + "rejected draft tokens would have "
         "overwritten rows still in view, and no rollback is built"),
        ("zero_inference", "weight streaming runs one stack of one layer "
         "kind; this family has a leading stack and periods of two kinds"),
        ("contiguous_cache", "the contiguous-cache generators keep "
         "per-head K and V of one head count; serve through "
         "serving_engine"),
    ))

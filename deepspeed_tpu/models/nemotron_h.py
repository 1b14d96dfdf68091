"""Nemotron-H-style decoder (``model_type: nemotron_h``): every layer is
ONE of three things alone behind its own norm and residual, in an order
stated by a string of letters that is not periodic: ``M`` a Mamba-2
mixer, ``*`` a grouped-query attention with no position encoding, ``E``
a mixture of experts.

With ``N`` the RMSNorm ``x / sqrt(mean x^2 + eps) * w`` (in f32), no
bias in any projection::

    x_0 = E[token];   x_(l+1) = x_l + F_l(N_l(x_l));   logits = N_f(x_L) W_head

``M`` is :func:`~deepspeed_tpu.models.granite_hybrid.ssm_mix`, the one
statement of the Mamba-2 mixer, with ``ssm_groups`` groups: head ``h``
reads ``B`` and ``C`` of group ``h // (heads / groups)`` and the gated
norm runs over a group's channels.  ``*`` on ``a = N(x)``: ``[q | k | v]
= a W_qkv``, causal softmax of ``q k^T / sqrt(head)``, no rotation,
``W_o``.  ``E`` on ``a``: ``s = sigmoid(a W_g)`` over all
``n_routed_experts`` in f32; the ``top_k`` largest of ``s + b`` (``b`` a
selection bias an expert: it moves the choice, not the weight); ``w =
routed_scaling_factor * s_j / sum s_j``; ``y = sum_j w_j W_down,j
relu(W_up,j a)^2 + W_sdown relu(W_sup a)^2``: two matrices an expert, no
gate.  A rank of an expert-parallel deployment holds ``experts_held =
(first, count)`` of the experts and computes their part only
(:func:`~deepspeed_tpu.parallel.moe.held_experts_ffn`); what the absent
experts would add is left out.

The letters are cut into *sections*, each a period and a count
(:func:`cut_pattern`; the published 52 are ``(MEMEM*E) x 5, ME, MEMEM*E,
(ME) x 4``), which the per-slot seam runs in order over one page pool
(the ``*`` layers alone), one state buffer (the ``M`` layers: the last
``conv_kernel - 1`` inputs of the convolution and ``S`` a head,
:class:`~deepspeed_tpu.models.family.StateRow`) and nothing at all for
an ``E`` layer (``Recurrent.ffn``).

An expert's ``moe_ffn_dim`` columns are stored in whole 128-lane tiles,
zeros behind (:attr:`NemotronHConfig.moe_ffn_stored`): ``relu(0)^2 = 0``
meets zero rows of ``W_down``, so the numbers are the published
width's, and the Mosaic grouped product takes whole tiles only.

Serving only: there is no ``loss_fn``.
"""

from __future__ import annotations

import dataclasses
import functools
import heapq
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.family import DecoderFamily, Recurrent, StateRow
from deepspeed_tpu.models.granite_hybrid import FAMILY as _GRANITE
from deepspeed_tpu.models.granite_hybrid import rms_norm, ssm_mix
from deepspeed_tpu.parallel.moe import held_experts_ffn, sigmoid_topk_route

# a letter's kind in ``Recurrent.period``: per-slot state, pool, neither
_KIND = {"M": True, "*": False, "E": None}


@functools.lru_cache(maxsize=None)
def cut_pattern(pattern: str) -> Tuple[Tuple[str, int], ...]:
    """``pattern`` as sections ``((period, count), ...)`` at the least
    cost: one a section (a loop of its own) and a period's letters once,
    however many sections run it (a loop body to write down); of equal
    costs the fewest sections.  Least cost first over (letters cut, the
    periods used so far)."""
    n = len(pattern)
    primitive = lambda u: not any(
        u == u[:q] * (len(u) // q) for q in range(1, len(u))
        if len(u) % q == 0)
    heap, seen = [(0, 0, 0, (), ())], set()
    while heap:
        cost, sections, i, known, cut = heapq.heappop(heap)
        if i == n:
            return cut
        if (i, known) in seen:
            continue
        seen.add((i, known))
        for p in range(1, n - i + 1):
            u = pattern[i:i + p]
            if not primitive(u):
                continue
            new = cost + 1 + (0 if u in known else p)
            now = known if u in known else tuple(sorted(known + (u,)))
            count = 1
            while True:
                heapq.heappush(heap, (new, sections + 1, i + p * count, now,
                                      cut + ((u, count),)))
                if pattern[i + p * count:i + p * (count + 1)] != u:
                    break
                count += 1
    return ()


@dataclasses.dataclass
class NemotronHConfig:
    vocab_size: int = 131072
    dim: int = 2688
    # the layers: (period of letters, how many of it), in order
    sections: Tuple[Tuple[str, int], ...] = (
        ("MEMEM*E", 5), ("ME", 1), ("MEMEM*E", 1), ("ME", 4))
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 8
    conv_kernel: int = 4
    moe_ffn_dim: int = 1856            # one expert's width, two matrices
    shared_ffn_dim: int = 3712
    n_routed_experts: int = 128        # what the router scores
    # (first, count) of the routed experts whose weights are here
    experts_held: Tuple[int, int] = (0, 128)
    top_k: int = 6
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    max_seq_len: int = 262144
    norm_eps: float = 1e-5
    # tokens a block of the chunked scan (the published ``chunk_size``):
    # the program's choice, not the model's
    ssm_block: int = 128

    def __post_init__(self):
        self.sections = tuple((str(p), int(n)) for p, n in self.sections)
        assert set(self.pattern) <= set(_KIND) and "M" in self.pattern \
            and "*" in self.pattern
        first, count = self.experts_held
        assert 0 <= first and first + count <= self.n_routed_experts
        assert self.n_heads % self.n_kv_heads == 0
        assert self.ssm_heads % self.ssm_groups == 0

    @classmethod
    def from_pattern(cls, pattern: str, **kw):
        """``hybrid_override_pattern`` (a letter a layer, as published)
        cut into its sections."""
        return cls(sections=cut_pattern(pattern), **kw)

    @property
    def pattern(self) -> str:
        return "".join(period * count for period, count in self.sections)

    @property
    def n_layers(self) -> int:
        return len(self.pattern)

    @property
    def n_ssm_layers(self) -> int:
        return self.pattern.count("M")

    @property
    def n_attn_layers(self) -> int:
        return self.pattern.count("*")

    @property
    def n_expert_layers(self) -> int:
        return self.pattern.count("E")

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_channels(self) -> int:
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def moe_ffn_stored(self) -> int:
        """Columns an expert's ``W_up`` is stored in: whole 128-lane
        tiles, zeros behind ``moe_ffn_dim`` (1,856 in 1,920: 3.4% more
        expert bytes; the Mosaic grouped product takes whole tiles, and
        on a v5e it is the faster of the two: PERF.md 6, PR 48)."""
        return -(-self.moe_ffn_dim // 128) * 128

    @classmethod
    def tiny(cls, **kw):
        """The published shape at a toy size: two periods in four
        sections with a part-period tail, two groups, an expert width
        that is not whole tiles, a share of the experts' router."""
        base = dict(vocab_size=256, dim=64,
                    sections=cut_pattern("MEM*EMEM*EMEMEM*EMEME"),
                    n_heads=4, n_kv_heads=2, head_dim=16, ssm_heads=4,
                    ssm_head_dim=16, ssm_state=16, ssm_groups=2,
                    moe_ffn_dim=40, shared_ffn_dim=80, n_routed_experts=8,
                    experts_held=(0, 8), top_k=3, max_seq_len=512,
                    ssm_block=8)
        base.update(kw)
        return cls(**base)


def _sections(cfg):
    return tuple((tuple(_KIND[c] for c in period), count)
                 for period, count in cfg.sections)


def _state_row(cfg) -> StateRow:
    return StateRow(cfg.n_ssm_layers,
                    (cfg.conv_kernel - 1, cfg.conv_channels),
                    (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state))


# ------------------------------------------------------------- parameters
_EXACT = ("attn_norm", "mlp_norm", "ssm_norm", "final_norm", "A_log",
          "dt_bias", "D", "conv_w", "conv_b", "gate", "gate_bias")


def _stack_shapes(cfg, letter: str):
    d = cfg.dim
    if letter == "M":
        # [z | xBC] in whole tiles and the dt block apart, as Granite's
        return cfg.n_ssm_layers, {
            "w_in": (d, cfg.ssm_inner + cfg.conv_channels),
            "w_dt": (d, cfg.ssm_heads), "w_out": (cfg.ssm_inner, d)}
    if letter == "*":
        H, KV, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        return cfg.n_attn_layers, {"wqkv": (d, (H + 2 * KV) * Dh),
                                   "wo": (H * Dh, d)}
    fs = cfg.shared_ffn_dim
    return cfg.n_expert_layers, {
        "gate": (d, cfg.n_routed_experts), "sw_up": (d, fs),
        "sw_down": (fs, d)}


def init_params(rng: jax.Array, cfg: NemotronHConfig,
                dtype=jnp.float32) -> Dict[str, Any]:
    """Three stacks, each in the model's order: ``ssm_blocks`` (the
    ``M`` layers), ``blocks`` (the ``*`` layers: the page pool's) and
    ``moe_blocks`` (the ``E`` layers: experts ``[L, Eh, d, stored]`` and
    ``[L, Eh, stored, d]`` with zeros behind the published width, the
    router ``[L, d, n_routed_experts]`` and its selection bias, drawn
    small so that a choice by the score alone shows).  Every matrix at
    the fan-in scale; gains drawn about 1, so that a norm left out
    shows; ``A_log``, ``dt_bias`` and ``D`` as the published layer
    initialises them (A in 1..16, a step of 1e-3..1e-1, D = 1)."""
    keys = iter(jax.random.split(rng, 48))

    def w(*sh):
        return (jax.random.normal(next(keys), sh)
                / np.sqrt(sh[-2])).astype(dtype)

    def gain(*sh):
        return (1.0 + 0.1 * jax.random.normal(next(keys), sh)).astype(dtype)

    def stack(letter):
        L, shapes = _stack_shapes(cfg, letter)
        tree = {n: w(L, *sh) for n, sh in shapes.items()}
        tree["mlp_norm" if letter == "E" else "attn_norm"] = gain(L, cfg.dim)
        return L, tree

    L, ssm = stack("M")
    H = cfg.ssm_heads
    u = lambda lo, hi: jax.random.uniform(next(keys), (L, H), minval=lo,
                                          maxval=hi)
    dt = jnp.exp(u(np.log(1e-3), np.log(1e-1)))
    ssm.update(
        conv_w=(jax.random.normal(
            next(keys), (L, cfg.conv_kernel, cfg.conv_channels))
            / np.sqrt(cfg.conv_kernel)).astype(dtype),
        conv_b=(0.1 * jax.random.normal(
            next(keys), (L, cfg.conv_channels))).astype(dtype),
        A_log=jnp.log(u(1.0, 16.0)).astype(jnp.float32),
        dt_bias=(dt + jnp.log(-jnp.expm1(-dt))).astype(jnp.float32),
        D=jnp.ones((L, H), jnp.float32),
        ssm_norm=gain(L, cfg.ssm_inner))
    L, moe = stack("E")
    Eh, f = cfg.experts_held[1], cfg.moe_ffn_dim
    behind = cfg.moe_ffn_stored - f
    moe.update(
        gate_bias=0.05 * jax.random.normal(
            next(keys), (L, cfg.n_routed_experts), jnp.float32),
        w_up=jnp.pad(w(L, Eh, cfg.dim, f), ((0, 0),) * 3 + ((0, behind),)),
        w_down=jnp.pad(w(L, Eh, f, cfg.dim),
                       ((0, 0),) * 2 + ((0, behind), (0, 0))))
    return {
        "embed": jax.random.normal(
            next(keys), (cfg.vocab_size, cfg.dim)).astype(dtype),
        "ssm_blocks": ssm, "blocks": stack("*")[1], "moe_blocks": moe,
        "final_norm": gain(cfg.dim),
        "lm_head": w(cfg.dim, cfg.vocab_size),
    }


def param_specs(cfg: NemotronHConfig) -> Dict[str, Any]:
    """Every leaf replicated: the family serves on one device (its
    ``check`` refuses a model or expert axis)."""
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return jax.tree.map(lambda a: P(*(None,) * a.ndim), shapes)


def param_count(cfg: NemotronHConfig) -> int:
    """At the published widths: the zeros an expert's columns are stored
    with are not parameters."""
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    stored = int(sum(np.prod(a.shape) for a in jax.tree.leaves(shapes)))
    return stored - 2 * cfg.n_expert_layers * cfg.experts_held[1] \
        * cfg.dim * (cfg.moe_ffn_stored - cfg.moe_ffn_dim)


# ------------------------------------------------------------ the pieces
def relu2(a):
    """``relu(a)^2``, squared in f32 and rounded once."""
    return jnp.square(jax.nn.relu(a.astype(jnp.float32))).astype(a.dtype)


def expert_layer(cfg, h, lp):
    """h [B, T, d] (normed) -> (this rank's part of the routed sum plus
    the shared expert, rows [Eh] int32 routed to each held expert)."""
    B, T, d = h.shape
    hf = h.reshape(-1, d)
    w, experts = sigmoid_topk_route(
        hf, lp["gate"], cfg.top_k, cfg.routed_scaling_factor,
        cfg.norm_topk_prob, bias=lp["gate_bias"])
    with jax.named_scope("moe_ffn"):
        y, rows = held_experts_ffn(
            hf, w, experts, lp["w_up"], None, lp["w_down"],
            first=cfg.experts_held[0], layer=lp.get("layer"),
            n_experts=lp["gate"].shape[-1], act=relu2)
        with jax.named_scope("moe_shared"):
            y = y + relu2(hf @ lp["sw_up"]) @ lp["sw_down"]
    return y.reshape(B, T, d), rows


# -------------------------------------------------------------- the hooks
def _embed(params, tokens, start, cfg):
    """No positions anywhere: ``ctx`` is empty."""
    with jax.named_scope("embed"):
        return params["embed"][tokens], ()


def _qkv(cfg, x, lp):
    """A ``*`` layer's (q [B, T, H, Dh], k, v [B, T, KV, Dh]), not
    rotated."""
    B, T, _ = x.shape
    with jax.named_scope("attn_qkv"):
        a = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        qkv = (a @ lp["wqkv"]).reshape(B, T, -1, cfg.head_dim)
        H, KV = cfg.n_heads, cfg.n_kv_heads
        return qkv[:, :, :H], qkv[:, :, H:H + KV], qkv[:, :, H + KV:]


def _out(cfg, x, attn, lp):
    """A ``*`` layer has no FFN half: the projection and the residual."""
    with jax.named_scope("attn_out"):
        return x + attn @ lp["wo"]


def _ssm_out(cfg, x, y, lp):
    """An ``M`` layer has no FFN half either."""
    return x + y


def _ffn(cfg, x, lp):
    """An ``E`` layer: the FFN alone (``Recurrent.ffn``)."""
    with jax.named_scope("mlp"):
        y, rows = expert_layer(
            cfg, rms_norm(x, lp["mlp_norm"], cfg.norm_eps), lp)
        return x + y, rows


def _head(params, x, cfg):
    with jax.named_scope("final_norm"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    with jax.named_scope("lm_head"):
        return jnp.einsum("btd,dv->btv", x, params["lm_head"],
                          preferred_element_type=jnp.float32)


def _check(cfg: NemotronHConfig, mesh, max_seq: int) -> None:
    if mesh is not None and any(mesh.size(ax) > 1
                                for ax in ("model", "expert")):
        raise NotImplementedError(
            "NemotronHConfig cannot serve with a model or expert axis > 1: "
            "the per-slot recurrent state is not sharded, and the exchange "
            "between the ranks of an expert layer is not built")
    if max_seq > cfg.max_seq_len:
        raise ValueError(f"max_seq {max_seq} is past the model's "
                         f"max_seq_len {cfg.max_seq_len}")


# What Granite's Mamba-2 layers refuse, these refuse, and for its reasons.
FAMILY = DecoderFamily(
    config_type=NemotronHConfig, embed=_embed, qkv=_qkv, out=_out,
    head=_head, param_specs=param_specs, quant_skip_paths=_EXACT,
    shard_axes=("model", "expert"), check=_check,
    expert_rows=lambda cfg: (cfg.experts_held[1],
                             cfg.top_k * cfg.n_expert_layers),
    router=lambda cfg: (cfg.n_routed_experts, cfg.top_k),
    whole_stacks=("w_up", "w_down"),
    recurrent=Recurrent(key="ssm_blocks",
                        period=lambda cfg: _sections(cfg)[0][0],
                        mix=ssm_mix, out=_ssm_out, state_row=_state_row,
                        write_scope="ssm_write", sections=_sections,
                        ffn=("moe_blocks", _ffn)),
    refuses=_GRANITE.refuses)

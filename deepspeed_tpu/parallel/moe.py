"""Mixture-of-experts with expert parallelism over the ``expert`` mesh axis.

Reference behavior: deepspeed/moe/{layer.py,sharded_moe.py,experts.py} —
TopKGate computes router logits, top-1/top-2 assignment with a capacity
limit, load-balance auxiliary loss; tokens are dispatched to expert ranks
with an all-to-all, expert FFNs run, and a second all-to-all returns
outputs to be combined by gate weight.

TPU design: dispatch/combine are einsums against a one-hot dispatch tensor
(the Mesh-TensorFlow/GShard formulation) rather than index shuffles —
dense, static-shaped, MXU-friendly.  Experts are a stacked ``[E, ...]``
pytree sharded over the ``expert`` axis; a sharding constraint on the
expert dim of the dispatched activations makes XLA emit the exact
all-to-all pair the reference hand-codes, riding ICI.  Capacity overflow
drops tokens (residual connection carries them), matching the reference's
``drop_tokens=True`` default.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.config import MoEConfig
from deepspeed_tpu.topology import MeshSpec

EXPERT_AXIS = "expert"


class GateOutput(NamedTuple):
    dispatch: jnp.ndarray      # [N, E, C] one-hot (float)
    combine: jnp.ndarray       # [N, E, C] gate-weighted dispatch
    aux_loss: jnp.ndarray      # load-balance loss (scalar)
    z_loss: jnp.ndarray        # router logit z-loss (scalar)
    expert_load: jnp.ndarray   # [E] fraction of tokens per expert


def capacity(n_tokens: int, n_experts: int, k: int, factor: float,
             min_capacity: int = 4) -> int:
    """ref: sharded_moe.py _capacity — ceil(k*N/E * factor), floored."""
    c = math.ceil(k * n_tokens / n_experts * factor)
    return max(int(c), min_capacity)


def top_k_gating(logits: jnp.ndarray, k: int, cap: int,
                 rng: Optional[jax.Array] = None,
                 noise_std: float = 0.0) -> GateOutput:
    """Top-k router (ref: sharded_moe.py top1gating/top2gating, unified).

    logits: [N, E] f32.  Position within each expert's capacity buffer is a
    cumsum over token order; tokens past ``cap`` are dropped (their
    dispatch row is zero — the residual path carries them through).
    """
    N, E = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)
    # z-loss (router logit regularizer, ref: sharded_moe gate z_loss)
    z = jax.scipy.special.logsumexp(logits, axis=-1)
    z_loss = jnp.mean(z ** 2)

    noisy = logits
    if noise_std > 0.0 and rng is not None:
        noisy = logits + noise_std * jax.random.normal(rng, logits.shape)

    dispatch = jnp.zeros((N, E, cap), jnp.float32)
    combine = jnp.zeros((N, E, cap), jnp.float32)
    # count[e]: tokens already assigned to expert e by earlier choices
    count = jnp.zeros((E,), jnp.int32)
    masked = noisy
    gates_sum = jnp.zeros((N,), jnp.float32)
    first_choice_mask = None

    for choice in range(k):
        sel = jnp.argmax(masked, axis=-1)                       # [N]
        onehot = jax.nn.one_hot(sel, E, dtype=jnp.float32)      # [N, E]
        if first_choice_mask is None:
            first_choice_mask = onehot
        # position of each token in its expert's buffer (token order)
        pos_in_expert = (jnp.cumsum(onehot, axis=0) - onehot    # [N, E]
                         + count[None, :].astype(jnp.float32))
        pos = jnp.sum(pos_in_expert * onehot, axis=-1)          # [N]
        keep = pos < cap
        gate = jnp.sum(probs * onehot, axis=-1) * keep          # [N]
        poshot = jax.nn.one_hot(jnp.minimum(pos, cap - 1).astype(jnp.int32),
                                cap, dtype=jnp.float32)         # [N, C]
        d = onehot[:, :, None] * poshot[:, None, :] * keep[:, None, None]
        dispatch = dispatch + d
        combine = combine + gate[:, None, None] * d
        gates_sum = gates_sum + gate
        count = count + jnp.sum(onehot, axis=0).astype(jnp.int32)
        masked = jnp.where(onehot > 0, -jnp.inf, masked)

    # renormalize combine weights over the chosen experts (ref: top2gating
    # normalizes gate values to sum to 1 across the k choices)
    if k > 1:
        combine = combine / jnp.maximum(gates_sum, 1e-9)[:, None, None]

    # load-balance loss: E * Σ_e (fraction tokens→e) * (mean router prob→e)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(first_choice_mask, axis=0)
    aux = E * jnp.sum(me * ce)
    return GateOutput(dispatch=dispatch, combine=combine, aux_loss=aux,
                      z_loss=z_loss, expert_load=jnp.sum(
                          jnp.sum(dispatch, axis=-1), axis=0) / max(N, 1))


@dataclasses.dataclass
class MoELayer:
    """Expert-parallel MoE layer (ref: deepspeed/moe/layer.py MoE).

    expert_fn: ``(expert_params, x[C, d]) -> y[C, d]`` for ONE expert;
        vmapped over the stacked ``[E, ...]`` expert params.
    """

    cfg: MoEConfig
    expert_fn: Callable
    mesh: Optional[MeshSpec] = None

    def __call__(self, gate_w: jnp.ndarray, expert_params: Any,
                 x: jnp.ndarray, train: bool = True,
                 rng: Optional[jax.Array] = None):
        """x: [B, T, d] → (y [B, T, d], aux_losses dict)."""
        cfg = self.cfg
        B, T, d = x.shape
        N = B * T
        xf = x.reshape(N, d)
        if self.mesh is not None:
            # keep tokens sharded over the joint batch axes through the
            # flatten + gating matmul (prevents an SPMD full-remat reshard
            # when the batch rides both data and expert axes)
            xf = jax.lax.with_sharding_constraint(
                xf, self.mesh.sharding(P(self.mesh.batch_spec()[0], None)))
        factor = cfg.capacity_factor if train else cfg.eval_capacity_factor
        cap = capacity(N, cfg.num_experts, cfg.top_k, factor,
                       cfg.min_capacity)
        with jax.named_scope("moe_router"):
            logits = (xf.astype(jnp.float32) @ gate_w.astype(jnp.float32))
            gate = top_k_gating(logits, cfg.top_k, cap, rng=rng)

        # dispatch: [N,E,C] x [N,d] -> [E,C,d]; constraining the E dim to the
        # expert axis makes XLA emit the token all-to-all onto ICI.
        ein = jnp.einsum("nec,nd->ecd", gate.dispatch.astype(x.dtype), xf)
        if self.mesh is not None and self.mesh.size(EXPERT_AXIS) > 1:
            ein = jax.lax.with_sharding_constraint(
                ein, self.mesh.sharding(P(EXPERT_AXIS, None, None)))
        out = jax.vmap(self.expert_fn)(expert_params, ein)     # [E, C, d]
        if self.mesh is not None and self.mesh.size(EXPERT_AXIS) > 1:
            out = jax.lax.with_sharding_constraint(
                out, self.mesh.sharding(P(EXPERT_AXIS, None, None)))
        y = jnp.einsum("nec,ecd->nd", gate.combine.astype(x.dtype), out)
        aux = {
            "moe_aux_loss": gate.aux_loss * cfg.aux_loss_weight,
            "moe_z_loss": gate.z_loss * cfg.z_loss_weight,
            "moe_expert_load": gate.expert_load,
        }
        return y.reshape(B, T, d), aux


def expert_param_specs(specs: Any) -> Any:
    """Prepend the expert axis to per-expert stacked param specs."""
    def one(s):
        rest = tuple(s) if s is not None else ()
        return P(EXPERT_AXIS, *rest)

    return jax.tree.map(one, specs,
                        is_leaf=lambda x: x is None or isinstance(x, P))


# ------------------------------------------- a share of the experts, served
def sigmoid_topk_route(h, gate, top_k: int, scale: float = 1.0,
                       normalize: bool = True, bias=None, groups=None):
    """Router of the sigmoid-scored families: ``h`` [N, d] against
    ``gate`` [d, E] over ALL E experts, in f32 whatever the inputs are
    (a bf16 score flips near-tied choices) -> (weights [N, k] f32,
    experts [N, k] int32).  The k largest (by ``s + bias`` [E]: the choice
    moves, not the weight), over their sum (``normalize``), x ``scale``.
    ``groups`` ``(n, keep)``: the choice is group-limited: the experts lie
    in ``n`` groups of consecutive E / n, a group's score is the sum of
    its two largest ``s + bias``, and only experts of the ``keep`` best
    groups may be chosen (a rank that holds whole groups is sent rows by
    the tokens that kept one of them, and by no other)."""
    with jax.named_scope("moe_router"):
        s = jax.nn.sigmoid(jnp.dot(h.astype(jnp.float32),
                                   gate.astype(jnp.float32),
                                   precision=jax.lax.Precision.HIGHEST))
        choice = s if bias is None else s + bias.astype(jnp.float32)
        if groups is not None:
            n, keep = groups
            by_group = choice.reshape(choice.shape[0], n, -1)
            best = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
            _, kept = jax.lax.top_k(best, keep)              # [N, keep]
            open_ = jnp.any(kept[..., None] == jnp.arange(n), axis=1)
            choice = jnp.where(open_[..., None], by_group,
                               -jnp.inf).reshape(choice.shape)
        top, idx = jax.lax.top_k(choice, top_k)
        if bias is not None or groups is not None:
            top = jnp.take_along_axis(s, idx, axis=-1)
        if normalize:
            top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
        return top * scale, idx.astype(jnp.int32)


def softmax_topk_route(h, gate, top_k: int, normalize: bool = True):
    """Router of the softmax-scored families: ``p = softmax(h W)`` over
    ALL E experts in f32 (as :func:`sigmoid_topk_route`) -> (weights,
    experts): the k largest, divided by their sum where ``normalize``."""
    with jax.named_scope("moe_router"):
        p = jax.nn.softmax(jnp.dot(h.astype(jnp.float32),
                                   gate.astype(jnp.float32),
                                   precision=jax.lax.Precision.HIGHEST))
        top, idx = jax.lax.top_k(p, top_k)
        if normalize:
            top = top / jnp.sum(top, axis=-1, keepdims=True)
        return top, idx.astype(jnp.int32)


# The granule of the two rules of shapes below: a pair buffer is whole
# granules (a power of two of them) and the rows rule crosses where the
# padding of an expert's group would be a granule.  What the Mosaic kernel
# tiles its rows by is its own (:func:`_held_ffn_tiles`), so that no
# program's buffer height or branch moves with the kernel's tile
_ROW_GRANULE = 256
# The pair buffer of a rank that holds a share of the experts, as a
# multiple of the pairs it is routed if the router is even.  The routed
# part alone on a v5e, ms a layer at 1,024 rows (PERF.md 6, PR 40, through
# the three ``gmm`` calls of that time): 64 of 512 experts of 2048 x 512,
# k = 10: 1.11 at 1.5 (2,048 rows), 1.16 at 2 (4,096), 1.26 at 4 (8,192),
# 1.68 with a row for every pair (16,384); 16 of 256 experts of 7680 x
# 2048, k = 8: 3.22, 3.22 (1,024 rows both), 3.34, 4.04.  2 and not 1.5: a
# pass more costs a whole pass, and a trained router is not even.  Since
# PR 52 a pass costs by the rows held, not by the buffer (PERF.md 6)
_PAIR_BOUND = 2


def _every_row_pays(N: int, k: int, Eh: int) -> bool:
    """Whether ``N`` rows routed ``k`` ways are cheaper with every one of
    the ``Eh`` held experts on every row than sorted into groups: a rule
    of the shapes alone.  Every expert on every row is ``N * Eh`` row
    products; the grouped product is at most ``N * k`` (every pair held)
    plus up to a granule of padding an expert.  Both pay the same a row
    product (6 d f gated, 4 d f not), so the widths cancel, and under a
    granule of rows both are one pass over the held weights, which the
    plain products make without a sort.

    On a v5e (ms for the three products; PERF.md 6, PR 34), 8 of 8 of
    4096 x 14336, k = 2: 256 rows 4.3 plain against 4.9 grouped, 384
    rows 6.0 against 5.5, 1,024 rows 16.0 against 8.3 (the rule crosses
    at 341); 16 of 256 of 7680 x 2048, k = 8: 256 rows 2.4 against 2.6,
    512 rows 4.5 against 2.9 (it crosses at 512, late for a share: few
    of its N * k pairs are held; :func:`_pair_buffer_rows` knows how
    many experts there are in all, this rule does not, and no cell's
    programs have a row count between the two crossings)."""
    return N * (Eh - k) < Eh * _ROW_GRANULE


def _pair_buffer_rows(N: int, k: int, Eh: int, E: int) -> int:
    """Sorted pairs a pass of the grouped branch takes, a rule of the
    shapes: whole granules, a power of two of them (programs of
    neighbouring row counts share one trace of the pass), enough for
    ``_PAIR_BOUND`` times the ``N * k * Eh / E`` pairs that are held if
    the router is even, and never more than the ``N * k`` pairs there
    are.  All the experts held (``Eh == E``): every pair.  On the TPU it
    bounds the kernel's tables (a token and a weight a sorted pair, in
    SMEM) and its grid; off it, the rows of ``ragged_dot``'s buffer."""
    tm = _ROW_GRANULE
    tiles = lambda rows: 1 << (-(-rows // tm) - 1).bit_length()
    return tm * min(tiles(N * k),
                    tiles(math.ceil(_PAIR_BOUND * N * k * Eh / E)))


def extra_pair_passes(sizes, N: int, k: int, E: int):
    """How many passes beyond the first :func:`held_experts_ffn` makes
    over its pair buffer for ``N`` rows routed ``k`` ways over ``E``
    experts, of which ``sizes`` [Eh] went to each held expert -> int32
    scalar: a pass takes :func:`_pair_buffer_rows` held pairs.  None
    where every held expert takes every row, nor where the buffer has a
    row for every pair.  What the engines count as
    ``serving_expert_pair_extra_passes``."""
    Eh = sizes.shape[0]
    C = _pair_buffer_rows(N, k, Eh, E)
    if _every_row_pays(N, k, Eh) or C >= N * k:
        return jnp.zeros((), jnp.int32)
    return jnp.maximum(-(-jnp.sum(sizes) // C) - 1, 0)


# ------------------------------------- a pass of the held experts, on the chip
# what a call may ask of the 128 MiB of a v5e's VMEM, and what is left
# beside the operands this file sizes (the compiler's own temporaries)
_HELD_VMEM_BYTES = 100 << 20
_HELD_VMEM_SLACK = 10 << 20


class HeldTiles(NamedTuple):
    """How ``dstpu_held_ffn`` cuts a pass, read from the shapes."""

    tm: int          # rows of one product: what the matrix unit is handed
    span: int        # rows a grid step holds: ``tm`` or a few of them
    tf: int          # columns of ``f`` a grid step holds
    vmem: int        # bytes of VMEM the call asks for
    rows: int        # rows of ``h`` a call takes: all, or a half, a quarter..


def _held_ffn_tiles(N: int, k: int, E: int, d: int, f: int, itemsize: int,
                    mats: int) -> Optional[HeldTiles]:
    """The tiles of :func:`held_ffn` for ``N`` rows routed ``k`` ways over
    ``E`` experts of ``mats`` matrices ``d`` x ``f``; None where the
    kernel does not run: a width that is not whole 128-lane tiles.

    ``rows``: ``N``, halved until a call's rows fit the VMEM beside one
    block of the weights (each part of the rows is then a call of its
    own).  ``tm``: the mean rows a held expert gets of those if the
    router is even, ``rows * k / E``, and half as many again, up to a
    power of two, no less than the bf16 sublane tile (16) and no more than
    the matrix unit's 128 rows.  ``tf``: the most columns of ``f`` whose
    blocks of the ``mats`` matrices, two buffers each, fit beside the rows
    (the [rows, d] f32 rows in and the [rows, d] f32 sum, a step's rows
    and their f32 products).  ``span``, the rows a grid step keeps from
    one block of ``f`` to the next: ``tm`` where ``tf`` is all of ``f`` (a
    step is then an expert, all its rows, a product at a time, and its
    weights are streamed once); where ``f`` is several blocks, four
    products' rows: an expert with more is several tiles, each streaming
    the blocks again."""
    if d % 128 or f % 128:
        return None
    rows = N
    while True:
        mean = max(1, -(-3 * rows * k // (2 * E)))
        tm = min(128, max(16, 1 << (mean - 1).bit_length()))
        for tf in (c for c in range(f, 0, -128) if f % c == 0):
            span = tm if tf == f else 4 * tm
            need = (2 * rows * d * 4 + span * d * (itemsize + 4)
                    + tm * d * 4 + 2 * mats * d * tf * itemsize
                    + 3 * tm * tf * 4 + _HELD_VMEM_SLACK)
            if need <= _HELD_VMEM_BYTES:
                return HeldTiles(tm, span, tf, need, rows)
        if rows % 2:
            return None
        rows //= 2


def _held_ffn_kernel(meta, t_expert, t_start, t_rows, tok, wt, h_hbm, *refs,
                     tm: int, act):
    """One grid step (tile ``t``, block ``j`` of ``f``): the tile's rows,
    ``tm`` at a time: picked from ``h`` by their tokens (at the tile's
    first block), through this block of its expert's matrices, into f32
    rows; after the last block each row, times its router weight, is
    added to its token's row of the sum, which stays in VMEM from the
    first step to the last.  ``meta``: (layer, live tiles, whether the
    sum starts at 0).  ``xb``/``acc`` hold the tile's rows across the
    blocks of ``f``; where ``f`` is one block they hold one product's."""
    *w_refs, w2_ref, sum_hbm, out_hbm, hv, ov, x32, xb, acc, sem = refs
    t, j, nf = pl.program_id(0), pl.program_id(1), pl.num_programs(1)
    f32, held = jnp.float32, xb.shape[0] // tm

    @pl.when((t == 0) & (j == 0))
    def _():
        rows = pltpu.make_async_copy(h_hbm, hv, sem.at[0])
        rows.start()

        @pl.when(meta[2] == 1)
        def _():
            ov[...] = jnp.zeros_like(ov)

        @pl.when(meta[2] != 1)
        def _():
            before = pltpu.make_async_copy(sum_hbm, ov, sem.at[1])
            before.start()
            before.wait()
        rows.wait()

    start, rows = t_start[t], t_rows[t]

    def product(s, _):
        lo, base = start + s * tm, pl.multiple_of((s % held) * tm, tm)
        at = pl.ds(base, tm)

        @pl.when(j == 0)
        def _():
            # rows past the tile's stay 0: their products are 0, and no
            # row of the sum is given them
            x32[...] = jnp.zeros_like(x32)

            def pick(r, _):
                x32[pl.ds(r, 1), :] = hv[pl.ds(tok[lo + r], 1), :]
                return 0
            jax.lax.fori_loop(0, jnp.minimum(tm, rows - s * tm), pick, 0)
            xb[at, :] = x32[...].astype(xb.dtype)

        x = xb[at, :]
        a = jnp.dot(x, w_refs[0][...], preferred_element_type=f32)
        if len(w_refs) == 2:
            a = jax.nn.silu(a) * jnp.dot(x, w_refs[1][...],
                                         preferred_element_type=f32)
        else:
            a = act(a)
        y = jnp.dot(a.astype(w2_ref.dtype), w2_ref[...],
                    preferred_element_type=f32)

        @pl.when(j == 0)
        def _():
            acc[at, :] = y

        @pl.when(j > 0)
        def _():
            acc[at, :] += y

        @pl.when(j == nf - 1)
        def _():
            def add(r, _):
                to = pl.ds(tok[lo + r], 1)
                ov[to, :] += acc[pl.ds(base + r, 1), :] * wt[lo + r]
                return 0
            jax.lax.fori_loop(0, jnp.minimum(tm, rows - s * tm), add, 0)
        return 0

    jax.lax.fori_loop(0, (rows + tm - 1) // tm, product, 0)

    @pl.when((t == pl.num_programs(0) - 1) & (j == nf - 1))
    def _():
        after = pltpu.make_async_copy(ov, out_hbm, sem.at[1])
        after.start()
        after.wait()


def _held_ffn_grid(part, first_row, span: int, blocks: int, C: int):
    """The tiles of a pass, from how many of its sorted pairs are each
    held expert's (``part`` [Eh]) and where they stand (``first_row``)
    -> (T, live tiles, and for each tile of the grid its expert, its
    first sorted pair and its rows): a few [Eh]-sized operations.

    ``f`` in one block: a tile is an expert, all its rows, and the grid
    is the experts; an expert without a row keeps the expert before it
    that has one (the first that has one, before that), so its step
    starts no copy, and does nothing.  ``f`` in several: an
    expert's rows in whole tiles of ``span``, the tiles behind the live
    ones keep the last live tile's expert."""
    Eh, i32 = part.shape[0], jnp.int32
    if blocks == 1:
        kept = jax.lax.cummax(jnp.where(part > 0, jnp.arange(Eh), -1))
        expert = jnp.where(kept < 0, jnp.argmax(part > 0), kept)
        return Eh, jnp.asarray(Eh, i32), expert, first_row, part
    T = C // span + Eh
    each = -(-part // span)
    ends = jnp.cumsum(each)
    live = ends[-1]
    t = jnp.minimum(jnp.arange(T, dtype=i32), jnp.maximum(live - 1, 0))
    expert = jnp.minimum(jnp.sum(ends[None, :] <= t[:, None], axis=1,
                                 dtype=i32), Eh - 1)
    within = (t - (ends - each)[expert]) * span
    rows = jnp.where(jnp.arange(T) < live,
                     jnp.clip(part[expert] - within, 0, span), 0)
    return T, live, expert, first_row[expert] + within, rows


def held_ffn(h, tok, wt, part, first_row, w1, w3, w2, layer, into, fresh, *,
             tiles: HeldTiles, act=None, interpret: bool = False):
    """One pass of the held experts as one Mosaic call, ``dstpu_held_ffn``:
    ``into`` [N, d] f32 + what this pass's sorted pairs contribute.

    ``h`` [N, d]; ``tok``/``wt`` [C]: the token and the router's weight of
    each sorted pair of the pass; ``part`` [Eh]: how many of them are each
    held expert's, ``first_row`` [Eh] where each expert's stand among the
    C; the weights [L, Eh, ...] whole and ``layer``, so that a layer is
    never sliced out of a scanned stack (``w3`` None: the two-matrix
    body, ``act`` between).  ``fresh``: ``into`` is all 0 (it is not read).

    The grid is :func:`_held_ffn_grid`'s tiles by the blocks of ``f``;
    the tables are scalar-prefetch operands and the weights' index maps
    read the tile's expert from them.  The kernel's cost follows the
    pairs held here and the weights of the experts that got one: nothing
    is sized by the pairs the router made but the two tables."""
    N, d = h.shape
    f = w1.shape[-1]
    tm, span, tf, vmem, _ = tiles
    nf, i32 = f // tf, jnp.int32
    T, live, expert, t_start, t_rows = _held_ffn_grid(
        part, first_row, span, nf, tok.shape[0])
    meta = jnp.stack([jnp.asarray(layer, i32), live.astype(i32),
                      jnp.asarray(fresh, i32)])
    # a dead tile keeps the last live tile's last block: no copy
    col = lambda t, j, meta: jnp.where(t < meta[1], j, nf - 1)
    wide = pl.BlockSpec((None, None, d, tf), lambda t, j, meta, e, *_: (
        meta[0], e[t], 0, col(t, j, meta)))
    back = pl.BlockSpec((None, None, tf, d), lambda t, j, meta, e, *_: (
        meta[0], e[t], col(t, j, meta), 0))
    ws = [w for w in (w1, w3) if w is not None]
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_held_ffn_kernel, tm=tm, act=act),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6, grid=(T, nf),
            in_specs=[anywhere] + [wide] * len(ws) + [back, anywhere],
            out_specs=anywhere,
            scratch_shapes=[
                pltpu.VMEM((N, d), jnp.float32),       # the rows, by token
                pltpu.VMEM((N, d), jnp.float32),       # the sum, by token
                pltpu.VMEM((tm, d), jnp.float32),      # a product's rows
                pltpu.VMEM((span, d), w1.dtype),       # a tile's rows
                pltpu.VMEM((span, d), jnp.float32),    # and what they gave
                pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((N, d), jnp.float32),
        input_output_aliases={6 + 1 + len(ws) + 1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
        name="dstpu_held_ffn",
    )(meta, expert.astype(i32), t_start.astype(i32), t_rows.astype(i32),
      tok.astype(i32), wt.astype(jnp.float32), h.astype(jnp.float32),
      *ws, w2, into)


def _kernel_passes(h, weights, group, sizes, w1, w3, w2, layer, C: int,
                   n_experts, tiles: HeldTiles, act):
    """The grouped branch of :func:`held_experts_ffn` through
    :func:`held_ffn` -> the f32 sum [N, d].  ``group`` [N * k]: each
    pair's held expert (``Eh``: not held, sorts last); ``sizes`` [Eh].
    Pass p takes the sorted pairs p * C .. (p + 1) * C: the held ones
    stand first, so one pass is all of them unless more than C are held.
    The router's weights ride the sort (no gather of them afterwards)."""
    N, k = weights.shape
    ends = jnp.cumsum(sizes)
    _, order, gain = jax.lax.sort(
        (group, jnp.arange(N * k, dtype=jnp.int32),
         weights.reshape(-1).astype(jnp.float32)), num_keys=1)
    tok, gain = (jnp.pad(a, (0, -N * k % C)) for a in (order // k, gain))
    stacks = [w if layer is not None or w is None else w[None]
              for w in (w1, w3, w2)]

    def one_pass(p, out):
        lo = p * C
        at = jnp.clip(ends - sizes, lo, lo + C)
        return held_ffn(
            h, *(jax.lax.dynamic_slice(a, (lo,), (C,)) for a in (tok, gain)),
            jnp.clip(ends, lo, lo + C) - at, at - lo, *stacks,
            0 if layer is None else layer, out, p == 0, tiles=tiles,
            act=act, interpret=jax.default_backend() != "tpu")

    out = jnp.zeros((N, h.shape[1]), jnp.float32)
    if C >= N * k:
        return one_pass(0, out)
    return jax.lax.fori_loop(
        0, 1 + extra_pair_passes(sizes, N, k, n_experts), one_pass, out)


def _grouped_product(x, w, sizes, layer=None):
    """``x`` [M, K] in groups of ``sizes`` consecutive rows, group g
    against ``w[g]`` [K, N] -> [M, N]; rows past the groups are left
    unspecified.  With ``layer``, ``w`` is the whole stack [L, G, K, N]
    and the groups are layer ``layer``'s.  XLA's ``ragged_dot`` on the
    layer's slice: the statement of the grouped branch where
    ``dstpu_held_ffn`` does not run (no TPU, a width off the 128-lane
    tiles), and what the tests hold the kernel to."""
    return jax.lax.ragged_dot(x, w if layer is None else w[layer], sizes)


def _on_chip() -> bool:
    return jax.default_backend() == "tpu"


def held_product(N: int, k: int, Eh: int, E: int, d: int, f: int,
                 itemsize: int, mats: int, grouped: bool = True):
    """Which statement of the held experts' FFN a program of ``N`` rows
    gets, a rule of the shapes and the backend -> (product, reason,
    tiles): what :func:`held_experts_ffn` follows and what an engine's
    ``/statusz`` reports of its chunk program (``kernels.experts``).
    ``every_row``: each held expert on every row; ``ragged_dot``: sorted
    pairs through XLA's grouped product; ``dstpu_held_ffn``: through the
    Mosaic kernel, with its ``tiles``."""
    if not grouped:
        return ("every_row",
                "the weights are no whole stacks held on one device", None)
    if _every_row_pays(N, k, Eh):
        return "every_row", f"{N} rows: a pass over the held weights", None
    if not _on_chip():
        return "ragged_dot", "no TPU backend", None
    tiles = _held_ffn_tiles(N, k, E, d, f, itemsize, mats)
    if tiles is None:
        return "ragged_dot", f"{d} x {f}: not whole 128-lane tiles", None
    return ("dstpu_held_ffn", f"products of {tiles.tm} rows, f in blocks of "
            f"{tiles.tf}" + f", {tiles.rows} rows a call" * (tiles.rows < N),
            tiles)


def held_experts_ffn(h, weights, experts, w1, w3, w2, first: int = 0,
                     layer=None, grouped: bool = True,
                     n_experts: Optional[int] = None, act=None):
    """The part of a routed FFN that the experts held here contribute:
    drop-free at any imbalance, static shapes.  ``h`` [N, d];
    ``weights``/``experts`` [N, k] from the router over all the
    ``n_experts`` there are (its gate's width; not said: no more than
    are held); the experts ``first .. first + Eh`` in the body the
    caller states: gated, ``(SiLU(h w1) * (h w3)) w2`` with ``w1``/``w3``
    [Eh, d, f] and ``w2`` [Eh, f, d], or (``w3`` None) ``act(h w1) w2``
    -> (y [N, d] in ``h``'s dtype, rows [Eh] int32 routed to each held
    expert).  With ``layer`` (a layer loop's traced index) the weights
    are the whole stacks [L, Eh, ...] and that layer's experts are meant.

    Many rows: every (token, expert) pair is sorted, the pairs whose
    expert is held first, by expert, and the rest (what other ranks
    compute) past them.  A pass takes a bound on the pairs held HERE
    (:func:`_pair_buffer_rows`), not every pair the router made; when
    more are held, further passes over the sorted order take the rest
    (:func:`extra_pair_passes` counts them), each adding into the same
    f32 sum.  On the TPU a pass is one Mosaic call (:func:`held_ffn`):
    the pairs' rows are picked from ``h`` in VMEM by their tokens, go
    through their expert's matrices a tile at a time, and are added to
    their tokens' rows of the sum in VMEM; what a pass costs follows the
    pairs held and the held weights' stream.  The branch alone on a v5e,
    ms a layer of a 1,024-row chunk, the three ``gmm`` calls between a
    gather and k slot gathers that it replaced -> the kernel [the floor
    of ``benchmark/roofline/moe.py``] (PERF.md 6, PR 52): 64 of 512
    experts of 2048 x 512, k = 10: 1.06 -> 0.61 [0.49]; 64 of 512 of
    2560 x 768, k = 8: 1.56 -> 1.07 [0.92]; 16 of 256 of 3072 x 1024,
    k = 10: 0.87 -> 0.49 [0.37]; 16 of 128 of two matrices 2688 x 1920,
    k = 6: 0.85 -> 0.51 [0.40]; 16 of 256 of 7680 x 2048, k = 8: 3.15 ->
    2.36 [1.84]; 8 of 8 of 4096 x 14336, k = 2: 8.58 -> 5.12 [3.66], and
    at 384 rows 5.56 -> 3.81 [3.44].  Elsewhere a pass gathers its
    rows into a buffer, makes XLA's ``ragged_dot`` of each matrix and
    takes each token's pairs back out of the buffer slot by slot.  Few
    rows (a decode step; :func:`_every_row_pays`): each held expert
    evaluates every row and the router's weight, zero where it did not
    choose the expert, combines them: one read an expert.

    ``grouped=False``: the caller's word that the weights are not plain
    arrays held whole on one device (sharded over a mesh; dequantised on
    the way in; a layer's slice of a stack, which a Mosaic call would
    copy): every held expert then evaluates every row at any row count."""
    N, k = experts.shape
    Eh = w1.shape[-3]
    if w3 is not None:
        mid = lambda a, b: jax.nn.silu(a) * b
        one = lambda a, b, c: (jax.nn.silu(h @ a) * (h @ b)) @ c
    else:
        mid = lambda a, b: act(a)
        one = lambda a, c: act(h @ a) @ c
    local = experts.reshape(-1) - first
    held = (local >= 0) & (local < Eh)
    group = jnp.where(held, local, Eh)               # not held: sorts last
    sizes = jnp.zeros((Eh + 1,), jnp.int32).at[group].add(1)[:Eh]
    with jax.named_scope("moe_routed"):
        product, _, tiles = held_product(
            N, k, Eh, n_experts or Eh, h.shape[1], w1.shape[-1],
            w1.dtype.itemsize, 2 + (w3 is not None), grouped)
        if product == "every_row":
            ws = tuple(w if layer is None else w[layer]
                       for w in (w1, w3, w2) if w is not None)
            gain = jnp.zeros((N, Eh + 1), jnp.float32).at[
                jnp.arange(N * k) // k, group].add(weights.reshape(-1))
            ys = jax.vmap(one)(*ws)                             # [Eh, N, d]
            out = jnp.einsum("ne,end->nd", gain[:, :Eh],
                             ys.astype(jnp.float32))
            return out.astype(h.dtype), sizes
        if tiles and tiles.rows < N:
            # more rows than the VMEM holds beside a block of the
            # weights: each part of the rows is a call of its own
            y, rows = zip(*(held_experts_ffn(
                h[at:at + tiles.rows], weights[at:at + tiles.rows],
                experts[at:at + tiles.rows], w1, w3, w2, first, layer,
                grouped, n_experts, act) for at in range(0, N, tiles.rows)))
            return jnp.concatenate(y), sum(rows)
        C = _pair_buffer_rows(N, k, Eh, n_experts or Eh)
        if tiles:
            out = _kernel_passes(h, weights, group, sizes, w1, w3, w2, layer,
                                 C, n_experts, tiles, act)
            return out.astype(h.dtype), sizes
        order = jnp.argsort(group)                   # stable

        def products(x, sizes):
            a = _grouped_product(x, w1, sizes, layer)
            b = w3 is not None and _grouped_product(x, w3, sizes, layer)
            return _grouped_product(mid(a, b), w2, sizes, layer)

        def stands():
            """Where each pair stands in the sorted order (``order`` is
            a permutation): what takes a product's rows back to their
            tokens by a gather, no scatter of wide rows."""
            return jnp.zeros((N * k,), jnp.int32).at[order].set(
                jnp.arange(N * k, dtype=jnp.int32))

        if C >= N * k:
            # every pair has a row: the rows added stand past every
            # group (a gathered row each, no product)
            pad = C - N * k
            y = products(
                h[(jnp.pad(order, (0, pad)) if pad else order) // k], sizes)
            # a token's k pairs are summed with the router's weights.  A
            # pair that is not held sat past the groups, where the
            # product left whatever was there.
            back = stands()
            y = jnp.where(held[:, None], y[back].astype(jnp.float32), 0.0) \
                * weights.reshape(-1, 1)
            return jnp.sum(y.reshape(N, k, -1), axis=1).astype(h.dtype), \
                sizes
        # one loop, so that a program traces the pass once
        ends = jnp.cumsum(sizes)
        held, back = held.reshape(N, k), stands().reshape(N, k)
        order = jnp.pad(order, (0, -N * k % C))

        def one_pass(p, out):
            lo = p * C
            # the part of each expert's run of pairs inside this pass
            part = jnp.clip(ends, lo, lo + C) \
                - jnp.clip(ends - sizes, lo, lo + C)
            y = products(
                h[jax.lax.dynamic_slice(order, (lo,), (C,)) // k], part)
            # slot by slot, a token's pair out of this pass's rows: k
            # gathers of N rows in one fusion, no [N, k, d] value
            at = back - lo
            here = held & (at >= 0) & (at < C)
            at = jnp.clip(at, 0, C - 1)
            for j in range(k):
                out = out + jnp.where(
                    here[:, j, None], y[at[:, j]].astype(jnp.float32), 0.0) \
                    * weights[:, j, None]
            return out

        out = jax.lax.fori_loop(
            0, 1 + extra_pair_passes(sizes, N, k, n_experts), one_pass,
            jnp.zeros((N, h.shape[1]), jnp.float32))
        return out.astype(h.dtype), sizes

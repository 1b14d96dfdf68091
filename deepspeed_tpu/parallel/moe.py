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
import math
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
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


# (rows, contraction, columns) tile of the Mosaic grouped product; the
# last two are fitted to each operand (``fit`` below)
_GMM_TILING = (256, 1920, 1024)
# The pair buffer of a rank that holds a share of the experts, as a
# multiple of the pairs it is routed if the router is even.  The routed
# part alone on a v5e, ms a layer at 1,024 rows (PERF.md 6, PR 40): 64 of
# 512 experts of 2048 x 512, k = 10: 1.11 at 1.5 (2,048 rows), 1.16 at 2
# (4,096), 1.26 at 4 (8,192), 1.68 with a row for every pair (16,384);
# 16 of 256 experts of 7680 x 2048, k = 8: 3.22, 3.22 (1,024 rows both),
# 3.34, 4.04.  2 and not 1.5: a pass more costs a whole pass, 4% buys
# twice the room over an even router, and a trained router is not even
_PAIR_BOUND = 2


def _every_row_pays(N: int, k: int, Eh: int) -> bool:
    """Whether ``N`` rows routed ``k`` ways are cheaper with every one of
    the ``Eh`` held experts on every row than sorted into groups: a rule
    of the shapes alone.  Every expert on every row is ``N * Eh`` row
    products; the grouped product is at most ``N * k`` (every pair held)
    plus up to a row tile of padding an expert.  Both pay the same a row
    product (6 d f gated, 4 d f not), so the widths cancel, and under a
    row tile of rows both are one pass over the held weights, which the
    plain products make without a sort and two gathers of wide rows.

    On a v5e (ms for the three products; PERF.md 6, PR 34), 8 of 8 of
    4096 x 14336, k = 2: 256 rows 4.3 plain against 4.9 grouped, 384
    rows 6.0 against 5.5, 1,024 rows 16.0 against 8.3 (the rule crosses
    at 341); 16 of 256 of 7680 x 2048, k = 8: 256 rows 2.4 against 2.6,
    512 rows 4.5 against 2.9 (it crosses at 512, late for a share: few
    of its N * k pairs are held; :func:`_pair_buffer_rows` knows how
    many experts there are in all, this rule does not, and no cell's
    programs have a row count between the two crossings)."""
    return N * (Eh - k) < Eh * _GMM_TILING[0]


def _grouped_product(x, w, sizes, layer=None):
    """``x`` [M, K] in groups of ``sizes`` consecutive rows, group g
    against ``w[g]`` [K, N] -> [M, N]; rows past the groups are left
    unspecified; M is whole row tiles.  With ``layer``, ``w`` is the
    whole stack [L, G, K, N] and the groups are layer ``layer``'s.  On a
    TPU the Mosaic grouped kernel (JAX's megablox ``gmm``, whose row tile
    is ours to choose) where its tiles divide the widths: it is handed
    the stack as L * G groups of which all but the layer's are empty (it
    visits none of them), because a layer sliced out of a scanned stack
    is a copy of its 1.5 GB before a Mosaic call (18 ms of a 78 ms chunk
    program, v5e, PR 33).  Elsewhere XLA's ``ragged_dot`` on the layer's
    slice."""
    tm, tk, tn = _GMM_TILING
    K, N = x.shape[1], w.shape[-1]
    if jax.default_backend() == "tpu" and not (K % 128 or N % 128):
        from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

        fit = lambda n, t: next(c for c in range(min(t, n), 0, -128)
                                if n % c == 0)
        if layer is not None:
            L, G = w.shape[:2]
            w = w.reshape(L * G, K, N)
            sizes = jax.lax.dynamic_update_slice(
                jnp.zeros((L * G,), sizes.dtype), sizes, (layer * G,))
        return gmm(x, w, sizes, preferred_element_type=x.dtype,
                   tiling=(tm, fit(K, tk), fit(N, tn)))
    return jax.lax.ragged_dot(x, w if layer is None else w[layer], sizes)


def _pair_buffer_rows(N: int, k: int, Eh: int, E: int) -> int:
    """Rows of the grouped branch's pair buffer, a rule of the shapes:
    whole row tiles, a power of two of them (programs of neighbouring
    row counts share one trace of the grouped product: 0.17 s each on
    the chip's host, PERF.md 6, PR 34), enough for ``_PAIR_BOUND`` times
    the ``N * k * Eh / E`` pairs that are held if the router is even,
    and never more than the ``N * k`` pairs there are.  All the experts
    held (``Eh == E``): every pair, as before the bound."""
    tm = _GMM_TILING[0]
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

    Many rows: every (token, expert) pair is a row; pairs whose expert is
    held sort first, by expert, and the rest (what other ranks compute)
    fall past the last group, where the grouped product visits no tile.
    The row buffer holds a bound on the pairs held HERE
    (:func:`_pair_buffer_rows`), not every pair the router made; when
    more are held, further passes over the sorted order take the rest
    (:func:`extra_pair_passes` counts them), each adding into the same
    f32 sum.  Few rows (a decode step; :func:`_every_row_pays`): each
    held expert evaluates every row and the router's weight, zero where
    it did not choose the expert, combines them: one read an expert.

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
        if not grouped or _every_row_pays(N, k, Eh):
            ws = tuple(w if layer is None else w[layer]
                       for w in (w1, w3, w2) if w is not None)
            gain = jnp.zeros((N, Eh + 1), jnp.float32).at[
                jnp.arange(N * k) // k, group].add(weights.reshape(-1))
            ys = jax.vmap(one)(*ws)                             # [Eh, N, d]
            out = jnp.einsum("ne,end->nd", gain[:, :Eh],
                             ys.astype(jnp.float32))
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

        C = _pair_buffer_rows(N, k, Eh, n_experts or Eh)
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
        # pass p takes the sorted pairs p * C .. (p + 1) * C: the held
        # ones stand first, so one pass is all of them unless more than
        # C are held.  One loop, so that a program traces the grouped
        # product once
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
            # gathers of N rows in one fusion, no [N, k, d] value (a
            # second-minor dimension of k is a relayout: 0.16 s of a
            # 3.8 s trace at k = 10, v5e, PR 35)
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

"""ZeRO redundancy elimination as GSPMD shardings.

Reference: deepspeed/runtime/zero/stage_1_and_2.py (DeepSpeedZeroOptimizer),
deepspeed/runtime/zero/stage3.py + partition_parameters.py.

The reference implements ZeRO imperatively: flatten params into contiguous
buffers, round-robin 1-D chunks across the DP group, hook backward to
reduce-scatter gradients, and all-gather params around each use (stage 3),
with bucketing/overlap machinery to hide latency.

On TPU most of that machinery is not needed — where state *lives* is a
sharding decision:

========  ======================  ==================  =====================
stage     optimizer state         gradients           parameters
========  ======================  ==================  =====================
0         replicated              replicated (psum)   replicated
1         sharded over data       replicated (psum)   replicated
2         sharded over data       sharded (r-scatter) replicated
3         sharded over data       sharded             sharded, gathered at
                                                      use by the forward
========  ======================  ==================  =====================

Each column is a per-leaf ``NamedSharding`` (``param_shardings``,
``optstate_shardings``, ``grad_constraint``).  Stages 0-2 leave the
schedule to XLA.  Stage 3 does not: where a parameter is *used* is stated
too.  A model's forward calls ``gather_at_use`` on the leaves it is about
to multiply by (one layer's slice inside the scan body, the embedding
beside it) and ``pin_to_batch`` on the activation it carries, so a layer
costs one bf16 all-gather forward, one in the recomputed forward, and one
reduce-scatter of its gradients, and no activation leaves its chip (the
one exception the compiler still takes: the embedding lookup's gradient,
see ``pin_to_batch``).  Until PR 36 the shardings stood alone, and on four
v5e chips GSPMD met a batch split over ``data`` and weights split over
``data`` on their hidden dimension with partly the tensor-parallel
program: 146 all-to-alls of activations a step, 36.9% of the step in
collectives and every one of them exposed (ledger, PR 35; 13.0% and
+64.6% tokens/s after, PERF.md 6).  A layer's gathers still wait inside
that layer (the compiler fuses the large ones into the product beside
them) and its reduce-scatters are exposed; fetching layer *l+1* under
layer *l*'s products is ROADMAP S2's next step.

Model-parallel (TP) shardings compose: callers pass ``param_specs`` — a
pytree of ``PartitionSpec`` matching the params pytree (or a callable
``leaf -> spec``) — and the ZeRO data axis is layered onto the remaining
unsharded dimension.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Union

import jax
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.topology import (BATCH_AXES, MeshSpec, ZERO_AXES,
                                    current_mesh, current_zero_stage,
                                    shard_leaf_spec)

SpecTree = Union[None, Callable, Any]


def resolve_specs(params: Any, param_specs: SpecTree) -> Any:
    """Normalize ``param_specs`` (None | callable | pytree) to a spec pytree.

    In the pytree form, a ``None`` leaf means replicated (the usual JAX
    convention) and is normalized to ``P()``.
    """
    if param_specs is None:
        return jax.tree.map(lambda _: P(), params)
    if callable(param_specs):
        return jax.tree.map(param_specs, params)
    return jax.tree.map(lambda s: P() if s is None else s, param_specs,
                        is_leaf=lambda x: x is None or isinstance(x, P))


def _zero_axis_size(ms: MeshSpec) -> int:
    n = 1
    for a in ZERO_AXES:
        n *= ms.size(a)
    return n


def _zero_spec(leaf, base: P, ms: MeshSpec) -> P:
    """Layer the data axis onto ``base`` for one leaf."""
    shape = getattr(leaf, "shape", ())
    if len(shape) == 0:
        return P()
    base = () if base is None else base
    # truncate: state leaves may have lower rank than the param they mirror
    # (e.g. factored second moments)
    taken = list(base)[:len(shape)] + [None] * max(0, len(shape) - len(base))
    return shard_leaf_spec(shape, "data", ms.size("data"), taken=taken)


def param_shardings(params: Any, ms: MeshSpec, stage: int,
                    param_specs: SpecTree = None):
    """Shardings for the master parameter pytree (stage 3 adds data axis)."""
    specs = resolve_specs(params, param_specs)

    def one(leaf, base):
        if stage >= 3 and _zero_axis_size(ms) > 1:
            return ms.sharding(_zero_spec(leaf, base, ms))
        return ms.sharding(base)

    return jax.tree.map(one, params, specs)


def optstate_shardings(opt_state: Any, params: Any, ms: MeshSpec, stage: int,
                       param_specs: SpecTree = None):
    """Shardings for optimizer-state pytrees.

    Subtrees that mirror the params structure (moments, master copies) get
    the params' specs (+ data axis for stage >=1, ref: stage_1_and_2.py
    partitioning of fp32 optimizer state); stray leaves are replicated.
    """
    specs = resolve_specs(params, param_specs)
    pstruct = jax.tree.structure(params)
    shard_state = stage >= 1 and _zero_axis_size(ms) > 1

    def spec_for(leaf, base):
        if shard_state:
            return ms.sharding(_zero_spec(leaf, base, ms))
        return ms.sharding(base if getattr(leaf, "ndim", 0) else P())

    def rec(node):
        if node is None:
            return None
        try:
            if jax.tree.structure(node) == pstruct:
                return jax.tree.map(spec_for, node, specs)
        except Exception:
            pass
        if jax.tree_util.all_leaves([node]):
            # stray leaf (step counters etc.): shard if it's a real array,
            # replicate scalars
            if shard_state and getattr(node, "ndim", 0) >= 1:
                return ms.sharding(_zero_spec(node, P(), ms))
            return ms.replicated()
        # generic one-level recursion — works for any registered pytree
        # container (dataclass states, optax NamedTuples, dicts, ...)
        one_level = jax.tree.structure(node, is_leaf=lambda x: x is not node)
        children = one_level.flatten_up_to(node)
        return jax.tree.unflatten(one_level, [rec(c) for c in children])

    return rec(opt_state)


def grad_constraint(grads: Any, ms: MeshSpec, stage: int,
                    param_specs: SpecTree = None):
    """Stage >=2: constrain grads to the data-sharded layout so XLA emits a
    reduce-scatter instead of an all-reduce (ref: stage_1_and_2.py
    ``reduce_scatter_gradients``)."""
    if stage < 2 or _zero_axis_size(ms) == 1:
        return grads
    specs = resolve_specs(grads, param_specs)
    return jax.tree.map(
        lambda g, base: jax.lax.with_sharding_constraint(
            g, ms.sharding(_zero_spec(g, base, ms))), grads, specs)


def _mesh_at_use() -> Optional[MeshSpec]:
    """The ambient mesh, where stage 3's two statements about a forward
    pass apply at this point of the trace; else None, and they return
    their argument.  They apply under an engine at stage 3 whose ZeRO axes
    are larger than 1, outside every ``shard_map`` that holds one of those
    axes (or a batch axis) manual: inside one (``onebit``, ``qwz``,
    ``qgz``) the data axis is the body's own, its parameters arrive
    gathered, and a constraint that names the axis is an error."""
    ms = current_mesh()
    if ms is None or current_zero_stage() < 3 or _zero_axis_size(ms) == 1:
        return None
    manual = set(jax.sharding.get_abstract_mesh().manual_axes)
    if manual & (set(ZERO_AXES) | set(BATCH_AXES)):
        return None
    return ms


def gather_at_use(tree: Any, base_specs: SpecTree = None,
                  stacked: bool = False) -> Any:
    """Stage 3's all-gather at use (ref: deepspeed/runtime/zero/
    partitioned_param_coordinator.py ``fetch_sub_module``): the leaves a
    forward pass is about to multiply by, whole on every chip of the ZeRO
    axes.

    ``tree`` is what the model holds at that point, already cast for
    compute, so bf16 travels: the embedding and final norm, or
    (``stacked``) one layer's slice of the stacked blocks inside the scan
    body.  ``base_specs`` are the model-parallel specs of the leaves as
    they are stored (the model's own ``param_specs``; for a slice, of the
    stacked leaf it was cut from): they stay, only the ZeRO axes go.

    Forward this is one all-gather a leaf.  Backward the leaf's gradient
    is left unconstrained, so that the stored layout ``grad_constraint``
    asks for reaches the product that makes it and the chips' partial
    sums leave as a reduce-scatter: the transpose of a plain constraint
    asks for the gradient whole on every chip first (an all-reduce of
    each leaf, as the compiled step showed), hence the ``custom_vjp``.
    Placed inside a ``jax.checkpoint`` body it is recomputed with it: a
    layer is gathered once forward, once backward, and never kept.
    """
    ms = _mesh_at_use()
    if ms is None:
        return tree
    return jax.tree.map(
        lambda x, base: _gather(
            x, ms.sharding(P(*base[1:]) if stacked else base)),
        tree, resolve_specs(tree, base_specs))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _gather(x, gathered):
    return jax.lax.with_sharding_constraint(x, gathered)


_gather.defvjp(lambda x, gathered: (_gather(x, gathered), None),
               lambda gathered, _, g: (g,))


def pin_to_batch(x):
    """An activation stays where its batch rows are: the leading dimension
    on the batch axes, the others left to the compiler (a ``seq`` or
    ``model`` axis may hold them).  Without this GSPMD meets a batch split
    over ``data`` and weights split over ``data`` and may re-split the
    activation by its hidden dimension instead of gathering the weights
    (146 all-to-alls a step on four chips, ledger, PR 35).

    One activation may still move, outside the layer loop: the gradient
    of the embedding lookup.  The table's gradient is stored by columns,
    and the compiler hands each chip every row's slice of columns (one
    all-to-all of an activation's bytes, 16 MB at GPT-2 1.3B's widths)
    to add its own columns up, instead of reducing a table-sized partial
    sum (206 MB): the cheaper program, left to it."""
    ms = _mesh_at_use()
    if ms is None:
        return x
    spec = P(ms.batch_spec()[0], *[P.UNCONSTRAINED] * (x.ndim - 1))
    return jax.lax.with_sharding_constraint(x, ms.sharding(spec))


def estimate_memory(num_params: int, dp_world: int, stage: int,
                    offload_optimizer: bool = False,
                    compute_bytes: int = 2, master_bytes: int = 4,
                    activation_bytes: int = 0) -> dict:
    """Per-device memory plan for a ZeRO stage (ref:
    deepspeed/runtime/zero/stage3.py estimate_zero3_model_states_mem_needs*
    / stage_1_and_2.py estimate_zero2_model_states_mem_needs*).

    Returns bytes per device for each state class plus the total.  The
    model: bf16 compute copy (replicated below stage 3, sharded at 3),
    f32 master + two Adam moments (sharded from stage 1; on host when
    ``offload_optimizer``), grads in compute dtype (sharded from stage 2).
    """
    if not 0 <= stage <= 3:
        raise ValueError(f"stage must be 0..3, got {stage}")
    if offload_optimizer and stage == 0:
        # reachable but degenerate: engine_offload_shardings applies the
        # host tier at any stage, so stage 0 pins the FULL replicated
        # optimizer copy to every host (the reference estimators only
        # model offload for ZeRO 1-3) — model it, but say so
        from deepspeed_tpu.utils.logging import logger
        logger.warning(
            "offload_optimizer at ZeRO stage 0 keeps the full replicated "
            "optimizer state on every host; use stage >= 1 to shard it")
    n, w = num_params, max(dp_world, 1)
    shard = lambda b: b // w
    opt = 3 * master_bytes * n                      # master + m + v
    plan = {
        "compute_params": shard(compute_bytes * n) if stage >= 3
        else compute_bytes * n,
        "gradients": shard(compute_bytes * n) if stage >= 2
        else compute_bytes * n,
        "optimizer_states": 0 if offload_optimizer
        else (shard(opt) if stage >= 1 else opt),
        "host_optimizer_states": (shard(opt) if stage >= 1 else opt)
        if offload_optimizer else 0,
        "activations": activation_bytes,
    }
    plan["device_total"] = (plan["compute_params"] + plan["gradients"]
                            + plan["optimizer_states"]
                            + plan["activations"])
    return plan


def sharded_init(init_fn: Callable[[], Any], ms: MeshSpec, stage: int,
                 param_specs: SpecTree = None) -> Any:
    """Materialize a parameter pytree directly into its ZeRO shardings.

    ref: deepspeed/runtime/zero/partition_parameters.py ``zero.Init`` — the
    reference intercepts ``Module.__init__`` so each rank only allocates its
    partition of every parameter.  Here the same guarantee falls out of XLA:
    ``init_fn`` is jitted with sharded ``out_shardings``, so (with JAX's
    partitionable threefry PRNG) each device generates and keeps only its
    own shard; the full tree never exists on one device.

    ``TrainingEngine`` applies this automatically when ``initialize()`` is
    given a callable ``params``; this helper is the standalone form.
    """
    abstract = jax.eval_shape(init_fn)
    shardings = param_shardings(abstract, ms, stage, param_specs)
    return jax.jit(init_fn, out_shardings=shardings)()


def unshard_params(params: Any, ms: MeshSpec):
    """Gather a stage-3 sharded pytree to replicated (for export/eval).

    ref: deepspeed/runtime/zero/partition_parameters.py GatheredParameters.
    """
    repl = ms.replicated()
    return jax.jit(lambda p: p, out_shardings=jax.tree.map(lambda _: repl, params))(params)

"""Optimizer core (ref: deepspeed/ops/adam/fused_adam.py,
deepspeed/ops/lamb/fused_lamb.py, deepspeed/ops/lion, deepspeed/ops/adagrad,
deepspeed/runtime/fp16/fused_optimizer.py).

The reference ships CUDA "fused" optimizers that loop over flat param
buffers in one kernel.  On TPU the idiomatic equivalent is a functional
``(init, update)`` pair over the param pytree: XLA fuses the elementwise
update chain into a single HBM pass per leaf.

The API is optax-compatible (init(params) -> state; update(grads, state,
params) -> (updates, state)) so user optax transforms drop in, but the
implementations here are self-contained.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp

ScalarOrSchedule = Union[float, Callable[[jnp.ndarray], jnp.ndarray]]


def _lr_at(lr: ScalarOrSchedule, step):
    return lr(step) if callable(lr) else jnp.asarray(lr, jnp.float32)


@dataclasses.dataclass(frozen=True)
class Optimizer:
    """A gradient transformation: functional mirror of the reference's
    torch.optim.Optimizer subclasses."""

    init: Callable[[Any], Any]
    update: Callable[[Any, Any, Any], Any]  # (grads, state, params) -> (updates, state)
    name: str = "optimizer"
    # Named mesh axis this optimizer communicates over (1-bit family);
    # None = no internal communication.  The engine checks this before
    # routing an optimizer into the compressed shard_map step.
    axis_name: Optional[str] = None


class AdamState(NamedTuple):
    step: jnp.ndarray
    mu: Any
    nu: Any


def adam(lr: ScalarOrSchedule = 1e-3, betas=(0.9, 0.999), eps: float = 1e-8,
         weight_decay: float = 0.0, adamw: bool = True,
         bias_correction: bool = True, name: str = "adamw") -> Optimizer:
    """Adam/AdamW (ref: deepspeed/ops/adam/fused_adam.py FusedAdam —
    ``adam_w_mode`` flag selects decoupled weight decay)."""
    b1, b2 = betas

    def init(params):
        z = lambda p: jnp.zeros_like(p, dtype=jnp.float32)
        return AdamState(jnp.zeros([], jnp.int32), jax.tree.map(z, params),
                         jax.tree.map(z, params))

    def update(grads, state, params):
        step = state.step + 1
        lr_t = _lr_at(lr, step)
        if weight_decay and not adamw:
            # classic L2: fold wd*p into the gradient before the moments
            # (ref: FusedAdam with adam_w_mode=False)
            grads = jax.tree.map(
                lambda g, p: g.astype(jnp.float32)
                + weight_decay * p.astype(jnp.float32), grads, params)
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g.astype(jnp.float32),
                          state.mu, grads)
        nu = jax.tree.map(
            lambda v, g: b2 * v + (1 - b2) * jnp.square(g.astype(jnp.float32)),
            state.nu, grads)
        if bias_correction:
            c1 = 1 - b1 ** step.astype(jnp.float32)
            c2 = 1 - b2 ** step.astype(jnp.float32)
        else:
            c1 = c2 = jnp.float32(1.0)

        def upd(m, v, p):
            u = (m / c1) / (jnp.sqrt(v / c2) + eps)
            if weight_decay and adamw:
                u = u + weight_decay * p.astype(jnp.float32)
            return (-lr_t * u).astype(p.dtype)

        updates = jax.tree.map(upd, mu, nu, params)
        return updates, AdamState(step, mu, nu)

    return Optimizer(init, update, name)


def adamw(lr: ScalarOrSchedule = 1e-3, **kw) -> Optimizer:
    return adam(lr, adamw=True, name="adamw", **kw)


class LambState(NamedTuple):
    step: jnp.ndarray
    mu: Any
    nu: Any


def lamb(lr: ScalarOrSchedule = 1e-3, betas=(0.9, 0.999), eps: float = 1e-6,
         weight_decay: float = 0.0, min_trust: float = 0.01,
         max_trust: float = 10.0) -> Optimizer:
    """LAMB with per-layer trust ratio (ref: deepspeed/ops/lamb/fused_lamb.py
    — the CUDA kernel computes per-tensor norms; here each leaf is a layer)."""
    b1, b2 = betas

    def init(params):
        z = lambda p: jnp.zeros_like(p, dtype=jnp.float32)
        return LambState(jnp.zeros([], jnp.int32), jax.tree.map(z, params),
                         jax.tree.map(z, params))

    def update(grads, state, params):
        step = state.step + 1
        lr_t = _lr_at(lr, step)
        c1 = 1 - b1 ** step.astype(jnp.float32)
        c2 = 1 - b2 ** step.astype(jnp.float32)
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g.astype(jnp.float32),
                          state.mu, grads)
        nu = jax.tree.map(
            lambda v, g: b2 * v + (1 - b2) * jnp.square(g.astype(jnp.float32)),
            state.nu, grads)

        def upd(m, v, p):
            u = (m / c1) / (jnp.sqrt(v / c2) + eps)
            if weight_decay:
                u = u + weight_decay * p.astype(jnp.float32)
            wn = jnp.linalg.norm(p.astype(jnp.float32))
            un = jnp.linalg.norm(u)
            trust = jnp.where(
                (wn > 0) & (un > 0),
                jnp.clip(wn / un, min_trust, max_trust), 1.0)
            return (-lr_t * trust * u).astype(p.dtype)

        updates = jax.tree.map(upd, mu, nu, params)
        return updates, LambState(step, mu, nu)

    return Optimizer(init, update, "lamb")


class LionState(NamedTuple):
    step: jnp.ndarray
    mu: Any


def lion(lr: ScalarOrSchedule = 1e-4, betas=(0.9, 0.99),
         weight_decay: float = 0.0) -> Optimizer:
    """Lion (ref: deepspeed/ops/lion/fused_lion.py)."""
    b1, b2 = betas

    def init(params):
        return LionState(jnp.zeros([], jnp.int32),
                         jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params))

    def update(grads, state, params):
        step = state.step + 1
        lr_t = _lr_at(lr, step)

        def upd(m, p, g):
            g = g.astype(jnp.float32)
            u = jnp.sign(b1 * m + (1 - b1) * g)
            if weight_decay:
                u = u + weight_decay * p.astype(jnp.float32)
            return (-lr_t * u).astype(p.dtype)

        updates = jax.tree.map(upd, state.mu, params, grads)
        mu = jax.tree.map(lambda m, g: b2 * m + (1 - b2) * g.astype(jnp.float32),
                          state.mu, grads)
        return updates, LionState(step, mu)

    return Optimizer(init, update, "lion")


class AdagradState(NamedTuple):
    step: jnp.ndarray
    accum: Any


def adagrad(lr: ScalarOrSchedule = 1e-2, eps: float = 1e-10,
            weight_decay: float = 0.0) -> Optimizer:
    """Adagrad (ref: deepspeed/ops/adagrad/cpu_adagrad.py)."""

    def init(params):
        return AdagradState(
            jnp.zeros([], jnp.int32),
            jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params))

    def update(grads, state, params):
        step = state.step + 1
        lr_t = _lr_at(lr, step)
        accum = jax.tree.map(lambda a, g: a + jnp.square(g.astype(jnp.float32)),
                             state.accum, grads)

        def upd(a, p, g):
            u = g.astype(jnp.float32) / (jnp.sqrt(a) + eps)
            if weight_decay:
                u = u + weight_decay * p.astype(jnp.float32)
            return (-lr_t * u).astype(p.dtype)

        return jax.tree.map(upd, accum, params, grads), AdagradState(step, accum)

    return Optimizer(init, update, "adagrad")


class SgdState(NamedTuple):
    step: jnp.ndarray
    momentum: Any


def sgd(lr: ScalarOrSchedule = 1e-2, momentum: float = 0.0,
        weight_decay: float = 0.0, nesterov: bool = False) -> Optimizer:
    def init(params):
        mom = jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params) \
            if momentum else None
        return SgdState(jnp.zeros([], jnp.int32), mom)

    def update(grads, state, params):
        step = state.step + 1
        lr_t = _lr_at(lr, step)

        def g32(p, g):
            g = g.astype(jnp.float32)
            return g + weight_decay * p.astype(jnp.float32) if weight_decay else g

        gs = jax.tree.map(g32, params, grads)
        if momentum:
            mom = jax.tree.map(lambda m, g: momentum * m + g, state.momentum, gs)
            eff = jax.tree.map(lambda m, g: g + momentum * m, mom, gs) if nesterov else mom
        else:
            mom, eff = None, gs
        updates = jax.tree.map(lambda p, u: (-lr_t * u).astype(p.dtype), params, eff)
        return updates, SgdState(step, mom)

    return Optimizer(init, update, "sgd")


# Per-optimizer default LRs (match each constructor's default above).
_DEFAULT_LR = {"adam": 1e-3, "adamw": 1e-3, "fusedadam": 1e-3, "lamb": 1e-3,
               "fusedlamb": 1e-3, "lion": 1e-4, "adagrad": 1e-2, "sgd": 1e-2}


def default_lr(name: str) -> float:
    return _DEFAULT_LR.get(name.lower(), 1e-3)


_REGISTRY = {
    "adam": lambda **kw: adam(adamw=kw.pop("adam_w_mode", True), **kw),
    "adamw": adamw,
    "fusedadam": lambda **kw: adam(adamw=kw.pop("adam_w_mode", True), **kw),
    "lamb": lamb,
    "fusedlamb": lamb,
    "lion": lion,
    "adagrad": adagrad,
    "sgd": sgd,
}


def _register_onebit():
    from deepspeed_tpu.ops import onebit

    _REGISTRY["onebitadam"] = onebit.onebit_adam
    _REGISTRY["onebitlamb"] = onebit.onebit_lamb
    _REGISTRY["zerooneadam"] = onebit.onebit_adam  # 0/1 Adam maps to the same comm scheme


def from_config(name: str, params: dict) -> Optimizer:
    """Build from the config ``optimizer`` block (ref:
    deepspeed/runtime/engine.py _configure_basic_optimizer)."""
    name = name.lower()
    if name.startswith("onebit") or name.startswith("zeroone"):
        _register_onebit()   # deferred: onebit imports this module
        # Outside the engine's compressed step (which runs under the
        # portable deepspeed_tpu.mesh.shard_map) there is no bound
        # named axis, so axis_name defaults to None — which means NO
        # compressed communication happens.  The engine passes
        # axis_name="data" itself when its compressed step is active
        # (deepspeed_tpu/comm_compress.py); warn loudly for everyone else
        # so nobody believes they enabled 32x comm reduction and didn't.
        params = dict(params)
        if params.setdefault("axis_name", None) is None:
            from deepspeed_tpu.utils.logging import logger

            logger.warning(
                "%s built with axis_name=None: momentum compression is "
                "INACTIVE (updates are exact Adam/LAMB with frozen "
                "variance). Use it through TrainingEngine on a "
                "data-parallel mesh, or pass axis_name= under your own "
                "shard_map, to get compressed communication.", name)
    if name not in _REGISTRY:
        raise ValueError(f"unknown optimizer {name!r}; known: {sorted(_REGISTRY)}")
    kw = dict(params)
    # reference key spellings
    if "lr" in kw and not callable(kw["lr"]):
        kw["lr"] = float(kw["lr"])
    if "betas" in kw:
        kw["betas"] = tuple(kw["betas"])
    kw.pop("torch_adam", None)
    return _REGISTRY[name](**kw)

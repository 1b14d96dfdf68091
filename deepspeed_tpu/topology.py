"""Device-mesh topology (replaces reference process groups, ref:
deepspeed/utils/groups.py).

The reference builds NCCL process groups per parallelism flavor (data,
tensor-"mpu", pipeline, expert, sequence).  On TPU there is ONE object —
a :class:`jax.sharding.Mesh` with named axes — and every "group" is a mesh
axis; XLA lowers collectives onto the ICI torus from sharding annotations.

Canonical axis order (outer→inner, chosen so that the innermost axes get
the fastest ICI links): ``("pipe", "data", "expert", "seq", "model")``.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXES = ("pipe", "data", "expert", "seq", "model")
# ZeRO shards params/grads/optimizer state over the data-parallel axes.
ZERO_AXES = ("data",)
# Batch dim is split over every token-replicating axis.
BATCH_AXES = ("data", "expert")


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Resolved axis sizes + the live Mesh."""

    sizes: Dict[str, int]
    mesh: Mesh

    @classmethod
    def build(cls, sizes: Dict[str, int], devices: Optional[Sequence] = None) -> "MeshSpec":
        from deepspeed_tpu.mesh import make_mesh

        devices = list(devices if devices is not None else jax.devices())
        full = {a: int(sizes.get(a, 1)) for a in AXES}
        return cls(sizes=full, mesh=make_mesh(full, devices=devices))

    # ------------------------------------------------------------ accessors
    def size(self, axis: str) -> int:
        return self.sizes[axis]

    @property
    def dp_world(self) -> int:
        return self.size("data") * self.size("expert")

    def sharding(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def batch_spec(self) -> P:
        """Global batch dim split across all token-parallel axes."""
        axes = tuple(a for a in BATCH_AXES if self.size(a) > 1)
        return P(axes if axes else None)


# Ambient mesh registry: the engine publishes its MeshSpec here so model
# code (ring/ulysses attention, MoE dispatch) can fetch shardings without
# threading the mesh through every call (the analogue of the reference's
# global process groups in deepspeed/utils/groups.py).
_CURRENT_MESH: Optional["MeshSpec"] = None
# The ZeRO stage of the engine that published the mesh: what zero.py's
# statements about a model's forward (gather_at_use, pin_to_batch) ask.
# Whoever publishes a mesh without one (serving, tests) publishes stage 0.
_CURRENT_ZERO_STAGE: int = 0


def set_current_mesh(ms: Optional["MeshSpec"], zero_stage: int = 0) -> None:
    global _CURRENT_MESH, _CURRENT_ZERO_STAGE
    _CURRENT_MESH = ms
    _CURRENT_ZERO_STAGE = int(zero_stage)


def current_mesh() -> Optional["MeshSpec"]:
    return _CURRENT_MESH


def current_zero_stage() -> int:
    return _CURRENT_ZERO_STAGE


def default_mesh(n_devices: Optional[int] = None) -> MeshSpec:
    """All devices on the data axis (pure DP/ZeRO)."""
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return MeshSpec.build({"data": len(devs)}, devices=devs)


def shard_leaf_spec(shape: Sequence[int], axis_name: str, axis_size: int,
                    taken: Sequence[Optional[str]] = ()) -> P:
    """Pick a PartitionSpec sharding one divisible dim of ``shape`` over
    ``axis_name``; replicate if nothing divides.

    This is the TPU analogue of the reference's flat-buffer partitioning
    (ref: deepspeed/runtime/zero/partition_parameters.py): instead of
    flattening params into NCCL-friendly 1-D chunks, each array keeps its
    shape and GSPMD shards its largest divisible dimension — XLA then emits
    the all-gather/reduce-scatter pairs the reference hand-schedules.
    """
    if axis_size <= 1:
        return P(*taken) if taken else P()
    taken = list(taken) + [None] * (len(shape) - len(taken))
    # Prefer the largest dim for even, MXU-friendly chunks.
    order = sorted(range(len(shape)), key=lambda i: -shape[i])
    for i in order:
        if taken[i] is None and shape[i] % axis_size == 0 and shape[i] >= axis_size:
            taken[i] = axis_name
            while taken and taken[-1] is None:
                taken.pop()
            return P(*taken)
    while taken and taken[-1] is None:
        taken.pop()
    return P(*taken) if taken else P()

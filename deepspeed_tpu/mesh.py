"""Mesh helpers — the SPMD core every parallel path builds on.

GSPMD (arXiv:2105.04663) is the compilation model: ONE jitted program,
named mesh axes, ``NamedSharding``/``PartitionSpec`` annotations, and
XLA choosing the collectives.  ``jax.shard_map`` (``axis_names=`` for
the axes the body manages itself, ``check_vma=``) is the escape hatch
for the paths that schedule their own collectives — pipeline ticks,
ring/Ulysses attention, int8 gradient wires, 1-bit momentum, the flash
kernel on a mesh — and callers use it and ``jax.lax.axis_size``
directly: there is one installed JAX and no spelling to resolve.
"""

from __future__ import annotations

import os
from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = [
    "make_mesh", "named_sharding", "pspec", "mesh_axis_sizes",
    "host_device_count", "detect_hierarchy_size",
]


# ------------------------------------------------------------- helpers
def make_mesh(axis_sizes: Dict[str, int],
              devices: Optional[Sequence] = None) -> Mesh:
    """Build a :class:`jax.sharding.Mesh` from ``{axis: size}`` in dict
    order over ``devices`` (default: all).  The named-axis Mesh is the
    modern idiom's single topology object — every "process group" of
    the reference is an axis of it."""
    devices = list(devices if devices is not None else jax.devices())
    names = tuple(axis_sizes)
    shape = [int(axis_sizes[a]) for a in names]
    total = int(np.prod(shape)) if shape else 1
    if total != len(devices):
        raise ValueError(
            f"mesh {dict(axis_sizes)} needs {total} devices, "
            f"have {len(devices)}")
    return Mesh(np.array(devices).reshape(shape), names)


def pspec(*axes) -> PartitionSpec:
    """``PartitionSpec`` constructor passthrough (one import site)."""
    return PartitionSpec(*axes)


def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    """``NamedSharding`` over ``mesh``; ``spec`` is either a single
    PartitionSpec or the axes to build one from."""
    if len(spec) == 1 and isinstance(spec[0], PartitionSpec):
        return NamedSharding(mesh, spec[0])
    return NamedSharding(mesh, PartitionSpec(*spec))


def mesh_axis_sizes(mesh: Mesh) -> Dict[str, int]:
    """``{axis: size}`` of a live Mesh (statusz / observability)."""
    return {a: int(s) for a, s in zip(mesh.axis_names,
                                      mesh.devices.shape)}


def detect_hierarchy_size(devices: Optional[Sequence] = None) -> int:
    """Devices per node for two-level collectives (comm/collectives.py).

    The physical boundary hierarchical collectives care about is the
    host: devices of one process share fast intra-node links (ICI /
    NVLink-class), cross-process traffic rides the slower DCN tier.  So
    the auto-detected ``hierarchy_size`` is the per-process device
    count — when every process holds the same number of devices and
    there is more than one process.  Single-process topologies (incl.
    the virtual-CPU test mesh) return 1: a flat axis, no hierarchy —
    callers treat 1 as "hierarchy off" rather than guessing a split
    that has no physical meaning.
    """
    devices = list(jax.devices() if devices is None else devices)
    if not devices:
        return 1
    per_proc: Dict[int, int] = {}
    for d in devices:
        p = int(getattr(d, "process_index", 0))
        per_proc[p] = per_proc.get(p, 0) + 1
    counts = set(per_proc.values())
    if len(per_proc) <= 1 or len(counts) != 1:
        return 1
    return counts.pop()


def host_device_count(n: int) -> None:
    """Ask XLA for ``n`` virtual host (CPU) devices — must run BEFORE
    the backend initializes.  The CPU-testable stand-in for a real
    multi-chip mesh (``--xla_force_host_platform_device_count``).

    A pre-existing flag asking for a DIFFERENT count raises here —
    failing at the point of conflict beats failing mid-run with a
    device-count mismatch after the flag silently lost."""
    import re

    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"xla_force_host_platform_device_count=(\d+)", flags)
    if m is not None:
        have = int(m.group(1))
        if have != int(n):
            raise ValueError(
                f"XLA_FLAGS already forces {have} host devices but "
                f"{int(n)} were requested — clear the flag (or match "
                "it) before the backend initializes")
        return
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={int(n)}")

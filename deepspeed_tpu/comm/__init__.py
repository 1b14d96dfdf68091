"""Communication backend over XLA collectives (ref: deepspeed/comm/comm.py
+ deepspeed/comm/torch.py NCCL backend).

The reference exposes a torch.distributed-style API (init_distributed,
all_reduce, all_gather, reduce_scatter, broadcast, all_to_all, barrier)
dispatched to NCCL/MPI.  The TPU-native equivalent has two levels:

1. **Inside SPMD code** (under ``shard_map``/``jit``): thin wrappers over
   ``jax.lax`` collectives keyed by mesh axis name.  XLA lowers these onto
   ICI rings; there is no handle/group plumbing.
2. **Host level**: process bring-up via ``jax.distributed`` and
   convenience whole-array ops that jit a collective over a mesh.

ReduceOp, ranks and world sizes mirror the reference names.
"""

from __future__ import annotations

import enum
import os
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_initialized = False

# --------------------------------------------------------------------------
# Comms logging (ref: deepspeed/comm comms_logger).  The default path now
# RECORDS: every SPMD wrapper below logs (op, per-shard bytes) at trace
# time via record_event, and the host-level whole-array ops log wall-
# timed records.  Caveat, documented on record_event too: a traced
# collective is logged once per COMPILATION of its enclosing jit, not
# once per step — jit caching means these counts answer "which ops, how
# many call sites, what shard volume", while the per-execution truth
# lives in the compiled-HLO digest (deepspeed_tpu/comm/digest.py).
# Surface into a MetricsRegistry with
# ``registry.fan_in_comms(comm.comms_logger())``.
# --------------------------------------------------------------------------
from deepspeed_tpu.utils.trace import CommsLogger as _CommsLogger

_comms_logger = _CommsLogger(enabled=True)


def comms_logger():
    """The backend's process-wide CommsLogger."""
    return _comms_logger


def configure_comms_logger(enabled: bool) -> None:
    """Toggle collective recording (ref: comms_logger config knob)."""
    _comms_logger.enabled = bool(enabled)


def _nbytes(x) -> int:
    """Per-shard payload bytes of an array or tracer (shape/dtype are
    static under tracing, so this is exact and trace-safe)."""
    try:
        size = 1
        for d in x.shape:
            size *= int(d)
        return size * x.dtype.itemsize
    except Exception:      # scalars / exotic leaves: count the op only
        return 0


class ReduceOp(enum.Enum):  # ref: deepspeed/comm/comm.py ReduceOp
    SUM = "sum"
    AVG = "avg"
    MAX = "max"
    MIN = "min"
    PRODUCT = "prod"


# --------------------------------------------------------------------------
# Host-level bring-up (ref: init_distributed / deepspeed/comm/comm.py)
# --------------------------------------------------------------------------
def init_distributed(dist_backend: str = "xla",
                     coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     **_compat) -> None:
    """Bring up multi-host JAX.  Single-host is a no-op.

    Env fallbacks mirror the launcher contract: COORDINATOR_ADDRESS,
    NUM_PROCESSES, PROCESS_ID (and the reference's RANK/WORLD_SIZE).
    """
    global _initialized
    if _initialized:
        return
    coordinator_address = coordinator_address or os.environ.get("COORDINATOR_ADDRESS")
    num_processes = num_processes or int(
        os.environ.get("NUM_PROCESSES", os.environ.get("WORLD_SIZE", "1")))
    process_id = process_id if process_id is not None else int(
        os.environ.get("PROCESS_ID", os.environ.get("RANK", "0")))
    if num_processes > 1 and coordinator_address:
        # CPU backend (multi-host simulation / DCN-only hosts): XLA's
        # cross-process CPU collectives need an implementation picked
        # before backend init — gloo ships in jaxlib (ref analogue: the
        # reference's gloo fallback next to NCCL in comm/comm.py)
        platforms = str(getattr(jax.config, "jax_platforms", "") or
                        os.environ.get("JAX_PLATFORMS", ""))
        if "cpu" in platforms:
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    _initialized = True


def is_initialized() -> bool:
    return _initialized


def get_rank() -> int:
    """Host process rank (ref: comm.get_rank).

    NOTE: under SPMD one process drives many chips, so rank/world_size
    count PROCESSES (consistent units).  The reference counts one rank
    per GPU; use :func:`get_device_count` for the chip count.
    """
    return jax.process_index()


def get_world_size() -> int:
    """Number of host processes (see :func:`get_rank` note)."""
    return jax.process_count()


def get_device_count() -> int:
    """Total accelerator chips across all hosts (the reference's world size)."""
    return jax.device_count()


def get_local_rank() -> int:
    return 0  # one process per host on TPU; devices are addressed via mesh


def barrier() -> None:
    """Cross-host barrier (ref: comm.barrier)."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        with _comms_logger.record("barrier", 0):
            multihost_utils.sync_global_devices("deepspeed_tpu.barrier")


# --------------------------------------------------------------------------
# SPMD collectives — call inside shard_map/pmap'd code with a mesh axis name
# --------------------------------------------------------------------------
def all_reduce(x, axis_name: str, op: ReduceOp = ReduceOp.SUM):
    """ref: comm.all_reduce → lax.psum/pmax/pmin/pmean on a mesh axis."""
    _comms_logger.record_event("all_reduce", _nbytes(x))
    if op in (ReduceOp.SUM,):
        return jax.lax.psum(x, axis_name)
    if op is ReduceOp.AVG:
        return jax.lax.pmean(x, axis_name)
    if op is ReduceOp.MAX:
        return jax.lax.pmax(x, axis_name)
    if op is ReduceOp.MIN:
        return jax.lax.pmin(x, axis_name)
    if op is ReduceOp.PRODUCT:
        # log-space for magnitude; track sign parity and zeros separately so
        # non-positive inputs don't produce NaN.
        mag = jnp.exp(jax.lax.psum(jnp.log(jnp.where(x == 0, 1.0, jnp.abs(x))),
                                   axis_name))
        neg = jax.lax.psum((x < 0).astype(jnp.int32), axis_name)
        has_zero = jax.lax.psum((x == 0).astype(jnp.int32), axis_name) > 0
        sign = jnp.where(neg % 2 == 0, 1.0, -1.0)
        return jnp.where(has_zero, 0.0, sign * mag)
    raise ValueError(f"unsupported op {op}")


def all_gather(x, axis_name: str, axis: int = 0, tiled: bool = True):
    """ref: comm.all_gather — concatenate shards along ``axis``."""
    _comms_logger.record_event("all_gather", _nbytes(x))
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name: str, axis: int = 0,
                   op: ReduceOp = ReduceOp.SUM):
    """ref: comm.reduce_scatter_base — sum then keep this rank's shard."""
    _comms_logger.record_event("reduce_scatter", _nbytes(x))
    if op not in (ReduceOp.SUM, ReduceOp.AVG):
        raise ValueError("reduce_scatter supports SUM/AVG")
    out = jax.lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=True)
    if op is ReduceOp.AVG:
        out = out / jax.lax.axis_size(axis_name)
    return out


def broadcast(x, axis_name: str, src: int = 0):
    """ref: comm.broadcast — everyone takes rank ``src``'s value."""
    _comms_logger.record_event("broadcast", _nbytes(x))
    return jax.lax.all_gather(x, axis_name, axis=0, tiled=False)[src]


def all_to_all(x, axis_name: str, split_axis: int, concat_axis: int):
    """ref: comm.all_to_all_single — the MoE/Ulysses workhorse."""
    _comms_logger.record_event("all_to_all", _nbytes(x))
    return jax.lax.all_to_all(x, axis_name, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)


def ppermute(x, axis_name: str, perm: Sequence):
    """Point-to-point ring shift (ref: NCCL send/recv pairs in pipe engine)."""
    _comms_logger.record_event("ppermute", _nbytes(x))
    return jax.lax.ppermute(x, axis_name, perm=perm)


def send_recv_next(x, axis_name: str, size: int):
    """Shift +1 around the ring — pipeline stage handoff."""
    return jax.lax.ppermute(x, axis_name, perm=[(i, (i + 1) % size) for i in range(size)])


def rank_in(axis_name: str):
    """Index of this shard along a mesh axis (inside SPMD code)."""
    return jax.lax.axis_index(axis_name)


# --------------------------------------------------------------------------
# Whole-array host-level collectives (convenience, jitted over a mesh)
# --------------------------------------------------------------------------
def mesh_all_reduce(x: jax.Array, mesh: Mesh, op: ReduceOp = ReduceOp.SUM) -> jax.Array:
    """Reduce a per-device-sharded array to a replicated one."""

    axes = mesh.axis_names

    def f(v):
        for a in axes:
            v = all_reduce(v, a, op)
        return v

    spec = P(axes)
    # host-level op: this record is WALL-TIMED (dispatch side) with the
    # full array's bytes, unlike the trace-time SPMD records above
    with _comms_logger.record("mesh_all_reduce", _nbytes(x)):
        return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=spec,
                                     out_specs=P()))(x)

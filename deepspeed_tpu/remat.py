"""Activation checkpointing policies (ref:
deepspeed/runtime/activation_checkpointing/checkpointing.py).

The reference re-implements torch checkpointing with partitioned/offloaded
activation storage.  On TPU this is ``jax.checkpoint`` + a rematerialization
policy: XLA recomputes the block in backward, trading FLOPs for HBM, and
GSPMD already keeps activations sharded (the reference's
``partition_activations``).

The reference's ``cpu_checkpointing`` (offload the saved activations to
host RAM instead of keeping them on-device) maps to the ``offload_*``
policies below: XLA moves the named residuals to ``pinned_host`` memory
after the forward and fetches them back for the backward — no recompute,
no HBM residency, and the device→host copies ride XLA's async
memory-space transfers.

Models tag their two big per-block intermediates with
``jax.ad_checkpoint.checkpoint_name``: ``attn_out`` (the attention
context, quadratic to recompute) and ``mlp_out`` (the FFN inner
activation) — the names ``save_attn`` keeps on-device and
``offload_attn`` spills to host.

The flash kernel's two results (``FLASH_NAMES``: a head's context and
its log-sum, which the backward reads) are kept by EVERY policy that
keeps a product, offloaded by the two that offload: the context is a
matmul's output hidden in a ``pallas_call``, where ``checkpoint_dots``
sees no ``dot_general``, and unkept it costs a second run of the whole
forward kernel in the backward (PERF.md 6, PR 62).  ``full`` still
recomputes it.
"""

from __future__ import annotations

import jax

from deepspeed_tpu.ops.attention_pallas_bwd import FLASH_NAMES

_NAMES = ("attn_out", "mlp_out")


def _and_flash(base, named=None):
    """``named``'s word on the flash kernel's two results (by default:
    saved), ``base``'s on everything else.  (``save_from_both_policies``
    joins bools only; an offload policy answers with a place.)"""
    flash = jax.checkpoint_policies.save_only_these_names(*FLASH_NAMES)
    named = named or flash

    def joined(prim, *args, **params):
        which = named if flash(prim, *args, **params) else base
        return which(prim, *args, **params)

    return joined


def _to_host():
    """The tagged intermediates and the flash kernel's two results, to
    host RAM."""
    return jax.checkpoint_policies.save_and_offload_only_these_names(
        names_which_can_be_saved=[],
        names_which_can_be_offloaded=list(_NAMES + FLASH_NAMES),
        offload_src="device", offload_dst="pinned_host")


def policy(name: str):
    """Map config policy names to jax.checkpoint policies."""
    if name in ("none", None):
        return None
    if name == "full":
        return jax.checkpoint_policies.nothing_saveable
    if name == "save_dots":
        # keep matmul outputs, recompute elementwise — the usual sweet spot
        return _and_flash(jax.checkpoint_policies.checkpoint_dots)
    if name == "save_dots_no_batch":
        return _and_flash(
            jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    if name == "save_attn":
        return jax.checkpoint_policies.save_only_these_names(
            *_NAMES, *FLASH_NAMES)
    if name == "offload_attn":
        # ref cpu_checkpointing: the tagged intermediates live in host
        # RAM between forward and backward instead of HBM
        return _to_host()
    if name == "offload_dots_no_batch":
        # heavier offload: every no-batch-dim matmul output goes to host
        return _and_flash(
            jax.checkpoint_policies.offload_dot_with_no_batch_dims(
                "device", "pinned_host"), _to_host())
    raise ValueError(f"unknown remat policy {name!r}")


_ON_DEVICE_FALLBACK = {
    "offload_attn": "save_attn",
    "offload_dots_no_batch": "save_dots_no_batch",
}


def resolve_policy(name: str) -> str:
    """Downgrade ``offload_*`` to its on-device twin when the backend
    cannot host-offload under SPMD (the CPU test mesh: XLA's
    partitioner RET_CHECKs on the placement annotations — same
    limitation offload.host_memory_supported gates for optimizer
    state).  Each twin keeps the SAME tensors; only WHERE they sit
    between forward and backward differs."""
    if name in _ON_DEVICE_FALLBACK:
        from deepspeed_tpu.offload import host_memory_supported

        if not host_memory_supported():
            from deepspeed_tpu.utils.logging import logger

            fallback = _ON_DEVICE_FALLBACK[name]
            logger.warning(
                "activation offload (%s) needs a backend with SPMD "
                "host-offload support; falling back to %s", name, fallback)
            return fallback
    return name


def checkpoint_block(fn, name: str = "full"):
    """Wrap a layer function with the named remat policy."""
    if name in ("none", None):
        return fn
    return jax.checkpoint(fn, policy=policy(name))

"""Per-collective communication digest (ref: deepspeed/comm/comm.py
``comms_logger`` — the reference counts every explicit NCCL call's bytes
and latency behind a ``comms_logger.enabled`` flag).

On TPU the collectives are not calls we make — GSPMD materializes them
inside the compiled step.  The observable source of truth is therefore
the compiled HLO: every ``all-reduce`` / ``all-gather`` /
``reduce-scatter`` / ``all-to-all`` / ``collective-permute`` instruction
appears there with its payload shapes.  :func:`analyze_collectives`
parses one compiled step into op counts + payload bytes per collective
kind (per step, not per second), and
:func:`TrainingEngine.comms_digest` feeds the digest to the monitor so
dashboards can watch what ICI is doing across rounds.

Estimated wire time uses a flat link-bandwidth model (v5e ICI ~
45 GB/s/link both directions, configurable): good for spotting a 4×
regression, not for microsecond accounting — real latency hiding
overlaps most of this behind compute.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute")

# one HLO instruction: "%name = <result-type> <opcode>(...)" where
# result-type is "bf16[4,128]{1,0}" or a tuple "(f32[8]{0}, s8[8]{0})".
# Async pairs must count ONCE: match the base op or its "-start" half,
# and reject the "-done" half via lookahead (plain "all-gather" followed
# by "-done" would otherwise match at the word boundary before the dash).
_INSTR = re.compile(
    r"=\s+(\([^)]*\)|[a-z0-9]+\[[^\]]*\]\S*)\s+"
    r"((?:all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?)(?!-done)\b")
# The TPU compiler emits a reduce-scatter as a custom fusion whose called
# computation is named all-reduce-scatter ("%fusion.386 = bf16[...]
# fusion(%x), kind=kCustom, calls=%all-reduce-scatter.clone"): no
# reduce-scatter opcode is left in the text (compiled for a v5e, PR 21).
_FUSED_REDUCE_SCATTER = re.compile(
    r"=\s+(\([^)]*\)|[a-z0-9]+\[[^\]]*\]\S*)\s+fusion\([^)]*\),"
    r"\s*kind=kCustom,\s*calls=%all-reduce-scatter\b")
# That called computation spells the reduction as a plain all-reduce of
# the whole operand ("%all-reduce-scatter.1.clone (input: bf16[8192,2048])
# -> bf16[2080,2048] { ... all-reduce(%pad) ... }"): the fusion above is
# the collective, and its body is not a second one (PR 36).
_FUSED_BODY = re.compile(
    r"^%all-reduce-scatter\b[^\n]*\{\n.*?^\}", re.M | re.S)
# An async collective the TPU compiler fuses is written out in each of the
# computations that make up the fusion, under one channel: count a channel
# once (the step compiled for four v5e chips names each layer's all-gather
# three times, PR 36).
_CHANNEL = re.compile(r"channel_id=(\d+)")
_SHAPE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")


def _shape_bytes(typestr: str) -> int:
    total = 0
    for dtype, dims in _SHAPE.findall(typestr):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def analyze_collectives(hlo_text: str,
                        link_gbps: float = 45.0) -> Dict[str, Any]:
    """Parse compiled HLO → per-kind {count, bytes} + totals.

    ``bytes`` is the RESULT payload of each collective instruction (what
    lands on this device per execution); ``-start``/``-done`` async pairs
    are counted once via the start op.
    """
    per_kind: Dict[str, Dict[str, int]] = {
        k: {"count": 0, "bytes": 0} for k in COLLECTIVE_KINDS}
    found, channels = [], set()
    for line in _FUSED_BODY.sub("", hlo_text).splitlines():
        m = _INSTR.search(line)
        if m is None:
            continue
        kind = m.group(2).replace("-start", "")
        ch = _CHANNEL.search(line)
        if ch is not None:
            if (kind, ch.group(1)) in channels:
                continue
            channels.add((kind, ch.group(1)))
        found.append((m.group(1), kind))
    found += [(t, "reduce-scatter")
              for t in _FUSED_REDUCE_SCATTER.findall(hlo_text)]
    for typestr, kind in found:
        per_kind[kind]["count"] += 1
        per_kind[kind]["bytes"] += _shape_bytes(typestr)
    total_bytes = sum(v["bytes"] for v in per_kind.values())
    total_count = sum(v["count"] for v in per_kind.values())
    return {
        "per_kind": {k: v for k, v in per_kind.items() if v["count"]},
        "total_collectives": total_count,
        "total_bytes": total_bytes,
        "est_wire_ms": round(1e3 * total_bytes / (link_gbps * 1e9), 3),
        "link_gbps_model": link_gbps,
    }


def digest_compiled(compiled, link_gbps: float = 45.0) -> Dict[str, Any]:
    """Digest a ``jax.stages.Compiled`` (adds XLA's own cost analysis
    bytes-accessed when the backend exposes it)."""
    out = analyze_collectives(compiled.as_text(), link_gbps)
    try:
        cost = compiled.cost_analysis()
        if cost:
            ca = cost[0] if isinstance(cost, (list, tuple)) else cost
            for key in ("bytes accessed", "flops"):
                if key in ca:
                    out[f"xla_{key.replace(' ', '_')}"] = float(ca[key])
    except Exception:  # cost analysis is backend-best-effort
        pass
    return out


def log_digest(monitor, digest: Dict[str, Any], step: int,
               prefix: str = "Comms") -> None:
    """Write a digest's scalars through a MonitorMaster."""
    scalars = {f"{prefix}/total_bytes": digest["total_bytes"],
               f"{prefix}/total_collectives": digest["total_collectives"],
               f"{prefix}/est_wire_ms": digest["est_wire_ms"]}
    for kind, v in digest["per_kind"].items():
        scalars[f"{prefix}/{kind}_bytes"] = v["bytes"]
        scalars[f"{prefix}/{kind}_count"] = v["count"]
    monitor.write_scalars(scalars, step)

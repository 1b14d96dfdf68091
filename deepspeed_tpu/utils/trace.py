"""The Python-side collective log (ref: ``deepspeed.comm``'s
comms-logger).  Spans and device captures live elsewhere:
``telemetry.Span`` is the program's one span type, ``/profilez``
(devprof) the operator's capture.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, Tuple


class CommsLogger:
    """Python-side collective log (ref: deepspeed/comm comms_logger).

    The comm backend calls :meth:`record` around each collective.
    Per-op totals accumulate in an aggregate dict (``summary()`` is
    O(ops), not O(records) — the telemetry fan-in polls it every
    publish tick), while ``records`` keeps only the most recent
    ``max_records`` raw ``(op, bytes, wall_s)`` tuples as a debugging
    view, so a long-lived process cannot grow it unboundedly.
    """

    def __init__(self, enabled: bool = True, max_records: int = 10_000):
        import collections

        self.enabled = enabled
        self._lock = threading.Lock()
        self.records: "collections.deque[Tuple[str, int, float]]" = \
            collections.deque(maxlen=max_records)
        self._totals: Dict[str, Dict[str, float]] = {}

    def _add(self, op: str, nbytes: int, wall_s: float) -> None:
        with self._lock:
            self.records.append((op, nbytes, wall_s))
            s = self._totals.setdefault(
                op, {"count": 0, "bytes": 0, "time_s": 0.0})
            s["count"] += 1
            s["bytes"] += nbytes
            s["time_s"] += wall_s

    @contextlib.contextmanager
    def record(self, op: str, nbytes: int):
        if not self.enabled:
            yield
            return
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self._add(op, nbytes, time.perf_counter() - t0)

    def record_event(self, op: str, nbytes: int,
                     wall_s: float = 0.0) -> None:
        """Append one record without timing a block — the comm backend
        uses this to log SPMD collectives at TRACE time (inside
        jit/shard_map there is no host wall clock to bracket; wall_s
        stays 0 and the count reflects traced call sites per
        compilation, not per-step executions — see
        ``deepspeed_tpu.comm`` for the caveat)."""
        if not self.enabled:
            return
        self._add(op, int(nbytes), wall_s)

    def summary(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {op: dict(s) for op, s in self._totals.items()}

    def reset(self) -> None:
        with self._lock:
            self.records.clear()
            self._totals.clear()

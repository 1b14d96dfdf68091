"""Device-truth observability: compile sentinel, per-phase device time,
and roofline accounting (no reference analogue; the fifth observability
pillar next to telemetry/tracing/history/incidents).

Every other timing surface in the repo is host wall-time
(``perf_counter`` in telemetry/request_trace), but the perf contract
lives on the device: the serving engine's prewarm/bucket-pad discipline
exists solely to keep XLA compiles out of TTFT, and ZeRO-Infinity's
(arXiv:2104.07857) efficiency claims are bandwidth/roofline claims.
This module closes the gap with three coupled capabilities:

- **Compile sentinel**: every XLA compile is attributed to a call-site
  ledger with timestamps, counted warmup vs **steady-state** (post
  first-token of the first request), and emitted as ``xla_compile``
  flight-recorder events on their own Chrome track.  Attribution comes
  from counting wrappers at the project's jit call sites (installed by
  the engine around the programs ``_build_programs`` produced) via the
  jitted function's ``_cache_size()`` — cheap, exact per site.  A
  process-wide ``jax.monitoring`` duration listener (installed once by
  :func:`install_compile_listener`, which the first :class:`DevProf`
  calls) pairs best-effort compile DURATIONS with the wrapper's
  counts.  A steady-state recompile is a **contract violation**: the
  incident probe trips a ``steady_state_recompile`` bundle and the
  bench gate pins ``steady_state_recompiles == 0``.

- **Per-phase device-time attribution**: sampled timed dispatches
  (rate-limited ``block_until_ready`` deltas on the
  ``devprof.sample_rate`` cadence) feed
  ``devprof_device_seconds_{prefill|decode|spec_verify|promote|sample}``
  counters plus a host-vs-device gap gauge (how far the async dispatch
  queue runs ahead of the host).

- **Roofline accounting**: the engine cost-analyzes its compiled sweep
  programs once at build (:mod:`deepspeed_tpu.profiler`'s
  ``cost_analysis`` path), the sentinel wrappers accumulate the
  per-dispatch flops/bytes estimates, and :meth:`DevProf.tick` turns
  the counter deltas into live MFU/MBU gauges against
  :func:`~deepspeed_tpu.timers.device_peak_flops` /
  :func:`~deepspeed_tpu.timers.device_peak_bandwidth`.

On-demand device traces: ``/profilez?capture_s=`` runs a bounded
``jax.profiler`` capture under ``tracing.dump_dir``; the capture
reference and the compile ledger ride incident bundles.
"""

from __future__ import annotations

import collections
import os
import threading
import time
from typing import Any, Dict, List, Optional

import jax

from deepspeed_tpu.config import DevprofConfig
from deepspeed_tpu.telemetry import mark as telemetry_mark
from deepspeed_tpu.timers import device_peak_bandwidth, device_peak_flops

# ------------------------------------------------------ phase vocabulary
# The phase names of the sampled device-time counters and of
# trace_report's device-time column.
PHASES = ("prefill", "decode", "spec_verify", "promote", "sample")

# default phase each sentinel site's dispatches attribute to
SITE_PHASES = {
    "prefill": "prefill",
    "chunk_prefill": "prefill",
    "decode_chunk": "decode",
    "spec_verify": "spec_verify",
}

# ------------------------------------------------- monitoring listener
# jax.monitoring has no per-listener unregister (only a global clear),
# so the process installs EXACTLY ONE duration listener, guarded here;
# every DevProf instance reads the shared recent-durations ring.
_COMPILE_EVENT_SUFFIX = "backend_compile_duration"
_listener_lock = threading.Lock()
_listener_installed = False
# (monotonic_t, duration_s) of recent backend compiles — best-effort
# pairing material for the wrappers' exact per-site counts
_recent_durations: "collections.deque" = collections.deque(maxlen=64)


def _on_event_duration(event: str, duration: float, **kw) -> None:
    if str(event).endswith(_COMPILE_EVENT_SUFFIX):
        _recent_durations.append((time.monotonic(), float(duration)))


def install_compile_listener() -> bool:
    """Install the process-wide compile-duration listener (idempotent).
    Returns True: the installed JAX has ``jax.monitoring`` (the wrappers
    pair its durations with their own exact counts)."""
    global _listener_installed
    with _listener_lock:
        if not _listener_installed:
            jax.monitoring.register_event_duration_secs_listener(
                _on_event_duration)
            _listener_installed = True
    return True


def compile_listener_installed() -> bool:
    return _listener_installed


def _take_recent_duration(max_age_s: float = 60.0) -> Optional[float]:
    """Pop the newest compile duration observed within ``max_age_s`` —
    best-effort pairing (a concurrent engine's compile can steal it;
    counts stay exact either way, only the duration column is
    heuristic)."""
    now = time.monotonic()
    try:
        while _recent_durations:
            t, d = _recent_durations.pop()
            if now - t <= max_age_s:
                return d
    except IndexError:
        pass
    return None


# ------------------------------------------------------- compile ledger
class CompileLedger:
    """Append-only (bounded) record of every attributed XLA compile:
    which call site, when, warmup or steady-state, and the best-effort
    backend duration.  Thread-safe; snapshot() is what incident
    bundles and /statusz carry."""

    def __init__(self, capacity: int = 256):
        self._lock = threading.Lock()
        self._entries: "collections.deque" = collections.deque(
            maxlen=int(capacity))
        self.warmup = 0
        self.steady = 0

    def record(self, site: str, steady: bool, n: int = 1,
               duration_s: Optional[float] = None) -> Dict[str, Any]:
        entry = {
            "site": str(site),
            "t": time.strftime("%Y-%m-%dT%H:%M:%S"),
            "t_monotonic": round(time.monotonic(), 3),
            "phase": "steady" if steady else "warmup",
            "n": int(n),
            "duration_s": (round(float(duration_s), 6)
                           if duration_s is not None else None),
        }
        with self._lock:
            self._entries.append(entry)
            if steady:
                self.steady += n
            else:
                self.warmup += n
        return entry

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "warmup_compiles": self.warmup,
                "steady_state_compiles": self.steady,
                "entries": list(self._entries),
            }


# ----------------------------------------------------- sentinel wrapper
class _SentinelFn:
    """Counting wrapper around one compiled program: detects compiles
    via the jitted function's ``_cache_size()`` delta (exact, per call
    site) and accumulates the site's cost-analysis flops/bytes per
    dispatch.  Transparent for non-jit callables (the ZeRO-Inference
    streamed executors): no cache to watch, dispatch accounting only.
    ``lower`` passes through for the build-time cost analysis."""

    __slots__ = ("jfn", "site", "_dp", "_last_n")

    def __init__(self, jfn, site: str, dp: "DevProf"):
        self.jfn = jfn
        self.site = str(site)
        self._dp = dp
        self._last_n = self._cache_size()

    def _cache_size(self) -> Optional[int]:
        f = getattr(self.jfn, "_cache_size", None)
        if f is None:
            return None
        try:
            return int(f())
        except Exception:
            return None

    # dstpu: hot-path
    def __call__(self, *a, **kw):
        out = self.jfn(*a, **kw)
        if self._last_n is not None:
            # jit compilation is synchronous at call time, so a cache
            # bump is visible the moment the dispatch returns
            n = self._cache_size()
            if n is not None and n != self._last_n:
                self._dp.on_compile(self.site, max(n - self._last_n, 1))
                self._last_n = n
        self._dp.on_dispatch(self.site)
        return out

    def lower(self, *a, **kw):
        return self.jfn.lower(*a, **kw)


# --------------------------------------------------------------- devprof
class DevProf:
    """One engine's device-truth profiler (single-writer: every mutator
    runs on the engine thread except :meth:`profilez`, which the HTTP
    thread serializes through ``_capture_lock``)."""

    def __init__(self, cfg: DevprofConfig, *, registry, tracer=None,
                 dump_dir: str = "/tmp/dstpu_flight",
                 clock=time.perf_counter):
        self.cfg = cfg
        self.enabled = bool(cfg.enabled)
        self.registry = registry
        self.tracer = tracer
        self.dump_dir = str(dump_dir)
        self._clock = clock
        self.ledger = CompileLedger()
        self.steady = False
        self._steady_t: Optional[float] = None
        self._capture_lock = threading.Lock()
        self.captures: List[Dict[str, Any]] = []
        # monitoring is the duration source; absence is fine (wrappers
        # alone count) — record which mode we're in for /statusz
        self.monitoring = install_compile_listener()
        r = registry
        self._c_comp_warm = r.counter(
            "devprof_compiles_warmup",
            "XLA compiles attributed before the first token of the "
            "first request (prewarm/bucket compiles — expected)")
        self._c_comp_steady = r.counter(
            "devprof_compiles_steady",
            "XLA compiles attributed AFTER steady state began — each "
            "one is a shape-discipline contract violation and trips a "
            "steady_state_recompile incident")
        self._c_dev = {
            "prefill": r.counter(
                "devprof_device_seconds_prefill",
                "sampled device-completion seconds of prefill "
                "dispatches (block_until_ready deltas on the "
                "devprof.sample_rate cadence)"),
            "decode": r.counter(
                "devprof_device_seconds_decode",
                "sampled device-completion seconds of decode-chunk "
                "dispatches"),
            "spec_verify": r.counter(
                "devprof_device_seconds_spec_verify",
                "sampled device-completion seconds of speculative "
                "verify sweeps"),
            "promote": r.counter(
                "devprof_device_seconds_promote",
                "sampled device-completion seconds of KV-tier promote "
                "scatters"),
            "sample": r.counter(
                "devprof_device_seconds_sample",
                "sampled device-completion seconds of batched "
                "boundary-sampling fetches"),
        }
        self._c_sampled = r.counter(
            "devprof_sampled_dispatches",
            "dispatches that paid the sampled block_until_ready sync "
            "(the devprof.sample_rate numerator)")
        self._g_gap = r.gauge(
            "devprof_host_device_gap_seconds",
            "EWMA of device-completion wait observed AFTER the host "
            "dispatch returned — how far the async dispatch queue "
            "runs ahead of the host clock (why host timings lie)")
        self._g_mfu = r.gauge(
            "devprof_mfu",
            "model flops utilization: cost-analysis flops dispatched "
            "per wall second / device peak flops")
        self._g_mbu = r.gauge(
            "devprof_mbu",
            "memory bandwidth utilization: cost-analysis bytes "
            "accessed per wall second / device peak HBM bandwidth")
        self._c_flops = r.counter(
            "devprof_flops_total",
            "cost-analysis flops dispatched (per-site XLA estimate x "
            "dispatch count — the MFU numerator)")
        self._c_bytes = r.counter(
            "devprof_bytes_total",
            "cost-analysis bytes accessed (per-site XLA estimate x "
            "dispatch count — the MBU numerator)")
        # deterministic per-phase stride: every round(1/rate)-th
        # dispatch pays the sync — no RNG on the hot path
        self._stride = (int(round(1.0 / cfg.sample_rate))
                        if cfg.sample_rate > 0 else 0)
        self._phase_n = {p: 0 for p in PHASES}
        self._costs: Dict[str, Dict[str, float]] = {}
        self._gap_ewma: Optional[float] = None
        # roofline tick state (counter deltas over wall intervals)
        self._tick_t: Optional[float] = None
        self._tick_flops = 0.0
        self._tick_bytes = 0.0
        self._probe_seen = 0            # incident-probe cursor
        self.peak_flops = device_peak_flops()
        self.peak_bw = device_peak_bandwidth()

    # --------------------------------------------------------- wiring
    def wrap(self, site: str, jfn):
        """Sentinel-wrap one compiled program (identity for None)."""
        if jfn is None:
            return None
        return _SentinelFn(jfn, site, self)

    def register_cost(self, site: str, flops: float,
                      bytes_accessed: float) -> None:
        self._costs[str(site)] = {"flops": float(flops),
                                  "bytes_accessed": float(bytes_accessed)}

    def cost_analyze(self, site: str, jfn, *args, **kw) -> bool:
        """Build-time roofline pass: lower+compile ``jfn`` at the
        given (abstract) args and record the compiler's flops/bytes
        estimate for ``site``.  Best-effort — a backend without
        ``cost_analysis`` (or a non-jit executor with no ``lower``)
        just leaves the site uncosted."""
        if not self.cfg.cost_analysis:
            return False
        lower = getattr(jfn, "lower", None)
        if lower is None:
            return False
        try:
            from deepspeed_tpu.profiler import xla_cost_analysis_lowered

            cost = xla_cost_analysis_lowered(lower(*args, **kw))
        except Exception:
            return False
        if not cost:
            return False
        self.register_cost(site, cost.get("flops", 0.0),
                           cost.get("bytes_accessed", 0.0))
        return True

    # ------------------------------------------------------- sentinel
    def mark_steady(self) -> None:
        """Flip warmup → steady state (the engine calls this at the
        first token of the first request).  From here every attributed
        compile is a contract violation."""
        if not self.steady:
            self.steady = True
            self._steady_t = time.monotonic()

    def on_compile(self, site: str, n: int = 1) -> None:
        """A sentinel wrapper detected ``n`` fresh compiles at
        ``site``: ledger + counters + an ``xla_compile`` event on its
        own Chrome track (steady-state ones are flagged)."""
        dur = _take_recent_duration() if self.monitoring else None
        entry = self.ledger.record(site, self.steady, n, dur)
        if self.steady:
            self._c_comp_steady.inc(n)
            # on the profiler's clock too: a capture shows WHICH step
            # recompiled, and at which site
            telemetry_mark(f"{self.registry.namespace}/xla_compile",
                           site=site, n=n)
        else:
            self._c_comp_warm.inc(n)
        if self.tracer is not None and self.tracer.enabled:
            self.tracer.event("xla_compile", attrs={
                "site": site, "n": n,
                "steady": self.steady,
                "duration_s": entry["duration_s"]})

    # dstpu: hot-path
    def on_dispatch(self, site: str) -> None:
        """Per-dispatch roofline accounting: add the site's one-time
        cost-analysis estimate to the flops/bytes counters (two float
        adds; uncosted sites cost one dict miss)."""
        c = self._costs.get(site)
        if c is not None:
            self._c_flops.inc(c["flops"])
            self._c_bytes.inc(c["bytes_accessed"])

    # ------------------------------------------------------- sampling
    # dstpu: hot-path
    def should_sample(self, phase: str) -> bool:
        """Deterministic stride gate: True on every
        ``round(1/sample_rate)``-th dispatch of ``phase``."""
        if self._stride == 0:
            return False
        n = self._phase_n[phase] + 1
        self._phase_n[phase] = n
        return n % self._stride == 0

    # dstpu: hot-path
    def observe_device(self, phase: str, value) -> float:
        """Time a sampled dispatch's device completion: the wait from
        host-dispatch-return to ready IS the host-vs-device gap the
        gauge tracks."""
        t0 = self._clock()
        # dstpu: host-sync-ok: sampled devprof attribution — one
        # block_until_ready per round(1/sample_rate) dispatches of
        # this phase, the module's documented measurement sync
        jax.block_until_ready(value)
        dt = self._clock() - t0
        self.record_device(phase, dt, gap=dt)
        return dt

    # dstpu: hot-path
    def record_device(self, phase: str, dev_s: float,
                      gap: Optional[float] = None) -> None:
        """Record an already-measured device-time sample (sites whose
        existing host sync brackets the device work — the boundary
        sample fetch — time themselves and report here)."""
        self._c_dev[phase].inc(dev_s)
        self._c_sampled.inc()
        if gap is not None:
            e = self._gap_ewma
            self._gap_ewma = gap if e is None else 0.8 * e + 0.2 * gap
            self._g_gap.set(self._gap_ewma)
        if self.tracer is not None and self.tracer.enabled:
            self.tracer.event("devprof_sample", attrs={
                "devprof_phase": phase, "dev_s": round(dev_s, 6)})

    # ------------------------------------------------------- roofline
    def tick(self, now: Optional[float] = None) -> None:
        """Exporter tick hook: turn flops/bytes counter deltas over
        the wall interval into live MFU/MBU gauges.  Rate-limited
        internally (~2/s) so the exporter-less inline path can call it
        every step without shrinking dt toward noise."""
        now = time.monotonic() if now is None else now
        if self._tick_t is not None and now - self._tick_t < 0.5:
            return
        f, b = self._c_flops.value, self._c_bytes.value
        if self._tick_t is not None:
            dt = now - self._tick_t
            if dt > 0:
                self._g_mfu.set((f - self._tick_flops) / dt /
                                self.peak_flops)
                self._g_mbu.set((b - self._tick_bytes) / dt /
                                self.peak_bw)
        self._tick_t, self._tick_flops, self._tick_bytes = now, f, b

    # -------------------------------------------------------- capture
    def capture(self, duration_s: float) -> Dict[str, Any]:
        """On-demand ``jax.profiler`` device trace under ``dump_dir``,
        capped at ``cfg.capture_max_s``.  Serialized: a second capture
        request while one runs returns an error instead of corrupting
        the profiler session."""
        d = min(float(duration_s), self.cfg.capture_max_s)
        if d <= 0:
            return {"error": "capture_s must be positive"}
        # dstpu: lock-ok: non-blocking try-acquire — a concurrent
        # capture request must get an error, never queue behind a
        # running profiler session (with-scoping cannot express this)
        if not self._capture_lock.acquire(blocking=False):
            return {"error": "a capture is already running"}
        try:
            path = os.path.join(
                self.dump_dir,
                f"devprof_capture_{os.getpid()}_"
                f"{len(self.captures) + 1}")
            os.makedirs(path, exist_ok=True)
            t0 = time.monotonic()
            jax.profiler.start_trace(path)
            try:
                time.sleep(d)
            finally:
                jax.profiler.stop_trace()
            ref = {
                "path": path,
                "t": time.strftime("%Y-%m-%dT%H:%M:%S"),
                "requested_s": round(float(duration_s), 3),
                "captured_s": round(time.monotonic() - t0, 3),
            }
            self.captures.append(ref)
            if self.tracer is not None and self.tracer.enabled:
                self.tracer.event("profile_capture", attrs=dict(ref))
            return ref
        except Exception as e:
            return {"error": repr(e)}
        finally:
            self._capture_lock.release()

    def profilez(self, capture_s=None) -> Dict[str, Any]:
        """The ``/profilez`` provider: without ``capture_s`` return
        the devprof status block; with it run a bounded device-trace
        capture and return its reference."""
        if capture_s is None:
            return self.statusz_block()
        try:
            d = float(capture_s)
        except (TypeError, ValueError):
            return {"error": f"invalid capture_s {capture_s!r}"}
        # copy before annotating: capture() stored the same ref dict in
        # self.captures, and the status block embeds that list — adding
        # the block to the ORIGINAL would make the document circular
        out = dict(self.capture(d))
        out["devprof"] = self.statusz_block()
        return out

    # ----------------------------------------------------------- read
    def statusz_block(self) -> Dict[str, Any]:
        led = self.ledger.snapshot()
        dev = {p: round(float(self._c_dev[p].value), 6) for p in PHASES}
        return {
            "enabled": True,
            "steady": self.steady,
            "monitoring": self.monitoring,
            "sample_rate": self.cfg.sample_rate,
            "compiles_warmup": led["warmup_compiles"],
            "compiles_steady": led["steady_state_compiles"],
            "device_seconds": dev,
            "host_device_gap_s": (round(self._gap_ewma, 6)
                                  if self._gap_ewma is not None
                                  else None),
            "mfu": round(float(self._g_mfu.value), 6),
            "mbu": round(float(self._g_mbu.value), 6),
            "flops_total": float(self._c_flops.value),
            "bytes_total": float(self._c_bytes.value),
            "peak_flops": self.peak_flops,
            "peak_hbm_bw": self.peak_bw,
            "cost_sites": {k: dict(v) for k, v in self._costs.items()},
            "captures": list(self.captures)[-4:],
        }

    def bundle_info(self) -> Dict[str, Any]:
        """What incident bundles attach: the full compile ledger plus
        recent capture references."""
        return {
            "compile_ledger": self.ledger.snapshot(),
            "captures": list(self.captures)[-4:],
        }

    def incident_probe(self):
        """IncidentManager probe: trip once per NEW steady-state
        compile batch (cursor-based — warmup compiles never trip)."""
        n = self.ledger.steady
        if n > self._probe_seen:
            fresh = n - self._probe_seen
            self._probe_seen = n
            led = self.ledger.snapshot()
            return "steady_state_recompile", {
                "phase": "steady_state_recompile",
                "new_compiles": fresh,
                "steady_state_compiles": n,
                "recent": led["entries"][-4:],
            }
        return None


class _NullDevProf:
    """Shared no-op stand-in when the block is off: wrap() is the
    identity, every gate is False, every read surface is the disabled
    block."""

    enabled = False
    steady = False
    monitoring = False
    captures: List[Dict[str, Any]] = []

    def wrap(self, site, jfn):
        return jfn

    def register_cost(self, site, flops, bytes_accessed):
        pass

    def cost_analyze(self, site, jfn, *args, **kw):
        return False

    def mark_steady(self):
        pass

    def on_compile(self, site, n=1):
        pass

    def on_dispatch(self, site):
        pass

    def should_sample(self, phase):
        return False

    def observe_device(self, phase, value):
        return 0.0

    def record_device(self, phase, dev_s, gap=None):
        pass

    def tick(self, now=None):
        pass

    def capture(self, duration_s):
        return {"error": "devprof disabled"}

    def profilez(self, capture_s=None):
        return {"enabled": False}

    def statusz_block(self):
        return {"enabled": False}

    def bundle_info(self):
        return {}

    def incident_probe(self):
        return None


NULL_DEVPROF = _NullDevProf()

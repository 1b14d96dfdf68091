"""Device-truth observability: the build ledger and the compile sentinel
(no reference analogue; the fifth observability pillar next to
telemetry/tracing/history/incidents).

The serving engine's prewarm/bucket-pad discipline exists solely to
keep XLA compiles out of TTFT, and a build is mostly the making ready
of programs.  This module says what each program cost, and holds the
build to its contract:

- **Build ledger**: one process-wide ``jax.monitoring`` listener
  (:func:`install_compile_listener`, idempotent; installed when this
  module is imported) takes JAX's own duration events by ``fun_name``
  and appends to the bounded :data:`BUILD_LEDGER` one entry a program
  made ready: ``{program, t_end, trace_s, lower_s, cache_load_s,
  compile_s, cache_hit, steady}``, and ``lower_faults``
  (:func:`_faults`).  A program is what ends in one
  ``backend_compile_duration``; before it, on the same thread, come its
  ``jaxpr_trace_duration``, its ``jaxpr_to_mlir_module_duration`` and,
  from the persistent cache, ``cache_hits`` or nothing.  The callbacks
  run *after* each phase: no frame of theirs is under the lowering
  loop.  Programs the project did not name (``dstpu_*``) go to one
  aggregate.  ``t_end`` is ``time.perf_counter()``.  The ledger hangs
  on no registry; :class:`BuildCounters` mirrors one engine's build
  into its registry where that is enabled.

- **Compile sentinel**: counting wrappers at the project's jit call
  sites detect a compile by the jitted function's ``_cache_size()`` —
  cheap, exact per site — and claim the ledger's entry of that name
  made on their own thread, so a site's seconds are its own.  Counted
  warmup vs **steady-state** (post first-token of the first request),
  and emitted as ``xla_compile`` flight-recorder events on their own
  Chrome track.  A steady-state recompile is a **contract violation**:
  the incident probe trips a ``steady_state_recompile`` bundle and the
  bench gate pins ``steady_state_recompiles == 0``.

- **Step ledger**: the steady state's record, beside the build's.
  :data:`STEP_LEDGER` keeps one row a ``ServingEngine.step()`` for the
  whole run (a capture holds seconds of it): the step's ordinal and its
  two instants on ``time.perf_counter``, the seconds of each phase its
  spans timed, what it dispatched by site (programs, rows, real
  tokens), and the *exposed* seconds: the stretches inside the step in
  which the device provably had nothing queued, from the return of a
  device-to-host fetch to the next dispatch call.  An engine with
  telemetry on writes through its :class:`StepRow`; with telemetry off
  nothing is written and no clock is read.

On-demand device traces: ``/profilez?capture_s=`` runs a bounded
``jax.profiler`` capture under ``tracing.dump_dir``; the capture
reference and the engine's compiles ride incident bundles.
"""

from __future__ import annotations

import collections
import itertools
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax

try:                            # Linux: a thread's own page faults
    from resource import RUSAGE_THREAD, getrusage
except ImportError:             # pragma: no cover
    getrusage = None

from deepspeed_tpu.config import DevprofConfig
from deepspeed_tpu.telemetry import mark as telemetry_mark

# ------------------------------------------------- monitoring listener
# jax.monitoring has no per-listener unregister (only a global clear),
# so the process installs its listeners EXACTLY ONCE, guarded here.
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
NAMED_PREFIX = "dstpu_"         # the programs the project named
_MAX_TRACES = 1 << 16           # traces kept a thread between programs
_INNER_KEPT = 10                # inner traces kept an entry, by seconds
_listener_lock = threading.Lock()
_listener_installed = False


def _faults() -> int:
    """Minor page faults of the calling thread so far.  A call that
    crosses the end of a 16 KiB chunk of CPython's frame stack maps a
    chunk in, and its return maps it out again: where the frames under
    a lowering end decides how often its recursion pays that (40 or
    1,900 faults a program, and seconds with them: PERF.md 6, PR 37)."""
    return getrusage(RUSAGE_THREAD).ru_minflt if getrusage else 0


class _Thread(threading.local):
    """What one thread has seen since its last program: the phases of a
    program arrive in order on the thread that makes it ready."""

    def __init__(self):
        # (name, seconds, t_end, the thread's page faults at t_end)
        self.traces: List[Tuple[str, float, float, int]] = []
        # (program, lower_s, trace_s, inner, lowering, lower_faults)
        self.lowered = None
        self.hit = False
        self.mirror: Optional["BuildCounters"] = None
        # named entries made ready here: a sentinel's or a span's to claim
        self.made: "collections.deque" = collections.deque(maxlen=32)
        # and what the program being traced said of itself (_on_build_word)
        self.named, self.notes = 0, {}
        self.sums = [0, 0, 0.0, 0.0, 0.0, 0.0]  # as BuildLedger.sums


_tl = _Thread()


def _program(fun_name) -> str:
    """``jit(dstpu_prefill)`` -> ``dstpu_prefill`` (a trace's bare name)."""
    name = str(fun_name)
    if name.endswith(")") and "(" in name:
        return name[name.index("(") + 1:-1]
    return name


def _largest(seconds: Dict[str, float]) -> Dict[str, float]:
    top = sorted(seconds.items(), key=lambda kv: -kv[1])[:_INNER_KEPT]
    return {k: round(v, 6) for k, v in top}


def _lowered(program: str, lower_s: float):
    """A lowering ended: find its trace among the thread's.  The
    outermost trace ends last, so it is the last one of the program's
    name; traces that ended inside its span are its inner functions
    (their seconds are inside its own), and traces after it ran while
    lowering (a threefry expansion, a kernel's body): inside ``lower_s``."""
    tl = _tl
    traces, tl.traces = tl.traces, []
    at = len(traces) - 1
    while at >= 0 and traces[at][0] != program:
        at -= 1
    if at < 0:
        return program, lower_s, 0.0, {}, {}, 0
    _, trace_s, t_end, faults = traces[at]
    began = t_end - trace_s - 1e-4
    inner: Dict[str, float] = {}
    for name, s, t, _ in reversed(traces[:at]):
        if t < began:
            break
        inner[name] = inner.get(name, 0.0) + s
    lowering: Dict[str, float] = {}
    for name, s, _, _ in traces[at + 1:]:
        lowering[name] = lowering.get(name, 0.0) + s
    return (program, lower_s, trace_s, _largest(inner),
            _largest(lowering), _faults() - faults)


def _on_event_duration(event: str, duration: float, **kw) -> None:
    if event == _TRACE_EVENT:           # thousands a program: first
        traces = _tl.traces
        if len(traces) >= _MAX_TRACES:  # traced, never lowered
            del traces[:]
        name = kw.get("fun_name")
        # a named program's lowering counts its page faults from here
        traces.append((name, duration, time.perf_counter(),
                       _faults() if str(name).startswith(NAMED_PREFIX)
                       else 0))
    elif event == _LOWER_EVENT:
        _tl.lowered = _lowered(_program(kw.get("fun_name")),
                               float(duration))
    elif event == _COMPILE_EVENT:
        BUILD_LEDGER.made_ready(_program(kw.get("fun_name")),
                                float(duration))


def _on_event(event: str, **kw) -> None:
    # too small for the cache, a program reports neither hit nor miss
    if event == _CACHE_HIT_EVENT:
        _tl.hit = True


def install_compile_listener() -> bool:
    """Install the process-wide build listeners (idempotent)."""
    global _listener_installed
    with _listener_lock:
        if not _listener_installed:
            jax.monitoring.register_event_duration_secs_listener(
                _on_event_duration)
            jax.monitoring.register_event_listener(_on_event)
            _listener_installed = True
    return True


# --------------------------------------------------------- build ledger
class BuildLedger:
    """Bounded record of every program the process made ready: which,
    when (``perf_counter``), its trace, lowering, cache-load and compile
    seconds, from the cache or not, warmup or steady-state.  Programs
    named ``dstpu_*`` keep an entry each; the rest (eager fills, a
    caller's own jits) are one aggregate.  Sums never drop; entries are
    bounded.  Thread-safe; snapshot() is what /statusz carries."""

    def __init__(self, capacity: int = 256, other_capacity: int = 1024):
        self._lock = threading.Lock()
        self._entries: "collections.deque" = collections.deque(
            maxlen=int(capacity))
        # (t_end, program, seconds) of the programs without an entry
        self._other: "collections.deque" = collections.deque(
            maxlen=int(other_capacity))
        self.other_programs = 0
        self.other_seconds = 0.0
        # programs, cache misses, trace, lower, cache-load, compile
        self.sums = [0, 0, 0.0, 0.0, 0.0, 0.0]

    def made_ready(self, program: str, seconds: float) -> None:
        """The listener's: ``program`` ended its backend compile on this
        thread, ``seconds`` of it (on a cache hit the retrieval and
        load, on a miss the compiler's)."""
        tl = _tl
        low, tl.lowered = tl.lowered, None
        hit, tl.hit = tl.hit, False
        if low is None or low[0] != program:
            low = (program, 0.0, 0.0, {}, {}, 0)
        _, lower_s, trace_s, inner, lowering, lower_faults = low
        load_s, compile_s = (seconds, 0.0) if hit else (0.0, seconds)
        parts = (1, 0 if hit else 1, trace_s, lower_s, load_s, compile_s)
        t_end = time.perf_counter()
        entry = None
        if program.startswith(NAMED_PREFIX):
            entry = {
                "program": program,
                "t_end": t_end,
                "trace_s": round(trace_s, 6),
                "lower_s": round(lower_s, 6),
                "cache_load_s": round(load_s, 6),
                "compile_s": round(compile_s, 6),
                "cache_hit": hit,
                "steady": False,
                "inner_trace_s": inner,
                "lowering_trace_s": lowering,
                # the thread's minor page faults while it lowered
                "lower_faults": lower_faults,
                # a sentinel's and a build span's, when one claims it
                "site": None, "span": None, "run_s": None,
            }
            tl.made.append(entry)
            tl.named += 1
        total = trace_s + lower_s + seconds
        with self._lock:
            self.sums = [a + b for a, b in zip(self.sums, parts)]
            if entry is not None:
                self._entries.append(entry)
            else:
                self._other.append((t_end, program, total))
                self.other_programs += 1
                self.other_seconds += total
        tl.sums = [a + b for a, b in zip(tl.sums, parts)]
        if tl.mirror is not None:
            tl.mirror.add(parts)

    def claim(self, program: Optional[str], n: int, site: str,
              steady: bool) -> List[Dict[str, Any]]:
        """The newest ``n`` unclaimed entries of ``program`` made ready
        on THIS thread, now the ``site``'s: exact, because a sentinel
        asks on the thread its dispatch compiled on, as it returns."""
        out: List[Dict[str, Any]] = []
        for e in reversed(_tl.made):
            if len(out) >= n:
                break
            if e["program"] == program and e["site"] is None:
                e["site"], e["steady"] = site, bool(steady)
                out.append(e)
        return out[::-1]

    def mark(self) -> Tuple:
        """Now, and this thread's sums so far: what :meth:`since` takes."""
        return (time.perf_counter(), *_tl.sums)

    def since(self, mark: Tuple) -> str:
        """What this thread made ready since ``mark``, for a log line."""
        n, miss, tr, lo, ld, co = (
            b - a for a, b in zip(mark[1:], _tl.sums))
        return ("%d programs (%d compiled) in %.1fs: trace %.2f, lower "
                "%.2f, cache load %.2f, compile %.2f"
                % (n, miss, time.perf_counter() - mark[0], tr, lo, ld, co))

    def snapshot(self, rows: bool = False,
                 last: Optional[int] = None) -> Dict[str, Any]:
        """JSON-safe.  ``rows``: the unnamed programs one by one too
        (``[t_end, program, seconds]``), for a reader that splits them
        at an instant.  ``last``: no more than that many entries."""
        with self._lock:
            n, miss, tr, lo, ld, co = self.sums
            names: Dict[str, List[float]] = {}
            for _, program, seconds in self._other:     # the rows kept
                row = names.setdefault(program, [0.0, 0])
                row[0] += seconds
                row[1] += 1
            top = sorted(names.items(), key=lambda kv: -kv[1][0])[:5]
            other = {
                "programs": self.other_programs,
                "seconds": round(self.other_seconds, 6),
                "top": [[k, round(v[0], 6), v[1]] for k, v in top],
            }
            if rows:
                other["rows"] = [list(r) for r in self._other]
            return {
                "programs": n, "cache_misses": miss,
                "trace_s": round(tr, 6), "lower_s": round(lo, 6),
                "cache_load_s": round(ld, 6), "compile_s": round(co, 6),
                "entries": [dict(e) for e in
                            list(self._entries)[-(last or 0):]],
                "other": other,
            }


BUILD_LEDGER = BuildLedger()


# the ledger's sums, in their order, as one engine's registry has them
_BUILD_COUNTERS = (
    ("build_programs", "programs made ready while this engine was built "
     "(from the compile cache or by the compiler)"),
    ("build_cache_misses", "of build_programs, those the compiler built: "
     "the persistent cache had none, or keeps none so small"),
    ("build_trace_seconds", "tracing the build's programs to jaxprs"),
    ("build_lower_seconds", "lowering the build's jaxprs to StableHLO"),
    ("build_cache_load_seconds", "fetching and loading the build's "
     "programs from the persistent compile cache"),
    ("build_compile_seconds", "the compiler's seconds on the build's "
     "cache misses"),
)


class BuildCounters:
    """One engine's build in its registry: the programs made ready on
    the building thread between :meth:`attach` and :meth:`built`,
    advanced by the listener's own callback; ``build_seconds`` is the
    wall time attached.  With the registry disabled nothing is
    attached: the process-wide ledger has the build all the same."""

    def __init__(self, registry):
        from deepspeed_tpu import IMPORT_SECONDS

        r = registry
        self._on = r.enabled
        self._c = [r.counter(name, text) for name, text in _BUILD_COUNTERS]
        self._g_build = r.gauge(
            "build_seconds",
            "wall time of the engine's constructor (training: and of "
            "its first step)")
        r.gauge("package_import_seconds",
                "first to last line of deepspeed_tpu/__init__.py"
                ).set(IMPORT_SECONDS)
        self._seconds = 0.0
        self.attach()

    def attach(self) -> None:
        self._t0 = time.perf_counter()
        if self._on:
            _tl.mirror = self

    def add(self, parts) -> None:
        for c, v in zip(self._c, parts):
            c.inc(v)

    def built(self) -> None:
        self._seconds += time.perf_counter() - self._t0
        self._g_build.set(self._seconds)
        if _tl.mirror is self:
            _tl.mirror = None


# ---------------------------------------------------------- step ledger
# a step's phases, in the order its spans are entered (serving_<phase>)
STEP_PHASES = ("admit", "prefill", "boundary", "grow_pages", "upload",
               "inputs", "dispatch", "token_sync", "append")
# where a step hands the device a program: a whole-prompt prefill, a
# prompt chunk, the decode chunk with its tokens up from the host, the
# speculative verify sweep, and the decode chunk dispatched AHEAD: before
# the tokens of the one before it were read, which it takes on the device
STEP_SITES = ("prefill", "chunk", "decode", "sweep", "decode_ahead")
# the longest window at the shortest step: ~110 steps/s for 74 s
_STEP_ROWS = 1 << 13


class StepLedger:
    """Bounded record of every serving step the process made with
    telemetry on: a preallocated ring of rows (the oldest dropped), each
    stored once as its step ends, raw, from values the step already
    had: the hot path pays a tuple and a list store, and everything a
    reader wants (phases by name, idle stretches by phase, the running
    sums) is worked out by :meth:`snapshot`.  Process-wide, as the build
    ledger, and on the same clock (``time.perf_counter``), so a reader
    cuts a window at two instants exactly and reads it after the engine
    is gone.  :meth:`snapshot` is what ``/statusz`` carries under
    ``steps``."""

    def __init__(self, capacity: int = _STEP_ROWS):
        self._lock = threading.Lock()           # snapshot's alone
        self._ring: List[Optional[Tuple]] = [None] * int(capacity)
        self._ordinals = itertools.count()
        self._folded = -1       # the newest ordinal in the sums
        self._unseen = 0        # rows the ring dropped before a fold
        self.steps = 0
        # seconds: inside steps, exposed, between steps, in the tick
        self.sums = [0.0, 0.0, 0.0, 0.0]
        self.programs = {site: [0, 0, 0] for site in STEP_SITES}

    @staticmethod
    def _as_read(row: Tuple) -> Dict[str, Any]:
        """A row as a reader takes it: the phases a step entered by
        name, in seconds, and its idle stretches cut to the step and
        given to the phases they fell in."""
        (n, t0, t1, last_end, tick0, tick1, k, queue, admitted,
         preempted, boundary, since, dispatched, idle, spans) = row
        # a span the step did not enter still holds an earlier step's
        entered = [(name, p0, p1) for name, (p0, p1)
                   in zip(STEP_PHASES, spans) if p0 >= t0 and p1 >= p0]
        if since is not None:   # it ended with nothing queued
            idle = idle + [(since, t1)]
        # a step that began drained is idle from its first instant
        idle = [(max(a, t0), min(b, t1)) for a, b in idle]
        idle = [(a, b) for a, b in idle if b > a]
        exposed = {}
        for name, p0, p1 in entered:
            inside = sum(max(min(b, p1) - max(a, p0), 0.0)
                         for a, b in idle)
            if inside > 0:
                exposed[name] = inside
        programs = {site: [0, 0, 0] for site in STEP_SITES}
        for site, rows, tokens in dispatched:
            p = programs[site]
            p[0] += 1
            p[1] += rows
            p[2] += tokens
        return {
            "n": n, "t0": t0, "t1": t1,
            "between_s": 0.0 if last_end is None else t0 - last_end,
            "tick_s": tick1 - tick0, "k": k, "queue": queue,
            "admitted": admitted, "preempted": preempted,
            "boundary_tokens": boundary, "drained": since is not None,
            "programs": programs,
            "phases": {name: p1 - p0 for name, p0, p1 in entered},
            "exposed_s": sum(b - a for a, b in idle),
            "exposed": exposed,
            "idle": [list(i) for i in idle],
        }

    def _fold(self, fresh: List[Dict[str, Any]], oldest: int) -> None:
        """The rows no snapshot has seen go into the running sums (the
        hot path keeps none): whoever reads folds.  A reader that comes
        less often than the ring turns misses the ordinals between the
        newest it folded and the ``oldest`` kept: ``unseen``."""
        if not fresh:
            return
        self._unseen += max(oldest - 1 - self._folded, 0)
        self._folded = fresh[-1]["n"]
        self.steps += len(fresh)
        sums = self.sums
        for r in fresh:
            sums[0] += r["t1"] - r["t0"]
            sums[1] += r["exposed_s"]
            sums[2] += r["between_s"]
            sums[3] += r["tick_s"]
            for site, p in r["programs"].items():
                total = self.programs[site]
                for i in range(3):
                    total[i] += p[i]

    def snapshot(self, last: Optional[int] = None) -> Dict[str, Any]:
        """JSON-safe: the running sums and the rows kept, oldest first
        (``last``: no more than that many, the newest).  A row: ``n``,
        ``t0``, ``t1``; ``between_s`` (from the end of this engine's
        last ``step()`` call to ``t0``: the caller's time) and
        ``tick_s`` (the control plane after ``t1``); ``k``, ``queue``
        (as the step began), ``admitted``, ``preempted``,
        ``boundary_tokens``; ``programs`` by site ``[programs, rows,
        real tokens]`` (decode: ``[1, max_batch, live slots]``);
        ``phases`` in seconds; ``exposed_s`` and ``exposed`` by phase
        (``idle``: the stretches themselves); ``drained``: the step
        ended with nothing queued, so the time to the next row's ``t0``
        is the device's idle time too.  The sums (``steps``,
        ``step_s``, ``exposed_s``, ``between_s``, ``tick_s``,
        ``programs``) hold every row a snapshot has seen."""
        with self._lock:
            raw = sorted((r for r in self._ring if r is not None),
                         key=lambda r: r[0])
            # /statusz asks for one row a second: it works out that
            # one and the rows since its last call, not the ring's 8,192
            first = raw[-last][0] if last and len(raw) > last else 0
            read = [self._as_read(r) for r in raw
                    if r[0] >= first or r[0] > self._folded]
            self._fold([r for r in read if r["n"] > self._folded],
                       raw[0][0] if raw else 0)
            rows = [r for r in read if r["n"] >= first]
            sums = self.sums
            return {
                "steps": self.steps, "unseen": self._unseen,
                "step_s": round(sums[0], 6),
                "exposed_s": round(sums[1], 6),
                "between_s": round(sums[2], 6),
                "tick_s": round(sums[3], 6),
                "programs": {k: list(v)
                             for k, v in self.programs.items()},
                "rows": rows,
            }


STEP_LEDGER = StepLedger()


class StepRow:
    """The row one engine has open, and its pen in the ledger.  The
    step's own code adds to the counts as it goes; the hooks are
    :meth:`dispatch`, called before a program is handed to the device
    (an idle stretch ends there: the one clock read), :meth:`edge` as
    that call returns (the program is queued: a capture shows where),
    and ``drained``, which the engine sets to the instant a
    device-to-host fetch returned: everything dispatched before it has
    run, so until the next dispatch the device has nothing queued.
    ``drained`` outlives the step: a step that ends on a fetch leaves
    the next one idle from its first instant.  Nothing here is worked
    out: a step's host time is the tail cell's latency, and a row is
    stored raw."""

    __slots__ = ("_ring", "_next", "_mark", "_step", "_tick", "_phases",
                 "n", "queue", "admitted", "preempted", "boundary",
                 "dispatched", "idle", "drained", "_end")

    def __init__(self, namespace: str, step, tick, phases,
                 ledger: StepLedger = STEP_LEDGER):
        """``step``, ``tick`` and ``phases`` (in :data:`STEP_PHASES`'
        order) are the engine's spans: they hold the clock readings a
        row is made of."""
        self._ring = ledger._ring
        self._next = ledger._ordinals.__next__
        self._mark = f"{namespace}/dispatch"
        self._step, self._tick, self._phases = step, tick, tuple(phases)
        self.drained: Optional[float] = None    # unknown until a fetch
        self._end: Optional[float] = None
        self.n = -1             # no step yet
        self.queue = self.admitted = self.preempted = self.boundary = 0
        self.dispatched: List[Tuple[str, int, int]] = []
        self.idle: List[Tuple[float, float]] = []

    # dstpu: hot-path
    def begin(self, queue: int) -> int:
        """A step begins: its process-wide ordinal (what
        ``dstpu/serving_step`` is annotated with)."""
        self.n = n = self._next()
        self.queue = queue
        return n

    # dstpu: hot-path
    def dispatch(self, site: str, rows: int, tokens: int) -> None:
        """Before the call that hands the device a program of ``rows``
        rows, ``tokens`` of them real."""
        since = self.drained
        if since is not None:
            self.idle.append((since, time.perf_counter()))
            self.drained = None
        self.dispatched.append((site, rows, tokens))

    # dstpu: hot-path
    def edge(self) -> None:
        """As that call returns: the device has the program, the host
        is about to wait for it or to prepare the next one."""
        site, rows, tokens = self.dispatched[-1]
        telemetry_mark(self._mark, site=site, rows=rows, tokens=tokens)

    # dstpu: hot-path
    def end(self, k: int) -> None:
        """The step and its tick ended: the row is stored, and what is
        counted from here on (a test's or a router's ``_admit_one``
        between steps) falls to the next."""
        n, step, tick = self.n, self._step, self._tick
        self._ring[n % len(self._ring)] = (
            n, step.t0, step.t1, self._end, tick.t0, tick.t1, k,
            self.queue, self.admitted, self.preempted, self.boundary,
            self.drained, self.dispatched, self.idle,
            [(sp.t0, sp.t1) for sp in self._phases])
        self._end = tick.t1
        self.admitted = self.preempted = self.boundary = 0
        self.dispatched, self.idle = [], []


class ProgramSpan:
    """``with span(site, end=128):`` around ONE dispatch that makes a
    program ready: the telemetry span it was built on (annotated with
    the site and the shape word), and on exit the entry that dispatch
    made gets the word and ``run_s``, the span's wall time less every
    part the ledger timed: the program's first run plus dispatch.  A
    context manager, so no frame of it is under the dispatch."""

    __slots__ = ("_span", "_site", "_word", "_t0", "_named", "_timed")

    def __init__(self, span):
        self._span = span
        self._site, self._word = "", {}

    def __call__(self, site: str, **word):
        self._site, self._word = site, word
        return self

    def __enter__(self):
        tl = _tl
        tl.notes, self._named, self._timed = {}, tl.named, sum(tl.sums[2:])
        self._span(site=self._site, **self._word).__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        wall = time.perf_counter() - self._t0
        self._span.__exit__(*exc)
        tl = _tl
        if tl.named > self._named:
            e = tl.made[-1]
            e["span"] = " ".join([self._site] + [
                f"{k}={v}" for k, v in {**self._word, **tl.notes}.items()])
            e["run_s"] = round(
                max(wall - (sum(tl.sums[2:]) - self._timed), 0.0), 6)
        return False


# ----------------------------------------------------- sentinel wrapper
class _SentinelFn:
    """Counting wrapper around one compiled program: detects compiles
    via the jitted function's ``_cache_size()`` delta (exact, per call
    site).  Transparent for non-jit callables (the ZeRO-Inference
    streamed executors): no cache to watch.  ``lower`` passes through
    (``jfn.lower(...)`` is the AOT door)."""

    __slots__ = ("jfn", "site", "_dp", "_last_n", "_program")

    def __init__(self, jfn, site: str, dp: "DevProf"):
        self.jfn = jfn
        self.site = str(site)
        self._dp = dp
        self._program = getattr(jfn, "__name__", None)
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
                self._dp.on_compile(self.site, max(n - self._last_n, 1),
                                    self._program)
                self._last_n = n
        return out

    def lower(self, *a, **kw):
        return self.jfn.lower(*a, **kw)


# --------------------------------------------------------------- devprof
class DevProf:
    """One engine's compile sentinel (single-writer: every mutator
    runs on the engine thread except :meth:`profilez`, which the HTTP
    thread serializes through ``_capture_lock``)."""

    def __init__(self, cfg: DevprofConfig, *, registry, tracer=None,
                 dump_dir: str = "/tmp/dstpu_flight"):
        self.cfg = cfg
        self.enabled = bool(cfg.enabled)
        self.registry = registry
        self.tracer = tracer
        self.dump_dir = str(dump_dir)
        self.ledger = BUILD_LEDGER
        self.compiles_warmup = 0
        self.compiles_steady = 0
        # what this engine's sites compiled: {site, n, steady, t,
        # duration_s, entries}; incident bundles carry it
        self._compiles: "collections.deque" = collections.deque(maxlen=64)
        self.steady = False
        self._capture_lock = threading.Lock()
        self.captures: List[Dict[str, Any]] = []
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
        self._probe_seen = 0            # incident-probe cursor

    # --------------------------------------------------------- wiring
    def wrap(self, site: str, jfn):
        """Sentinel-wrap one compiled program (identity for None)."""
        if jfn is None:
            return None
        return _SentinelFn(jfn, site, self)

    # ------------------------------------------------------- sentinel
    def mark_steady(self) -> None:
        """Flip warmup → steady state (the engine calls this at the
        first token of the first request).  From here every attributed
        compile is a contract violation."""
        self.steady = True

    def on_compile(self, site: str, n: int = 1,
                   program: Optional[str] = None) -> None:
        """A sentinel wrapper detected ``n`` fresh compiles at
        ``site``: the ledger's entries of ``program`` become the
        site's, counters + an ``xla_compile`` event on its own Chrome
        track (steady-state ones are flagged)."""
        entries = self.ledger.claim(program, n, site, self.steady)
        dur = (round(sum(e["trace_s"] + e["lower_s"] + e["cache_load_s"]
                         + e["compile_s"] for e in entries), 6)
               if entries else None)
        self._compiles.append({
            "site": str(site), "n": int(n),
            "t": time.strftime("%Y-%m-%dT%H:%M:%S"),
            "phase": "steady" if self.steady else "warmup",
            "duration_s": dur, "entries": entries})
        if self.steady:
            self.compiles_steady += n
            self._c_comp_steady.inc(n)
            # on the profiler's clock too: a capture shows WHICH step
            # recompiled, and at which site
            telemetry_mark(f"{self.registry.namespace}/xla_compile",
                           site=site, n=n)
        else:
            self.compiles_warmup += n
            self._c_comp_warm.inc(n)
        if self.tracer is not None and self.tracer.enabled:
            self.tracer.event("xla_compile", attrs={
                "site": site, "n": n,
                "steady": self.steady,
                "duration_s": dur})

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
        return {
            "enabled": True,
            "steady": self.steady,
            "monitoring": self.monitoring,
            "compiles_warmup": self.compiles_warmup,
            "compiles_steady": self.compiles_steady,
            "captures": list(self.captures)[-4:],
        }

    def compile_ledger(self) -> Dict[str, Any]:
        """This engine's compiles, site by site, each with the build
        ledger's entries it claimed."""
        return {
            "warmup_compiles": self.compiles_warmup,
            "steady_state_compiles": self.compiles_steady,
            "entries": [dict(c, entries=[dict(e) for e in c["entries"]])
                        for c in self._compiles],
        }

    def bundle_info(self) -> Dict[str, Any]:
        """What incident bundles attach: the engine's compiles plus
        recent capture references."""
        return {
            "compile_ledger": self.compile_ledger(),
            "captures": list(self.captures)[-4:],
        }

    def incident_probe(self):
        """IncidentManager probe: trip once per NEW steady-state
        compile batch (cursor-based — warmup compiles never trip)."""
        n = self.compiles_steady
        if n > self._probe_seen:
            fresh = n - self._probe_seen
            self._probe_seen = n
            return "steady_state_recompile", {
                "phase": "steady_state_recompile",
                "new_compiles": fresh,
                "steady_state_compiles": n,
                "recent": self.compile_ledger()["entries"][-4:],
            }
        return None


class _NullDevProf:
    """Shared no-op stand-in when the block is off: wrap() is the
    identity and every read surface is the disabled block."""

    enabled = False
    steady = False
    monitoring = False
    captures: List[Dict[str, Any]] = []

    def wrap(self, site, jfn):
        return jfn

    def mark_steady(self):
        pass

    def profilez(self, capture_s=None):
        return {"enabled": False}

    def statusz_block(self):
        return {"enabled": False}

    def bundle_info(self):
        return {}

    def incident_probe(self):
        return None


NULL_DEVPROF = _NullDevProf()

# whatever the process jits from here on is in the ledger
install_compile_listener()


# what a program says of itself while it is traced: the event a rule
# of the shapes records deep inside a model (``ops/attention_pallas.py``:
# which flash backward a step runs, and why), on JAX's own bus, so the
# kernels import nothing of the tracing
BUILD_WORD_EVENT = "/dstpu/build_word"


def _on_build_word(event: str, **word) -> None:
    """Words for the build span the trace runs under: :class:`ProgramSpan`
    writes them after its own into the ``span`` of the entry its dispatch
    made (no entry where the trace was cached: nothing was built).  A
    word said twice with two answers keeps both (``fused+split``);
    outside a span the words go when the next one starts.  (At the
    file's end: a Mosaic kernel's payload carries the lines of the
    frames above its call, ``_SentinelFn.__call__`` among them.)"""
    if event == BUILD_WORD_EVENT:
        notes = _tl.notes
        for k, v in word.items():
            had = str(notes.get(k, v)).split("+")
            notes[k] = "+".join(had if str(v) in had else had + [str(v)])


jax.monitoring.register_event_listener(_on_build_word)

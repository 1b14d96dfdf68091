"""The steady state from inside: the program's process-wide step ledger
(``deepspeed_tpu.devprof.STEP_LEDGER``, PR 53), one row a ``step()``
the engine made with telemetry on, stamped on the clock
``harness/clock.py`` reads.  The readers take the rows that lie whole
inside the window, all of it and not the traced tail; the engine is
gone when they run, the ledger is the process's.  A program without a
ledger (the parent of the PR that brought it) reads nothing."""

from benchmark.harness.clock import percentile

STALL = 10.0        # a stall: this many medians of its own composition


def ledger():
    """The ledger's snapshot, or None where the program keeps none."""
    try:
        from deepspeed_tpu.devprof import STEP_LEDGER
    except ImportError:
        return None
    return STEP_LEDGER.snapshot()


def inside(run, snap):
    """The rows of the steps that began and ended inside the window."""
    w = run.window
    return [r for r in snap["rows"]
            if w["t_open"] <= r["t0"] and r["t1"] <= w["t_end"]]


def seconds(row):
    return row["t1"] - row["t0"]


def prompt_programs(row):
    """A step's composition: the prompt programs it dispatched, whole
    prompts and chunks alike (a decode program rides in every step that
    has a slot decoding)."""
    return row["programs"]["prefill"][0] + row["programs"]["chunk"][0]


def by_composition(rows):
    """prompt programs a step -> the seconds of the steps of that
    many."""
    out = {}
    for r in rows:
        out.setdefault(prompt_programs(r), []).append(seconds(r))
    return out


def stalls(rows):
    """The rows longer than ``STALL`` times the median step of their
    own composition: a first-fill step of five chunks is not a stall,
    a 3 s step of two is."""
    median = {k: percentile(v, 50) for k, v in by_composition(rows).items()}
    return [r for r in rows
            if seconds(r) > STALL * median[prompt_programs(r)]]


def read(run, what):
    """Percent, over the whole window.  ``host_exposed_share``: the
    seconds inside steps in which the device provably had nothing
    queued (from the return of a device-to-host fetch to the next
    dispatch call) over the window's.  ``prefill_fill``: real prompt
    tokens over the rows the prefill and chunk programs ran.
    ``stall_share``: the window's share spent in stalled steps."""
    snap = ledger()
    if snap is None:
        return None
    rows = inside(run, snap)
    window_s = run.window["t_end"] - run.window["t_open"]
    if not rows or window_s <= 0:
        return None
    if what == "host_exposed_share":
        return 100.0 * sum(r["exposed_s"] for r in rows) / window_s
    if what == "stall_share":
        return 100.0 * sum(seconds(r) for r in stalls(rows)) / window_s
    if what == "prefill_fill":
        ran = [r["programs"][site] for r in rows
               for site in ("prefill", "chunk")]
        padded = sum(p[1] for p in ran)
        return 100.0 * sum(p[2] for p in ran) / padded if padded else None
    raise ValueError(f"step_ledger reads no {what!r}")

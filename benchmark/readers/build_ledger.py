"""The set-up time from inside: the program's process-wide build ledger
(``deepspeed_tpu.devprof.BUILD_LEDGER``, PR 37), one entry a program
the process made ready, stamped on the clock ``harness/clock.py``
reads.  The engine is gone when readers run; the ledger is the
process's.  A program without a ledger (the parent of the PR that
brought it) reads nothing."""


def ledger():
    """The ledger's snapshot with the unnamed programs one by one, or
    None where the program keeps none."""
    try:
        from deepspeed_tpu.devprof import BUILD_LEDGER
    except ImportError:
        return None
    return BUILD_LEDGER.snapshot(rows=True)


def before(run, snap):
    """The named entries and the unnamed programs' rows made ready
    before the window opened: what ``setup_s`` paid for."""
    t_open = run.window["t_open"]
    return ([e for e in snap["entries"] if e["t_end"] <= t_open],
            [r for r in snap["other"]["rows"] if r[0] <= t_open])


def read(run, what):
    """Seconds of set-up by part, summed over the programs made ready
    before the window opened.  ``trace_s``, ``lower_s``,
    ``cache_load_s``, ``compile_s``: the programs the project named
    (``dstpu_*``), from JAX's own duration events; ``other_s`` and
    ``other_programs``: all four parts of every other program (eager
    fills, the harness's weights) and their count;
    ``package_import_s``: first to last line of the package's
    ``__init__``."""
    if what == "package_import_s":
        import deepspeed_tpu

        return getattr(deepspeed_tpu, "IMPORT_SECONDS", None)
    snap = ledger()
    if snap is None:
        return None
    entries, rows = before(run, snap)
    if what == "other_programs":
        return len(rows)
    if what == "other_s":
        return sum(r[2] for r in rows)
    return sum(e[what] for e in entries)

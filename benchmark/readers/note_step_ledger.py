from benchmark.harness import scopes
from benchmark.harness import trace as T
from benchmark.harness.clock import percentile
from benchmark.readers import step_ledger

LONGEST = 5


def _ms(seconds):
    return round(1e3 * seconds, 3)


def _step(row):
    """One step for reading: its phases and its counts."""
    return {
        "n": row["n"], "ms": _ms(step_ledger.seconds(row)),
        "phases_ms": {k: _ms(v) for k, v in row["phases"].items()
                      if v >= 5e-5},
        "exposed_ms": _ms(row["exposed_s"]),
        "programs": {k: v for k, v in row["programs"].items() if v[0]},
        "queue": row["queue"], "admitted": row["admitted"],
        "boundary_tokens": row["boundary_tokens"],
        "preempted": row["preempted"]}


def host_idle(rows):
    """The stretches in which the ledger says the device had nothing
    queued: the exposed ones inside steps, and from a step that ended
    drained to the next step's first instant (its tick and the
    caller's time)."""
    out = []
    for prev, row in zip([None] + rows, rows):
        if prev is not None and prev["drained"] \
                and row["n"] == prev["n"] + 1:
            out.append((prev["t1"], row["t0"]))
        out.extend((a, b) for a, b in row["idle"])
    return T.union(out)


def agreement(run, rows):
    """A traced run: the ledger beside the device over the traced
    stretch.  The capture's steps carry their ordinals, so the offset
    between the profiler's clock and the ledger's is measured on the
    steps both hold."""
    scoped = scopes.of_run(run)
    if scoped is None or not scoped.ops:
        return None
    by_n = {r["n"]: r for r in rows}
    offsets = sorted(
        s.start - by_n[int(s.stats["n"])]["t0"] for s in scoped.spans
        if s.name == scopes.STEP_SPAN and s.stats.get("n", "").isdigit()
        and int(s.stats["n"]) in by_n)
    window = scopes.window_of(scoped)
    if not offsets or window is None:
        return None
    offset = percentile(offsets, 50)
    chip = min(scoped.ops)
    busy = T.union((o.start, o.start + o.dur) for o in scoped.ops[chip])
    device = T.length(T.minus([window], busy))
    stretch = (window[0] - offset, window[1] - offset)
    idle = host_idle(rows)
    ledger = T.length(T.minus([stretch], T.minus([stretch], idle)))
    # an idle stretch ends where a dispatch CALL begins; the decode
    # program's call itself (the jit's own host work before the program
    # is queued) is the first part of what the ledger cannot prove
    calls = sum(r["phases"].get("dispatch", 0.0) for r in rows
                if stretch[0] <= r["t0"] and r["t1"] <= stretch[1])
    return {
        "stretch_s": round(window[1] - window[0], 6),
        "ledger_idle_s": round(ledger, 6),
        "chip0_idle_s": round(device, 6),
        "dispatch_calls_s": round(calls, 6),
        "ledger_over_device": round(ledger / device, 4) if device else None,
        "steps_matched": len(offsets),
        "clock_offset_s": offset,
        "offset_residual_p95_ms": _ms(percentile(
            [abs(o - offset) for o in offsets], 95))}


def read(run):
    """For an earlier line, over the whole window: its seconds by phase
    and the exposed seconds by phase; ``between`` (the caller's time)
    and the ticks; what was dispatched by site as ``[programs, rows,
    real tokens]``; steps and their median by the prompt programs they
    dispatched (0, 1, 2, 3+); the stalled steps' count; the longest
    steps, each with its phases and counts; and, traced, the ledger's
    idle seconds beside chip 0's over the traced stretch."""
    snap = step_ledger.ledger()
    if snap is None:
        return None
    rows = step_ledger.inside(run, snap)
    if not rows:
        return None
    phases, exposed, programs = {}, {}, {}
    for r in rows:
        for k, v in r["phases"].items():
            phases[k] = phases.get(k, 0.0) + v
        for k, v in r["exposed"].items():
            exposed[k] = exposed.get(k, 0.0) + v
        for site, p in r["programs"].items():
            total = programs.setdefault(site, [0, 0, 0])
            for i in range(3):
                total[i] += p[i]
    composed = {}
    for k, v in step_ledger.by_composition(rows).items():
        composed.setdefault(str(k) if k < 3 else "3+", []).extend(v)
    longest = sorted(rows, key=step_ledger.seconds)[-LONGEST:][::-1]
    return {
        "window_s": round(run.window["t_end"] - run.window["t_open"], 6),
        "steps": len(rows), "unseen_by_any_reader": snap["unseen"],
        "step_s": round(sum(map(step_ledger.seconds, rows)), 6),
        "phase_s": {k: round(v, 6) for k, v in phases.items()},
        "exposed_s": round(sum(r["exposed_s"] for r in rows), 6),
        "exposed_by_phase_s": {k: round(v, 6) for k, v in exposed.items()},
        "between_s": round(sum(r["between_s"] for r in rows[1:]), 6),
        "tick_s": round(sum(r["tick_s"] for r in rows), 6),
        "host_idle_s": round(T.length(host_idle(rows)), 6),
        "programs": programs,
        "steps_by_prompt_programs": {
            k: [len(v), _ms(percentile(v, 50))]
            for k, v in sorted(composed.items())},
        "stalled_steps": len(step_ledger.stalls(rows)),
        "longest_steps": [_step(r) for r in longest],
        "device": agreement(run, rows)}

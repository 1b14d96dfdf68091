from benchmark.readers import build_ledger

PARTS = ("trace_s", "lower_s", "cache_load_s", "compile_s", "run_s")


def lap_of(run, t):
    """The lap of ``note_setup`` an instant fell in; ``window`` after
    the last of them."""
    edge = run.t_process_start
    for name, seconds in run.window["setup_laps"].items():
        edge += seconds
        if t <= edge:
            return name
    return "window"


def read(run):
    """For an earlier line: one row a program the project named, in the
    order they were made ready (its name, the ``build_program`` span's
    word, the five parts in seconds, the lowering thread's minor page
    faults while it lowered, hit or miss, the lap it fell in, the
    largest inner traces); the programs it did not name as a count
    and seconds a lap, and the five names with most seconds; the parts
    summed a lap."""
    snap = build_ledger.ledger()
    if snap is None:
        return None
    rows, laps = [], {}
    for e in snap["entries"]:
        lap = lap_of(run, e["t_end"])
        rows.append([e["program"], e["span"]]
                    + [e[k] for k in PARTS]
                    + [e.get("lower_faults"),
                       "hit" if e["cache_hit"] else "miss", lap,
                       dict(list(e["inner_trace_s"].items())[:3])])
        total = laps.setdefault(lap, dict.fromkeys(PARTS, 0.0))
        for k in PARTS:
            total[k] += e[k] or 0.0
    other = {}
    for t, _, seconds in snap["other"]["rows"]:
        row = other.setdefault(lap_of(run, t), [0, 0.0])
        row[0] += 1
        row[1] += seconds
    return {"columns": ["program", "span"] + list(PARTS)
            + ["lower_faults", "cache", "lap", "inner_trace_s"],
            "programs": rows,
            "by_lap": {k: {p: round(s, 3) for p, s in v.items()}
                       for k, v in laps.items()},
            "other_by_lap": {k: [n, round(s, 3)]
                             for k, (n, s) in other.items()},
            "other_top": snap["other"]["top"]}

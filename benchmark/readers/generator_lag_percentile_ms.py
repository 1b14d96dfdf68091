from benchmark.harness.clock import percentile


def read(run, q):
    """How late ``submit`` was called against the instant the request
    was due: the generator shares the engine's thread, so arrivals wait
    for the step in flight."""
    w = run.window
    if w["kind"] != "serve":
        return None
    led = w["ledger"]
    p = percentile([lag for rid, lag in led.lag.items()
                    if led.due_at[rid] >= w["t_open"]], q)
    return None if p is None else 1e3 * p

from benchmark.harness import trace


def read(run):
    """1 - union of operation intervals over the traced window; the
    chips' mean."""
    if run.traced is None:
        return None
    idle = trace.idle_share(run.traced)
    return None if idle is None else 100.0 * idle

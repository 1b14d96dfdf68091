from benchmark.harness.clock import percentile
from benchmark.readers import _window


def read(run):
    """Host clock around ``step()``, which ends in a fetched token."""
    if run.window["kind"] != "serve":
        return None
    p = percentile([s[1] - s[0] for s in _window.steps(run)], 50)
    return None if p is None else 1e3 * p

from benchmark.harness.clock import percentile
from benchmark.readers import _window


def read(run, q):
    """The q-th percentile of the gaps between a request's consecutive
    output tokens, over every such gap inside the window."""
    if run.window["kind"] != "serve":
        return None
    p = percentile(_window.gaps(run), q)
    return None if p is None else 1e3 * p

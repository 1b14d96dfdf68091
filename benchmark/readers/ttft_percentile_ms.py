from benchmark.harness.clock import percentile
from benchmark.readers import _window


def read(run, q):
    """The q-th percentile of the wait from the instant a request was
    due to its first token."""
    if run.window["kind"] != "serve":
        return None
    p = percentile(_window.first_token_waits(run), q)
    return None if p is None else 1e3 * p

from benchmark.readers import _window


def read(run):
    """Allocator pages in use over the pool, peak over the window."""
    if run.window["kind"] != "serve":
        return None
    rows = _window.steps(run)
    return 100.0 * max(s[5] for s in rows) if rows else None

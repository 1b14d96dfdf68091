from benchmark.readers import _window


def read(run):
    """The program's occupancy gauge after every ``step()``, mean."""
    if run.window["kind"] != "serve":
        return None
    rows = _window.steps(run)
    return 100.0 * sum(s[4] for s in rows) / len(rows) if rows else None

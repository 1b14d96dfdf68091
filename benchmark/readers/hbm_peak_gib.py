def read(run):
    """``memory_stats()["peak_bytes_in_use"]`` after the window, on the
    fullest chip."""
    m = run.window["memory"]
    return None if m is None else m / 2 ** 30

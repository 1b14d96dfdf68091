def read(run):
    """Programs fetched from the persistent compilation cache before
    the window opened (JAX's own cache events)."""
    return run.window["compile_cache_hits"]

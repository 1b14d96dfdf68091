def read(run):
    """The program's count of recompute preemptions, over the window."""
    if run.window["kind"] != "serve":
        return None
    return run.window["preemptions"]

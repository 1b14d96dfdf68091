def read(run):
    """For an earlier line: what the correctness check compared."""
    w = run.window
    return w.get("token_check") or w.get("probe")

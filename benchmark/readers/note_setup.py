def read(run):
    """For an earlier line: where the set-up time went, in seconds."""
    return {k: round(v, 3) for k, v in run.window["setup_laps"].items()}

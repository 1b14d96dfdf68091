def read(run):
    """Process start to the first instant of the window."""
    return run.window["setup_s"]

def read(run):
    """Programs built inside the window.  Serving: the program's devprof
    sentinel; training: the harness's listener.  Expect 0."""
    return run.window["compiles_steady"]

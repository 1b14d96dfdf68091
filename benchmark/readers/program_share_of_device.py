from benchmark.harness import scopes


def read(run, programs):
    """Time in the runs of the programs whose names hold one of
    ``programs`` ("XLA Modules" line) over the traced window."""
    scoped = scopes.of_run(run)
    if scoped is None or not scopes.programs_named(scoped, "dstpu_"):
        return None                # a program that names nothing
    share = scopes.program_share(scoped, programs)
    return None if share is None else 100.0 * share

from benchmark.harness import scopes


def read(run, program):
    """Collective operations inside one run of ``program`` on chip 0."""
    scoped = scopes.of_run(run)
    if scoped is None:
        return None
    return scopes.collectives_in_a_run(scoped, program)

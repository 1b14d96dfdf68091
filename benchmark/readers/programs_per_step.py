from benchmark.harness import scopes


def read(run):
    """Program runs ("XLA Modules" line, chip 0) that start inside one
    ``dstpu/serving_step`` span, the median over the traced steps."""
    scoped = scopes.of_run(run)
    if scoped is None:
        return None
    return scopes.programs_per_step(scoped)

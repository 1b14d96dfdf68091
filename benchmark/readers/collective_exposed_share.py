from benchmark.harness import trace


def read(run):
    """Time in all-gather, reduce-scatter, all-reduce and all-to-all
    operations during which nothing else runs on that chip, over the
    traced window; the chips' mean."""
    if run.traced is None or not run.traced.ops:
        return None
    share = trace.exposed_collective_share(run.traced)
    return None if share is None else 100.0 * share

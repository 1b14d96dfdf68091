from benchmark.harness import scopes


def read(run):
    """Self time of the device operations no named scope of the program
    covers (pool-shaped copies aside, which count as ``kv_copy``) over
    busy time: how much the names miss."""
    scoped = scopes.of_run(run)
    if scoped is None or not scoped.ops or not scopes.named(scoped):
        return None
    share = scopes.share_of_busy(scoped, [scopes.UNSCOPED],
                                 scopes.pool_shapes(run.window))
    return None if share is None else 100.0 * share

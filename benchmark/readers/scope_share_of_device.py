from benchmark.harness import scopes


def read(run, scopes_counted, pool_copies=False):
    """Self time of the device operations traced under the program's
    named scopes ``scopes_counted`` over the traced window, the chips'
    mean.  With ``pool_copies``, an operation that has no scope and
    whose result is the K/V pool or one layer of it counts too: a copy
    the compiler put in."""
    scoped = scopes.of_run(run)
    if scoped is None or not scoped.ops:
        return None
    counted = list(scopes_counted) + ([scopes.KV_COPY] if pool_copies else [])
    share = scopes.share_of_window(scoped, counted,
                                   scopes.pool_shapes(run.window))
    if share is None or not scopes.named(scoped):
        return None                # a program that names nothing
    return 100.0 * share

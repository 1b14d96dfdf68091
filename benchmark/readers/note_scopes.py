from benchmark.harness import scopes


def read(run):
    """For an earlier line: per named scope the self seconds (chips'
    mean), operations, collective and backward seconds; the unscoped
    operations with most self time; the Mosaic kernels by name."""
    scoped = scopes.of_run(run)
    if scoped is None or not scoped.ops:
        return None
    pool = scopes.pool_shapes(run.window)
    rows = scopes.by_scope(scoped, pool)
    return {"by_scope": {s: {k: round(v, 6) for k, v in r.items()}
                         for s, r in sorted(rows.items(),
                                            key=lambda kv: -kv[1]["self_s"])},
            "unscoped_top": [[n, round(t, 6)] for n, t in
                             scopes.unscoped_ops(scoped, pool)],
            "kernels": scopes.kernel_seconds(scoped)}

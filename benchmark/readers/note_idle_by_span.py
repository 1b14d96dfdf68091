from benchmark.harness import scopes


def read(run):
    """For an earlier line: chip 0's idle seconds by the innermost of
    the ``bench/`` and ``dstpu/`` spans that covers most of each gap,
    and the share of ``dstpu/serving_step`` its children cover."""
    scoped = scopes.of_run(run)
    if scoped is None:
        return None
    cover = scopes.coverage(scoped)
    return {"idle_s": {n: round(t, 6) for n, t in
                       scopes.idle_by_span(scoped)},
            "children_cover_step": None if cover is None
            else round(cover, 4)}

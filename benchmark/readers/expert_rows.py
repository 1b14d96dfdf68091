def read(run, what):
    """From the rows the programs counted inside the window (the
    runner's ``expert_rows``, one count a held expert, and
    ``routed_rows``, every (row, expert) pair routed anywhere, idle
    slots' and padding rows included): ``held_share``, the held
    experts' rows over all pairs, in percent (the experts held over the
    experts there are, if the router is even); ``max_over_mean``, the
    busiest held expert's rows over the mean.  A program that counts
    nothing reads nothing."""
    held = run.window.get("expert_rows")
    routed = run.window.get("routed_rows")
    if not held or not routed or not sum(held):
        return None
    if what == "held_share":
        return 100.0 * sum(held) / routed
    return max(held) / (sum(held) / len(held))

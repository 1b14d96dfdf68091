from benchmark.readers import _window


def read(run):
    """Tokens the engine took through inside the window, over the window
    (``Ledger.tokens_through``): each prompt token once as it first
    passed prefill, each output token once as it was produced, nothing
    for a request that failed.  Over a steady state this is the prompt +
    generated tokens of completed requests per second (vLLM's total
    token throughput) without its ends: crediting a request only when it
    completes leaves up to a slot's worth of work, per slot, uncounted
    or counted late at either end of a window (PR 23 measured 4.6%
    spread that way against 1.8%).  The runner fails a run in which the
    two readings differ by more than those ends can explain."""
    w = run.window
    if w["kind"] != "serve":
        return None
    return w["tokens_through"] / _window.seconds(run)

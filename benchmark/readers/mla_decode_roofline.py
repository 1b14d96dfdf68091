from benchmark.harness import scopes
from benchmark.readers import _window
from benchmark.roofline import mla

KERNEL = "dstpu_mla_decode"


def live_rows(run, t0, t1):
    """(rows decoding, the positions they attend over between them) in
    the step that ran from ``t0`` to ``t1``, from the ledger: a request
    decodes from the step its first token is stamped in to the step it
    ends in, over its prompt and the tokens it had before this one."""
    led = run.window["ledger"]
    rows = tokens = 0
    for rid, stamps in led.stamps.items():
        if not stamps or stamps[0] > t1:
            continue
        ended = led.ended.get(rid)
        if ended is not None and ended[0] < t1:
            continue
        rows += 1
        tokens += led.requests[rid].prompt_len \
            + sum(1 for t in stamps if t <= t1) - 1
    return rows, tokens


def read(run):
    """The least time the calls of ``dstpu_mla_decode`` could take on
    this device (``roofline/mla.py``: operations over the peak rate or
    bytes over the memory's, whichever is larger, call by call, at the
    live lengths the ledger gives for the steps traced) over the time
    they took.  A program without the kernel reads nothing."""
    scoped = scopes.of_run(run)
    if scoped is None or run.peaks is None or not scoped.ops:
        return None
    calls = [o for o in next(iter(scoped.ops.values())) if o.kernel == KERNEL]
    cfg = run.window["program_config"]
    if not calls or not hasattr(cfg, "kv_lora_rank"):
        return None
    # a decode program calls the kernel once a layer, and the traced
    # stretch is the window's end: its last decoding steps
    programs = len(calls) // cfg.n_layers
    steps = [s for s in _window.steps(run) if s[4] > 0][-programs:]
    if not steps:
        return None
    least = 0.0
    for s in steps:
        rows, tokens = live_rows(run, s[0], s[1])
        least += cfg.n_layers * mla.floor_seconds(
            cfg.n_heads, cfg.row_width, cfg.kv_lora_rank, tokens, rows,
            run.peaks)
    took = sum(o.dur for o in calls) * len(steps) * cfg.n_layers / len(calls)
    return 100.0 * least / took

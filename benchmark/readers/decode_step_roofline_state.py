from benchmark.harness.clock import percentile
from benchmark.readers import _window


def read(run):
    """``decode_step_roofline`` for a model whose slots keep a recurrent
    state beside their pages (``families/<family>.py::
    state_bytes_per_slot``): the least time a decode step could take,
    reading every weight and the live K/V once and reading and writing
    every live slot's state once, at the published bandwidth, over the
    median host time of the steps that only decoded.  That reader counts
    weights and K/V alone, and would read a step that moves 3.5 GB of
    state as slower than it is.  A family without such a state reads
    nothing."""
    w = run.window
    per_slot = getattr(run.family, "state_bytes_per_slot", None)
    if w["kind"] != "serve" or run.peaks is None or per_slot is None:
        return None
    rows = _window.decode_only(run)
    if not rows:
        return None
    cfg = w["program_config"]
    engine = dict(run.config["serving"]["engine"], **run.cell["engine"])
    live = [s[5] * w["pool_pages"] * w["page_size"] for s in rows]
    slots = percentile([s[4] for s in rows], 50) * engine["max_batch"]
    least = (run.family.weight_bytes(cfg)
             + percentile(live, 50) * run.family.kv_bytes_per_token(cfg)
             + 2 * slots * per_slot(cfg)) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / percentile([s[1] - s[0] for s in rows], 50)

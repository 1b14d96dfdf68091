from benchmark.harness.clock import percentile
from benchmark.readers import _window


def read(run):
    """The least time a decode step could take, reading every weight and
    the live K/V once at the published bandwidth, over the median host
    time of the steps that only decoded.  Memory bounds it."""
    w = run.window
    if w["kind"] != "serve" or run.peaks is None:
        return None
    rows = _window.decode_only(run)
    if not rows:
        return None
    cfg = w["program_config"]
    weights = run.family.weight_bytes(cfg)
    per_token = run.family.kv_bytes_per_token(cfg)
    live = [s[5] * w["pool_pages"] * w["page_size"] for s in rows]
    least = (weights + percentile(live, 50) * per_token) \
        / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / percentile([s[1] - s[0] for s in rows], 50)

from benchmark.harness.clock import percentile
from benchmark.readers import _window


def read(run):
    """For an earlier line: where on the curve the 95th percentile sits."""
    if run.window["kind"] != "serve":
        return None
    gaps = _window.gaps(run)
    rows = _window.steps(run)
    waits = _window.first_token_waits(run)
    w = run.window
    led = w["ledger"]
    offered = [r for r, t in led.due_at.items() if t >= w["t_open"]]
    lives = [t - led.due_at[r] for r, (t, _) in w["ended"].items()]
    return {
        "gaps": len(gaps), "steps": len(rows), "first_tokens": len(waits),
        "requests_ended": len(w["ended"]), "requests_offered": len(offered),
        "lifetime_s_mean": sum(lives) / len(lives) if lives else None,
        "ttft_ms": {f"p{q}": round(1e3 * percentile(waits, q), 1)
                    for q in (50, 95)} if waits else {},
        "gap_ms": {f"p{q}": round(1e3 * percentile(gaps, q), 3)
                   for q in (50, 80, 90, 94, 95, 96, 99)} if gaps else {},
        "tokens_of_completed_requests_per_s": (
            w["tokens_completed"] / _window.seconds(run)),
        "steps_with_prefill_share": (
            sum(1 for s in rows if s[2] or s[3]) / len(rows)
            if rows else None),
        "queue_depth_at_quarter_and_end": [
            rows[len(rows) // 4][6], rows[-1][6]] if rows else None,
        # a stall shows here and nowhere in the percentiles: the five
        # longest steps, and the longest wait between two steps
        "longest_steps_ms": sorted(
            (round(1e3 * (s[1] - s[0]), 1) for s in rows), reverse=True)[:5],
        "longest_between_steps_ms": round(1e3 * max(
            (b[0] - a[1] for a, b in zip(rows, rows[1:])), default=0.0), 1),
    }

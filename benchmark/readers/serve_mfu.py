from benchmark.readers import _window


def read(run):
    """FLOPs the model as routed needs for the requests completed (each
    token attends half its request's length on average), per second,
    over the peak.  An end-to-end utilisation, not a kernel's share."""
    w = run.window
    if w["kind"] != "serve" or run.peaks is None:
        return None
    cfg, led = w["program_config"], w["ledger"]
    flops = 0.0
    for rid in w["completed"]:
        n = led.requests[rid].prompt_len + len(led.stamps[rid])
        flops += n * run.family.serve_flops_per_token(cfg, n / 2)
    return 100.0 * flops / _window.seconds(run) / (
        run.peaks["bf16_flops_per_s"] * run.chips)

"""The one line a run ends with."""

import json

from benchmark.harness import trace as tracelib


def device_block(run):
    # what the device reports, and nothing added to it:
    # memory_stats()["peak_bytes_in_use"] on the fullest chip
    dev = dict(run.device, memory_peak_bytes=run.window["memory"])
    if run.traced is not None:
        w = tracelib.window(run.traced)
        dev["busy_s"] = tracelib.busy_seconds(run.traced)
        dev["window_s"] = None if w is None else w[1] - w[0]
    return dev


def breakdown(run):
    if run.traced is None:
        return None
    return {"device_ops": [list(x) for x in tracelib.top_ops(run.traced)],
            "idle_gaps": [list(x) for x in tracelib.idle_gaps(run.traced)]}


def line(run, outcome, metrics):
    out = {"correct": not outcome["problems"],
           "attempted": outcome["attempted"], "failed": outcome["failed"],
           "metrics": metrics, "device": device_block(run)}
    extra = breakdown(run)
    if extra is not None:
        out["breakdown"] = extra
    return json.dumps(out)

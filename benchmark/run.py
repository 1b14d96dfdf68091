#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process each time.  It needs a TPU with the chips the cell asks
for (there is no CPU fallback), makes weights and traffic from
``--seed``, warms the cell's own shapes, measures for ``--seconds``,
checks the outputs outside the window and prints one JSON object as the
last line of its standard output: the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``.

``--rehearse`` (only with ``JAX_PLATFORMS=cpu``) walks the same code at
a toy size and never prints that line.  See README.md beside this file.
"""

import time

T_PROCESS_START = time.perf_counter()

import argparse
import json
import os
import sys

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def note(**obj):
    """An earlier line: worth reading, not a result."""
    print(json.dumps(obj), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--override", default=None, metavar="JSON",
                    help='for the builder\'s sweeps only, never the '
                         'driver\'s: {"traffic": {...}, "cell": {...}} '
                         'laid over the files')
    args = ap.parse_args(argv)
    if args.rehearse and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit("--rehearse runs only with JAX_PLATFORMS=cpu")

    sys.path.insert(0, CHECKOUT)
    if not os.path.isdir(os.path.join(CHECKOUT, "deepspeed_tpu")):
        raise SystemExit(f"no deepspeed_tpu beside {CHECKOUT}/benchmark: "
                         "the benchmark measures the program, and it is "
                         "not here")
    from benchmark import manifest
    from benchmark.harness import cell, device, result

    seconds = manifest.RUN_SECONDS if args.seconds is None else args.seconds
    outdir = os.path.join(CHECKOUT, "chiprun_out", "benchmark")
    run = cell.load_run(args.workload, args.seed, seconds, bool(args.trace),
                        args.rehearse, T_PROCESS_START, outdir,
                        json.loads(args.override or "{}"))
    if args.rehearse:
        seconds = run.seconds = run.traffic.get("rehearse_seconds", seconds)
        if run.chips > 1:
            from deepspeed_tpu.mesh import host_device_count

            host_device_count(run.chips)
        run.device = device.info()
    else:
        run.device = device.require_tpu(run.chips)
        run.peaks = device.peaks(run.device["kind"])
    cache_dir = device.enable_compile_cache()
    run.compiles = device.CompileCounter()
    note(cell=run.name, seed=run.seed, seconds=seconds, trace=run.trace,
         device=run.device, compile_cache_dir=cache_dir,
         rehearsal=args.rehearse, override=args.override)

    outcome = cell.runner(run.traffic["kind"])(run)
    run.window, run.traced = outcome["window"], outcome["trace"]
    names = run.cell["per_layer" if run.trace else "end_to_end"]
    metrics = cell.read_metrics(run, names)
    for n in run.cell.get("notes", []):
        note(**{n: cell.reader(n)(run)})
    if outcome["problems"]:
        note(problems=outcome["problems"])
    if args.rehearse:
        note(rehearsal="passed" if not outcome["problems"] else "failed",
             metrics=metrics, attempted=outcome["attempted"])
        return 0 if not outcome["problems"] else 1
    print(result.line(run, outcome, metrics), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

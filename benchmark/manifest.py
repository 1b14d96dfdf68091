#!/usr/bin/env python3
"""BENCHMARK.json, written from the files under this directory.

    python benchmark/manifest.py            # print what the files say
    python benchmark/manifest.py --write    # write ../BENCHMARK.json
    python benchmark/manifest.py --check    # exit 1 if they disagree

The files are the truth: a cell lists the metrics it reports, a metric's
file gives its unit, layer, ``moves`` and bound, a configuration's file
its source and what was reduced.  A PR that adds a cell or a metric adds
the files and runs ``--write``; entries that were there do not change.
"""

import glob
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
COMMAND = ["python3", "benchmark/run.py"]
PATHS = ["benchmark"]
RUN_SECONDS = 51


def _load(kind):
    out = {}
    for path in sorted(glob.glob(os.path.join(HERE, kind, "*.json"))):
        with open(path) as f:
            out[os.path.basename(path)[:-len(".json")]] = json.load(f)
    return out


def build():
    cells, metrics, configs = (_load(k) for k in
                               ("workloads", "metrics", "configs"))
    used = {}                                   # metric -> cells, in order
    for name, cell in cells.items():
        for m in cell["end_to_end"] + cell["per_layer"]:
            used.setdefault(m, []).append(name)

    def entry(name, keys):
        m = metrics[name]
        out = {"name": name, "unit": m["unit"], "better": m["better"]}
        out.update((k, m[k]) for k in keys)
        if len(used[name]) < len(cells):
            out["workloads"] = used[name]
        return out

    e2e = [n for n in metrics if "bound" in metrics[n] and n in used]
    per_layer = [n for n in metrics if "bound" not in metrics[n]
                 and n in used]
    return {
        "command": COMMAND, "paths": PATHS, "run_seconds": RUN_SECONDS,
        "configs": [
            {"name": n, "source": c["source"],
             "file": f"benchmark/configs/{n}.json",
             "reduced": c["reduced"], "why": c["why"]}
            for n, c in configs.items()
            if any(cell["config"] == n for cell in cells.values())],
        "workloads": [
            {"name": n, "config": c["config"], "traffic": c["traffic"],
             "chips": c["chips"], "why": c["why"]}
            for n, c in cells.items()],
        "end_to_end": [entry(n, ("bound", "source")) for n in e2e],
        "per_layer": [entry(n, ("source", "layer", "moves"))
                      for n in per_layer],
    }


def main(argv):
    text = json.dumps(build(), indent=2) + "\n"
    path = os.path.join(ROOT, "BENCHMARK.json")
    if "--write" in argv:
        with open(path, "w") as f:
            f.write(text)
    elif "--check" in argv:
        with open(path) as f:
            if f.read() != text:
                print("BENCHMARK.json differs from the files under "
                      "benchmark/: run benchmark/manifest.py --write")
                return 1
    else:
        sys.stdout.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

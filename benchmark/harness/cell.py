"""Finding a cell's files by name, and the run they describe.

``<config>.<traffic>`` is a cell.  Its own file names the two; each has
a file of its own; a mix's ``kind`` names a runner, a module with one
``run(run)``; each metric the cell reports has a file that names a
reader, and each reader is a module with one ``read(run)``.  A later PR
adds files; it edits none.
"""

import dataclasses
import importlib
import json
import os
from typing import Any, Dict, Optional

from benchmark.harness import HERE, clock


def load_json(kind, name):
    path = os.path.join(HERE, kind, name + ".json")
    if not os.path.isfile(path):
        raise SystemExit(f"no {kind}/{name}.json under {HERE}")
    with open(path) as f:
        return json.load(f)


def metric(name):
    m = load_json("metrics", name)
    m["name"] = name
    return m


def reader(name):
    return importlib.import_module(f"benchmark.readers.{name}").read


def runner(kind):
    return importlib.import_module(f"benchmark.runners.{kind}").run


def family(name):
    return importlib.import_module(f"benchmark.families.{name}")


def merge(base, over):
    """``over`` laid on ``base``, group by group."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


@dataclasses.dataclass
class Run:
    """One run of one cell: its files, its arguments, and, once the
    runner is through, the ``window`` and ``trace`` the readers read."""

    name: str
    cell: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    family: Any
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    chips: int
    t_process_start: float
    out_dir: str
    compiles: Any = None
    device: Optional[Dict[str, Any]] = None
    peaks: Optional[Dict[str, Any]] = None
    window: Optional[Dict[str, Any]] = None
    traced: Any = None
    laps: Dict[str, float] = dataclasses.field(default_factory=dict)

    @property
    def trace_dir(self):
        return os.path.join(self.out_dir, "trace", self.name)

    @property
    def seed32(self):
        """The seed folded to what a PRNG key takes."""
        return (self.seed ^ (self.seed >> 31)) & 0x7FFFFFFF

    def program_config(self, **overrides):
        model = self.config["model"]
        if self.rehearse:
            model = self.family.toy(model)
        return self.family.program_config(model, **overrides)

    def lap(self, name, now=None):
        """Seconds of set-up since the last lap, by name: where
        ``setup_s`` goes, for an earlier line."""
        now = clock.now() if now is None else now
        self.laps[name] = now - self.t_process_start - sum(self.laps.values())


def load_run(name, seed, seconds, trace, rehearse, t_process_start, outdir,
             override=None):
    cell = load_json("workloads", name)
    config = load_json("configs", cell["config"])
    traffic = load_json("traffic", cell["traffic"])
    if name != f"{cell['config']}.{cell['traffic']}":
        raise SystemExit(f"workloads/{name}.json names {cell['config']} and "
                         f"{cell['traffic']}: a cell is <config>.<traffic>")
    if rehearse:
        # the same files at a toy size: each carries its own small print
        cell = merge(cell, cell.get("rehearse", {}))
        traffic = merge(traffic, traffic.get("rehearse", {}))
    override = override or {}
    cell = merge(cell, override.get("cell", {}))
    traffic = merge(traffic, override.get("traffic", {}))
    return Run(name=name, cell=cell, config=config, traffic=traffic,
               family=family(config["family"]), seed=seed, seconds=seconds,
               trace=trace, rehearse=rehearse, chips=cell["chips"],
               t_process_start=t_process_start, out_dir=outdir)


def read_metrics(run, names):
    """name -> {"value", "unit"} for every metric whose reader found
    something to read."""
    out = {}
    for name in names:
        m = metric(name)
        value = reader(m["reader"])(run, **m.get("args", {}))
        if value is not None:
            out[name] = {"value": float(value), "unit": m["unit"]}
    return out

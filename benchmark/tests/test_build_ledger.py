"""The ``v37.*`` readers over the program's build ledger (PR 37): a
rehearsal of one serving cell and of the training cell through
``--override`` (no cell lists the metrics yet), and the split at the
window's first instant on a made-up ledger."""

import json
import math
import os
import subprocess
import sys
import types

import pytest

from benchmark import manifest
from benchmark.harness import cell
from benchmark.readers import build_ledger, note_build

ROOT = manifest.ROOT
METRICS = ["v37.build_trace_s", "v37.build_lower_s",
           "v37.build_cache_load_s", "v37.build_compile_s",
           "v37.build_other_s", "v37.build_other_programs",
           "v37.package_import_s"]
SECONDS = [m for m in METRICS if m.endswith("_s")]
OVERRIDE = {"cell": {"per_layer": METRICS,
                     "notes": ["note_setup", "note_build"]}}


def test_the_metric_files_say_what_the_readers_give():
    for name in METRICS:
        m = cell.metric(name)
        assert m["layer"] == "entry points" and m["moves"] == "setup_s"
        assert m["source"] == "program_counter" and m["better"] == "lower"
        assert m["unit"] == ("count" if name.endswith("programs") else "s")
        assert m["reader"] == "build_ledger"
        assert callable(cell.reader(m["reader"]))
    # each has its file, and BENCHMARK.json is what the files say
    names = os.listdir(os.path.join(ROOT, "benchmark", "metrics"))
    assert {m + ".json" for m in METRICS} <= set(names)
    assert manifest.main(["--check"]) == 0


@pytest.mark.parametrize("workload,programs,built_in", [
    ("gpt2-1.3b.serve.chat-0.8knee",
     {"dstpu_prefill", "dstpu_chunk", "dstpu_boundary", "dstpu_decode"},
     "engine"),
    ("gpt2-1.3b.train.zero3-x4",
     {"dstpu_make_state", "dstpu_train_step"}, "warm_steps"),
])
def test_a_rehearsal_reads_every_metric(workload, programs, built_in):
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", workload, "--seed", str(2 ** 31 + 37), "--trace",
         "1", "--rehearse", "--override", json.dumps(OVERRIDE)],
        cwd=ROOT, env=dict(
            {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"},
            JAX_PLATFORMS="cpu"),   # the cell asks for its own devices
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    lines = {}
    for text in out.stdout.strip().splitlines():
        if text.startswith("{"):
            lines.update(json.loads(text))
    assert lines["rehearsal"] == "passed"
    got = {k: v["value"] for k, v in lines["metrics"].items()}
    assert sorted(got) == sorted(METRICS)
    assert all(math.isfinite(v) and v >= 0 for v in got.values())
    # every part is inside the set-up it splits
    setup_s = sum(lines["note_setup"].values())
    assert 0 < sum(got[m] for m in SECONDS) <= setup_s
    assert got["v37.build_cache_load_s"] == 0       # no cache on the CPU
    assert got["v37.build_compile_s"] > 0 and got["v37.build_trace_s"] > 0
    assert got["v37.build_other_programs"] >= 1     # the weights' jit
    # one row a program, each in the lap that built it, and the metrics
    # are the rows made ready before the window, no later ones
    note = lines["note_build"]
    at = {c: i for i, c in enumerate(note["columns"])}
    rows = note["programs"]
    assert {r[at["program"]] for r in rows} == programs
    assert all(r[at["cache"]] == "miss" for r in rows)
    assert rows[-1][at["lap"]] == built_in
    early = [r for r in rows if r[at["lap"]] != "window"]
    for part in ("trace_s", "lower_s", "compile_s"):
        assert got[f"v37.build_{part}"] == pytest.approx(
            sum(r[at[part]] for r in early), abs=1e-6)
    others = note["other_by_lap"]
    assert got["v37.build_other_programs"] == sum(
        n for lap, (n, _) in others.items() if lap != "window")
    if workload.endswith("knee"):
        # the warm-up's spans name every row, and the engine lap is
        # mostly what the ledger timed
        assert all(r[at["span"]] for r in rows)
        timed = sum(note["by_lap"]["engine"].values()) \
            + others["engine"][1]
        assert 0.5 * lines["note_setup"]["engine"] < timed \
            <= lines["note_setup"]["engine"]


def _run(t_open, laps):
    return types.SimpleNamespace(
        t_process_start=100.0,
        window={"t_open": t_open, "setup_laps": laps})


def _entry(program, t_end, **parts):
    e = dict(program=program, t_end=t_end, trace_s=0.0, lower_s=0.0,
             cache_load_s=0.0, compile_s=0.0, run_s=None, span=None,
             cache_hit=False, inner_trace_s={})
    e.update(parts)
    return e


def test_entries_are_split_at_the_windows_first_instant(monkeypatch):
    snap = {"entries": [
        _entry("dstpu_prefill", 104.0, trace_s=0.5, lower_s=1.0,
               cache_load_s=0.25, cache_hit=True, span="prefill end=128",
               run_s=0.01),
        _entry("dstpu_decode", 109.0, trace_s=0.25, lower_s=2.0,
               compile_s=4.0),
        _entry("dstpu_decode", 111.5, trace_s=8.0, compile_s=16.0)],
        "other": {"rows": [[101.0, "<lambda>", 3.0],
                           [105.0, "broadcast_in_dim", 0.125],
                           [112.0, "convert_element_type", 32.0]],
                  "top": [["<lambda>", 3.0, 1]]}}
    monkeypatch.setattr(build_ledger, "ledger", lambda: snap)
    run = _run(110.0, {"imports": 0.5, "weights": 2.0, "engine": 5.0,
                       "warm_start": 2.5})
    read = lambda what: build_ledger.read(run, what)
    assert read("trace_s") == 0.75 and read("lower_s") == 3.0
    assert read("cache_load_s") == 0.25 and read("compile_s") == 4.0
    assert read("other_s") == 3.125 and read("other_programs") == 2
    assert read("package_import_s") > 0
    note = note_build.read(run)
    assert [r[-2] for r in note["programs"]] == ["engine", "warm_start",
                                                 "window"]
    assert note["programs"][0][:2] == ["dstpu_prefill", "prefill end=128"]
    assert note["programs"][0][note["columns"].index("cache")] == "hit"
    assert note["by_lap"]["engine"]["lower_s"] == 1.0
    assert note["other_by_lap"] == {"weights": [1, 3.0],
                                    "engine": [1, 0.125],
                                    "window": [1, 32.0]}
    # a window that opened earlier had paid for less
    assert build_ledger.read(_run(105.0, {}), "compile_s") == 0.0


def test_a_program_without_a_ledger_reads_nothing(monkeypatch):
    """The parent of the PR that brought the ledger: the readers return
    None, and the result line leaves the metrics out."""
    monkeypatch.setattr(build_ledger, "ledger", lambda: None)
    run = _run(110.0, {"engine": 10.0})
    for what in ("trace_s", "lower_s", "cache_load_s", "compile_s",
                 "other_s", "other_programs"):
        assert build_ledger.read(run, what) is None
    assert note_build.read(run) is None
    import deepspeed_tpu

    monkeypatch.delattr(deepspeed_tpu, "IMPORT_SECONDS")
    assert build_ledger.read(run, "package_import_s") is None

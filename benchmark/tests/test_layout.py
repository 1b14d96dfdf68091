"""BENCHMARK.json against the files, the characters the contract
allows, and a cell, a mix and a metric added as new files only."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmark import manifest
from benchmark.harness import cell, device

ROOT = manifest.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE = re.compile(r"^[^\t\n]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_manifest_is_what_the_files_say(bench):
    assert bench == manifest.build()
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_names_units_and_lines_use_only_the_characters_allowed(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    names = []
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and LINE.match(c["source"])
        assert LINE.match(c["why"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        names.append(c["name"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert LINE.match(w["why"]) and w["chips"] in (1, 4)
        assert w["config"] in [c["name"] for c in bench["configs"]]
        names.append(w["name"])
    e2e = [m["name"] for m in bench["end_to_end"]]
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert LINE.match(m["layer"]) and m["moves"] in e2e
    cells = [w["name"] for w in bench["workloads"]]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= set(cells)
        names.append(m["name"])
    assert len(names) == len(set(names))
    assert "setup_s" in e2e
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(cells) // 4)


def test_every_cell_reports_what_the_contract_asks(bench):
    layer = {m["name"]: m for m in bench["per_layer"]}
    for w in bench["workloads"]:
        c = cell.load_json("workloads", w["name"])
        assert "setup_s" in c["end_to_end"] and len(c["end_to_end"]) >= 2
        assert c["per_layer"]
        for m in c["per_layer"]:       # what it moves is reported here too
            assert layer[m]["moves"] in c["end_to_end"], (w["name"], m)
        for m in c["end_to_end"] + c["per_layer"] + c.get("notes", []):
            spec = cell.metric(m) if m in c["end_to_end"] + c["per_layer"] \
                else {"reader": m}
            assert callable(cell.reader(spec["reader"]))
        config = cell.load_json("configs", c["config"])
        assert callable(cell.family(config["family"]).program_config)
        assert cell.load_json("traffic", c["traffic"])["kind"]


def test_files_are_named_from_the_characters_of_a_name():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    top = os.path.join(ROOT, "benchmark")
    for d, dirs, files in os.walk(top):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            assert ok.match(os.path.relpath(os.path.join(d, f), ROOT)), f


def test_an_unknown_device_has_no_peaks():
    assert device.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert device.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        device.peaks("cpu")


def test_a_cell_a_mix_and_a_metric_added_as_files_only(tmp_path):
    """A later PR may add files and may edit none: copy the benchmark,
    add a cell (with a mix of its own and a runner for its kind), a
    per-layer metric and its reader as new files, and rehearse the new
    cell."""
    copy = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), copy / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), copy)
    os.symlink(os.path.join(ROOT, "deepspeed_tpu"), copy / "deepspeed_tpu")
    os.symlink(os.path.join(ROOT, "csrc"), copy / "csrc")
    before = {p: p.read_bytes() for p in (copy / "benchmark").rglob("*")
              if p.is_file()}

    b = copy / "benchmark"
    mix = json.loads((b / "traffic" / "serve.chat-sat.json").read_text())
    mix["about"] = "a mix a later PR adds: shorter answers"
    mix["kind"] = "serve_backlog_twice"      # and a runner of its own kind
    (b / "runners" / "serve_backlog_twice.py").write_text(
        "from benchmark.harness import serve\n\n\n"
        "def run(run):\n    return serve.run_serving(run, backlog=True)\n")
    mix["rehearse"]["output_tokens"] = {"median": 5, "lo": 3, "hi": 8}
    (b / "traffic" / "serve.chat-short-sat.json").write_text(json.dumps(mix))
    new = json.loads((b / "workloads" /
                      "gpt2-1.3b.serve.chat-0.8knee.json").read_text())
    name = "gpt2-1.3b.serve.chat-short-sat"
    new.update(name=name, traffic="serve.chat-short-sat",
               end_to_end=["serve_tokens_per_s", "setup_s"],
               per_layer=["batch_occupancy.sat", "steps_per_request"])
    (b / "workloads" / f"{name}.json").write_text(json.dumps(new))
    (b / "metrics" / "steps_per_request.json").write_text(json.dumps({
        "unit": "count", "better": "lower", "source": "program_counter",
        "reader": "steps_per_request", "layer": "serving scheduler",
        "moves": "serve_tokens_per_s"}))
    (b / "readers" / "steps_per_request.py").write_text(
        "from benchmark.readers import _window\n\n\n"
        "def read(run):\n"
        "    return len(_window.steps(run)) / max(1, len("
        "run.window['completed']))\n")

    out = subprocess.run(
        [sys.executable, str(b / "run.py"), "--workload", name, "--seed",
         str(2 ** 31 + 17), "--trace", "1", "--rehearse"],
        cwd=copy, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] == "passed"
    assert last["metrics"]["steps_per_request"]["value"] > 0
    assert last["metrics"]["batch_occupancy.sat"]["unit"] == "%"
    assert "correct" not in last          # a rehearsal is never a result
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"

"""The ``v53.*`` readers over the program's step ledger (PR 53): a
rehearsal of the tail cell and of a saturated one through ``--override``
(no cell lists the metrics yet), the arithmetic on a made-up ledger, and
the ledger beside a made-up capture."""

import json
import math
import os
import subprocess
import sys
import types

import pytest

from benchmark import manifest
from benchmark.harness import cell, scopes
from benchmark.readers import note_step_ledger, step_ledger

ROOT = manifest.ROOT
WHAT = ("host_exposed_share", "prefill_fill", "stall_share")
METRICS = [f"v53.{w}.{s}" for w in WHAT for s in ("sat", "tail")]
SITES = ("prefill", "chunk", "decode", "sweep")


def test_the_metric_files_say_what_the_readers_give():
    for name in METRICS:
        m = cell.metric(name)
        assert m["layer"] == "serving scheduler" and m["unit"] == "%"
        assert m["moves"] == ("itl_p95_ms" if name.endswith(".tail")
                              else "serve_tokens_per_s")
        fill = "prefill_fill" in name
        assert m["better"] == ("higher" if fill else "lower")
        assert m["source"] == ("program_counter" if fill
                               else "program_span")
        assert m["reader"] == "step_ledger"
        assert m["args"]["what"] in WHAT and m["args"]["what"] in name
        assert callable(cell.reader(m["reader"]))
    assert callable(cell.reader("note_step_ledger"))
    # the open cell lists the .tail forms (PR 58); no cell the .sat yet
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"]: m.get("workloads")
                  for m in json.load(f)["per_layer"]}
    for name in METRICS:
        assert listed.get(name) == (
            ["gpt2-1.3b.serve.chat-0.8knee"] if name.endswith(".tail")
            else None)
    assert manifest.main(["--check"]) == 0


@pytest.mark.parametrize("workload,suffix,prompts", [
    ("gpt2-1.3b.serve.chat-0.8knee", "tail", "prefill"),
    ("mixtral-8x7b-d4.serve.docs-sat", "sat", "chunk"),
])
def test_a_rehearsal_reads_every_metric(workload, suffix, prompts):
    metrics = [f"v53.{w}.{suffix}" for w in WHAT]
    override = {"cell": {"per_layer": metrics,
                         "notes": ["note_step_ledger"]}}
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", workload, "--seed", str(2 ** 31 + 53), "--trace",
         "1", "--rehearse", "--override", json.dumps(override)],
        cwd=ROOT, env=dict(
            {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"},
            JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    lines = {}
    for text in out.stdout.strip().splitlines():
        if text.startswith("{"):
            lines.update(json.loads(text))
    assert lines["rehearsal"] == "passed"
    got = {k[4:-len(suffix) - 1]: v["value"]
           for k, v in lines["metrics"].items()}
    assert sorted(got) == sorted(WHAT)
    assert all(math.isfinite(v) and 0 <= v <= 100 for v in got.values())
    note = lines["note_step_ledger"]
    # the whole window's steps, none lost, and their time inside it
    assert note["steps"] > 10 and note["unseen_by_any_reader"] == 0
    assert 0 < note["step_s"] <= note["window_s"]
    assert sum(note["phase_s"].values()) == pytest.approx(
        note["step_s"], rel=0.05)
    # exposed seconds lie in phases, and are the metric's numerator
    assert 0 < note["exposed_s"] < note["step_s"]
    # (what falls between two spans of a 2 ms step on the CPU, under
    # the profiler, is in no phase; on the chip they tile 99.9%)
    assert 0.8 * note["exposed_s"] \
        <= sum(note["exposed_by_phase_s"].values()) \
        <= note["exposed_s"] + 1e-5
    assert got["host_exposed_share"] == pytest.approx(
        100 * note["exposed_s"] / note["window_s"], rel=1e-3)
    assert "token_sync" not in note["exposed_by_phase_s"]
    assert note["host_idle_s"] >= note["exposed_s"]
    # what was dispatched: prompts by the cell's own path, one decode
    # program a step with a slot decoding, padding counted
    programs, rows, tokens = note["programs"][prompts]
    assert programs > 0 and 0 < tokens <= rows
    assert got["prefill_fill"] == pytest.approx(100 * tokens / rows)
    other = "chunk" if prompts == "prefill" else "prefill"
    assert note["programs"][other] == [0, 0, 0]
    assert 0 < note["programs"]["decode"][0] <= note["steps"]
    by = note["steps_by_prompt_programs"]
    assert sum(n for n, _ in by.values()) == note["steps"]
    assert set(by) <= {"0", "1", "2", "3+"}
    longest = note["longest_steps"]
    assert len(longest) == 5
    assert [s["ms"] for s in longest] == sorted(
        (s["ms"] for s in longest), reverse=True)
    assert all(s["phases_ms"] and s["programs"] for s in longest)
    assert note["device"] is None       # no device line on the CPU


def _row(n, t0, t1, prefill=(0, 0, 0), chunk=(0, 0, 0), live=1,
         exposed=None, drained=True, between=0.0, idle=None):
    exposed = exposed or {}
    return {"n": n, "t0": t0, "t1": t1, "between_s": between,
            "tick_s": 0.0, "k": 1, "queue": 0, "admitted": 0,
            "preempted": 0, "boundary_tokens": 0, "drained": drained,
            "programs": {"prefill": list(prefill), "chunk": list(chunk),
                         "decode": [1, 4, live], "sweep": [0, 0, 0]},
            "phases": {"admit": (t1 - t0) / 2, "token_sync": (t1 - t0) / 2},
            "exposed_s": sum(exposed.values()), "exposed": exposed,
            "idle": idle or []}


def _run(t_open, t_end):
    return types.SimpleNamespace(
        traced=None, window={"t_open": t_open, "t_end": t_end})


def test_the_window_is_cut_at_its_two_instants(monkeypatch):
    rows = [_row(0, 99.0, 99.9, chunk=(1, 256, 256)),     # before it
            _row(1, 99.95, 100.05, chunk=(1, 256, 256)),  # straddles
            _row(2, 100.1, 100.2, chunk=(1, 256, 200),
                 exposed={"admit": 0.01, "append": 0.03}),
            _row(3, 100.2, 100.3, prefill=(2, 128, 56),
                 exposed={"inputs": 0.06}),
            _row(4, 100.3, 100.4),
            _row(5, 109.95, 110.5, chunk=(4, 1024, 1024))]  # ends late
    monkeypatch.setattr(step_ledger, "ledger",
                        lambda: {"rows": rows, "unseen": 0})
    run = _run(100.0, 110.0)
    assert [r["n"] for r in step_ledger.inside(run, step_ledger.ledger())] \
        == [2, 3, 4]
    read = lambda what: step_ledger.read(run, what)
    assert read("host_exposed_share") == pytest.approx(100 * 0.10 / 10.0)
    assert read("prefill_fill") == pytest.approx(100 * 256 / 384)
    assert read("stall_share") == 0.0
    with pytest.raises(ValueError):
        read("another")
    note = note_step_ledger.read(run)
    assert note["steps"] == 3 and note["programs"]["chunk"] == [1, 256, 200]
    assert note["steps_by_prompt_programs"] == {
        "0": [1, 100.0], "1": [1, 100.0], "2": [1, 100.0]}
    assert note["exposed_by_phase_s"] == {"admit": 0.01, "append": 0.03,
                                          "inputs": 0.06}
    # a window that holds no whole step, or no prompt program, reads
    # nothing, and says so with None
    assert step_ledger.read(_run(100.21, 100.29), "stall_share") is None
    assert step_ledger.read(_run(100.3, 100.4), "prefill_fill") is None


def test_a_stall_is_judged_against_steps_of_its_own_composition(
        monkeypatch):
    t, rows = 0.0, []

    def step(seconds, chunks):
        nonlocal t
        rows.append(_row(len(rows), t, t + seconds,
                         chunk=(chunks, 256 * chunks, 256 * chunks)))
        t += seconds

    for _ in range(20):
        step(0.02, 0)           # decode alone
        step(0.10, 2)           # two chunks beside it
    step(0.60, 5)               # a first fill: alone of its kind
    step(0.19, 0)               # under ten medians of a decode step
    step(0.25, 0)               # over: stalled
    step(1.50, 2)               # a 1.5 s step of two chunks: stalled
    monkeypatch.setattr(step_ledger, "ledger",
                        lambda: {"rows": rows, "unseen": 0})
    run = _run(0.0, t)
    assert sorted(step_ledger.seconds(r) for r in step_ledger.stalls(rows)) \
        == pytest.approx([0.25, 1.50])
    assert step_ledger.read(run, "stall_share") == pytest.approx(
        100 * 1.75 / t)
    note = note_step_ledger.read(run)
    assert note["stalled_steps"] == 2
    assert [s["ms"] for s in note["longest_steps"]] == [
        1500.0, 600.0, 250.0, 190.0, 100.0]
    assert note["steps_by_prompt_programs"]["3+"] == [1, 600.0]


def test_a_program_without_a_ledger_reads_nothing(monkeypatch):
    """The parent of the PR that brought the ledger: the readers return
    None, and the result line leaves the metrics out."""
    monkeypatch.setattr(step_ledger, "ledger", lambda: None)
    run = _run(100.0, 110.0)
    for what in WHAT:
        assert step_ledger.read(run, what) is None
    assert note_step_ledger.read(run) is None
    # and on the program itself: a module without the name
    import deepspeed_tpu.devprof as devprof

    monkeypatch.undo()
    monkeypatch.delattr(devprof, "STEP_LEDGER")
    assert step_ledger.ledger() is None


def test_the_ledger_beside_a_capture(monkeypatch):
    """Steps are matched by ordinal, the offset between the two clocks
    is their median difference, and over the traced stretch the
    ledger's idle seconds (exposed, and from a drained step's end to
    the next step's start) stand beside chip 0's."""
    offset = 5000.0             # profiler's clock less the ledger's
    rows, spans, ops = [], [], []
    for i in range(10):
        t0 = 100.0 + 0.1 * i
        # the device runs from 10 ms into the step to 70 ms into it;
        # the fetch returns at 72 ms, the step ends at 80 ms, the
        # caller takes 20 ms, and the next dispatch call comes 8 ms in
        rows.append(_row(50 + i, t0, t0 + 0.08, between=0.02,
                         idle=[[t0, t0 + 0.008], [t0 + 0.072, t0 + 0.08]],
                         exposed={"admit": 0.008, "append": 0.008}))
        jitter = 2e-5 if i == 7 else 0.0
        spans.append(scopes.Span("dstpu/serving_step",
                                 t0 + offset + jitter, 0.08,
                                 {"n": str(50 + i)}))
        ops.append(scopes.Op("fusion.1:bf16[8]", t0 + offset + 0.01, 0.06))
    spans.append(scopes.Span("dstpu/serving_step", 99.0 + offset, 0.08,
                             {"n": "7"}))       # a step the ring dropped
    scoped = scopes.Scoped({0: ops[4:]}, {}, spans)
    monkeypatch.setattr(step_ledger, "ledger",
                        lambda: {"rows": rows, "unseen": 0})
    monkeypatch.setattr(scopes, "of_run", lambda run: scoped)
    got = note_step_ledger.read(_run(100.0, 101.0))["device"]
    assert got["steps_matched"] == 10
    assert got["clock_offset_s"] == pytest.approx(offset)
    assert got["offset_residual_p95_ms"] == pytest.approx(0.011, abs=2e-3)
    # the stretch: first operation's start to the last one's end
    assert got["stretch_s"] == pytest.approx(0.56)
    assert got["chip0_idle_s"] == pytest.approx(5 * 0.04)
    # of each 40 ms gap the ledger can prove 2 + 8 + 20 + 8 - 2 = 36
    assert got["ledger_idle_s"] == pytest.approx(5 * 0.036)
    assert got["ledger_over_device"] == pytest.approx(0.9)
    # the decode dispatch's own call, in the steps whole in the stretch
    assert got["dispatch_calls_s"] == 0.0
    for r in rows:
        r["phases"]["dispatch"] = 0.001
    got = note_step_ledger.read(_run(100.0, 101.0))["device"]
    # (the stretch opens inside one step and closes inside another)
    assert got["dispatch_calls_s"] == pytest.approx(4 * 0.001)

"""Per-request tracing + flight recorder (ISSUE 4): ring semantics,
Chrome/JSONL exports, sampling, serving identity, and hang postmortems
— all tier-1 (CPU, fast) except where noted."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax

from deepspeed_tpu.config import Config, TracingConfig
from deepspeed_tpu.request_trace import (FlightRecorder, NULL_TRACER,
                                         RequestTracer, events_to_chrome,
                                         postmortem_dump,
                                         read_jsonl, request_breakdown)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def gpt2_model():
    from deepspeed_tpu.models import gpt2

    cfg = gpt2.GPT2Config.tiny(dim=64, n_layers=2, n_heads=4,
                               max_seq_len=128)
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    return params, cfg


def _engine(params, cfg, **kw):
    from deepspeed_tpu.inference.serving import serving_engine

    kw.setdefault("max_batch", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("num_pages", 32)
    kw.setdefault("max_seq", 48)
    kw.setdefault("prefill_bucket", 8)
    kw.setdefault("decode_chunk", 4)
    return serving_engine(params, cfg, **kw)


def _serve(eng, cfg, n=4, prompt_len=12, new_tokens=8, seed=0):
    rng = np.random.default_rng(seed)
    for i in range(n):
        eng.submit(i, rng.integers(1, cfg.vocab_size, prompt_len).tolist(),
                   max_new_tokens=new_tokens)
    return eng.run()


class TestFlightRecorder:
    def test_ring_overflow_keeps_newest(self):
        r = FlightRecorder(capacity=8)
        for i in range(20):
            r.append((i, i, -1, "p", None))
        evs = r.events()
        assert len(evs) == 8
        assert [e[0] for e in evs] == list(range(12, 20))  # newest win
        assert r.dropped == 12
        assert r.total == 20
        r.clear()
        assert r.events() == [] and r.total == 0

    def test_under_capacity_order(self):
        r = FlightRecorder(capacity=8)
        for i in range(3):
            r.append((i, None, -1, "p", None))
        assert [e[0] for e in r.events()] == [0, 1, 2]
        assert r.dropped == 0

    def test_bad_capacity_raises(self):
        with pytest.raises(ValueError):
            FlightRecorder(capacity=0)

    def test_concurrent_writers_drop_nothing_under_capacity(self):
        import threading

        r = FlightRecorder(capacity=64 * 1024)
        n_threads, per = 8, 2000

        def work(tid):
            for i in range(per):
                r.append((time.monotonic_ns(), tid, -1, "e", None))

        ts = [threading.Thread(target=work, args=(t,))
              for t in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert r.total == n_threads * per
        assert len(r.events()) == n_threads * per


class TestTracer:
    def test_sampling_deterministic_and_rate_zero(self):
        tr = RequestTracer(sample_rate=0.5)
        decisions = [tr.sampled(i) for i in range(200)]
        assert decisions == [tr.sampled(i) for i in range(200)]
        assert 40 < sum(decisions) < 160        # roughly half
        # rate 0 IS disabled: nothing emits, not even non-request events
        tr0 = RequestTracer(sample_rate=0.0)
        assert not tr0.enabled
        tr0.event("anything", req=1)
        assert tr0.recorder.total == 0
        assert NULL_TRACER.sampled("x") is False
        with pytest.raises(ValueError):
            RequestTracer(sample_rate=1.5)

    def test_config_block_parses(self):
        c = Config.from_dict({"tracing": {"sample_rate": 0.25,
                                          "ring_capacity": 128}})
        assert c.tracing.enabled and c.tracing.sample_rate == 0.25
        assert TracingConfig.coerce(False).enabled is False
        assert TracingConfig.coerce(None).enabled is True
        assert RequestTracer.from_config(
            TracingConfig.coerce(False)) is NULL_TRACER
        with pytest.raises(ValueError):
            TracingConfig.coerce({"sample_rate": 2.0})

    def test_fold_comms_delta(self):
        from deepspeed_tpu.utils.trace import CommsLogger

        cl = CommsLogger()
        cl.record_event("all_reduce", 1024, 0.5)
        tr = RequestTracer()
        tr.fold_comms(cl)
        tr.fold_comms(cl)                        # no new records: no event
        evs = [e for e in tr.recorder.events()
               if e[3] == "comm_all_reduce"]
        assert len(evs) == 1
        assert evs[0][4]["bytes"] == 1024
        cl.record_event("all_reduce", 512, 0.1)
        tr.fold_comms(cl)
        evs = [e for e in tr.recorder.events()
               if e[3] == "comm_all_reduce"]
        assert len(evs) == 2 and evs[1][4]["bytes"] == 512


class TestServingTrace:
    def test_lifecycle_edges_recorded(self, gpt2_model):
        params, cfg = gpt2_model
        eng = _engine(params, cfg)
        assert eng.tracer.enabled                # default-on recorder
        _serve(eng, cfg, n=4)
        phases = [e[3] for e in eng.tracer.recorder.events()]
        for ph in ("queued", "admitted", "first_token", "decode_batch",
                   "finish"):
            assert phases.count(ph) >= 1, ph
        assert phases.count("queued") == 4
        assert phases.count("finish") == 4
        # TTFT cross-check (acceptance): trace mean vs telemetry mean
        # within 1 ms — same edges, independent clock plumbing
        bd = request_breakdown(eng.tracer.recorder.events())
        h = eng.registry.snapshot()["histograms"]["serving_ttft_seconds"]
        assert h["count"] == 4
        assert abs(h["mean"] - bd["summary"]["ttft_s"]["mean"]) < 1e-3

    def test_chrome_export_valid_catapult(self, gpt2_model, tmp_path):
        params, cfg = gpt2_model
        eng = _engine(params, cfg)
        _serve(eng, cfg, n=4)
        path = str(tmp_path / "trace.json")
        eng.tracer.export_chrome(path)
        with open(path) as f:
            trace = json.loads(f.read())         # valid JSON on disk
        evs = [e for e in trace["traceEvents"] if e["ph"] != "M"]
        ts = [e["ts"] for e in evs]
        assert ts == sorted(ts)                  # monotonic
        assert all(t >= 0 for t in ts)
        # matched async begin/end per request id, stack-disciplined
        depth = {}
        span_names = set()
        for e in evs:
            if e.get("cat") == "request" and e["ph"] in ("b", "e"):
                d = depth.get(e["id"], 0) + (1 if e["ph"] == "b" else -1)
                assert d >= 0, e
                depth[e["id"]] = d
                span_names.add(e["name"])
        assert all(v == 0 for v in depth.values())
        assert len(depth) == 4                   # one track per request
        # queued→admitted→first-token→finish covered by the span set
        assert {"request", "queued", "prefill", "decode"} <= span_names

    def test_jsonl_roundtrip(self, gpt2_model, tmp_path):
        params, cfg = gpt2_model
        eng = _engine(params, cfg)
        _serve(eng, cfg, n=2)
        path = str(tmp_path / "trace.jsonl")
        eng.tracer.export_jsonl(path)
        back = read_jsonl(path)
        orig = eng.tracer.recorder.events()
        assert len(back) == len(orig)
        assert [e[3] for e in back] == [e[3] for e in orig]
        assert [e[0] for e in back] == [e[0] for e in orig]

    def test_sampling_zero_emits_nothing(self, gpt2_model):
        params, cfg = gpt2_model
        eng = _engine(params, cfg, tracing={"sample_rate": 0.0})
        assert eng.tracer is NULL_TRACER
        _serve(eng, cfg, n=2)
        assert eng.tracer.recorder.total == 0
        eng2 = _engine(params, cfg, tracing=False)
        assert not eng2.tracer.enabled

    def test_output_token_identical_tracing_on_off(self, gpt2_model):
        params, cfg = gpt2_model
        out = {}
        for key, tracing in (("on", True), ("off", False)):
            eng = _engine(params, cfg, tracing=tracing)
            out[key] = _serve(eng, cfg, n=4, seed=3)
        assert out["on"] == out["off"]

    def test_shared_tracer_and_breakdown(self, gpt2_model):
        params, cfg = gpt2_model
        tr = RequestTracer()
        eng = _engine(params, cfg, tracing=tr)
        assert eng.tracer is tr
        _serve(eng, cfg, n=3)
        bd = request_breakdown(tr.recorder.events())
        assert bd["summary"]["requests"] == 3
        for comp in ("queue_wait_s", "prefill_s", "decode_s", "ttft_s",
                     "total_s"):
            c = bd["summary"][comp]
            assert c["n"] == 3
            assert 0 <= c["p50"] <= c["p95"]

    def test_zero_inference_stream_events(self):
        from deepspeed_tpu.models import llama

        cfg = llama.LlamaConfig.tiny(dim=64, n_layers=2, n_heads=4,
                                     n_kv_heads=2)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        eng = _engine(params, cfg,
                      zero_inference={"enabled": True, "tier": "host"})
        _serve(eng, cfg, n=2, new_tokens=4)
        phases = {e[3] for e in eng.tracer.recorder.events()}
        assert "zi_stream_fetch_issue" in phases
        assert "finish" in phases
        # fetch events render on the zero_inference track in the export
        trace = events_to_chrome(eng.tracer.recorder.events())
        names = {e["args"]["name"] for e in trace["traceEvents"]
                 if e["ph"] == "M" and e["name"] == "thread_name"}
        assert "zero_inference" in names and "serving" in names


class TestPostmortem:
    def test_simulated_hang_dump_names_stuck_request(self, gpt2_model,
                                                     tmp_path):
        params, cfg = gpt2_model
        eng = _engine(params, cfg, max_batch=1)
        rng = np.random.default_rng(0)
        eng.submit("stuck-req", rng.integers(1, cfg.vocab_size, 12).tolist(),
                   max_new_tokens=16)
        eng.submit("starved-req",
                   rng.integers(1, cfg.vocab_size, 12).tolist(),
                   max_new_tokens=16)
        eng.step()                 # admit + one decode chunk, no finish
        paths = postmortem_dump("unit_test", out_dir=str(tmp_path))
        assert paths
        blob = "".join(open(p).read() for p in paths)
        assert "stuck-req" in blob       # the in-flight request's events
        assert "starved-req" in blob     # the queued one too
        meta = json.loads(open(paths[0]).readline())
        assert meta["flight_recorder"]["reason"] == "unit_test"
        # dump is reparseable and ends with the LAST events
        evs = read_jsonl(paths[0])
        assert evs and evs[0][0] <= evs[-1][0]

    def test_watchdog_timeout_dumps_and_exits_42(self, tmp_path):
        """Forced watchdog timeout in a SUBPROCESS: the hang must leave
        a flight-recorder dump whose events identify the hung request,
        then abort with the launcher-visible exit code 42."""
        script = r"""
import os, time
from deepspeed_tpu.request_trace import RequestTracer
from deepspeed_tpu.utils.watchdog import Watchdog

tr = RequestTracer()
tr.event("queued", req="hung-req-77")
tr.event("admitted", req="hung-req-77", slot=0)
wd = Watchdog(timeout_s=0.5, poll_s=0.05).start()
wd.pet()
time.sleep(60)      # never pets again: the simulated hung collective
"""
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   DSTPU_TRACE_DUMP_DIR=str(tmp_path))
        proc = subprocess.run([sys.executable, "-c", script], env=env,
                              cwd=REPO, capture_output=True, text=True,
                              timeout=180)
        assert proc.returncode == 42, proc.stderr[-2000:]
        dumps = [p for p in os.listdir(tmp_path)
                 if p.startswith("flight_watchdog_timeout")]
        assert dumps, os.listdir(tmp_path)
        blob = open(tmp_path / dumps[0]).read()
        assert "hung-req-77" in blob
        assert "admitted" in blob

    def test_watchdog_guards_failing_on_timeout(self, tmp_path):
        """A raising on_timeout callback must not mask the abort path;
        with abort disabled the watchdog still records it fired."""
        from deepspeed_tpu.utils.watchdog import Watchdog

        calls = []

        def bad_callback():
            calls.append(1)
            raise RuntimeError("dump failed")

        wd = Watchdog(timeout_s=0.2, poll_s=0.05,
                      on_timeout=bad_callback, abort_on_timeout=False)
        wd.start()
        deadline = time.monotonic() + 10.0
        while not wd.fired and time.monotonic() < deadline:
            time.sleep(0.05)
        wd.stop()
        assert wd.fired and calls == [1]

    def test_flush_all_exporters(self, tmp_path):
        from deepspeed_tpu.telemetry import (MetricsRegistry,
                                             TelemetryExporter,
                                             flush_all_exporters)

        reg = MetricsRegistry()
        reg.counter("c").inc(3)
        path = str(tmp_path / "metrics.prom")
        exp = TelemetryExporter(reg, prometheus_path=path,
                                interval_s=3600.0)
        exp.maybe_export()               # first tick consumed
        reg.counter("c").inc(4)
        assert flush_all_exporters() >= 1   # force despite interval
        assert "c 7" in open(path).read()

    def test_excepthook_chain_dumps(self, tmp_path, monkeypatch):
        import deepspeed_tpu.request_trace as rt

        monkeypatch.setattr(rt, "_excepthook_installed", False)
        seen = []
        monkeypatch.setattr(sys, "excepthook",
                            lambda *a: seen.append(a), raising=False)
        rt.install_excepthook()
        tr = RequestTracer()
        tr.event("queued", req="exc-req")
        monkeypatch.setenv("DSTPU_TRACE_DUMP_DIR", str(tmp_path))
        try:
            raise RuntimeError("boom")
        except RuntimeError:
            sys.excepthook(*sys.exc_info())
        assert seen                       # previous hook still ran
        dumps = [p for p in os.listdir(tmp_path)
                 if p.startswith("flight_exception")]
        assert dumps

"""Serving control plane (ISSUE 6): per-tier SLO classification &
goodput accounting, the /statusz//healthz//requestz introspection
server, the /metrics lifecycle fix, and the bench regression gate —
all tier-1 (CPU, fast)."""

import json
import os
import sys
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax

from deepspeed_tpu.config import Config, SLOConfig, SLOTierObjective
from deepspeed_tpu.slo import NULL_SLO_TRACKER, SLOTracker
from deepspeed_tpu.telemetry import (MetricsRegistry,
                                     parse_prometheus_text)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


# --------------------------------------------------------------- helpers
class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt
        return self.t


def make_tracker(clock, tiers=None, registry=None, tracer=None, **kw):
    cfg = SLOConfig.coerce({
        "tiers": tiers or {"default": {"ttft_s": 1.0,
                                       "deadline_s": 10.0}},
        **kw})
    return SLOTracker(cfg, registry or MetricsRegistry(),
                      tracer=tracer, clock=clock)


class RecordingTracer:
    enabled = True

    def __init__(self):
        self.events = []

    def event(self, phase, req=None, slot=-1, attrs=None):
        self.events.append((phase, req, attrs))


# ------------------------------------------------------------ config
class TestSLOConfig:
    def test_coerce_and_defaults(self):
        c = SLOConfig.coerce(None)
        assert not c.enabled
        c = SLOConfig.coerce(True)
        assert c.enabled and "default" in c.tiers
        c = SLOConfig.coerce({"tiers": {"fast": {"ttft_s": 0.5}},
                              "default_tier": "fast"})
        assert c.enabled and c.tiers["fast"].ttft_s == 0.5
        # declaring tiers without covering default_tier is a config
        # error, not a silent KeyError at submit time
        with pytest.raises(ValueError, match="default_tier"):
            SLOConfig.coerce({"tiers": {"fast": {"ttft_s": 0.5}}})

    def test_validation(self):
        with pytest.raises(ValueError, match="positive"):
            SLOTierObjective.from_dict({"ttft_s": -1})
        with pytest.raises(ValueError, match="target"):
            SLOTierObjective.from_dict({"target": 0.0})
        with pytest.raises(ValueError, match="window_s"):
            SLOConfig.coerce({"window_s": 0})
        with pytest.raises(ValueError, match="burn_windows"):
            SLOConfig.coerce({"burn_windows_s": []})
        with pytest.raises(TypeError):
            SLOConfig.coerce(42)
        # explicit enabled: false disables even with tiers present
        assert not SLOConfig.coerce(
            {"enabled": False, "tiers": {"x": {}}}).enabled

    def test_config_block_parse(self):
        c = Config.from_dict({"slo": {
            "tiers": {"interactive": {"ttft_s": 0.2, "target": 0.999},
                      "batch": {"deadline_s": 60}},
            "default_tier": "interactive"}})
        assert c.slo.enabled
        assert c.slo.tiers["interactive"].ttft_s == 0.2
        assert c.slo.tiers["batch"].deadline_s == 60.0
        # absent block stays disabled
        assert not Config.from_dict({}).slo.enabled

    def test_default_tier_mismatch_caught(self):
        # sanity for the test above written with a narrative assert
        c = SLOConfig.coerce({"tiers": {"default": {}}})
        assert c.default_tier in c.tiers


# ----------------------------------------------------------- classifier
class TestSLOClassification:
    def test_deadline_exactly_met_attains(self):
        clk = FakeClock()
        tr = make_tracker(clk, tiers={"default": {"deadline_s": 10.0}})
        tr.on_submit("r")
        clk.advance(10.0)          # finish lands EXACTLY on the bound
        assert tr.on_finish("r") is True
        # one nanosecond-ish past it violates
        tr.on_submit("r2")
        clk.advance(10.0 + 1e-6)
        assert tr.on_finish("r2") is False

    def test_ttft_and_itl_violations_attributed(self):
        clk = FakeClock()
        reg = MetricsRegistry()
        tr = make_tracker(clk, registry=reg, tiers={"default": {
            "ttft_s": 1.0, "itl_s": 0.5}})
        # ttft blows, itl fine
        tr.on_submit("a")
        clk.advance(2.0)
        tr.on_token("a")
        clk.advance(0.1)
        tr.on_token("a")
        assert tr.on_finish("a") is False
        # ttft fine, worst gap blows
        tr.on_submit("b")
        clk.advance(0.5)
        tr.on_token("b")
        clk.advance(0.9)           # the bad gap
        tr.on_token("b")
        clk.advance(0.1)
        tr.on_token("b")
        assert tr.on_finish("b") is False
        cnt = reg.snapshot()["counters"]
        assert cnt["slo_default_ttft_violations"] == 1
        assert cnt["slo_default_itl_violations"] == 1
        assert cnt["slo_default_deadline_violations"] == 0
        assert cnt["slo_default_violated_requests"] == 2

    def test_zero_traffic_window_reports_one_not_nan(self):
        clk = FakeClock()
        reg = MetricsRegistry()
        tr = make_tracker(clk, registry=reg, window_s=5.0)
        snap = tr.snapshot()
        t = snap["tiers"]["default"]
        assert t["attainment"] == 1.0
        assert t["goodput_tokens_per_s"] == 0.0
        assert all(b == 0.0 for b in t["burn_rates"].values())
        assert reg.snapshot()["gauges"]["slo_default_attainment"] == 1.0
        # violations age OUT of the window too: attainment returns to
        # 1.0 once the engine idles past window_s
        tr.on_submit("r")
        clk.advance(20.0)          # blows the 10s deadline
        assert tr.on_finish("r") is False
        assert tr.snapshot()["tiers"]["default"]["attainment"] == 0.0
        clk.advance(6.0)           # sample ages out of the 5s window
        assert tr.snapshot()["tiers"]["default"]["attainment"] == 1.0

    def test_goodput_counts_only_attained_tokens(self):
        clk = FakeClock()
        reg = MetricsRegistry()
        tr = make_tracker(clk, registry=reg,
                          tiers={"default": {"deadline_s": 5.0}})
        tr.on_submit("ok")
        for _ in range(7):
            clk.advance(0.1)
            tr.on_token("ok")
        assert tr.on_finish("ok") is True
        tr.on_submit("late")
        for _ in range(9):
            clk.advance(1.0)
            tr.on_token("late")
        assert tr.on_finish("late") is False
        cnt = reg.snapshot()["counters"]
        assert cnt["slo_default_tokens"] == 16
        assert cnt["slo_default_goodput_tokens"] == 7

    def test_unknown_tier_and_disabled_tier_raise(self):
        tr = make_tracker(FakeClock())
        with pytest.raises(ValueError, match="unknown SLO tier"):
            tr.on_submit("r", tier="nope")
        with pytest.raises(ValueError, match="disabled"):
            NULL_SLO_TRACKER.on_submit("r", tier="interactive")
        NULL_SLO_TRACKER.on_submit("r")        # no tier: fine, no-op
        assert NULL_SLO_TRACKER.on_finish("r") is None

    def test_unknown_ids_ignored_and_forget(self):
        tr = make_tracker(FakeClock())
        tr.on_token("never-submitted")         # no throw
        assert tr.on_finish("never-submitted") is None
        tr.on_submit("r")
        tr.forget("r")
        assert tr.on_finish("r") is None

    def test_burn_alert_multiwindow_with_hysteresis(self):
        clk = FakeClock()
        tracer = RecordingTracer()
        reg = MetricsRegistry()
        tr = make_tracker(
            clk, registry=reg, tracer=tracer,
            tiers={"default": {"deadline_s": 1.0, "target": 0.5}},
            window_s=10.0, burn_windows_s=(10.0, 40.0),
            burn_threshold=1.5)
        # every request violates: rate 1.0 / budget 0.5 = burn 2.0 > 1.5
        for i in range(4):
            tr.on_submit(i)
            clk.advance(2.0)
            tr.on_finish(i)
        alerts = [e for e in tracer.events if e[0] == "slo_burn_alert"]
        assert len(alerts) == 1, "alert must fire ONCE per trip"
        assert alerts[0][2]["tier"] == "default"
        assert alerts[0][2]["burn_10s"] > 1.5
        assert reg.snapshot()["counters"][
            "slo_default_burn_alerts"] == 1
        # recover: violations age out of both windows, then a fresh
        # violation burst trips a SECOND alert (hysteresis re-armed)
        clk.advance(50.0)
        for i in range(8):
            tr.on_submit(f"ok{i}")
            clk.advance(0.1)
            tr.on_finish(f"ok{i}")
        assert not tr.snapshot()["tiers"]["default"]["alert_active"]
        clk.advance(50.0)
        for i in range(4):
            tr.on_submit(f"bad{i}")
            clk.advance(2.0)
            tr.on_finish(f"bad{i}")
        alerts = [e for e in tracer.events if e[0] == "slo_burn_alert"]
        assert len(alerts) == 2

    def test_maybe_refresh_decays_idle_gauges(self):
        """An idle engine's burn gauges must decay as violations age
        out of the window — the time-driven refresh, not a finish
        event, is what un-latches them for a /metrics-only scraper."""
        clk = FakeClock()
        reg = MetricsRegistry()
        tr = make_tracker(
            clk, registry=reg,
            tiers={"default": {"deadline_s": 1.0, "target": 0.5}},
            window_s=10.0, burn_windows_s=(10.0,), burn_threshold=1.5)
        tr.on_submit("r")
        clk.advance(5.0)
        tr.on_finish("r")
        g = reg.snapshot()["gauges"]
        assert g["slo_default_burn_rate_10s"] == 2.0
        assert tr._tiers["default"].alert_active
        # nothing finishes; time passes; maybe_refresh (the engine's
        # per-step call) decays the gauge and re-arms the alert
        clk.advance(60.0)
        tr.maybe_refresh()
        g = reg.snapshot()["gauges"]
        assert g["slo_default_burn_rate_10s"] == 0.0
        assert g["slo_default_attainment"] == 1.0
        assert not tr._tiers["default"].alert_active
        # rate limit: a second call inside min_interval_s is one
        # compare and returns untouched
        tr.maybe_refresh()

    def test_alert_hook_may_reenter_tracker(self):
        """The alert fires OUTSIDE the tracker lock, so a hook that
        calls back into snapshot() (the natural enrichment) must not
        deadlock the serving thread."""
        clk = FakeClock()
        seen = []
        cfg = SLOConfig.coerce({
            "tiers": {"default": {"deadline_s": 1.0, "target": 0.5}},
            "burn_windows_s": (10.0,), "burn_threshold": 1.0})
        tr = SLOTracker(cfg, MetricsRegistry(),
                        alert_hook=lambda tier, info: seen.append(
                            tr.snapshot()["tiers"][tier]["attainment"]),
                        clock=clk)
        tr.on_submit("r")
        clk.advance(5.0)
        tr.on_finish("r")       # would hang forever if fired under lock
        assert seen == [0.0]

    def test_pluggable_alert_hook_replaces_default(self):
        clk = FakeClock()
        got = []
        cfg = SLOConfig.coerce({
            "tiers": {"default": {"deadline_s": 1.0, "target": 0.5}},
            "burn_windows_s": (10.0,), "burn_threshold": 1.0})
        tracer = RecordingTracer()
        tr = SLOTracker(cfg, MetricsRegistry(), tracer=tracer,
                        alert_hook=lambda tier, info: got.append(
                            (tier, info)),
                        clock=clk)
        tr.on_submit("r")
        clk.advance(5.0)
        tr.on_finish("r")
        assert got and got[0][0] == "default"
        assert not any(e[0] == "slo_burn_alert" for e in tracer.events)


# ------------------------------------------------------- engine fixture
@pytest.fixture(scope="module")
def gpt2_model():
    from deepspeed_tpu.models import gpt2

    cfg = gpt2.GPT2Config.tiny(dim=32, n_layers=2, n_heads=2,
                               max_seq_len=64)
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _engine(cfg, params, **kw):
    from deepspeed_tpu.inference.serving import serving_engine

    kw.setdefault("max_batch", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("num_pages", 16)
    kw.setdefault("max_seq", 32)
    kw.setdefault("prefill_bucket", 8)
    return serving_engine(params, cfg, **kw)


SLO_BLOCK = {"tiers": {"interactive": {"ttft_s": 60.0,
                                       "deadline_s": 120.0},
                       "batch": {"deadline_s": 600.0, "target": 0.9}},
             "default_tier": "interactive"}


# ------------------------------------------------------- engine wiring
class TestEngineSLO:
    def test_tiers_classified_and_exposed(self, gpt2_model, devices):
        cfg, params = gpt2_model
        eng = _engine(cfg, params, slo=SLO_BLOCK)
        for i in range(4):
            eng.submit(i, [3 + i, 5, 7], max_new_tokens=5,
                       tier="batch" if i % 2 else None)
        out = eng.run()
        assert len(out) == 4
        cnt = eng.registry.snapshot()["counters"]
        # generous targets on a tiny model: everything attains
        assert cnt["slo_interactive_attained_requests"] == 2
        assert cnt["slo_batch_attained_requests"] == 2
        assert cnt["slo_interactive_goodput_tokens"] == 10
        assert cnt["slo_batch_goodput_tokens"] == 10
        # prometheus exposition carries the family
        fams = parse_prometheus_text(eng.registry.prometheus_text())
        assert "dstpu_slo_interactive_attainment" in fams
        assert "dstpu_slo_batch_goodput_tokens" in fams
        snap = eng.slo_tracker.snapshot()
        assert snap["tiers"]["interactive"]["attainment"] == 1.0

    def test_unknown_tier_rejected_before_queue(self, gpt2_model,
                                                devices):
        cfg, params = gpt2_model
        eng = _engine(cfg, params, slo=SLO_BLOCK)
        with pytest.raises(ValueError, match="unknown SLO tier"):
            eng.submit("r", [3, 5], max_new_tokens=2, tier="nope")
        assert len(eng.queue) == 0
        # slo disabled + explicit tier: loud failure, not a silent drop
        eng2 = _engine(cfg, params)
        with pytest.raises(ValueError, match="disabled"):
            eng2.submit("r", [3, 5], max_new_tokens=2,
                        tier="interactive")

    def test_tokens_identical_slo_on_off(self, gpt2_model, devices):
        cfg, params = gpt2_model
        prompts = {0: [3, 5, 7], 1: [11, 2], 2: [9, 9, 4]}
        outs = {}
        for on in (True, False):
            eng = _engine(cfg, params, slo=SLO_BLOCK if on else None)
            for rid, p in prompts.items():
                eng.submit(rid, p, max_new_tokens=6)
            outs[on] = eng.run()
        assert outs[True] == outs[False]
        assert len(outs[False]) == 3

    def test_preempted_request_keeps_original_arrival(self, devices):
        from deepspeed_tpu.models import llama
        from deepspeed_tpu.inference.serving import serving_engine

        cfg = llama.LlamaConfig.tiny(dim=32, n_layers=2, n_heads=2,
                                     n_kv_heads=2)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        # tiny pool: both sequences cannot hold all their pages at once
        # (same geometry as test_serving's preemption test)
        eng = serving_engine(
            params, cfg, max_batch=2, page_size=4, num_pages=7,
            max_seq=40, prefill_bucket=4,
            slo={"tiers": {"default": {"deadline_s": 300.0}}})
        eng.submit("x", [5, 9, 2], max_new_tokens=12)
        eng.submit("y", [17, 3, 3], max_new_tokens=12)
        arrivals = {r.req_id: r.t_arrival for r in eng.queue}
        out = eng.run()
        assert len(out) == 2
        assert int(eng.registry.snapshot()["counters"][
            "serving_preempted_requests"]) >= 1
        cnt = eng.registry.snapshot()["counters"]
        # the preempted request classified ONCE, against its original
        # arrival — never re-registered by the requeue
        assert cnt["slo_default_attained_requests"] + \
            cnt["slo_default_violated_requests"] == 2
        # requeued incarnation carried t_arrival through (both
        # finished; their recorded arrivals were the submit-time ones)
        assert len(arrivals) == 2

    def test_slo_without_telemetry_still_classifies(self, gpt2_model,
                                                    devices):
        cfg, params = gpt2_model
        eng = _engine(cfg, params, telemetry=False, slo=SLO_BLOCK)
        eng.submit("r", [3, 5, 7], max_new_tokens=4)
        eng.run()
        # registry metrics are no-ops, but the window classification is
        # real: the snapshot view still answers
        snap = eng.slo_tracker.snapshot()
        assert snap["tiers"]["interactive"]["window_finished"] == 1
        assert snap["tiers"]["interactive"]["attainment"] == 1.0


# ----------------------------------------------------- introspection
class TestIntrospection:
    def test_statusz_healthz_requestz_http_roundtrip(self, gpt2_model,
                                                     devices):
        cfg, params = gpt2_model
        eng = _engine(cfg, params, slo=SLO_BLOCK,
                      telemetry={"http_port": 0, "interval_s": 0.0})
        try:
            for i in range(3):
                eng.submit(i, [3 + i, 5, 7], max_new_tokens=4)
            eng.run()
            base = f"http://127.0.0.1:{eng._tel_exporter.port}"

            def get(path):
                with urllib.request.urlopen(base + path,
                                            timeout=10) as r:
                    return json.loads(r.read().decode())

            s = get("/statusz")
            assert s["schema_version"] == 1
            assert s["engine"] == "ServingEngine"
            assert len(s["slots"]) == 2
            assert s["queue"]["depth"] == 0
            assert 0.0 <= s["kv"]["utilization"] <= 1.0
            assert s["slo"]["enabled"]
            assert s["slo"]["tiers"]["interactive"]["attainment"] == 1.0
            assert "serving_admitted_requests" in \
                s["metrics"]["counters"]
            h = get("/healthz")
            assert h["alive"] and h["ready"]
            assert h["last_step_age_s"] is not None
            r = get("/requestz?id=1")
            assert r["found"] and r["state"] == "finished"
            phases = [e["phase"] for e in r["events"]]
            assert "queued" in phases and "finish" in phases
            assert "ttft_s" in r.get("breakdown", {})
            # unknown id → 404 with a JSON body
            with pytest.raises(urllib.error.HTTPError) as ei:
                get("/requestz?id=zzz")
            assert ei.value.code == 404
            with pytest.raises(urllib.error.HTTPError) as ei:
                get("/requestz")           # missing query
            assert ei.value.code == 400
            # /metrics still serves the exposition on the same port
            with urllib.request.urlopen(base + "/metrics",
                                        timeout=10) as resp:
                fams = parse_prometheus_text(resp.read().decode())
            assert "dstpu_serving_admitted_requests" in fams
        finally:
            eng.shutdown()

    def test_statusz_shows_live_slots_and_queue(self, gpt2_model,
                                                devices):
        cfg, params = gpt2_model
        eng = _engine(cfg, params, max_batch=1)
        eng.submit("a", [3, 5, 7], max_new_tokens=4)
        eng.submit("b", [4, 6], max_new_tokens=4)
        eng.step()                      # a admitted, b queued
        s = eng.statusz()               # providers also work in-process
        assert s["active_slots"] == 1
        assert s["slots"][0]["req"] == "a"
        assert s["slots"][0]["state"] == "decode"
        assert s["slots"][0]["pages"] >= 1
        assert s["queue"]["depth"] == 1
        assert s["queue"]["head"][0]["req"] == "b"
        rz = eng.requestz("b")
        assert rz["state"] == "queued" and rz["found"]
        eng.run()

    def test_healthz_watchdog_feed(self, gpt2_model, devices):
        from deepspeed_tpu.utils.watchdog import Watchdog

        cfg, params = gpt2_model
        eng = _engine(cfg, params,
                      telemetry={"http_port": 0, "interval_s": 0.0})
        try:
            wd = Watchdog(timeout_s=600.0)   # not started: no thread
            eng.attach_watchdog(wd)
            h = eng.healthz()
            assert h["ready"] and not h["watchdog"]["fired"]
            assert h["watchdog"]["last_heartbeat_age_s"] >= 0.0
            wd.fired = True                  # simulate the timeout path
            assert not eng.healthz()["ready"]
            # the HTTP endpoint turns unready into a 503
            base = f"http://127.0.0.1:{eng._tel_exporter.port}"
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(base + "/healthz", timeout=10)
            assert ei.value.code == 503
            assert json.loads(ei.value.read().decode())["ready"] is False
        finally:
            eng.shutdown()

    def test_zero_inference_statusz_carries_stream_view(self, devices):
        from deepspeed_tpu.models import llama
        from deepspeed_tpu.inference.serving import serving_engine

        cfg = llama.LlamaConfig.tiny(dim=32, n_layers=2, n_heads=2,
                                     n_kv_heads=2)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        eng = serving_engine(
            params, cfg, zero_inference={"enabled": True},
            max_batch=2, page_size=8, num_pages=16, max_seq=32,
            prefill_bucket=8)
        eng.submit("r", [5, 9, 2], max_new_tokens=4)
        eng.run()
        s = eng.statusz()
        zi = s["zero_inference"]
        assert zi["plan"]["n_streamed"] == 2
        assert zi["layer_sweeps"] > 0
        assert zi["bytes_uploaded"] > 0
        assert "stream_stall_s" in zi

    def test_http_lifecycle_fixed_port_back_to_back(self, gpt2_model,
                                                    devices):
        """Satellite: back-to-back engine constructions on ONE fixed
        port (the test suite's pattern) must not EADDRINUSE or leak
        the serving thread — shutdown() is the teardown contract."""
        import socket
        import threading

        cfg, params = gpt2_model
        with socket.socket() as sck:      # grab a free fixed port
            sck.bind(("127.0.0.1", 0))
            port = sck.getsockname()[1]
        for round_ in range(3):
            eng = _engine(cfg, params,
                          telemetry={"http_port": port,
                                     "interval_s": 0.0})
            assert eng._tel_exporter.port == port
            base = f"http://127.0.0.1:{port}"
            with urllib.request.urlopen(base + "/healthz",
                                        timeout=10) as r:
                assert json.loads(r.read().decode())["alive"]
            eng.shutdown()
            eng.shutdown()                # idempotent
            assert not any(
                t.name == "dstpu-telemetry-http" and t.is_alive()
                for t in threading.enumerate()), \
                f"round {round_}: serving thread leaked"

    def test_statusz_sample_stamp_roundtrip(self):
        """Acceptance: STATUSZ_SAMPLE.json is stamped in-repo (by
        tools/telemetry_dump.py over real HTTP) and parses against the
        versioned schema."""
        path = os.path.join(REPO, "STATUSZ_SAMPLE.json")
        assert os.path.exists(path), \
            "run tools/telemetry_dump.py --cpu to stamp it"
        with open(path) as f:
            d = json.load(f)
        s = d["statusz"]
        assert s["schema_version"] == 1
        for key in ("engine", "uptime_s", "slots", "queue", "kv",
                    "prefix_cache", "speculative", "slo", "metrics"):
            assert key in s, f"statusz schema lost {key!r}"
        assert s["slo"]["enabled"]
        for tier in s["slo"]["tiers"].values():
            assert 0.0 <= tier["attainment"] <= 1.0
            assert "goodput_tokens_per_s" in tier
            assert tier["burn_rates"]
        assert d["healthz"]["alive"] is True
        assert d["requestz_sample"]["found"] is True
        assert any(e["phase"] == "finish"
                   for e in d["requestz_sample"]["events"])

    def test_dstpu_top_renders_sample(self):
        """The TUI renders a frame from the committed sample snapshot
        (schema drift breaks this before it breaks an operator)."""
        import dstpu_top

        with open(os.path.join(REPO, "STATUSZ_SAMPLE.json")) as f:
            d = json.load(f)
        lines = dstpu_top.render(d["statusz"], d["healthz"])
        text = "\n".join(lines)
        assert "READY" in text
        assert "kv" in text and "tier" in text
        assert "interactive" in text and "batch" in text


# -------------------------------------------------------- stats shim
class TestStatsShimRemoved:
    def test_stats_attribute_gone(self, gpt2_model, devices):
        """The PR 6 deprecation shim was removed on its announced PR 9
        schedule: reading .stats is now an AttributeError, not a
        warning — readers must use engine.registry.snapshot()."""
        cfg, params = gpt2_model
        eng = _engine(cfg, params)
        with pytest.raises(AttributeError):
            eng.stats


# -------------------------------------------------------- bench gate
class TestBenchGate:
    def _manifest(self):
        with open(os.path.join(REPO, "BENCH_BASELINE.json")) as f:
            return json.load(f)

    def test_gate_passes_on_committed_evidence(self):
        from bench_gate import run_gate

        verdict = run_gate(self._manifest(), REPO)
        failed = [r for r in verdict["rows"] if r["status"] == "FAIL"]
        assert verdict["ok"], f"gate fails on committed evidence: " \
                              f"{failed}"
        assert verdict["passed"] >= 8

    def test_gate_fails_on_synthetic_regression(self, tmp_path):
        from bench_gate import run_gate

        # copy the evidence, regress one metric 40% past its bound
        for f in ("SPEC_BENCH.json", "PREFIX_BENCH.json",
                  "SERVING_BENCH.json", "SERVING_OVERHEAD.json"):
            src = os.path.join(REPO, f)
            if os.path.exists(src):
                with open(src) as fh:
                    (tmp_path / f).write_text(fh.read())
        spec = json.loads((tmp_path / "SPEC_BENCH.json").read_text())
        spec["spec_ab"]["speedup"] *= 0.5
        (tmp_path / "SPEC_BENCH.json").write_text(json.dumps(spec))
        verdict = run_gate(self._manifest(), str(tmp_path))
        assert not verdict["ok"]
        bad = [r for r in verdict["rows"] if r["status"] == "FAIL"]
        assert any(r["path"] == "spec_ab.speedup" for r in bad)
        assert all("regressed past bound" in r["reason"] for r in bad)

    def test_schema_break_fails_missing_file_skips(self, tmp_path):
        from bench_gate import run_gate

        manifest = {"entries": [
            {"file": "GONE.json", "path": "value", "baseline": 1.0},
            {"file": "PRESENT.json", "path": "deleted.metric",
             "baseline": 1.0},
        ]}
        (tmp_path / "PRESENT.json").write_text('{"other": 1}')
        v = run_gate(manifest, str(tmp_path))
        by_file = {r["file"]: r for r in v["rows"]}
        assert by_file["GONE.json"]["status"] == "SKIP"
        assert by_file["PRESENT.json"]["status"] == "FAIL"
        assert "schema break" in by_file["PRESENT.json"]["reason"]
        assert not v["ok"]
        # --strict turns the skip into a failure
        v = run_gate(manifest, str(tmp_path), strict=True)
        assert {r["status"] for r in v["rows"]} == {"FAIL"}

    def test_lower_is_better_and_when_guard(self, tmp_path):
        from bench_gate import run_gate

        (tmp_path / "E.json").write_text(json.dumps(
            {"backend": "cpu", "overhead": 0.5, "tps": 10.0}))
        manifest = {"entries": [
            {"file": "E.json", "path": "overhead", "baseline": 0.1,
             "direction": "lower", "abs_tol": 0.05},
            {"file": "E.json", "path": "tps", "baseline": 100.0,
             "when": {"path": "backend", "equals": "tpu"}},
        ]}
        v = run_gate(manifest, str(tmp_path))
        by_path = {r["path"]: r for r in v["rows"]}
        assert by_path["overhead"]["status"] == "FAIL"   # 0.5 > 0.15
        assert by_path["tps"]["status"] == "SKIP"        # cpu != tpu

    def test_update_rebaselines(self, tmp_path):
        from bench_gate import run_gate, update_baselines

        (tmp_path / "E.json").write_text('{"v": 7.5}')
        manifest = {"entries": [
            {"file": "E.json", "path": "v", "baseline": 100.0,
             "rel_tol": 0.1}]}
        assert not run_gate(manifest, str(tmp_path))["ok"]
        res = update_baselines(manifest, str(tmp_path))
        assert res["updated"] == 1
        assert manifest["entries"][0]["baseline"] == 7.5
        assert run_gate(manifest, str(tmp_path))["ok"]

    def test_cli_exit_codes(self, tmp_path):
        """--check exits 0 on the committed evidence and nonzero on a
        regressed copy (the enforced-contract acceptance)."""
        import subprocess

        env = dict(os.environ, JAX_PLATFORMS="cpu")
        tool = os.path.join(REPO, "tools", "bench_gate.py")
        rc = subprocess.run(
            [sys.executable, tool, "--check"], env=env,
            capture_output=True, text=True, timeout=120)
        assert rc.returncode == 0, rc.stdout + rc.stderr
        # regressed copy in a scratch root
        for f in ("SPEC_BENCH.json", "PREFIX_BENCH.json",
                  "SERVING_BENCH.json", "SERVING_OVERHEAD.json"):
            src = os.path.join(REPO, f)
            if os.path.exists(src):
                with open(src) as fh:
                    (tmp_path / f).write_text(fh.read())
        prefix = json.loads(
            (tmp_path / "PREFIX_BENCH.json").read_text())
        prefix["prefix_ab"]["hit_rate"] = 0.2
        (tmp_path / "PREFIX_BENCH.json").write_text(
            json.dumps(prefix))
        rc = subprocess.run(
            [sys.executable, tool, "--check", "--files-root",
             str(tmp_path)], env=env,
            capture_output=True, text=True, timeout=120)
        assert rc.returncode == 1, rc.stdout + rc.stderr
        assert "FAIL" in rc.stdout

"""Unified runtime telemetry (ISSUE 2): registry primitives, sinks, and
the serving-engine instrumentation — all tier-1 (CPU, fast)."""

import json
import threading
import urllib.request

import numpy as np
import pytest

import jax

from deepspeed_tpu.config import Config, TelemetryConfig
from deepspeed_tpu.telemetry import (LATENCY_BUCKETS_S, MetricsRegistry,
                                     NULL_METRIC, TelemetryExporter,
                                     parse_prometheus_text)


class TestPrimitives:
    def test_counter_gauge_basics(self):
        r = MetricsRegistry()
        c = r.counter("c", "help text")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        with pytest.raises(ValueError):
            c.inc(-1)
        g = r.gauge("g")
        g.set(7)
        g.set(4.25)
        assert g.value == 4.25
        # get-or-create returns the SAME object; kind mismatch raises
        assert r.counter("c") is c
        with pytest.raises(TypeError):
            r.gauge("c")

    def test_histogram_bucket_boundaries_and_inf(self):
        r = MetricsRegistry()
        h = r.histogram("h", buckets=(1.0, 2.0, 5.0))
        # le semantics: a value exactly on a bound lands IN that bucket
        h.observe(1.0)       # -> le=1
        h.observe(1.5)       # -> le=2
        h.observe(2.0)       # -> le=2
        h.observe(4.9)       # -> le=5
        h.observe(100.0)     # -> +Inf only
        cum = dict((le, c) for le, c in h.bucket_counts())
        assert cum[1.0] == 1
        assert cum[2.0] == 3
        assert cum[5.0] == 4
        assert cum[float("inf")] == 5          # +Inf is always total
        assert h.count == 5
        assert h.sum == pytest.approx(109.4)
        with pytest.raises(ValueError):
            r.histogram("bad", buckets=(2.0, 1.0))
        with pytest.raises(ValueError):
            # same name, different buckets: a silent split-brain metric
            r.histogram("h", buckets=(1.0, 2.0))

    def test_thread_safety_under_concurrent_writers(self):
        r = MetricsRegistry()
        c = r.counter("tc")
        h = r.histogram("th", buckets=(0.5,))
        n_threads, per_thread = 8, 2000

        def work():
            for i in range(per_thread):
                c.inc()
                h.observe(float(i % 2))       # half le=0.5, half +Inf

        ts = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        total = n_threads * per_thread
        assert c.value == total
        assert h.count == total
        cum = dict(h.bucket_counts())
        assert cum[0.5] == total // 2
        assert cum[float("inf")] == total

    def test_disabled_registry_is_noop(self):
        r = MetricsRegistry(enabled=False)
        c = r.counter("x")
        # every accessor hands back the SHARED null singleton: no state,
        # no lock, nothing to pay on a hot path
        assert c is NULL_METRIC
        assert r.gauge("y") is NULL_METRIC
        assert r.histogram("z") is NULL_METRIC
        c.inc(100)
        NULL_METRIC.observe(1.0)
        NULL_METRIC.set(5.0)
        assert c.value == 0.0
        with r.span("anything"):             # no TraceAnnotation either
            pass
        snap = r.snapshot()
        assert snap["enabled"] is False
        assert snap["counters"] == {} and snap["histograms"] == {}
        assert r.prometheus_text().strip() == ""

    def test_span_records_wall_time(self):
        r = MetricsRegistry()
        with r.span("phase"):
            pass
        h = r.histogram("phase_seconds")
        assert h.count == 1
        assert 0.0 <= h.sum < 1.0

    def test_null_metric_full_read_surface(self):
        # shims read .sum/.count/.bucket_counts off disabled metrics
        assert NULL_METRIC.sum == 0.0
        assert NULL_METRIC.count == 0
        assert NULL_METRIC.bucket_counts() == []

    def test_nonfinite_values_export_not_crash(self):
        r = MetricsRegistry(namespace="t")
        r.gauge("loss").set(float("nan"))
        r.gauge("norm").set(float("inf"))
        fams = parse_prometheus_text(r.prometheus_text())
        import math

        assert math.isnan(fams["t_loss"]["samples"]["t_loss"])
        assert fams["t_norm"]["samples"]["t_norm"] == float("inf")


class TestSinks:
    def test_prometheus_round_trip(self, tmp_path):
        r = MetricsRegistry(namespace="t")
        r.counter("reqs", "requests served").inc(3)
        r.gauge("depth").set(2.5)
        h = r.histogram("lat", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        h.observe(2.0)
        path = str(tmp_path / "metrics.prom")
        r.write_prometheus(path)
        with open(path) as f:
            fams = parse_prometheus_text(f.read())
        assert fams["t_reqs"]["type"] == "counter"
        assert fams["t_reqs"]["samples"]["t_reqs"] == 3
        assert fams["t_depth"]["samples"]["t_depth"] == 2.5
        lat = fams["t_lat"]
        assert lat["type"] == "histogram"
        assert lat["samples"]["t_lat_bucket|le=0.1"] == 1
        assert lat["samples"]["t_lat_bucket|le=1"] == 2
        assert lat["samples"]["t_lat_bucket|le=+Inf"] == 3
        assert lat["samples"]["t_lat_count"] == 3
        assert lat["samples"]["t_lat_sum"] == pytest.approx(2.55)
        # the parsed view must agree with the snapshot view
        snap = r.snapshot()
        assert snap["counters"]["reqs"] == 3
        assert snap["histograms"]["lat"]["count"] == 3

    def test_monitor_bridge(self, tmp_path):
        from deepspeed_tpu.monitor import MonitorMaster

        mon = MonitorMaster({"csv_monitor": {
            "enabled": True, "output_path": str(tmp_path),
            "job_name": "t"}})
        r = MetricsRegistry()
        r.counter("c").inc(4)
        r.histogram("h", buckets=(1.0,)).observe(0.5)
        exp = TelemetryExporter(r, monitor=mon, interval_s=0.0)
        assert exp.maybe_export(step=7)
        mon.flush()
        csv = (tmp_path / "t" / "Telemetry_c.csv").read_text()
        assert "7,4.0" in csv
        mean = (tmp_path / "t" / "Telemetry_h_mean.csv").read_text()
        assert "7,0.5" in mean
        mon.close()

    def test_exporter_interval_and_http(self, tmp_path):
        r = MetricsRegistry(namespace="t")
        r.counter("c").inc()
        prom = str(tmp_path / "m.prom")
        exp = TelemetryExporter(r, prometheus_path=prom,
                                interval_s=3600.0, http_port=0)
        try:
            assert exp.maybe_export(step=1)       # first call fires
            assert not exp.maybe_export(step=2)   # rate-limited
            assert exp.maybe_export(step=3, force=True)
            fams = parse_prometheus_text(open(prom).read())
            assert fams["t_c"]["samples"]["t_c"] == 1
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{exp.port}/metrics", timeout=5).read()
            assert parse_prometheus_text(
                body.decode())["t_c"]["samples"]["t_c"] == 1
        finally:
            exp.close()

    def test_comms_fan_in(self):
        from deepspeed_tpu.utils.trace import CommsLogger

        cl = CommsLogger()
        with cl.record("all_reduce", 1024):
            pass
        cl.record_event("all_gather", 512)
        r = MetricsRegistry()
        r.fan_in_comms(cl)
        snap = r.snapshot()["counters"]
        assert snap["comm_all_reduce_calls"] == 1
        assert snap["comm_all_reduce_bytes"] == 1024
        assert snap["comm_all_gather_bytes"] == 512
        # second fan-in with no new records must not double-count
        r.fan_in_comms(cl)
        assert r.snapshot()["counters"]["comm_all_reduce_bytes"] == 1024
        with cl.record("all_reduce", 1024):
            pass
        r.fan_in_comms(cl)
        assert r.snapshot()["counters"]["comm_all_reduce_bytes"] == 2048

    def test_comm_backend_records_collectives(self, devices):
        """The default comm path now records: tracing a collective logs
        (op, per-shard bytes) into the backend's CommsLogger."""
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from deepspeed_tpu import comm
        from deepspeed_tpu.topology import MeshSpec

        cl = comm.comms_logger()
        cl.reset()
        ms = MeshSpec.build({"data": 8})
        x = np.arange(8, dtype=np.float32).reshape(8, 1)
        jax.jit(shard_map(lambda v: comm.all_reduce(v, "data"),
                          mesh=ms.mesh, in_specs=P("data"),
                          out_specs=P("data")))(x)
        s = cl.summary()
        assert s["all_reduce"]["count"] >= 1
        assert s["all_reduce"]["bytes"] >= 4     # one f32/shard
        cl.reset()


class TestConfigBlock:
    def test_defaults_and_parsing(self):
        c = Config.from_dict({})
        assert c.telemetry.enabled is True
        assert c.telemetry.prometheus_path is None
        c = Config.from_dict({"telemetry": {
            "enabled": True, "interval_s": 1.5,
            "prometheus_path": "/tmp/x.prom", "monitor_bridge": False}})
        assert c.telemetry.interval_s == 1.5
        assert c.telemetry.prometheus_path == "/tmp/x.prom"
        assert c.telemetry.monitor_bridge is False
        assert Config.from_dict(
            {"telemetry": {"enabled": False}}).telemetry.enabled is False

    def test_coerce_and_validation(self):
        assert TelemetryConfig.coerce(None).enabled is True
        assert TelemetryConfig.coerce(False).enabled is False
        assert TelemetryConfig.coerce({"interval_s": 0}).interval_s == 0
        with pytest.raises(ValueError, match="interval_s"):
            TelemetryConfig.coerce({"interval_s": -1})
        with pytest.raises(ValueError, match="http_port"):
            TelemetryConfig.coerce({"http_port": 99999})
        with pytest.raises(TypeError):
            TelemetryConfig.coerce(3.5)


@pytest.fixture(scope="module")
def gpt2_model():
    from deepspeed_tpu.models import gpt2

    cfg = gpt2.GPT2Config.tiny(dim=32, n_layers=2, n_heads=2,
                               max_seq_len=64)
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _gpt2_engine(cfg, params, **kw):
    from deepspeed_tpu.inference.serving import serving_engine

    return serving_engine(params, cfg, max_batch=2, page_size=8,
                          num_pages=16, max_seq=32, prefill_bucket=8,
                          **kw)


class TestServingTelemetry:
    def test_ttft_queue_depth_and_stats_shim(self, gpt2_model, devices):
        cfg, params = gpt2_model
        eng = _gpt2_engine(cfg, params)
        for i in range(4):                     # 4 requests, 2 slots
            eng.submit(i, [3 + i, 5, 7], max_new_tokens=5)
        assert eng.registry.snapshot()["gauges"][
            "serving_queue_depth"] == 4
        out = eng.run()
        assert len(out) == 4
        snap = eng.registry.snapshot()
        cnt, gauges, hists = (snap["counters"], snap["gauges"],
                              snap["histograms"])
        # one TTFT observation per request, exactly once (requeues and
        # chunked decode must not double-count)
        assert hists["serving_ttft_seconds"]["count"] == 4
        assert hists["serving_ttft_seconds"]["sum"] > 0
        # inter-token: every generated token after a request's first
        generated = sum(len(v) - 3 for v in out.values())
        assert hists["serving_inter_token_seconds"]["count"] == \
            generated - 4
        assert cnt["serving_admitted_requests"] == 4
        assert cnt["serving_decode_steps"] >= 5
        assert gauges["serving_queue_depth"] == 0       # drained
        assert 0.0 <= gauges["serving_kv_page_utilization"] <= 1.0
        # the step span feeds both the histogram and a TraceAnnotation
        assert hists["serving_step_seconds"]["count"] >= 5
        assert cnt["serving_admitted_requests"] == 4

    def test_tokens_identical_with_telemetry_disabled(self, gpt2_model,
                                                      devices):
        cfg, params = gpt2_model
        prompts = {0: [3, 5, 7], 1: [11, 2], 2: [9, 9, 4]}
        outs = {}
        for tel in (True, False):
            eng = _gpt2_engine(cfg, params, telemetry=tel)
            for rid, p in prompts.items():
                eng.submit(rid, p, max_new_tokens=6)
            outs[tel] = eng.run()
        assert outs[True] == outs[False]
        assert len(outs[False]) == 3

    def test_prometheus_file_from_serving_run(self, gpt2_model, devices,
                                              tmp_path):
        """Acceptance: a gpt2 serving run produces a Prometheus
        exposition file that parses back."""
        cfg, params = gpt2_model
        eng = _gpt2_engine(cfg, params)
        eng.submit("r", [5, 9, 2], max_new_tokens=6)
        eng.run()
        path = str(tmp_path / "serving.prom")
        eng.registry.write_prometheus(path)
        fams = parse_prometheus_text(open(path).read())
        ns = eng.registry.namespace
        assert fams[f"{ns}_serving_ttft_seconds"]["type"] == "histogram"
        assert fams[f"{ns}_serving_ttft_seconds"]["samples"][
            f"{ns}_serving_ttft_seconds_count"] == 1
        assert fams[f"{ns}_serving_admitted_requests"]["samples"][
            f"{ns}_serving_admitted_requests"] == 1

    def test_config_block_reaches_init_serving(self, gpt2_model, devices):
        from deepspeed_tpu.inference import init_serving

        cfg, params = gpt2_model
        eng = init_serving(params, cfg,
                           config={"telemetry": {"enabled": False}},
                           max_batch=2, page_size=8, num_pages=16,
                           max_seq=32, prefill_bucket=8)
        assert not eng.registry.enabled
        eng = init_serving(params, cfg, max_batch=2, page_size=8,
                           num_pages=16, max_seq=32, prefill_bucket=8)
        assert eng.registry.enabled

    def test_serving_sink_keys_drive_an_exporter(self, gpt2_model,
                                                 devices, tmp_path):
        """A telemetry block with prometheus_path on a SERVING engine
        must actually export (the exporter ticks from step())."""
        cfg, params = gpt2_model
        prom = str(tmp_path / "serve.prom")
        eng = _gpt2_engine(cfg, params,
                           telemetry={"prometheus_path": prom,
                                      "interval_s": 0.0})
        eng.submit("r", [5, 9, 2], max_new_tokens=4)
        eng.run()
        fams = parse_prometheus_text(open(prom).read())
        assert fams["dstpu_serving_admitted_requests"]["samples"][
            "dstpu_serving_admitted_requests"] == 1
        eng._tel_exporter.close()

    def test_shared_registry_across_engines(self, gpt2_model, devices):
        cfg, params = gpt2_model
        reg = MetricsRegistry(namespace="shared")
        e1 = _gpt2_engine(cfg, params, telemetry=reg)
        e2 = _gpt2_engine(cfg, params, telemetry=reg)
        assert e1.registry is reg and e2.registry is reg
        e1.submit("a", [5, 9], max_new_tokens=4)
        e2.submit("b", [7, 2], max_new_tokens=4)
        e1.run()
        e2.run()
        assert reg.snapshot()["counters"][
            "serving_admitted_requests"] == 2


class TestStreamingTelemetry:
    def test_zero_inference_metrics(self, devices):
        """Streamed serving populates upload/sweep counters, the wait
        histogram, and keeps the stats shim keys the benches read."""
        from deepspeed_tpu.inference.serving import serving_engine
        from deepspeed_tpu.models import llama

        cfg = llama.LlamaConfig.tiny(dim=32, n_layers=2, n_heads=2,
                                     n_kv_heads=2)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        zi = serving_engine(
            params, cfg, zero_inference={"enabled": True, "tier": "host"},
            max_batch=2, page_size=8, num_pages=16, max_seq=32,
            prefill_bucket=8)
        zi.submit("a", [5, 9, 2], max_new_tokens=4)
        zi.run()
        snap = zi.registry.snapshot()
        cnt = snap["counters"]
        assert cnt["zi_layer_sweeps"] >= 4       # prefill + decode steps
        assert cnt["zi_layer_h2d_uploads"] >= \
            cnt["zi_layer_sweeps"] * zi.plan["n_streamed"]
        assert cnt["zi_bytes_uploaded"] > 0
        assert cnt["zi_stream_bytes_read"] > 0   # TierLayerReader fan-in
        assert snap["histograms"][
            "zi_prefetch_wait_seconds"]["count"] >= 0

    def test_zero_inference_serves_with_telemetry_disabled(self, devices):
        """The streamed engine must serve with telemetry off (null
        metrics answer .sum/.value on every streaming hot path)."""
        from deepspeed_tpu.inference.serving import serving_engine
        from deepspeed_tpu.models import llama

        cfg = llama.LlamaConfig.tiny(dim=32, n_layers=2, n_heads=2,
                                     n_kv_heads=2)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        zi = serving_engine(
            params, cfg, zero_inference={"enabled": True, "tier": "host"},
            max_batch=2, page_size=8, num_pages=16, max_seq=32,
            prefill_bucket=8, telemetry=False)
        zi.submit("a", [5, 9], max_new_tokens=3)
        outs = zi.run()
        assert len(outs["a"]) == 5               # prompt + 3 generated
        assert not zi.registry.enabled
        assert zi.registry.snapshot()["counters"] == {}


class TestAioTelemetry:
    def test_read_write_counters_and_pending_gauge(self, tmp_path):
        from deepspeed_tpu import telemetry as tel
        from deepspeed_tpu.io.aio import AioHandle

        reg = MetricsRegistry()
        prev = tel.set_default_registry(reg)
        try:
            h = AioHandle(n_threads=2)
            path = str(tmp_path / "blob.bin")
            buf = np.arange(64, dtype=np.float32)
            fd = h.open(path, write=True)
            h.pwrite(fd, buf, 0)
            assert h.wait() == 0
            h.close(fd)
            rbuf = np.empty_like(buf)
            fd = h.open(path)
            h.pread(fd, rbuf, 0)
            assert h.wait() == 0
            h.close(fd)
            np.testing.assert_array_equal(rbuf, buf)
            snap = reg.snapshot()
            assert snap["counters"]["aio_writes_submitted"] == 1
            assert snap["counters"]["aio_reads_submitted"] == 1
            assert snap["counters"]["aio_read_bytes"] == buf.nbytes
            assert snap["gauges"]["aio_pending_depth"] == 0  # post-wait
        finally:
            tel.set_default_registry(prev)

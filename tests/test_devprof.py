"""Device-truth observability (ISSUE 17).

Fast lane: config coercion, the compile ledger's warmup/steady split,
sentinel counting against a real tiny jit (a forced shape poke counted
EXACTLY once, zero across a steady-shape run), the deterministic
sampling stride, roofline tick math against a synthetic clock, the
phase-vocabulary normalization telemetry.span() applies, the incident
probe's cursor semantics, /profilez JSON safety, and the profiler.py
cost-analysis path reconciled against the analytic FLOPs formula.

Slow lane: real-engine contracts — a served run records zero
steady-state recompiles (warmup split correct), a forced off-contract
dispatch after steady records exactly ONE attributed recompile and
trips a ``steady_state_recompile`` incident whose bundle carries the
compile ledger, token identity with devprof on vs off, the /statusz +
/profilez HTTP round-trip, per-replica fleet namespaces, and the
engine's decode cost-analysis reconciled against
``transformer_decode_flops``.
"""

import json
import os
import sys
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

from deepspeed_tpu.config import DevprofConfig  # noqa: E402
from deepspeed_tpu.devprof import (NULL_DEVPROF, PHASES,  # noqa: E402
                                   CompileLedger, DevProf)
from deepspeed_tpu.telemetry import MetricsRegistry  # noqa: E402


def _devprof(registry=None, tracer=None, **kw):
    kw.setdefault("enabled", True)
    return DevProf(DevprofConfig.coerce(kw),
                   registry=registry or MetricsRegistry(),
                   tracer=tracer)


# --------------------------------------------------------------- config
class TestConfig:
    def test_coerce_forms(self):
        assert not DevprofConfig.coerce(None).enabled
        assert not DevprofConfig.coerce(False).enabled
        assert DevprofConfig.coerce(True).enabled
        c = DevprofConfig.coerce({"sample_rate": 0.25})
        assert c.enabled and c.sample_rate == 0.25
        assert not DevprofConfig.coerce({"enabled": False}).enabled
        assert DevprofConfig.coerce(c) is c
        with pytest.raises(TypeError):
            DevprofConfig.coerce(3)

    def test_validation(self):
        with pytest.raises(ValueError):
            DevprofConfig.coerce({"sample_rate": 1.5})
        with pytest.raises(ValueError):
            DevprofConfig.coerce({"capture_max_s": 0})

    def test_serving_config_block(self):
        from deepspeed_tpu.config import Config

        cfg = Config.from_dict(
            {"train_batch_size": 1,
             "devprof": {"sample_rate": 0.1}})
        assert cfg.devprof.enabled
        assert cfg.devprof.sample_rate == 0.1


# --------------------------------------------------------------- ledger
class TestLedger:
    def test_warmup_steady_split(self):
        led = CompileLedger()
        led.record("prefill", steady=False, n=3)
        led.record("decode_chunk", steady=False)
        led.record("decode_chunk", steady=True, duration_s=0.5)
        snap = led.snapshot()
        assert snap["warmup_compiles"] == 4
        assert snap["steady_state_compiles"] == 1
        assert len(snap["entries"]) == 3
        assert snap["entries"][-1]["phase"] == "steady"
        assert snap["entries"][-1]["duration_s"] == 0.5

    def test_bounded(self):
        led = CompileLedger(capacity=4)
        for i in range(10):
            led.record(f"s{i}", steady=False)
        snap = led.snapshot()
        assert snap["warmup_compiles"] == 10      # counts never drop
        assert len(snap["entries"]) == 4          # entries bounded


# ------------------------------------------------------------- sentinel
class TestSentinel:
    def test_counts_real_jit_compiles_exactly_once(self):
        import jax
        import jax.numpy as jnp

        dp = _devprof(sample_rate=0.0)
        fn = dp.wrap("decode_chunk", jax.jit(lambda x: x * 2 + 1))
        x8 = jnp.zeros((8,), jnp.float32)
        fn(x8)                                    # warmup compile
        assert dp.ledger.warmup == 1
        for _ in range(5):                        # steady shape: cached
            fn(x8)
        dp.mark_steady()
        assert dp.ledger.steady == 0
        for _ in range(5):
            fn(x8)
        assert dp.ledger.steady == 0              # no false positives
        fn(jnp.zeros((9,), jnp.float32))          # the shape poke
        assert dp.ledger.steady == 1              # exactly once
        fn(jnp.zeros((9,), jnp.float32))
        assert dp.ledger.steady == 1              # cached thereafter
        snap = dp.ledger.snapshot()
        assert snap["entries"][-1]["site"] == "decode_chunk"
        assert snap["entries"][-1]["phase"] == "steady"

    def test_non_jit_passthrough(self):
        dp = _devprof()
        fn = dp.wrap("prefill", lambda x: x + 1)  # streamed executor
        assert fn(1) == 2
        assert dp.ledger.warmup == 0              # no cache to watch
        assert dp.wrap("x", None) is None

    def test_dispatch_cost_accounting(self):
        dp = _devprof()
        dp.register_cost("decode_chunk", flops=100.0,
                         bytes_accessed=40.0)
        fn = dp.wrap("decode_chunk", lambda: None)
        for _ in range(3):
            fn()
        snap = dp.registry.snapshot()["counters"]
        assert snap["devprof_flops_total"] == 300.0
        assert snap["devprof_bytes_total"] == 120.0


# ------------------------------------------------------------- sampling
class TestSampling:
    def test_deterministic_stride(self):
        dp = _devprof(sample_rate=0.25)           # stride 4
        hits = [dp.should_sample("decode") for _ in range(12)]
        assert hits == [False, False, False, True] * 3
        # phases stride independently
        assert [dp.should_sample("prefill")
                for _ in range(4)] == [False] * 3 + [True]

    def test_rate_zero_never_samples(self):
        dp = _devprof(sample_rate=0.0)
        assert not any(dp.should_sample("decode") for _ in range(50))

    def test_observe_device_records_phase_and_gap(self):
        import jax.numpy as jnp

        dp = _devprof(sample_rate=1.0)
        dt = dp.observe_device("decode", jnp.zeros((4,)))
        assert dt >= 0.0
        cnt = dp.registry.snapshot()["counters"]
        assert cnt["devprof_device_seconds_decode"] == pytest.approx(dt)
        assert cnt["devprof_sampled_dispatches"] == 1
        g = dp.registry.snapshot()["gauges"]
        assert g["devprof_host_device_gap_seconds"] >= 0.0

    def test_record_device_self_timed(self):
        dp = _devprof()
        dp.record_device("sample", 0.125)
        cnt = dp.registry.snapshot()["counters"]
        assert cnt["devprof_device_seconds_sample"] == 0.125


# ------------------------------------------------------------- roofline
class TestRoofline:
    def test_tick_turns_deltas_into_mfu_mbu(self):
        dp = _devprof()
        dp.peak_flops = 1000.0
        dp.peak_bw = 100.0
        dp.register_cost("decode_chunk", flops=500.0,
                         bytes_accessed=10.0)
        fn = dp.wrap("decode_chunk", lambda: None)
        dp.tick(now=100.0)
        fn()                                      # 500 flops, 10 bytes
        dp.tick(now=101.0)                        # over 1 s
        g = dp.registry.snapshot()["gauges"]
        assert g["devprof_mfu"] == pytest.approx(0.5)
        assert g["devprof_mbu"] == pytest.approx(0.1)

    def test_tick_rate_limited(self):
        dp = _devprof()
        dp.peak_flops = 1000.0
        dp.register_cost("s", flops=500.0, bytes_accessed=0.0)
        fn = dp.wrap("s", lambda: None)
        dp.tick(now=100.0)
        fn()
        dp.tick(now=100.1)                        # < 0.5 s: ignored
        g = dp.registry.snapshot()["gauges"]
        assert g["devprof_mfu"] == 0.0            # no update yet
        dp.tick(now=101.0)
        g = dp.registry.snapshot()["gauges"]
        assert g["devprof_mfu"] == pytest.approx(0.5)

    def test_cost_analyze_records_site(self):
        import jax
        import jax.numpy as jnp

        dp = _devprof()
        jfn = jax.jit(lambda a, b: a @ b)
        n = 16
        s = jax.ShapeDtypeStruct((n, n), jnp.float32)
        assert dp.cost_analyze("prefill", jfn, s, s)
        flops = dp._costs["prefill"]["flops"]
        assert flops == pytest.approx(2.0 * n ** 3, rel=0.2)


# ----------------------------------------------------- phase vocabulary
class TestPhaseVocabulary:
    def test_span_keeps_the_callers_word_everywhere(self):
        r = MetricsRegistry(namespace="dstpu")
        span = r.span("decode_chunk", "help")
        # one word: the histogram family and the TraceAnnotation label
        # a capture shows are both the caller's literal name
        assert "decode_chunk_seconds" in r.snapshot()["histograms"]
        assert span._label == "dstpu/decode_chunk"


# ------------------------------------------------------- incident probe
class TestIncidentProbe:
    def test_cursor_trips_once_per_batch(self):
        dp = _devprof()
        assert dp.incident_probe() is None
        dp.ledger.record("prefill", steady=False)  # warmup never trips
        assert dp.incident_probe() is None
        dp.mark_steady()
        dp.ledger.record("decode_chunk", steady=True)
        cls, attrs = dp.incident_probe()
        assert cls == "steady_state_recompile"
        assert attrs["new_compiles"] == 1
        assert dp.incident_probe() is None         # cursor advanced
        dp.ledger.record("decode_chunk", steady=True, n=2)
        cls, attrs = dp.incident_probe()
        assert attrs["new_compiles"] == 2


# ------------------------------------------------------------- surfaces
class TestSurfaces:
    def test_statusz_block_shape(self):
        dp = _devprof(sample_rate=0.5)
        b = dp.statusz_block()
        assert b["enabled"] and not b["steady"]
        assert b["compiles_warmup"] == 0
        assert b["compiles_steady"] == 0
        assert set(b["device_seconds"]) == set(PHASES)
        json.dumps(b)                              # serializable

    def test_profilez_status_json_safe(self):
        dp = _devprof()
        json.dumps(dp.profilez())                  # no capture: status
        assert "error" in dp.profilez("bogus")

    def test_bundle_info_carries_ledger(self):
        dp = _devprof()
        dp.ledger.record("prefill", steady=False)
        info = dp.bundle_info()
        assert info["compile_ledger"]["warmup_compiles"] == 1
        json.dumps(info)

    def test_null_devprof_surface(self):
        fn = object()
        assert NULL_DEVPROF.wrap("x", fn) is fn
        assert not NULL_DEVPROF.should_sample("decode")
        assert NULL_DEVPROF.statusz_block() == {"enabled": False}
        assert NULL_DEVPROF.incident_probe() is None
        NULL_DEVPROF.mark_steady()
        assert not NULL_DEVPROF.steady


# ----------------------------------------------- profiler reconciliation
class TestProfilerCostAnalysis:
    def test_matmul_flops_match_analytic(self):
        import jax.numpy as jnp

        from deepspeed_tpu.profiler import xla_cost_analysis

        n = 32
        a = jnp.zeros((n, n), jnp.float32)
        cost = xla_cost_analysis(lambda a, b: a @ b, a, a)
        assert cost["flops"] == pytest.approx(2.0 * n ** 3, rel=0.2)
        assert cost["bytes_accessed"] > 0

    def test_get_model_profile_wakes(self):
        import jax.numpy as jnp

        from deepspeed_tpu.profiler import get_model_profile

        n = 16
        a = jnp.zeros((n, n), jnp.float32)
        out = get_model_profile(lambda a, b: a @ b, (a, a),
                                print_profile=False, iters=2)
        assert out["flops"] == pytest.approx(2.0 * n ** 3, rel=0.2)
        assert out["latency_s"] > 0
        assert 0.0 <= out["mfu"]


# ------------------------------------------------------------ the engine
def _tiny_engine(params, cfg, **kw):
    from deepspeed_tpu.inference.serving import serving_engine

    base = dict(max_batch=2, page_size=8, num_pages=12, max_seq=64,
                prefill_bucket=8)
    base.update(kw)
    return serving_engine(params, cfg, **base)


@pytest.fixture(scope="module")
def gpt2_tiny():
    import jax

    from deepspeed_tpu.models import gpt2

    cfg = gpt2.GPT2Config.tiny(dim=64, n_layers=2, n_heads=4,
                               max_seq_len=128)
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    import numpy as np

    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab_size, 9).tolist()
               for _ in range(4)]
    return params, cfg, prompts


@pytest.mark.slow
class TestEngineContract:
    def test_zero_steady_recompiles_and_warmup_split(self, gpt2_tiny):
        params, cfg, prompts = gpt2_tiny
        eng = _tiny_engine(params, cfg, telemetry=True,
                           devprof={"sample_rate": 1.0})
        try:
            assert not eng.devprof.steady        # build-time warmup
            assert eng.devprof.ledger.warmup > 0
            warm = eng.devprof.ledger.warmup
            for i, p in enumerate(prompts):
                eng.submit(i, p, max_new_tokens=5)
            eng.run()
            # the steady boundary flipped at the FIRST token and no
            # compile crossed it — the zero-recompile contract
            assert eng.devprof.steady
            assert eng.devprof.ledger.steady == 0
            assert eng.devprof.ledger.warmup == warm
            b = eng.statusz()["devprof"]
            assert b["steady"] and b["compiles_steady"] == 0
            # sampled attribution landed real device seconds
            dev = b["device_seconds"]
            assert dev["prefill"] > 0 and dev["decode"] > 0
            assert dev["sample"] > 0
            cnt = eng.registry.snapshot()["counters"]
            assert cnt["devprof_sampled_dispatches"] > 0
            assert cnt["devprof_flops_total"] > 0
        finally:
            eng.shutdown()

    def test_forced_recompile_counted_once_and_trips_incident(
            self, gpt2_tiny, tmp_path):
        import jax.numpy as jnp

        params, cfg, prompts = gpt2_tiny
        eng = _tiny_engine(
            params, cfg, telemetry=True,
            devprof={"sample_rate": 0.0},
            incidents={"dir": str(tmp_path / "inc"),
                       "eval_interval_s": 0.001})
        try:
            for i, p in enumerate(prompts[:2]):
                eng.submit(i, p, max_new_tokens=4)
            eng.run()
            assert eng.devprof.steady
            assert eng.devprof.ledger.steady == 0
            # the type poke: an off-contract decode dispatch (a
            # uint32 ordinal, not int32) the warmup set never
            # compiled — this is exactly the drift the sentinel exists
            # to catch
            out, eng.cache = eng._decode_chunk_fn(
                eng.params, jnp.zeros((eng.max_batch, 1), jnp.int32),
                eng.cache, eng._key, jnp.zeros((), jnp.uint32),
                jnp.zeros((eng.max_batch,), jnp.float32))
            del out
            assert eng.devprof.ledger.steady == 1   # exactly once
            captured = eng.incident_mgr.evaluate()
            assert "steady_state_recompile" in captured
            meta = [b for b in eng.incident_mgr.bundles
                    if b["incident"] == "steady_state_recompile"]
            assert len(meta) == 1
            with open(meta[0]["path"]) as f:
                bundle = json.load(f)
            # the bundle carries the attached ledger: site, phase,
            # timestamps — enough to find the drifting call site
            led = bundle["devprof"]["compile_ledger"]
            assert led["steady_state_compiles"] == 1
            assert led["entries"][-1]["site"] == "decode_chunk"
            assert led["entries"][-1]["phase"] == "steady"
            assert bundle["trigger"]["new_compiles"] == 1
        finally:
            eng.shutdown()

    def test_token_identity_devprof_on_off(self, gpt2_tiny):
        params, cfg, prompts = gpt2_tiny
        outs = []
        for on in (False, True):
            eng = _tiny_engine(
                params, cfg, telemetry=bool(on) or None,
                devprof={"sample_rate": 1.0} if on else None)
            try:
                for i, p in enumerate(prompts):
                    eng.submit(i, p, max_new_tokens=5)
                outs.append(eng.run())
            finally:
                eng.shutdown()
        # measurement is read-only: full-rate sampled syncs and the
        # sentinel wrappers change nothing the model computes
        assert outs[0] == outs[1]

    def test_statusz_profilez_http_round_trip(self, gpt2_tiny):
        params, cfg, prompts = gpt2_tiny
        eng = _tiny_engine(params, cfg,
                           telemetry={"http_port": 0,
                                      "interval_s": 0.05},
                           devprof={"sample_rate": 1.0})
        try:
            for i, p in enumerate(prompts[:2]):
                eng.submit(i, p, max_new_tokens=4)
            eng.run()
            base = f"http://127.0.0.1:{eng._tel_exporter.port}"

            def get(path):
                with urllib.request.urlopen(base + path,
                                            timeout=10) as r:
                    return json.loads(r.read().decode())

            dp = get("/statusz")["devprof"]
            assert dp["enabled"] and dp["steady"]
            assert dp["compiles_steady"] == 0
            pz = get("/profilez")
            assert pz["compiles_warmup"] == dp["compiles_warmup"]
            bad = get("/profilez?capture_s=bogus")
            assert "error" in bad
            # the exporter tick drove the roofline gauges (MFU/MBU
            # keys present in the devprof block and /metrics)
            assert "mfu" in dp and "mbu" in dp
        finally:
            eng.shutdown()

    def test_decode_cost_reconciles_with_analytic(self, gpt2_tiny):
        from deepspeed_tpu.models import gpt2 as gpt2_mod
        from deepspeed_tpu.profiler import transformer_decode_flops

        params, cfg, prompts = gpt2_tiny
        eng = _tiny_engine(params, cfg, telemetry=True, devprof=True)
        try:
            sites = eng.statusz()["devprof"]["cost_sites"]
            assert "decode_chunk" in sites
            per_chunk = sites["decode_chunk"]["flops"]
            K = eng.decode_chunk
            n_params = gpt2_mod.param_count(cfg)
            kv = eng.max_pages_per_seq * eng.page_size
            analytic = eng.max_batch * K * transformer_decode_flops(
                n_params, cfg.n_layers, cfg.dim, kv)
            # XLA's estimate counts the fused program (embeddings,
            # norms, sampling, paged gathers) against the matmul-only
            # analytic bound over the FULL padded kv span — agreement
            # within 3x is the documented reconciliation: same order
            # of magnitude, per-chunk, per-batch scaling correct
            assert analytic / 3.0 <= per_chunk <= analytic * 3.0
        finally:
            eng.shutdown()

    def test_fleet_per_replica_namespaces(self, gpt2_tiny):
        from deepspeed_tpu.fleet import fleet_router

        params, cfg, prompts = gpt2_tiny
        router = fleet_router(
            params, cfg, fleet={"replicas": 2}, max_batch=2,
            page_size=8, num_pages=12, max_seq=64, prefill_bucket=8,
            devprof={"sample_rate": 1.0})
        try:
            for i, p in enumerate(prompts):
                router.submit(i, p, max_new_tokens=4)
            router.run()
            for r in router.replicas.values():
                b = r.engine.statusz()["devprof"]
                assert b["enabled"]
                assert b["compiles_steady"] == 0
                # each replica owns its namespace: the sentinel
                # counters live under dstpu_r{i}, never shared
                ns = r.engine.registry.namespace
                assert ns == f"dstpu_{r.id}"
                cnt = r.engine.registry.snapshot()["counters"]
                assert cnt["devprof_compiles_warmup"] > 0
        finally:
            router.shutdown()


@pytest.mark.parametrize("chunk,page,chunk_programs", [
    (16, 4, 3),      # tables of 4, 8 and 16 pages: a chunk spans 4
    (8, 8, 4),       # 1, 2, 4, 8: a chunk is one page
    (64, 4, 1),      # the chunk spans the whole row
])
def test_warmup_compiles_the_table_widths_a_chunk_can_be_given(
        gpt2_tiny, chunk, page, chunk_programs):
    """The build-time warm-up compiles the chunk program at the table
    widths ``_advance_prefill`` draws from and no narrower: a chunk's
    table spans the chunk itself at least, so a width under the power of
    two that holds ``prefill_chunk`` tokens is never dispatched.  Prompts
    of every length then compile nothing."""
    import numpy as np

    params, cfg, _ = gpt2_tiny
    eng = _tiny_engine(params, cfg, page_size=page, num_pages=160 // page,
                       prefill_bucket=0, prefill_chunk=chunk,
                       telemetry=True, devprof={"sample_rate": 0.0})
    try:
        sites = [e["site"] for e in eng.devprof.ledger.snapshot()["entries"]]
        assert sites.count("chunk_prefill") == chunk_programs
        warm = eng.devprof.ledger.warmup
        rng = np.random.default_rng(5)
        for i, n in enumerate((1, chunk - 1, chunk, chunk + 1, 33, 50)):
            n = min(n, 60)
            eng.submit(i, rng.integers(1, cfg.vocab_size, n).tolist(),
                       max_new_tokens=3)
        eng.run()
        assert eng.devprof.ledger.steady == 0
        assert eng.devprof.ledger.warmup == warm
    finally:
        eng.shutdown()

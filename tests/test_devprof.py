"""Device-truth observability (ISSUE 17; the build ledger, ISSUE 37).

Fast lane: config coercion (the keys the block no longer has are
dropped, not refused), the build ledger against real ``jax.jit`` calls
(all four parts by name, a cache hit and a miss, unnamed programs in
the aggregate, bounded, ``t_end`` on ``perf_counter``), sentinel
counting against a real tiny jit (a forced shape poke counted EXACTLY
once with its real seconds, zero across a steady-shape run), two
engines on one listener, the incident probe's cursor semantics,
/profilez and /statusz JSON safety, a tiny serving build and a tiny
training build read from the ledger, and the profiler.py cost-analysis
path reconciled against the analytic FLOPs formula.

Slow lane: real-engine contracts — a served run records zero
steady-state recompiles (warmup split correct), a forced off-contract
dispatch after steady records exactly ONE attributed recompile and
trips a ``steady_state_recompile`` incident whose bundle carries the
compile ledger, token identity with devprof on vs off, the /statusz +
/profilez HTTP round-trip, and per-replica fleet namespaces.
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

from deepspeed_tpu import devprof as devprof_mod  # noqa: E402
from deepspeed_tpu.config import DevprofConfig  # noqa: E402
from deepspeed_tpu.devprof import (BUILD_LEDGER, NULL_DEVPROF,  # noqa: E402
                                   BuildCounters, BuildLedger, DevProf,
                                   ProgramSpan)
from deepspeed_tpu.telemetry import MetricsRegistry  # noqa: E402

PARTS = ("trace_s", "lower_s", "cache_load_s", "compile_s")


def _devprof(registry=None, tracer=None, **kw):
    kw.setdefault("enabled", True)
    return DevProf(DevprofConfig.coerce(kw),
                   registry=registry or MetricsRegistry(),
                   tracer=tracer)


def _named(name, body):
    """A fresh function under a program name: a new jit of it compiles
    whatever the process compiled before."""
    body.__name__ = body.__qualname__ = name
    return body


def _entries(name):
    return [e for e in BUILD_LEDGER.snapshot()["entries"]
            if e["program"] == name]


# --------------------------------------------------------------- config
class TestConfig:
    def test_coerce_forms(self):
        assert not DevprofConfig.coerce(None).enabled
        assert not DevprofConfig.coerce(False).enabled
        assert DevprofConfig.coerce(True).enabled
        c = DevprofConfig.coerce({"capture_max_s": 2})
        assert c.enabled and c.capture_max_s == 2.0
        assert not DevprofConfig.coerce({"enabled": False}).enabled
        assert DevprofConfig.coerce(c) is c
        with pytest.raises(TypeError):
            DevprofConfig.coerce(3)

    def test_validation(self):
        with pytest.raises(ValueError):
            DevprofConfig.coerce({"capture_max_s": 0})

    def test_serving_config_block(self):
        from deepspeed_tpu.config import Config

        cfg = Config.from_dict(
            {"train_batch_size": 1,
             "devprof": {"capture_max_s": 3}})
        assert cfg.devprof.enabled
        assert cfg.devprof.capture_max_s == 3.0

    @pytest.mark.parametrize("block", [
        {"sample_rate": 0.0, "cost_analysis": False},   # the benchmark's
        {"sample_rate": 0.05},                          # the tools'
        {"sample_rate": 1.0, "capture_max_s": 4},
    ])
    def test_keys_the_block_no_longer_has_are_dropped(self, block):
        """The benchmark's harness, which no PR of this kind may edit,
        and older callers pass the sampled half's keys: they build."""
        for c in (DevprofConfig.from_dict(block),
                  DevprofConfig.coerce(block)):
            assert not hasattr(c, "sample_rate")
            assert not hasattr(c, "cost_analysis")
            assert c.capture_max_s == float(block.get("capture_max_s", 10))
        assert DevprofConfig.coerce(block).enabled
        dp = DevProf(DevprofConfig.coerce(block),
                     registry=MetricsRegistry())
        assert dp.enabled and "sample_rate" not in dp.statusz_block()


def test_the_frames_under_a_builds_lowering_keep_their_words():
    """A tripwire, not a rule.  CPython keeps frames in 16 KiB chunks
    and maps a chunk in and out each time a call crosses a chunk's end
    and returns; which call of JAX's lowering recursion does turns on
    the words of every frame under it, and on the chip's host one
    local more in ``_devprof_warmup`` was +20% of every program's
    lowering (PERF.md 6, PR 37: +1 to +1.5 s of ``setup_s``).  A PR
    that changes one of these numbers changes set-up: measure it on
    the chip (``note_build``'s ``lower_faults``), then write the new
    number here.  PR 47 took ``serving_engine`` 37 -> 35 and
    ``ServingEngine.__init__`` 78 -> 74 (a local and the sampler's fork
    went): on the chip the programs' lowering fell (``lower_s`` 5.39 ->
    4.15 s in the tail cell, 8.80-9.13 -> 6.55-6.62 in chat-sat), their
    tracing rose (2.27 -> 2.86, 3.44-3.51 -> 3.99-4.00) and warm
    ``setup_s`` read 39.24 -> 40.27 and 41.37-42.51 -> 38.12-38.41
    (my chip runs, PR 47; PERF.md 6)."""
    from deepspeed_tpu import initialize
    from deepspeed_tpu.engine import TrainingEngine
    from deepspeed_tpu.inference import serving

    def words(fn):
        c = fn.__code__
        return (c.co_nlocals + len(c.co_cellvars) + len(c.co_freevars)
                + c.co_stacksize)

    assert {
        "serving_engine": words(serving.serving_engine),
        "ServingEngine.__init__": words(serving.ServingEngine.__init__),
        "_devprof_warmup": words(serving.ServingEngine._devprof_warmup),
        "_SentinelFn.__call__": words(devprof_mod._SentinelFn.__call__),
        "initialize": words(initialize),
        "TrainingEngine.__init__": words(TrainingEngine.__init__),
        "train_batch": words(TrainingEngine.train_batch),
    } == {
        "serving_engine": 35, "ServingEngine.__init__": 74,
        "_devprof_warmup": 31, "_SentinelFn.__call__": 12,
        "initialize": 30, "TrainingEngine.__init__": 39,
        "train_batch": 10,
    }


# --------------------------------------------------------------- ledger
class TestLedger:
    def test_a_named_program_has_every_part_by_name(self):
        """Real ``jax.jit`` calls on the CPU: a ``dstpu_*`` program's
        entry has its trace, lowering and compile seconds, and the
        functions traced inside it by name, not added again."""
        import jax
        import jax.numpy as jnp

        inner = jax.jit(_named("dstpu_t_inner", lambda x: jnp.sin(x) * 2))

        def body(x):
            k = jax.random.split(jax.random.PRNGKey(0))[0]
            return inner(x) + jax.random.normal(k, x.shape)

        t0 = time.perf_counter()
        jax.jit(_named("dstpu_t_parts", body))(jnp.ones((3, 5)))
        t1 = time.perf_counter()
        (e,) = _entries("dstpu_t_parts")
        assert e["trace_s"] > 0 and e["lower_s"] > 0 and e["compile_s"] > 0
        assert e["cache_load_s"] == 0.0 and e["cache_hit"] is False
        assert e["steady"] is False
        assert t0 < e["t_end"] < t1                 # perf_counter's clock
        # the inner jit was traced inside the outer's trace: by name
        # under the entry, its seconds within the outer's, and no entry
        # of its own (it was never compiled alone)
        assert "dstpu_t_inner" in e["inner_trace_s"]
        assert e["inner_trace_s"]["dstpu_t_inner"] <= e["trace_s"]
        assert len(e["inner_trace_s"]) <= 10
        assert _entries("dstpu_t_inner") == []
        # the threefry expansion is traced while LOWERING: its seconds
        # are inside lower_s, kept apart from the trace's (names nest
        # in names, so it is each that fits, not their sum)
        assert "_threefry_split" in e["lowering_trace_s"]
        assert max(e["lowering_trace_s"].values()) <= e["lower_s"]
        assert e["trace_s"] + e["lower_s"] + e["compile_s"] < t1 - t0

    def test_an_entry_counts_its_lowerings_page_faults(self):
        """``lower_faults`` is the lowering thread's own minor page
        faults between the program's trace and the end of its lowering
        (what a call across the end of a frame-stack chunk costs), and
        ``mark`` / ``since`` give a log line the thread's split."""
        import jax
        import jax.numpy as jnp

        x = jnp.ones((4, 4))                # an eager program of its own
        mark = BUILD_LEDGER.mark()
        before = devprof_mod._faults()
        jax.jit(_named("dstpu_t_faults", lambda x: jnp.tanh(x) @ x.T))(x)
        spent = devprof_mod._faults() - before
        (e,) = _entries("dstpu_t_faults")
        assert isinstance(e["lower_faults"], int)
        assert 0 <= e["lower_faults"] <= spent
        words = BUILD_LEDGER.since(mark)
        assert words.startswith("1 programs (1 compiled) in ")
        assert "trace" in words and "lower" in words and "compile" in words

    def test_an_unnamed_program_goes_to_the_aggregate(self):
        import jax.numpy as jnp

        before = BUILD_LEDGER.snapshot(rows=True)
        x = jnp.full((3, 7, 11), 2.5)               # an eager fill
        y = (x * 3).sum()                           # and two more
        del y
        after = BUILD_LEDGER.snapshot(rows=True)
        assert len(after["entries"]) == len(before["entries"])
        grew = after["other"]["programs"] - before["other"]["programs"]
        assert grew >= 1
        assert after["programs"] - before["programs"] == grew
        assert after["other"]["seconds"] > before["other"]["seconds"]
        rows = after["other"]["rows"][-grew:]
        assert all(not name.startswith("dstpu_") and s > 0
                   for _, name, s in rows)
        assert len(after["other"]["top"]) <= 5
        assert "rows" not in BUILD_LEDGER.snapshot()["other"]

    def test_bounded(self):
        led = BuildLedger(capacity=4, other_capacity=3)
        for i in range(10):
            led.made_ready(f"dstpu_p{i}", 0.25)
            led.made_ready(f"eager{i}", 0.5)
        snap = led.snapshot(rows=True)
        assert snap["programs"] == 20               # counts never drop
        assert snap["compile_s"] == pytest.approx(7.5)
        assert [e["program"] for e in snap["entries"]] == [
            "dstpu_p6", "dstpu_p7", "dstpu_p8", "dstpu_p9"]
        assert snap["other"]["programs"] == 10
        assert snap["other"]["seconds"] == pytest.approx(5.0)
        assert len(snap["other"]["rows"]) == 3      # rows bounded
        # the five names with most seconds, of the rows kept
        assert [t[0] for t in snap["other"]["top"]] == [
            "eager7", "eager8", "eager9"]

    def test_t_end_is_perf_counter_and_monotone(self):
        import jax
        import jax.numpy as jnp

        t0 = time.perf_counter()
        for i in range(3):
            jax.jit(_named("dstpu_t_mono", lambda x, i=i: x + i))(
                jnp.ones((2, i + 1)))
        t1 = time.perf_counter()
        ends = [e["t_end"] for e in _entries("dstpu_t_mono")]
        assert len(ends) == 3
        assert t0 < ends[0] < ends[1] < ends[2] < t1

    def test_counters_mirror_only_the_attached_build(self):
        import jax
        import jax.numpy as jnp

        r, x = MetricsRegistry(), jnp.ones(4)
        mirror = BuildCounters(r)                   # attached
        jax.jit(_named("dstpu_t_mirror", lambda x: x * 3))(x)
        mirror.built()                              # detached
        jax.jit(_named("dstpu_t_after", lambda x: x * 5))(x)
        snap = r.snapshot()
        cnt, g = snap["counters"], snap["gauges"]
        (e,) = _entries("dstpu_t_mirror")
        assert cnt["build_programs"] == 1 and cnt["build_cache_misses"] == 1
        assert cnt["build_trace_seconds"] == pytest.approx(
            e["trace_s"], abs=1e-5)
        assert cnt["build_compile_seconds"] == pytest.approx(
            e["compile_s"], abs=1e-5)
        assert cnt["build_cache_load_seconds"] == 0
        parts = sum(cnt[f"build_{k}_seconds"] for k in
                    ("trace", "lower", "cache_load", "compile"))
        assert 0 < parts <= g["build_seconds"]
        assert g["package_import_seconds"] > 0
        # a registry that is off mirrors nothing and costs no attach
        off = BuildCounters(MetricsRegistry(enabled=False))
        assert devprof_mod._tl.mirror is None
        off.built()

    def test_program_span_gives_the_entry_its_word_and_run(self):
        import jax
        import jax.numpy as jnp

        r = MetricsRegistry()
        span = ProgramSpan(r.span("build_program"))
        fn = jax.jit(_named("dstpu_t_span", lambda x: x @ x))
        with span("prefill", end=8):
            fn(jnp.ones((8, 8)))
        (e,) = _entries("dstpu_t_span")
        assert e["span"] == "prefill end=8"
        wall = r.snapshot()["histograms"]["build_program_seconds"]["sum"]
        parts = sum(e[k] for k in PARTS)
        assert 0 <= e["run_s"] <= wall - parts + 1e-3
        with span("prefill", end=8):                # cached: no program
            fn(jnp.ones((8, 8)))
        assert len(_entries("dstpu_t_span")) == 1

    def test_a_program_adds_its_own_words_to_the_span_it_is_built_under(self):
        """An event ``BUILD_WORD_EVENT`` on JAX's bus at trace time (the
        flash backward's rule of the shapes, PR 62): after the span's
        own words, both answers where a word was said with two, and gone
        with the next span."""
        import jax
        import jax.numpy as jnp

        def body(x):
            for path in ("fused", "split", "fused"):
                jax.monitoring.record_event(
                    devprof_mod.BUILD_WORD_EVENT, flash_bwd=path, why="fits")
            return x @ x

        span = ProgramSpan(MetricsRegistry().span("build_program"))
        with span("train_step"):
            jax.jit(_named("dstpu_t_noted", body))(jnp.ones((8, 8)))
        with span("decode_chunk", b=2):
            jax.jit(_named("dstpu_t_silent", lambda x: x + x))(
                jnp.ones((8, 8)))
        (noted,), (silent,) = (_entries("dstpu_t_noted"),
                               _entries("dstpu_t_silent"))
        assert noted["span"] == "train_step flash_bwd=fused+split why=fits"
        assert silent["span"] == "decode_chunk b=2"


_CACHE_PROBE = """
import json, sys, tempfile
import jax, jax.numpy as jnp
jax.config.update("jax_compilation_cache_dir", tempfile.mkdtemp())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
from deepspeed_tpu.devprof import BUILD_LEDGER
def make():
    def dstpu_t_cached(x):
        return jnp.tanh(x) @ x
    return jax.jit(dstpu_t_cached)
x = jnp.ones((16, 16))
make()(x)           # compiled, and written to the cache
make()(x)           # the same module under a new jit: read back
print(json.dumps(BUILD_LEDGER.snapshot()))
"""


@pytest.fixture(scope="module")
def cache_probe():
    """A miss and a hit against a temporary ``jax_compilation_cache_dir``,
    in a process of their own: the ledger there holds these two programs
    and nothing the suite's own cache (conftest.py) has read back."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    snap = json.loads(out.stdout.strip().splitlines()[-1])
    return snap, [e for e in snap["entries"]
                  if e["program"] == "dstpu_t_cached"]


@pytest.mark.parametrize("which,hit", [(0, False), (1, True)],
                         ids=["miss", "hit"])
def test_cache_hit_and_miss(cache_probe, which, hit):
    snap, entries = cache_probe
    assert len(entries) == 2
    e = entries[which]
    assert e["cache_hit"] is hit
    assert e["trace_s"] > 0 and e["lower_s"] > 0
    # a program's backend seconds are the cache's on a hit and the
    # compiler's on a miss, never both
    assert (e["cache_load_s"] > 0) is hit
    assert (e["compile_s"] > 0) is not hit
    assert snap["cache_load_s"] >= entries[1]["cache_load_s"]
    assert snap["cache_misses"] == snap["programs"] - 1


# ------------------------------------------------------------- sentinel
class TestSentinel:
    def test_counts_real_jit_compiles_exactly_once(self):
        import jax
        import jax.numpy as jnp

        dp = _devprof()
        fn = dp.wrap("decode_chunk", jax.jit(
            _named("dstpu_t_once", lambda x: x * 2 + 1)))
        x8 = jnp.zeros((8,), jnp.float32)
        fn(x8)                                    # warmup compile
        assert dp.compiles_warmup == 1
        for _ in range(5):                        # steady shape: cached
            fn(x8)
        dp.mark_steady()
        assert dp.compiles_steady == 0
        for _ in range(5):
            fn(x8)
        assert dp.compiles_steady == 0            # no false positives
        fn(jnp.zeros((9,), jnp.float32))          # the shape poke
        assert dp.compiles_steady == 1            # exactly once
        fn(jnp.zeros((9,), jnp.float32))
        assert dp.compiles_steady == 1            # cached thereafter
        last = dp.compile_ledger()["entries"][-1]
        assert last["site"] == "decode_chunk"
        assert last["phase"] == "steady"

    def test_a_steady_compile_has_its_real_seconds(self):
        """The entry a steady-state compile made is the site's, flagged
        ``steady``, with the seconds JAX reported for THAT program."""
        import jax
        import jax.numpy as jnp

        dp = _devprof()
        fn = dp.wrap("prefill", jax.jit(
            _named("dstpu_t_steady", lambda x: jnp.cumsum(x) * 2)))
        fn(jnp.zeros((8,)))
        dp.mark_steady()
        fn(jnp.zeros((5,)))
        warm, steady = _entries("dstpu_t_steady")
        assert (warm["steady"], steady["steady"]) == (False, True)
        assert warm["site"] == steady["site"] == "prefill"
        assert steady["compile_s"] > 0 and steady["lower_s"] > 0
        rec = dp.compile_ledger()["entries"][-1]
        assert rec["entries"][0]["t_end"] == steady["t_end"]
        assert rec["duration_s"] == pytest.approx(
            sum(steady[k] for k in PARTS), abs=1e-5)

    def test_two_engines_one_listener_and_neither_steals(self):
        """Two engines' sentinels in one process: one listener, and each
        site's seconds are its own program's, though both programs have
        one name and compile at the same time on two threads."""
        import jax
        import jax.numpy as jnp
        from jax._src import monitoring

        n = len(monitoring.get_event_duration_listeners())
        dps = [_devprof(), _devprof()]
        assert devprof_mod.install_compile_listener()
        assert len(monitoring.get_event_duration_listeners()) == n
        # engine 0 compiles a heavy program, engine 1 a light one (eight
        # inverses: 0.35 s against 0.04; one read 0.109 against 0.116 on
        # a loaded machine, PR 50)
        def heavy(x):
            for _ in range(8):
                x = jnp.linalg.inv(x @ x.T + jnp.eye(24))
            return x.sum()

        bodies = [heavy, lambda x: x + 1]
        fns = [dp.wrap(f"site{i}", jax.jit(_named("dstpu_t_two", b)))
               for i, (dp, b) in enumerate(zip(dps, bodies))]
        gate = threading.Barrier(2)

        def build(i):
            gate.wait()
            fns[i](jnp.ones((24, 24)))

        threads = [threading.Thread(target=build, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        mine = [dp.compile_ledger()["entries"] for dp in dps]
        assert [len(m) for m in mine] == [1, 1]
        ends = {e["t_end"]: e for e in _entries("dstpu_t_two")}
        assert len(ends) == 2
        for i, m in enumerate(mine):
            (claimed,) = m[0]["entries"]
            assert claimed["site"] == f"site{i}"
            assert ends[claimed["t_end"]]["site"] == f"site{i}"
        heavy, light = (m[0]["entries"][0] for m in mine)
        assert heavy["t_end"] != light["t_end"]
        assert heavy["compile_s"] > light["compile_s"]

    def test_non_jit_passthrough(self):
        dp = _devprof()
        fn = dp.wrap("prefill", lambda x: x + 1)  # streamed executor
        assert fn(1) == 2
        assert dp.compiles_warmup == 0            # no cache to watch
        assert dp.wrap("x", None) is None


# ----------------------------------------------------- phase vocabulary
class TestPhaseVocabulary:
    def test_span_keeps_the_callers_word_everywhere(self):
        r = MetricsRegistry(namespace="dstpu")
        span = r.span("decode_chunk", "help")
        # one word: the histogram family and the TraceAnnotation label
        # a capture shows are both the caller's literal name
        assert "decode_chunk_seconds" in r.snapshot()["histograms"]
        assert span._label == "dstpu/decode_chunk"

    def test_a_span_called_with_keywords_annotates_them(self, monkeypatch):
        import jax

        seen = []

        class Annotation:
            def __init__(self, label, **kw):
                seen.append((label, kw))

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
        r = MetricsRegistry()
        with r.span("build_program")(site="prefill", end=128):
            pass
        with r.span("serving_step"):
            pass
        assert seen == [("dstpu/build_program",
                         {"site": "prefill", "end": 128}),
                        ("dstpu/serving_step", {})]
        off = MetricsRegistry(enabled=False).span("build_program")
        with off(site="prefill", end=128):
            pass
        assert len(seen) == 2


# ------------------------------------------------------- incident probe
class TestIncidentProbe:
    def test_cursor_trips_once_per_batch(self):
        dp = _devprof()
        assert dp.incident_probe() is None
        dp.on_compile("prefill")                   # warmup never trips
        assert dp.incident_probe() is None
        dp.mark_steady()
        dp.on_compile("decode_chunk")
        cls, attrs = dp.incident_probe()
        assert cls == "steady_state_recompile"
        assert attrs["new_compiles"] == 1
        assert attrs["recent"][-1]["site"] == "decode_chunk"
        assert dp.incident_probe() is None         # cursor advanced
        dp.on_compile("decode_chunk", 2)
        cls, attrs = dp.incident_probe()
        assert attrs["new_compiles"] == 2


# ------------------------------------------------------------- surfaces
class TestSurfaces:
    def test_statusz_block_shape(self):
        dp = _devprof()
        b = dp.statusz_block()
        assert b["enabled"] and not b["steady"]
        assert b["compiles_warmup"] == 0
        assert b["compiles_steady"] == 0
        assert set(b) == {"enabled", "steady", "monitoring",
                          "compiles_warmup", "compiles_steady",
                          "captures"}
        json.dumps(b)                              # serializable

    def test_profilez_status_json_safe(self):
        dp = _devprof()
        json.dumps(dp.profilez())                  # no capture: status
        assert "error" in dp.profilez("bogus")

    def test_bundle_info_carries_ledger(self):
        dp = _devprof()
        dp.on_compile("prefill")
        info = dp.bundle_info()
        assert info["compile_ledger"]["warmup_compiles"] == 1
        json.dumps(info)

    def test_null_devprof_surface(self):
        fn = object()
        assert NULL_DEVPROF.wrap("x", fn) is fn
        assert not hasattr(NULL_DEVPROF, "should_sample")
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


@pytest.fixture(scope="module")
def built(gpt2_tiny):
    """One tiny serving build with the sentinel and its warm-up, and
    what the ledger held before it."""
    params, cfg, _ = gpt2_tiny
    before = BUILD_LEDGER.snapshot()
    t0 = time.perf_counter()
    eng = _tiny_engine(params, cfg, telemetry=True,
                       devprof={"sample_rate": 0.0, "cost_analysis": False})
    t1 = time.perf_counter()
    yield eng, before, (t0, t1)
    eng.shutdown()


class TestServingBuild:
    def test_one_entry_a_warm_up_dispatch(self, built):
        eng, before, (t0, t1) = built
        snap = eng.statusz()["build"]
        mine = [e for e in snap["entries"] if t0 < e["t_end"] < t1]
        # prefill at 8 multiples of the bucket, the chunk program at
        # tables of 1, 2, 4 and 8 pages, the boundary sampler, what
        # writes its token into the decode's operand, the decode
        assert [e["span"] for e in mine] == (
            [f"prefill end={8 * i}" for i in range(1, 9)]
            + [f"chunk_prefill w={w}" for w in (1, 2, 4, 8)]
            + ["boundary", "join", "decode_chunk b=2"])
        assert [e["program"] for e in mine] == (
            ["dstpu_prefill"] * 8 + ["dstpu_chunk"] * 4
            + ["dstpu_boundary", "dstpu_join", "dstpu_decode"])
        assert [e["site"] for e in mine] == (
            ["prefill"] * 8 + ["chunk_prefill"] * 4
            + ["boundary", "join", "decode_chunk"])
        assert len(mine) == eng.devprof.compiles_warmup == 15
        for e in mine:
            assert e["trace_s"] > 0 and e["lower_s"] > 0
            assert e["compile_s"] > 0 and not e["cache_hit"]
            assert e["run_s"] >= 0 and not e["steady"]

    def test_parts_sum_to_no_more_than_the_build(self, built):
        eng, before, (t0, t1) = built
        reg = eng.registry.snapshot()
        cnt, g = reg["counters"], reg["gauges"]
        parts = sum(cnt[f"build_{k}_seconds"] for k in
                    ("trace", "lower", "cache_load", "compile"))
        assert 0 < parts <= g["build_seconds"] <= t1 - t0
        # the counters are the build's alone: the named entries and the
        # eager fills of the same stretch, nothing of an earlier engine
        after = BUILD_LEDGER.snapshot()
        assert cnt["build_programs"] == after["programs"] - before["programs"]
        assert cnt["build_programs"] >= 15 + 1
        assert cnt["build_cache_misses"] == cnt["build_programs"]
        named = sum(e[k] for e in after["entries"] for k in PARTS
                    if t0 < e["t_end"] < t1)
        assert named <= parts + 1e-4
        runs = sum(e["run_s"] for e in after["entries"]
                   if t0 < e["t_end"] < t1)
        assert parts + runs <= g["build_seconds"]

    @pytest.mark.parametrize("name,count", [
        ("build_alloc", 1), ("build_programs", 2), ("build_warmup", 1),
        ("build_program", 15)])
    def test_build_spans_are_entered_once_each(self, built, name, count):
        eng, _, _ = built
        h = eng.registry.snapshot()["histograms"][f"{name}_seconds"]
        assert h["count"] == count
        assert h["sum"] <= eng.registry.snapshot()["gauges"]["build_seconds"]

    def test_statusz_build_is_json_safe(self, built):
        eng, _, _ = built
        doc = json.loads(json.dumps(eng.statusz()["build"]))
        assert set(doc) == {"programs", "cache_misses", "trace_s",
                            "lower_s", "cache_load_s", "compile_s",
                            "entries", "other"}
        assert set(doc["other"]) == {"programs", "seconds", "top"}
        assert doc["programs"] == len(doc["entries"]) + \
            doc["other"]["programs"] or len(doc["entries"]) == 64
        for e in doc["entries"]:
            assert set(PARTS) <= set(e)

    def test_without_the_block_the_ledger_still_has_the_build(
            self, gpt2_tiny):
        """No devprof, no telemetry: nothing is warmed and nothing is
        mirrored, and the process-wide ledger records what the engine
        does compile, when it first serves."""
        params, cfg, prompts = gpt2_tiny
        eng = _tiny_engine(params, cfg, telemetry=False)
        try:
            assert eng.registry.snapshot()["counters"] == {}
            t0 = time.perf_counter()
            eng.submit(0, prompts[0], max_new_tokens=2)
            eng.run()
            got = {e["program"] for e in BUILD_LEDGER.snapshot()["entries"]
                   if e["t_end"] > t0}
            assert got == {"dstpu_prefill", "dstpu_boundary",
                           "dstpu_join", "dstpu_decode"}
        finally:
            eng.shutdown()


def test_a_training_build_has_its_step_in_the_ledger(gpt2_tiny):
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import gpt2

    params, cfg, _ = gpt2_tiny
    t0 = time.perf_counter()
    engine, *_ = deepspeed_tpu.initialize(
        loss_fn=gpt2.loss_fn(cfg), params=params,
        config={"train_batch_size": 8, "telemetry": {"enabled": True},
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}})
    batch = {"tokens": np.arange(8 * 17, dtype=np.int32).reshape(8, 17)
             % cfg.vocab_size}
    for _ in range(3):
        engine.train_batch(batch)
    mine = [e for e in BUILD_LEDGER.snapshot()["entries"]
            if e["t_end"] > t0]
    assert [e["program"] for e in mine] == ["dstpu_make_state",
                                            "dstpu_train_step"]
    step = mine[1]
    assert step["span"] == "train_step" and step["run_s"] >= 0
    assert step["trace_s"] > 0 and step["lower_s"] > 0
    assert step["compile_s"] > 0
    reg = engine.registry.snapshot()
    cnt, hist = reg["counters"], reg["histograms"]
    assert cnt["build_programs"] >= 2
    assert cnt["build_compile_seconds"] >= step["compile_s"]
    assert hist["build_state_seconds"]["count"] == 1
    assert hist["build_step_seconds"]["count"] == 1
    # the first dispatch is the build's, not a step of the histogram
    assert hist["train_step_seconds"]["count"] == 2
    assert reg["gauges"]["build_seconds"] >= hist[
        "build_step_seconds"]["sum"]


@pytest.mark.slow
class TestEngineContract:
    def test_zero_steady_recompiles_and_warmup_split(self, gpt2_tiny):
        params, cfg, prompts = gpt2_tiny
        eng = _tiny_engine(params, cfg, telemetry=True, devprof=True)
        try:
            assert not eng.devprof.steady        # build-time warmup
            assert eng.devprof.compiles_warmup > 0
            warm = eng.devprof.compiles_warmup
            for i, p in enumerate(prompts):
                eng.submit(i, p, max_new_tokens=5)
            eng.run()
            # the steady boundary flipped at the FIRST token and no
            # compile crossed it — the zero-recompile contract
            assert eng.devprof.steady
            assert eng.devprof.compiles_steady == 0
            assert eng.devprof.compiles_warmup == warm
            b = eng.statusz()["devprof"]
            assert b["steady"] and b["compiles_steady"] == 0
            cnt = eng.registry.snapshot()["counters"]
            assert cnt["devprof_compiles_warmup"] == warm
        finally:
            eng.shutdown()

    def test_forced_recompile_counted_once_and_trips_incident(
            self, gpt2_tiny, tmp_path):
        import jax.numpy as jnp

        params, cfg, prompts = gpt2_tiny
        eng = _tiny_engine(
            params, cfg, telemetry=True,
            devprof=True,
            incidents={"dir": str(tmp_path / "inc"),
                       "eval_interval_s": 0.001})
        try:
            for i, p in enumerate(prompts[:2]):
                eng.submit(i, p, max_new_tokens=4)
            eng.run()
            assert eng.devprof.steady
            assert eng.devprof.compiles_steady == 0
            # the type poke: an off-contract decode dispatch (a
            # uint32 ordinal, not int32) the warmup set never
            # compiled — this is exactly the drift the sentinel exists
            # to catch
            out, eng.cache = eng._decode_chunk_fn(
                eng.params, jnp.zeros((eng.max_batch, 1), jnp.int32),
                eng.cache, eng._key, jnp.zeros((), jnp.uint32),
                jnp.zeros((eng.max_batch,), jnp.float32))
            del out
            assert eng.devprof.compiles_steady == 1   # exactly once
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
            # with the build ledger's entry for it: the real seconds
            (e,) = led["entries"][-1]["entries"]
            assert e["program"] == "dstpu_decode" and e["steady"]
            assert e["compile_s"] > 0
            assert bundle["trigger"]["new_compiles"] == 1
        finally:
            eng.shutdown()

    def test_token_identity_devprof_on_off(self, gpt2_tiny):
        params, cfg, prompts = gpt2_tiny
        outs = []
        for on in (False, True):
            eng = _tiny_engine(
                params, cfg, telemetry=bool(on) or None,
                devprof=True if on else None)
            try:
                for i, p in enumerate(prompts):
                    eng.submit(i, p, max_new_tokens=5)
                outs.append(eng.run())
            finally:
                eng.shutdown()
        # measurement is read-only: the sentinel wrappers, the warm-up
        # and the build spans change nothing the model computes
        assert outs[0] == outs[1]

    def test_statusz_profilez_http_round_trip(self, gpt2_tiny):
        params, cfg, prompts = gpt2_tiny
        eng = _tiny_engine(params, cfg,
                           telemetry={"http_port": 0,
                                      "interval_s": 0.05},
                           devprof=True)
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
            build = get("/statusz")["build"]
            assert build["programs"] >= dp["compiles_warmup"]
        finally:
            eng.shutdown()

    def test_fleet_per_replica_namespaces(self, gpt2_tiny):
        from deepspeed_tpu.fleet import fleet_router

        params, cfg, prompts = gpt2_tiny
        router = fleet_router(
            params, cfg, fleet={"replicas": 2}, max_batch=2,
            page_size=8, num_pages=12, max_seq=64, prefill_bucket=8,
            devprof=True)
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
                       telemetry=True, devprof=True)
    try:
        sites = [e["site"] for e in eng.devprof.compile_ledger()["entries"]]
        assert sites.count("chunk_prefill") == chunk_programs
        warm = eng.devprof.compiles_warmup
        rng = np.random.default_rng(5)
        for i, n in enumerate((1, chunk - 1, chunk, chunk + 1, 33, 50)):
            n = min(n, 60)
            eng.submit(i, rng.integers(1, cfg.vocab_size, n).tolist(),
                       max_new_tokens=3)
        eng.run()
        assert eng.devprof.compiles_steady == 0
        assert eng.devprof.compiles_warmup == warm
    finally:
        eng.shutdown()

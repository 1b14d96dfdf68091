"""Fault injection + graceful degradation (ISSUE 9): the seeded
FaultPlan, bounded aio retry/backoff and the synchronous fallback rung,
spilled-page checksums → re-prefill, slot-level failure isolation,
load shedding with typed rejections and per-tier SLO accounting, the
structured fatal + postmortem on an unrecoverable weight stream, and
the no-leak page accounting every scenario must leave behind.

Correctness oracle throughout: the fault-free engine — every injected
failure may cost retries, fallbacks or re-prefills, but a COMPLETED
request's tokens must be identical to the clean run (greedy decode is
a pure function of the prompt)."""

import os

import numpy as np
import pytest

import jax

from deepspeed_tpu import faults
from deepspeed_tpu.config import Config, FaultsConfig, KVTierConfig
from deepspeed_tpu.faults import (ChecksumError, FatalStreamError,
                                  FaultPlan, InjectedFault,
                                  retry_with_backoff)
from deepspeed_tpu.inference.kv_tier import KVTierPool
from deepspeed_tpu.inference.serving import (RequestFailed, RequestShed,
                                             serving_engine)
from deepspeed_tpu.models import gpt2, llama

KW = dict(max_batch=2, page_size=8, num_pages=12, max_seq=64,
          prefill_bucket=8)


@pytest.fixture(scope="module")
def gpt2_model():
    cfg = gpt2.GPT2Config.tiny(dim=64, n_layers=2, n_heads=4,
                               max_seq_len=128)
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def llama_model():
    cfg = llama.LlamaConfig.tiny(dim=32, n_layers=2, n_heads=2,
                                 n_kv_heads=2)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    """Every test starts and ends with no process-wide plan installed
    (a leaked plan would inject into unrelated suites)."""
    faults.clear_fault_plan()
    yield
    faults.clear_fault_plan()


def revisit_phases(vocab, seed=7):
    """warm → flush (demotes the shared prefix) → revisit (tier
    promotion) — the workload that exercises the promote path."""
    rng = np.random.default_rng(seed)
    pref = rng.integers(1, vocab, 16).tolist()
    mk = lambda: pref + rng.integers(1, vocab, 3).tolist()
    flush = [rng.integers(1, vocab, 24).tolist() for _ in range(4)]
    return [[mk(), mk()], flush, [mk(), mk()]]


def run_phases(eng, phases, n_new=6):
    i = 0
    for ph in phases:
        for p in ph:
            eng.submit(i, p, max_new_tokens=n_new)
            i += 1
        eng.run()
    out = dict(eng.finished)
    eng.shutdown()
    return out


# ------------------------------------------------------------- config
class TestFaultsConfig:
    def test_coerce_forms(self):
        assert not FaultsConfig.coerce(None).enabled
        assert FaultsConfig.coerce({}).enabled      # block = opt-in
        assert not FaultsConfig.coerce({"enabled": False}).enabled
        with pytest.raises(TypeError):
            FaultsConfig.coerce(3)

    def test_bad_rule_fails_at_parse(self):
        with pytest.raises(ValueError, match="subsystem"):
            FaultsConfig.coerce({"rules": [{"subsystem": "nope"}]})
        with pytest.raises(ValueError, match="rate"):
            FaultsConfig.coerce(
                {"rules": [{"subsystem": "slot", "rate": 0.0}]})
        with pytest.raises(ValueError, match="latency_s"):
            FaultsConfig.coerce(
                {"rules": [{"subsystem": "slot", "mode": "latency"}]})
        with pytest.raises(ValueError, match="unknown faults rule"):
            FaultsConfig.coerce(
                {"rules": [{"subsystem": "slot", "bogus": 1}]})

    def test_config_block_parses(self):
        c = Config.from_dict({"faults": {
            "seed": 3, "rules": [{"subsystem": "aio_read",
                                  "rate": 0.5, "count": 2}]}})
        assert c.faults.enabled and c.faults.seed == 3
        assert Config.from_dict({}).faults.enabled is False

    def test_retry_knob_validation(self):
        with pytest.raises(ValueError, match="io_retries"):
            KVTierConfig.coerce({"io_retries": -1})
        k = KVTierConfig.coerce({"io_retries": "3",
                                 "disable_after": "0"})
        assert k.io_retries == 3 and k.disable_after == 0

    def test_encoder_families_reject_faults(self, devices):
        from deepspeed_tpu.models import bert

        cfg = bert.BertConfig.tiny(dim=32, n_layers=2, n_heads=2)
        params = bert.init_params(jax.random.PRNGKey(0), cfg)
        with pytest.raises(NotImplementedError, match="faults"):
            serving_engine(params, cfg, faults={"rules": []},
                           max_batch=2)
        with pytest.raises(NotImplementedError, match="shedding"):
            serving_engine(params, cfg, shed_queue_depth=4,
                           max_batch=2)


# --------------------------------------------------------------- plan
class TestFaultPlan:
    def test_deterministic_across_instances(self):
        rules = [{"subsystem": "aio_read", "rate": 0.4},
                 {"subsystem": "slot", "rate": 0.7}]
        a, b = FaultPlan(rules, seed=5), FaultPlan(rules, seed=5)
        seq_a = [(bool(a.fire("aio_read")), bool(a.fire("slot")))
                 for _ in range(50)]
        seq_b = [(bool(b.fire("aio_read")), bool(b.fire("slot")))
                 for _ in range(50)]
        assert seq_a == seq_b
        assert any(x for x, _ in seq_a) and not all(x for x, _ in seq_a)
        # a different seed gives a different schedule
        c = FaultPlan(rules, seed=6)
        seq_c = [(bool(c.fire("aio_read")), bool(c.fire("slot")))
                 for _ in range(50)]
        assert seq_c != seq_a

    def test_count_after_and_match(self):
        p = FaultPlan([{"subsystem": "slot", "rate": 1.0, "count": 2,
                        "after": 1, "match": "tgt"}])
        hits = [bool(p.fire("slot", key=k))
                for k in ("tgt-a", "other", "tgt-b", "tgt-c", "tgt-d")]
        # "other" never matches; the first matching opportunity is
        # skipped (after=1); then exactly 2 fire
        assert hits == [False, False, True, True, False]
        snap = p.snapshot()
        assert snap["injected"] == 2
        assert snap["rules"][0]["seen"] == 4       # matches only

    def test_count_gates_effect_not_stream(self):
        """Changing count must not shift later draw decisions — the
        rate stream advances per seen opportunity regardless."""
        mk = lambda n: FaultPlan([{"subsystem": "slot", "rate": 0.5,
                                   "count": n}], seed=9)
        unlimited = [bool(mk(None).fire("slot")) for _ in range(1)]
        a, b = mk(1), mk(99)
        seq_a = [bool(a.fire("slot")) for _ in range(30)]
        seq_b = [bool(b.fire("slot")) for _ in range(30)]
        # where both still had budget, decisions agree
        fired = 0
        for x, y in zip(seq_a, seq_b):
            if fired < 1:
                assert x == y
            if y:
                fired += 1
        assert sum(seq_a) == 1
        del unlimited

    def test_install_clear_semantics(self):
        p1, p2 = FaultPlan([], seed=0), FaultPlan([], seed=0)
        faults.install_fault_plan(p1)
        faults.install_fault_plan(p2)
        faults.clear_fault_plan(p1)      # stale clear: no-op
        assert faults.active_plan() is p2
        faults.clear_fault_plan(p2)
        assert faults.active_plan() is None

    def test_inject_and_latency(self):
        faults.install_fault_plan(FaultPlan(
            [{"subsystem": "slot", "rate": 1.0, "count": 1},
             {"subsystem": "sync_read", "mode": "latency",
              "latency_s": 0.001}]))
        with pytest.raises(InjectedFault):
            faults.inject("slot")
        assert faults.inject("slot") is False     # count exhausted
        assert faults.inject("sync_read") is True  # latency only

    def test_retry_with_backoff_bounded(self):
        calls = []

        def fn():
            calls.append(1)
            raise IOError("nope")

        with pytest.raises(IOError):
            retry_with_backoff(fn, attempts=3, backoff_s=0.0)
        assert len(calls) == 4                    # 1 try + 3 retries


# ------------------------------------------------------ aio + kv pool
class TestIOFaults:
    def test_aio_injected_error_surfaces_at_wait(self, tmp_path):
        from deepspeed_tpu.io.aio import AioHandle

        path = str(tmp_path / "f.bin")
        data = np.arange(64, dtype=np.float32)
        h = AioHandle(2)
        fd = h.open(path, write=True)
        h.pwrite(fd, data)
        assert h.wait() == 0
        h.close(fd)
        faults.install_fault_plan(FaultPlan(
            [{"subsystem": "aio_read", "rate": 1.0, "count": 1}]))
        buf = np.zeros(64, np.float32)
        fd = h.open(path)
        h.pread(fd, buf)                  # swallowed
        assert h.wait() == 1              # reported as a failed op
        h.pread(fd, buf)                  # budget exhausted: real read
        assert h.wait() == 0
        h.close(fd)
        np.testing.assert_array_equal(buf, data)

    def test_checksum_mismatch_raises_on_decode(self):
        pool = KVTierPool(KVTierConfig.coerce({"host_pool_bytes":
                                               1 << 20}),
                          page_shape=(2, 2, 8, 16),
                          page_dtype=np.float32)
        rng = np.random.default_rng(0)
        k = rng.standard_normal((2, 2, 8, 16)).astype(np.float32)
        v = rng.standard_normal((2, 2, 8, 16)).astype(np.float32)
        faults.install_fault_plan(FaultPlan(
            [{"subsystem": "kv_corrupt", "rate": 1.0, "count": 1}]))
        assert pool.demote(b"k1" * 8, k, v) == "host"
        e = pool.entries[b"k1" * 8]
        with pytest.raises(ChecksumError):
            pool.decode(b"k1" * 8, e.data)
        faults.clear_fault_plan()
        # a clean demote round-trips
        assert pool.demote(b"k2" * 8, k, v) == "host"
        e2 = pool.entries[b"k2" * 8]
        dk, dv = pool.decode(b"k2" * 8, e2.data)
        np.testing.assert_array_equal(dk, k)

    def test_spill_write_failure_drops_gracefully(self, tmp_path):
        pool = KVTierPool(
            KVTierConfig.coerce({"host_pool_bytes": 0,
                                 "nvme_dir": str(tmp_path),
                                 "io_retries": 1,
                                 "io_retry_backoff_s": 0.0}),
            page_shape=(2, 2, 8, 16), page_dtype=np.float32)
        k = np.zeros((2, 2, 8, 16), np.float32)
        faults.install_fault_plan(FaultPlan(
            [{"subsystem": "aio_write", "rate": 1.0}]))
        # host pool holds nothing → direct-to-NVMe; persistent write
        # faults exhaust the retry and the entry DROPS (no raise)
        assert pool.demote(b"k1" * 8, k, k) is None
        assert pool.spill_failures == 1
        assert pool.write_retries >= 1
        assert not pool.has(b"k1" * 8)

    def test_pool_disable_circuit(self):
        pool = KVTierPool(KVTierConfig.coerce({}),
                          page_shape=(2, 2, 8, 16),
                          page_dtype=np.float32)
        k = np.zeros((2, 2, 8, 16), np.float32)
        assert pool.demote(b"k1" * 8, k, k) == "host"
        assert pool.has(b"k1" * 8)
        pool.disable("test breaker")
        assert not pool.has(b"k1" * 8)            # hits become misses
        assert pool.demote(b"k2" * 8, k, k) is None
        assert pool.occupancy()["disabled"] == "test breaker"
        # entries stay intact for an in-flight promotion's reads
        assert b"k1" * 8 in pool.entries


# --------------------------------------------- engine: tier fallbacks
class TestTierDegradation:
    def test_checksum_mismatch_reprefills_token_identical(
            self, gpt2_model, devices):
        cfg, params = gpt2_model
        phases = revisit_phases(cfg.vocab_size)
        off = run_phases(serving_engine(
            params, cfg, prefix_cache=True, **KW), phases)
        eng = serving_engine(
            params, cfg, prefix_cache=True, kv_tier=True,
            faults={"rules": [{"subsystem": "kv_corrupt",
                               "rate": 1.0}]}, **KW)
        on = run_phases(eng, phases)
        assert on == off
        assert eng._n_kvt_checksum > 0
        assert eng._n_kvt_fallbacks > 0
        assert eng.check_leaks() == []

    def test_aio_retry_then_sync_fallback_token_identical(
            self, gpt2_model, devices, tmp_path):
        cfg, params = gpt2_model
        phases = revisit_phases(cfg.vocab_size, seed=3)
        off = run_phases(serving_engine(
            params, cfg, prefix_cache=True, **KW), phases)
        eng = serving_engine(
            params, cfg, prefix_cache=True,
            kv_tier={"enabled": True, "host_pool_bytes": 4096,
                     "nvme_dir": str(tmp_path), "io_retries": 1,
                     "io_retry_backoff_s": 0.0},
            faults={"rules": [{"subsystem": "aio_read",
                               "rate": 1.0, "count": 6}]}, **KW)
        on = run_phases(eng, phases)
        assert on == off
        cnt = eng.registry.snapshot()["counters"]
        assert cnt.get("kv_tier_io_retries", 0) > 0
        # persistent-enough faults pushed at least one fence to the
        # synchronous fallback rung
        assert cnt.get("kv_tier_sync_fallbacks", 0) >= 1
        assert eng.check_leaks() == []

    def test_unrecoverable_promotion_falls_back_to_prefill(
            self, gpt2_model, devices, tmp_path):
        """aio AND sync reads both dead: the KV promotion's fatal is
        NOT engine-fatal — the tier is optional, the span re-prefills
        and tokens stay identical."""
        cfg, params = gpt2_model
        phases = revisit_phases(cfg.vocab_size, seed=5)
        off = run_phases(serving_engine(
            params, cfg, prefix_cache=True, **KW), phases)
        eng = serving_engine(
            params, cfg, prefix_cache=True,
            kv_tier={"enabled": True, "host_pool_bytes": 4096,
                     "nvme_dir": str(tmp_path), "io_retries": 0,
                     "io_retry_backoff_s": 0.0},
            faults={"rules": [{"subsystem": "aio_read", "rate": 1.0},
                              {"subsystem": "sync_read",
                               "rate": 1.0}]}, **KW)
        on = run_phases(eng, phases)
        assert on == off
        assert eng.check_leaks() == []

    def test_repeated_failures_trip_tier_breaker(
            self, gpt2_model, devices):
        cfg, params = gpt2_model
        phases = revisit_phases(cfg.vocab_size)
        eng = serving_engine(
            params, cfg, prefix_cache=True,
            kv_tier={"enabled": True, "disable_after": 1},
            faults={"rules": [{"subsystem": "kv_corrupt",
                               "rate": 1.0}]}, **KW)
        i = 0
        for ph in phases:
            for p in ph:
                eng.submit(i, p, max_new_tokens=6)
                i += 1
            eng.run()
        assert eng._kv_pool.disabled is not None
        h = eng.healthz()
        assert h["degraded"] is True
        assert any("kv_tier_disabled" in r for r in h["reasons"])
        assert h["ready"] is True                 # degraded ≠ unready
        assert eng.check_leaks() == []
        eng.shutdown()


# ------------------------------------------- engine: slot isolation
class TestSlotIsolation:
    def test_neighbor_requests_complete_identically(
            self, gpt2_model, devices):
        cfg, params = gpt2_model
        rng = np.random.default_rng(1)
        prompts = {f"req{i}": rng.integers(1, cfg.vocab_size,
                                           10).tolist()
                   for i in range(4)}
        base = serving_engine(params, cfg, **KW)
        for rid, p in prompts.items():
            base.submit(rid, p, max_new_tokens=5)
        ref = base.run()

        eng = serving_engine(
            params, cfg,
            faults={"rules": [{"subsystem": "slot", "match": "req1",
                               "count": 1}]}, **KW)
        for rid, p in prompts.items():
            eng.submit(rid, p, max_new_tokens=5)
        outs = eng.run()
        assert isinstance(outs["req1"], RequestFailed)
        assert outs["req1"].reason in ("slot_exception",
                                       "admit_exception")
        for rid in ("req0", "req2", "req3"):
            assert outs[rid] == ref[rid]
        assert eng._n_failed == 1
        assert eng.check_leaks() == []
        eng.shutdown()

    def test_failed_request_emits_trace_and_slo(self, gpt2_model,
                                                devices):
        cfg, params = gpt2_model
        eng = serving_engine(
            params, cfg, slo={"tiers": {"t": {}}, "default_tier": "t"},
            faults={"rules": [{"subsystem": "slot", "match": "bad",
                               "count": 1}]}, **KW)
        eng.submit("bad", [5, 9, 2], max_new_tokens=4, tier="t")
        outs = eng.run()
        assert isinstance(outs["bad"], RequestFailed)
        snap = eng.slo_tracker.snapshot()
        life = snap["tiers"]["t"]["lifetime"]
        assert life["failed"] == 1 and life["violated"] == 1
        evs = [e for e in eng.tracer.recorder.events()
               if e[3] == "request_failed"]
        assert len(evs) == 1
        eng.shutdown()

    def test_admit_exception_releases_pages(self, gpt2_model,
                                            devices, monkeypatch):
        """The satellite bugfix: an exception between page allocation
        and slot publish must release the pages (they used to leak)."""
        cfg, params = gpt2_model
        eng = serving_engine(params, cfg, **KW)

        def boom(*a, **k):
            raise RuntimeError("injected prefill failure")

        monkeypatch.setattr(eng, "_prefill", boom)
        eng.submit("x", [5, 9, 2], max_new_tokens=4)
        outs = eng.run()
        assert isinstance(outs["x"], RequestFailed)
        assert outs["x"].reason == "admit_exception"
        al = eng.allocator
        assert not al.owned and len(al.free) == eng.trash_page
        assert eng.check_leaks() == []


# ----------------------------------------------- engine: load shedding
class TestLoadShedding:
    def test_queue_depth_shed_typed_and_counted(self, gpt2_model,
                                                devices):
        cfg, params = gpt2_model
        eng = serving_engine(
            params, cfg, shed_queue_depth=2,
            slo={"tiers": {"gold": {}}, "default_tier": "gold"},
            **KW)
        for i in range(4):
            r = eng.submit(i, [5, 9, 2], max_new_tokens=3,
                           tier="gold")
            assert (r is None) == (i < 2)
        assert isinstance(r, RequestShed)
        assert r.reason == "queue_depth" and r.tier == "gold"
        outs = eng.run()
        served = [k for k, v in outs.items() if isinstance(v, list)]
        shed = [k for k, v in outs.items()
                if isinstance(v, RequestShed)]
        assert len(served) == 2 and len(shed) == 2
        life = eng.slo_tracker.snapshot()["tiers"]["gold"]["lifetime"]
        assert life["shed"] == 2
        assert life["violated"] == 0              # sheds never ran
        cnt = eng.registry.snapshot()["counters"]
        assert cnt["serving_shed_requests"] == 2
        assert cnt["slo_gold_shed_requests"] == 2
        assert eng.check_leaks() == []
        eng.shutdown()

    def test_deadline_shed_at_admission(self, gpt2_model, devices):
        import time as _time

        cfg, params = gpt2_model
        eng = serving_engine(
            params, cfg, shed_expired_deadline=True,
            slo={"tiers": {"rt": {"deadline_s": 0.001}},
                 "default_tier": "rt"}, **KW)
        eng.submit("late", [5, 9, 2], max_new_tokens=3)
        _time.sleep(0.01)
        outs = eng.run()
        assert isinstance(outs["late"], RequestShed)
        assert outs["late"].reason == "deadline"
        assert eng._shed_by_reason["deadline"] == 1
        eng.shutdown()

    def test_shed_validates_tier(self, gpt2_model, devices):
        cfg, params = gpt2_model
        eng = serving_engine(
            params, cfg, shed_queue_depth=1,
            slo={"tiers": {"t": {}}, "default_tier": "t"}, **KW)
        eng.submit(0, [5, 9], max_new_tokens=2)
        with pytest.raises(ValueError, match="unknown SLO tier"):
            eng.submit(1, [5, 9], max_new_tokens=2, tier="nope")
        eng.run()
        eng.shutdown()
        # slo off + named tier on the shed path raises like on_submit
        e2 = serving_engine(params, cfg, shed_queue_depth=1, **KW)
        e2.submit(0, [5, 9], max_new_tokens=2)
        with pytest.raises(ValueError, match="slo block is disabled"):
            e2.submit(1, [5, 9], max_new_tokens=2, tier="gold")
        e2.run()

    def test_shed_requires_slo_for_deadline(self, gpt2_model,
                                            devices):
        cfg, params = gpt2_model
        with pytest.raises(ValueError, match="shed_expired_deadline"):
            serving_engine(params, cfg, shed_expired_deadline=True,
                           **KW)

    def test_healthz_degraded_while_shedding(self, gpt2_model,
                                             devices):
        cfg, params = gpt2_model
        eng = serving_engine(params, cfg, shed_queue_depth=1, **KW)
        eng.submit(0, [5, 9], max_new_tokens=2)
        eng.submit(1, [5, 9], max_new_tokens=2)   # shed
        h = eng.healthz()
        assert h["degraded"] is True
        assert "load_shedding_active" in h["reasons"]
        assert h["ready"] is True                 # 200, not 503
        eng.run()


# ------------------------------------------------- ZI stream fatality
class TestZIStreamFatal:
    def test_postmortem_on_unrecoverable_stream(self, llama_model,
                                                devices, tmp_path):
        cfg, params = llama_model
        zi = serving_engine(
            params, cfg,
            zero_inference={"enabled": True, "tier": "nvme",
                            "nvme_path": str(tmp_path / "zi"),
                            "io_retries": 1,
                            "io_retry_backoff_s": 0.0},
            tracing={"dump_dir": str(tmp_path / "dump")},
            max_batch=2, page_size=8, num_pages=16, max_seq=32,
            prefill_bucket=8)
        faults.install_fault_plan(FaultPlan(
            [{"subsystem": "aio_read", "rate": 1.0},
             {"subsystem": "sync_read", "rate": 1.0}]))
        zi.submit("a", [5, 9, 2], max_new_tokens=4)
        with pytest.raises(FatalStreamError) as ei:
            zi.run()
        # the structured fatal carries its flight-recorder postmortem
        assert ei.value.postmortem_paths
        assert any(os.path.exists(p) for p in ei.value.postmortem_paths)

    def test_transient_stream_faults_keep_identity(self, llama_model,
                                                   devices, tmp_path):
        cfg, params = llama_model
        kw = dict(max_batch=2, page_size=8, num_pages=16, max_seq=32,
                  prefill_bucket=8)
        ref = serving_engine(params, cfg, **kw)
        ref.submit("a", [5, 9, 2], max_new_tokens=4)
        want = ref.run()["a"]
        zi = serving_engine(
            params, cfg,
            zero_inference={"enabled": True, "tier": "nvme",
                            "nvme_path": str(tmp_path / "zi2"),
                            "io_retries": 2,
                            "io_retry_backoff_s": 0.0}, **kw)
        faults.install_fault_plan(FaultPlan(
            [{"subsystem": "aio_read", "rate": 1.0, "count": 10}]))
        zi.submit("a", [5, 9, 2], max_new_tokens=4)
        assert zi.run()["a"] == want
        assert zi._reader.io_retries > 0 or \
            zi._reader.sync_fallbacks > 0


# ------------------------------------------------------ introspection
class TestRobustnessIntrospection:
    def test_statusz_robustness_block(self, gpt2_model, devices):
        cfg, params = gpt2_model
        eng = serving_engine(
            params, cfg, shed_queue_depth=1,
            faults={"rules": [{"subsystem": "slot", "match": "f",
                               "count": 1}]}, **KW)
        eng.submit("f", [5, 9, 2], max_new_tokens=3)
        eng.submit("s", [5, 9, 2], max_new_tokens=3)   # shed
        eng.run()
        rb = eng.statusz()["robustness"]
        assert rb["shed_requests"] == 1
        assert rb["failed_requests"] == 1
        assert rb["shed_rate"] == 0.5
        assert rb["faults"]["injected"] >= 1
        assert rb["degraded"] is True
        eng.shutdown()

    def test_dstpu_top_renders_robustness(self, gpt2_model, devices):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "dstpu_top", os.path.join(
                os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))),
                "tools", "dstpu_top.py"))
        dstpu_top = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(dstpu_top)
        cfg, params = gpt2_model
        eng = serving_engine(params, cfg, shed_queue_depth=1, **KW)
        eng.submit(0, [5, 9], max_new_tokens=2)
        eng.submit(1, [5, 9], max_new_tokens=2)   # shed
        eng.run()
        text = "\n".join(dstpu_top.render(eng.statusz(),
                                          eng.healthz()))
        assert "rbst" in text and "shed 1" in text
        assert "DEGRADED" in text

    def test_shed_and_fail_events_in_ring(self, gpt2_model, devices):
        cfg, params = gpt2_model
        eng = serving_engine(
            params, cfg, shed_queue_depth=1,
            faults={"rules": [{"subsystem": "slot", "match": "f",
                               "count": 1}]}, **KW)
        eng.submit("f", [5, 9, 2], max_new_tokens=3)
        eng.submit("s", [5, 9, 2], max_new_tokens=3)
        eng.run()
        phases = [e[3] for e in eng.tracer.recorder.events()]
        assert "request_shed" in phases
        assert "request_failed" in phases
        eng.shutdown()

"""Aux subsystems: timers, monitor, profiler, trace, watchdog."""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.timers import (SynchronizedWallClockTimer, ThroughputTimer,
                                  device_peak_bandwidth, device_peak_flops)
from deepspeed_tpu.monitor import CsvMonitor, MonitorMaster
from deepspeed_tpu.profiler import (FlopsProfiler, get_model_profile,
                                    params_count, transformer_train_flops,
                                    transformer_decode_flops)
from deepspeed_tpu.utils.trace import CommsLogger
from deepspeed_tpu.utils.watchdog import NanGuard, Watchdog


def test_wallclock_timer():
    timers = SynchronizedWallClockTimer()
    t = timers("fwd")
    t.start()
    time.sleep(0.01)
    t.stop()
    e = t.elapsed(reset=False)
    assert 0.005 < e < 1.0
    msg = timers.log(["fwd"])
    assert "fwd" in msg
    assert timers("fwd").elapsed() == 0.0  # log() reset it


def test_throughput_timer_mfu():
    tt = ThroughputTimer(batch_size=4, seq_len=128,
                         flops_per_sample=1e9, start_step=1)
    for _ in range(4):
        tt.start()
        time.sleep(0.002)
        tt.stop()
    s = tt.summary()
    assert s["samples_per_sec"] > 0
    assert s["tokens_per_sec"] == pytest.approx(s["samples_per_sec"] * 128)
    assert s["tflops"] > 0 and s["mfu"] > 0
    assert device_peak_flops() > 0


@pytest.mark.parametrize("kind,want", [
    ("TPU v5 lite", (197, 819)),           # what a v5e reports
    ("cpu", (1, 100)),
    ("TPU v9 imaginary", None),            # unknown: an error, no default
])
def test_device_peaks_keyed_by_device_kind(monkeypatch, kind, want):
    """(TFLOP/s bf16, GB/s HBM) per ``device_kind``."""
    class Dev:
        device_kind = kind

    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    if want is None:
        with pytest.raises(KeyError, match="no peak"):
            device_peak_flops()
        with pytest.raises(KeyError, match="no peak"):
            device_peak_bandwidth()
    else:
        assert (device_peak_flops() / 1e12,
                device_peak_bandwidth() / 1e9) == want


def test_csv_monitor(tmp_path):
    m = CsvMonitor(str(tmp_path), "job")
    m.write_events([("loss", 1.5, 0), ("loss", 1.2, 1), ("lr", 1e-4, 0)])
    m.flush()
    m.close()
    loss_csv = tmp_path / "job" / "loss.csv"
    assert loss_csv.exists()
    lines = loss_csv.read_text().strip().splitlines()
    assert lines[0] == "step,loss" and len(lines) == 3


def test_monitor_master(tmp_path):
    cfg = {"csv_monitor": {"enabled": True, "output_path": str(tmp_path),
                           "job_name": "mm"}}
    mm = MonitorMaster(cfg)
    assert mm.enabled
    mm.write_scalars({"loss": 0.5}, step=3)
    mm.flush()
    assert (tmp_path / "mm" / "loss.csv").exists()
    mm.close()
    assert not MonitorMaster({}).enabled


def test_comet_monitor_gated_and_logs(tmp_path, monkeypatch):
    """ref: deepspeed/monitor/comet.py — import-gated like wandb; when
    comet_ml IS importable, metrics flow through Experiment.log_metric."""
    import sys
    import types

    from deepspeed_tpu.monitor import CometMonitor

    # absent comet_ml → disabled backend, master skips it, no crash
    # (forced: a developer machine may genuinely have comet_ml)
    monkeypatch.setitem(sys.modules, "comet_ml", None)
    assert not CometMonitor(project="p").enabled
    mm = MonitorMaster({"comet": {"enabled": True, "project": "p"}})
    assert not mm.enabled

    logged = []

    class _Exp:
        def set_name(self, n):
            logged.append(("name", n))

        def log_metric(self, tag, value, step=None):
            logged.append((tag, value, step))

        def flush(self):
            pass

        def end(self):
            logged.append(("end",))

    fake = types.ModuleType("comet_ml")
    fake.start = lambda **kw: _Exp()
    monkeypatch.setitem(sys.modules, "comet_ml", fake)
    m = CometMonitor(project="p", experiment_name="run1")
    assert m.enabled
    m.write_events([("loss", 0.5, 7)])
    m.close()
    assert ("name", "run1") in logged and ("loss", 0.5, 7) in logged


def test_flops_profiler_matmul():
    a = jnp.ones((128, 256), jnp.float32)
    b = jnp.ones((256, 64), jnp.float32)
    prof = FlopsProfiler(lambda x, y: x @ y)
    s = prof.profile(a, b, iters=2, warmup=1)
    # XLA counts 2*M*N*K flops for the matmul
    assert s["flops"] == pytest.approx(2 * 128 * 256 * 64, rel=0.1)
    assert s["latency_s"] > 0 and s["tflops"] > 0


def test_get_model_profile_and_params():
    params = {"w": jnp.ones((16, 16)), "b": jnp.ones((16,))}
    out = get_model_profile(lambda p, x: x @ p["w"] + p["b"],
                            (params, jnp.ones((4, 16))), params=params,
                            iters=1, print_profile=False)
    assert out["params"] == 16 * 16 + 16
    assert params_count(params) == 272


def test_analytic_flops():
    f6 = transformer_train_flops(1e9, 1000)
    assert f6 == pytest.approx(6e12)
    f8 = transformer_train_flops(1e9, 1000, checkpoint_activations=True)
    assert f8 == pytest.approx(8e12)
    fa = transformer_train_flops(1e9, 1000, n_layers=4, hidden=512, seq_len=256)
    assert fa > f6
    assert transformer_decode_flops(1e9, 4, 512, 100) > 2e9


def test_comms_logger():
    cl = CommsLogger()
    with cl.record("all_reduce", 1024):
        pass
    with cl.record("all_reduce", 2048):
        pass
    with cl.record("all_gather", 512):
        pass
    s = cl.summary()
    assert s["all_reduce"]["count"] == 2 and s["all_reduce"]["bytes"] == 3072
    assert s["all_gather"]["count"] == 1
    cl.reset()
    assert cl.summary() == {}


def test_nan_guard():
    good = {"a": jnp.ones(3), "b": jnp.zeros(2)}
    bad = {"a": jnp.array([1.0, jnp.nan, 2.0]), "b": jnp.zeros(2)}
    assert bool(NanGuard.all_finite(good))
    assert not bool(NanGuard.all_finite(bad))
    # jit-compatible
    assert not bool(jax.jit(NanGuard.all_finite)(bad))
    new = {"a": jnp.full(3, 9.0), "b": jnp.full(2, 9.0)}
    old = {"a": jnp.zeros(3), "b": jnp.zeros(2)}
    kept = NanGuard.where_finite(bad, new, old)
    np.testing.assert_allclose(kept["a"], old["a"])
    took = NanGuard.where_finite(good, new, old)
    np.testing.assert_allclose(took["a"], new["a"])


def test_watchdog_fires_and_pets():
    fired = []
    wd = Watchdog(timeout_s=0.15, on_timeout=lambda: fired.append(1),
                  abort_on_timeout=False, poll_s=0.03).start()
    for _ in range(5):  # heartbeats keep it alive
        time.sleep(0.05)
        wd.pet()
    assert not wd.fired
    time.sleep(0.4)  # stop petting → fires
    assert wd.fired and fired == [1]
    wd.stop()


class TestDataAnalyzer:
    """ref: data_pipeline/data_sampling/data_analyzer.py"""

    def _dataset(self, n=40, seed=0):
        rng = np.random.default_rng(seed)
        return [{"tokens": np.concatenate([
            rng.integers(1, 50, rng.integers(3, 20)),
            np.zeros(rng.integers(0, 5), np.int64)])} for _ in range(n)]

    def test_sharded_map_then_merge(self, tmp_path):
        from deepspeed_tpu.data.analyzer import DataAnalyzer, seqlen_metric

        ds = self._dataset()
        for w in range(3):
            DataAnalyzer({"seqlen": seqlen_metric(0)}, str(tmp_path),
                         worker_id=w, num_workers=3).run_map(ds)
        merged = DataAnalyzer({"seqlen": seqlen_metric(0)},
                              str(tmp_path), num_workers=3).merge(len(ds))
        want = [float(np.sum(np.asarray(s["tokens"]) != 0)) for s in ds]
        np.testing.assert_array_equal(merged["seqlen"], want)
        # load + indexer handoff
        idx = DataAnalyzer.indexer(str(tmp_path), "seqlen")
        easy = idx.eligible(max_difficulty=8)
        assert all(want[i] <= 8 for i in easy)

    def test_missing_shard_raises(self, tmp_path):
        from deepspeed_tpu.data.analyzer import DataAnalyzer, seqlen_metric

        ds = self._dataset(10)
        DataAnalyzer({"seqlen": seqlen_metric()}, str(tmp_path),
                     worker_id=0, num_workers=2).run_map(ds)
        with pytest.raises(FileNotFoundError):
            DataAnalyzer({"seqlen": seqlen_metric()}, str(tmp_path),
                         num_workers=2).merge(len(ds))

    def test_vocab_rarity_orders_rare_higher(self, tmp_path):
        from deepspeed_tpu.data.analyzer import VocabRarity

        common = {"tokens": np.full(10, 7)}
        rare = {"tokens": np.asarray([43, 44, 45])}
        ds = [common] * 20 + [rare]
        vr = VocabRarity(vocab_size=64, pad_token_id=0).fit(ds)
        assert vr(rare) > vr(common)

    def test_curriculum_end_to_end(self, tmp_path):
        """Analyzer difficulties drive a seqlen curriculum: early batches
        draw only short samples, late batches see everything."""
        from deepspeed_tpu.data.analyzer import DataAnalyzer, seqlen_metric
        from deepspeed_tpu.data.curriculum import (CurriculumConfig,
                                                   CurriculumScheduler)

        ds = self._dataset(60, seed=1)
        an = DataAnalyzer({"seqlen": seqlen_metric(0)}, str(tmp_path))
        an.run_map(ds)
        an.merge(len(ds))
        idx = DataAnalyzer.indexer(str(tmp_path), "seqlen")
        sched = CurriculumScheduler(CurriculumConfig(
            enabled=True, min_difficulty=5, max_difficulty=20,
            total_curriculum_step=100))
        lens = np.asarray([float(np.sum(s["tokens"] != 0)) for s in ds])
        early = idx.sample(16, sched.get_difficulty(0))
        late = idx.sample(16, sched.get_difficulty(100))
        assert lens[early].max() <= 5
        assert lens[late].max() > 5

    def test_vocab_rarity_unseen_is_hard_and_oob_raises(self):
        from deepspeed_tpu.data.analyzer import VocabRarity

        ds = [{"tokens": np.full(10, 7)}]
        vr = VocabRarity(vocab_size=16, pad_token_id=0).fit(ds)
        seen = vr({"tokens": np.asarray([7, 7])})
        unseen = vr({"tokens": np.asarray([3, 4])})
        assert unseen > seen  # out-of-corpus tokens rank hardest
        with pytest.raises(ValueError, match="vocab_size"):
            VocabRarity(vocab_size=8).fit([{"tokens": np.asarray([9])}])


class TestEngineCurriculum:
    """The parsed curriculum block drives train_batch (ref:
    engine.curriculum_scheduler + megatron curriculum_seqlen)."""

    @pytest.mark.slow
    def test_seqlen_curriculum_truncates_and_learns(self, devices):
        import deepspeed_tpu as dstpu
        from deepspeed_tpu.models import llama

        cfg = llama.LlamaConfig.tiny()
        engine, _, _, _ = dstpu.initialize(
            loss_fn=llama.loss_fn(cfg),
            params=llama.init_params(jax.random.PRNGKey(0), cfg),
            config={"train_micro_batch_size_per_gpu": 1,
                    "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                    "curriculum_learning": {
                        "enabled": True, "curriculum_type": "seqlen",
                        "min_difficulty": 9, "max_difficulty": 33,
                        "schedule_config": {"total_curriculum_step": 4,
                                            "difficulty_step": 8}}})
        assert engine.curriculum_difficulty() == 9
        toks = jnp.asarray(np.random.default_rng(0).integers(
            0, cfg.vocab_size, (8, 33)), jnp.int32)
        losses = [float(engine.train_batch({"tokens": toks}))
                  for _ in range(6)]
        assert np.isfinite(losses).all()
        # ramped to max, floored to the difficulty_step grid (the
        # reference scheduler does the same: 33 -> 32 at step 8)
        assert engine.curriculum_difficulty() == 32

    def test_no_curriculum_block_is_inert(self, devices):
        import deepspeed_tpu as dstpu

        engine, _, _, _ = dstpu.initialize(
            loss_fn=lambda p, b: jnp.sum(p["w"] * b["x"].mean()),
            params={"w": jnp.ones(4)},
            config={"train_batch_size": 8})
        assert engine.curriculum_scheduler is None
        assert engine.curriculum_difficulty() is None

    @pytest.mark.slow
    def test_torch_idiom_applies_curriculum(self, devices):
        import deepspeed_tpu as dstpu
        from deepspeed_tpu.models import llama

        cfg = llama.LlamaConfig.tiny()
        engine, _, _, _ = dstpu.initialize(
            loss_fn=llama.loss_fn(cfg),
            params=llama.init_params(jax.random.PRNGKey(0), cfg),
            config={"train_micro_batch_size_per_gpu": 1,
                    "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                    "curriculum_learning": {
                        "enabled": True, "curriculum_type": "seqlen",
                        "min_difficulty": 9, "max_difficulty": 33,
                        "schedule_config": {"total_curriculum_step": 400,
                                            "difficulty_step": 8}}})
        toks = jnp.asarray(np.random.default_rng(0).integers(
            0, cfg.vocab_size, (8, 33)), jnp.int32)
        loss = engine({"tokens": toks})          # torch idiom
        engine.backward(loss)
        engine.step()
        # same truncated-program shapes as train_batch: difficulty 9
        # means the compiled step saw [8, 9] tokens — compare losses
        l2 = float(engine.train_batch({"tokens": toks}))
        assert np.isfinite(float(loss)) and np.isfinite(l2)

    def test_infinity_rejects_curriculum(self, devices):
        import deepspeed_tpu as dstpu

        with pytest.raises(ValueError, match="curriculum"):
            dstpu.initialize(
                loss_fn=lambda p, b: jnp.sum(p["w"]), params={"w": jnp.ones(4)},
                config={"train_batch_size": 8,
                        "optimizer": {"type": "adamw",
                                      "params": {"lr": 1e-3}},
                        "curriculum_learning": {"enabled": True},
                        "zero_optimization": {"offload_optimizer": {
                            "device": "cpu", "scheduled": True}}})


class TestEngineAuxBlocks:
    """PLD / eigenvalue / random_ltd config blocks surface as live engine
    objects (ref: the reference engine's attributes) — no inert parses."""

    def _engine(self, extra):
        import deepspeed_tpu as dstpu

        cfg = {"train_batch_size": 8,
               "optimizer": {"type": "adamw", "params": {"lr": 1e-3}}}
        cfg.update(extra)
        e, _, _, _ = dstpu.initialize(
            loss_fn=lambda p, b: jnp.mean((b["x"] @ p["w"]) ** 2),
            params={"w": jnp.ones((4, 2)) * 0.3}, config=cfg)
        return e

    def test_pld_attribute_advances(self, devices):
        e = self._engine({"progressive_layer_drop": {
            "enabled": True, "theta": 0.6, "gamma": 0.01}})
        assert e.progressive_layer_drop is not None
        t0 = e.progressive_layer_drop.get_theta()
        for _ in range(50):
            e.train_batch({"x": jnp.ones((8, 4), jnp.float32)})
        assert e.progressive_layer_drop.get_theta() < t0

    def test_eigenvalue_attribute_computes(self, devices):
        e = self._engine({"eigenvalue": {"enabled": True, "max_iter": 8,
                                         "tol": 1e-2}})
        x = jnp.ones((8, 4), jnp.float32)
        lam = e.eigenvalue.compute(
            lambda p: jnp.mean((x @ p["w"]) ** 2), e.module_params())
        assert float(lam) > 0

    def test_random_ltd_factory(self, devices):
        e = self._engine({"random_ltd": {
            "enabled": True,
            "total_layer_num": 4, "random_ltd_layer_num": 2,
            "random_ltd_schedule": {"min_value": 16, "max_value": 64,
                                    "schedule_config": {
                                        "seq_per_step": 16,
                                        "require_steps": 10}}}})
        sched = e.random_ltd_scheduler(seq_len=64)
        # reference schema mapped, not dropped: ramp starts at min_value
        # and quantizes by seq_per_step
        assert sched.keep_at(0) == 16
        assert sched.keep_at(10) == 64
        assert sched.keep_at(5) % 16 == 0
        e2 = self._engine({})
        with pytest.raises(ValueError, match="random_ltd"):
            e2.random_ltd_scheduler(seq_len=64)


class TestPacking:
    def test_pack_documents_first_fit_and_truncate(self):
        from deepspeed_tpu.data.packing import (pack_documents,
                                                packing_efficiency)

        docs = [[1] * 6, [2] * 3, [3] * 4, [4] * 12, [5] * 2, []]
        toks, segs = pack_documents(docs, seq_len=10)
        # doc4 truncated to 10; empties skipped; first-fit: row0=[d1,d2],
        # row1=[d3,d5], row2=[d4 truncated]
        assert toks.shape == segs.shape and toks.shape[1] == 10
        for r in range(toks.shape[0]):
            live = segs[r] > 0
            # per-row ids are 1..n contiguous, padding zeros at the tail
            ids = segs[r][live]
            assert list(np.unique(ids)) == list(range(1, ids.max() + 1))
            assert not live[np.argmin(live):].any() or live.all()
        assert 0.5 < packing_efficiency(segs) <= 1.0
        # round-trip: every non-empty doc's tokens appear contiguously
        flat = [t for d in docs for t in d[:10]]
        assert sorted(toks[segs > 0].tolist()) == sorted(flat)

    def test_packed_loader_static_shapes_and_training(self, devices):
        from deepspeed_tpu.data.packing import PackedDataLoader
        from deepspeed_tpu.models import llama

        cfg = llama.LlamaConfig.tiny()
        rng = np.random.default_rng(0)
        docs = [rng.integers(1, cfg.vocab_size,
                             rng.integers(4, 20)).tolist()
                for _ in range(120)]
        dl = PackedDataLoader(docs, batch_rows=8, seq_len=32)
        batches = list(dl)
        assert len(batches) >= 2
        for b in batches:
            assert b["tokens"].shape == (8, 33)          # T+1 contract
            assert b["segment_ids"].shape == (8, 33)
        # every document's tokens survive exactly once across batches
        total_live = sum(int((b["segment_ids"] > 0).sum()) for b in batches)
        assert total_live == sum(len(d) for d in docs)

        import deepspeed_tpu as dstpu

        engine, _, _, _ = dstpu.initialize(
            loss_fn=llama.loss_fn(cfg),
            params=llama.init_params(jax.random.PRNGKey(0), cfg),
            config={"train_micro_batch_size_per_gpu": 8,
                    "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                    "zero_optimization": {"stage": 0}})
        ls = [float(engine.train_batch(
            {"tokens": jnp.asarray(b["tokens"]),
             "segment_ids": jnp.asarray(b["segment_ids"])}))
            for b in batches[:3]]
        assert all(np.isfinite(ls)), ls

"""Engine-integrated comm compression (ref: deepspeed/runtime/fp16/onebit/
adam.py; ZeRO++ zero_quantized_gradients).

Proves the round-1 verdict item: a config flag alone must produce int8
on the wire — numerics via trajectory comparison, the collective choice
via compiled-HLO inspection.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import deepspeed_tpu as dstpu
from deepspeed_tpu import comm_compress
from deepspeed_tpu.ops import optim as ops_optim
from deepspeed_tpu.topology import MeshSpec


def mlp_loss(params, batch):
    h = jnp.tanh(batch["x"] @ params["w1"] + params["b1"])
    pred = h @ params["w2"] + params["b2"]
    return jnp.mean((pred - batch["y"]) ** 2)


def make_params():
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    return {"w1": jax.random.normal(k1, (16, 32)) * 0.3,
            "b1": jnp.zeros((32,)),
            "w2": jax.random.normal(k2, (32, 4)) * 0.3,
            "b2": jnp.zeros((4,))}


def make_batch(n=64):
    rng = np.random.default_rng(0)
    return {"x": jnp.asarray(rng.normal(size=(n, 16)), jnp.float32),
            "y": jnp.asarray(rng.normal(size=(n, 4)), jnp.float32)}


def build(config_extra=None, optimizer=None, opt_type="adamw",
          opt_params=None, accum=1):
    cfg = {
        "train_micro_batch_size_per_gpu": 64 // 8 // accum,
        "gradient_accumulation_steps": accum,
        "optimizer": {"type": opt_type, "params": opt_params or {"lr": 5e-2}},
        "mesh": {"data": 8},
    }
    if config_extra:
        cfg.update(config_extra)
    engine, _, _, _ = dstpu.initialize(
        loss_fn=mlp_loss, params=make_params(), config=cfg,
        optimizer=optimizer)
    return engine


def compiled_text(engine, batch):
    return engine.lower_step(batch).compile().as_text()


class TestQuantizedAllReduce:
    def test_matches_mean_within_int8_tol(self, devices):
        ms = MeshSpec.build({"data": 8})
        x = jnp.asarray(
            np.random.default_rng(1).normal(size=(8, 40, 7)), jnp.float32)

        def f(xs):
            local = xs[0]
            return comm_compress.quantized_all_reduce(local, "data")[None]

        got = jax.shard_map(
            f, mesh=ms.mesh, in_specs=(P("data"),), out_specs=P("data"),
            check_vma=False)(x)
        want = jnp.mean(x, axis=0)
        for d in range(8):
            np.testing.assert_allclose(got[d], want, atol=2e-2, rtol=2e-2)

    @pytest.mark.slow
    def test_padding_path(self, devices):
        ms = MeshSpec.build({"data": 8})
        # size 13: needs padding to 8*512
        x = jnp.asarray(
            np.random.default_rng(2).normal(size=(8, 13)), jnp.float32)

        def f(xs):
            return comm_compress.quantized_all_reduce(xs[0], "data")[None]

        got = jax.shard_map(
            f, mesh=ms.mesh, in_specs=(P("data"),), out_specs=P("data"),
            check_vma=False)(x)
        np.testing.assert_allclose(got[0], jnp.mean(x, 0), atol=2e-2,
                                   rtol=2e-2)


class TestQgzEngine:
    def test_mode_resolved_and_trajectory_close(self, devices):
        exact = build({"zero_optimization": {"stage": 2}})
        qgz = build({"zero_optimization": {
            "stage": 2, "zero_quantized_gradients": True}})
        assert exact.grad_comm_mode is None
        assert qgz.grad_comm_mode == "qgz"
        batch = make_batch()
        le = [float(exact.train_batch(batch)) for _ in range(6)]
        lq = [float(qgz.train_batch(batch)) for _ in range(6)]
        assert lq[-1] < lq[0], "qgz engine did not learn"
        np.testing.assert_allclose(lq, le, rtol=0.1)

    def test_hlo_contains_int8_all_to_all(self, devices):
        qgz = build({"zero_optimization": {
            "stage": 1, "zero_quantized_gradients": True}})
        txt = compiled_text(qgz, make_batch())
        assert "all-to-all" in txt, "qgZ step emitted no all-to-all"
        assert "s8[" in txt, "qgZ step carries no int8 payload"

    def test_grad_accum_composes(self, devices):
        qgz = build({"zero_optimization": {
            "stage": 0, "zero_quantized_gradients": True}}, accum=2)
        batch = make_batch()
        losses = [float(qgz.train_batch(batch)) for _ in range(5)]
        assert losses[-1] < losses[0]


class TestQwzEngine:
    """ZeRO++ zero_quantized_weights: stage-3 param all-gather as int8."""

    def test_mode_resolved_and_trajectory_close(self, devices):
        exact = build({"zero_optimization": {"stage": 3}})
        qwz = build({"zero_optimization": {
            "stage": 3, "zero_quantized_weights": True}})
        assert exact.grad_comm_mode is None
        assert qwz.grad_comm_mode == "qwz"
        batch = make_batch()
        le = [float(exact.train_batch(batch)) for _ in range(6)]
        lq = [float(qwz.train_batch(batch)) for _ in range(6)]
        assert lq[-1] < lq[0], "qwz engine did not learn"
        np.testing.assert_allclose(lq, le, rtol=0.1)

    def test_hlo_contains_int8_all_gather(self, devices):
        qwz = build({"zero_optimization": {
            "stage": 3, "zero_quantized_weights": True}})
        txt = compiled_text(qwz, make_batch())
        assert "all-gather" in txt, "qwZ step emitted no all-gather"
        assert "s8[" in txt, "qwZ step carries no int8 payload"

    def test_combines_with_qgz_and_accum(self, devices):
        both = build({"zero_optimization": {
            "stage": 3, "zero_quantized_weights": True,
            "zero_quantized_gradients": True}}, accum=2)
        assert both.grad_comm_mode == "qwz"
        txt = compiled_text(both, make_batch())
        assert "all-to-all" in txt, "qgZ grad wire missing from qwZ step"
        batch = make_batch()
        losses = [float(both.train_batch(batch)) for _ in range(6)]
        assert losses[-1] < losses[0]

    def test_flat_state_layout_and_export(self, devices):
        qwz = build({"zero_optimization": {
            "stage": 3, "zero_quantized_weights": True}})
        W = qwz.mesh.size("data")
        assert qwz.state.params.shape == (W, qwz._qwz_chunk)
        assert qwz.state.params.sharding.spec[0] == "data"
        batch = make_batch()
        qwz.train_batch(batch)
        # export reassembles model-shaped leaves from the flat buffer
        mp = qwz.module_params()
        assert mp["w1"].shape == (16, 32)
        # eval path (exact weights, no int8) runs
        assert float(qwz.eval_batch(batch)) > 0

    def test_nonfinite_grad_skips_update(self, devices):
        qwz = build({"zero_optimization": {
            "stage": 3, "zero_quantized_weights": True}})
        good = make_batch()
        qwz.train_batch(good)
        flat_before = np.asarray(qwz.state.params)
        bad = dict(good)
        bad["x"] = good["x"].at[0, 0].set(jnp.nan)  # one device's shard
        qwz.train_batch(bad)
        assert int(qwz.metrics["overflow"]) == 1
        np.testing.assert_array_equal(flat_before,
                                      np.asarray(qwz.state.params))
        assert qwz.skipped_steps == 1


class TestOnebitEngine:
    def test_warmup_matches_exact_adam(self, devices):
        ob = build(opt_type="OnebitAdam",
                   opt_params={"lr": 5e-2, "freeze_step": 4})
        assert ob.grad_comm_mode == "onebit"
        ref = build(optimizer=ops_optim.adam(
            lr=5e-2, bias_correction=False, weight_decay=0.0))
        batch = make_batch()
        lo = [float(ob.train_batch(batch)) for _ in range(4)]
        lr_ = [float(ref.train_batch(batch)) for _ in range(4)]
        np.testing.assert_allclose(lo, lr_, rtol=1e-4, atol=1e-5)

    def test_compressed_phase_learns(self, devices):
        ob = build(opt_type="OnebitAdam",
                   opt_params={"lr": 5e-2, "freeze_step": 3})
        batch = make_batch()
        losses = [float(ob.train_batch(batch)) for _ in range(10)]
        assert losses[-1] < losses[3] < losses[0]

    def test_error_feedback_state_stacked_per_device(self, devices):
        ob = build(opt_type="OnebitAdam",
                   opt_params={"lr": 5e-2, "freeze_step": 2})
        err = ob.state.opt_state.err
        assert err["w1"].shape == (8, 16, 32)
        # err leading dim is sharded over data (each device owns its slice)
        sh = err["w1"].sharding
        assert sh.spec[0] == "data"
        # after compressed steps the error feedback is nonzero
        batch = make_batch()
        for _ in range(5):
            ob.train_batch(batch)
        assert float(jnp.abs(ob.state.opt_state.err["w1"]).max()) > 0

    def test_nonfinite_grad_skips_update(self, devices):
        ob = build(opt_type="OnebitAdam",
                   opt_params={"lr": 5e-2, "freeze_step": 2})
        good = make_batch()
        ob.train_batch(good)
        params_before = jax.tree.map(np.asarray, ob.state.params)
        bad = dict(good)
        # poison ONE device's shard only: the skip must be global consensus
        bad["x"] = good["x"].at[0, 0].set(jnp.nan)
        ob.train_batch(bad)
        assert int(ob.metrics["overflow"]) == 1
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(a, np.asarray(b)),
            params_before, ob.state.params)
        assert ob.skipped_steps == 1

    def test_hlo_contains_int8_all_gather(self, devices):
        ob = build(opt_type="OnebitAdam",
                   opt_params={"lr": 5e-2, "freeze_step": 2})
        txt = compiled_text(ob, make_batch())
        assert "all-gather" in txt
        assert "s8[" in txt, "onebit step carries no int8 payload"


class TestGates:
    def test_onebit_rejects_zero_stage(self, devices):
        with pytest.raises(ValueError, match="1-bit"):
            build({"zero_optimization": {"stage": 1}},
                  opt_type="OnebitAdam", opt_params={"lr": 1e-2})

    def test_qgz_rejects_stage3(self, devices):
        with pytest.raises(ValueError, match="stages 0-2"):
            build({"zero_optimization": {
                "stage": 3, "zero_quantized_gradients": True}})

    def test_qwz_sharded_init_thunk(self, devices):
        """zero.Init thunk composes with the qwZ flat-shard layout: the
        thunk is traced into the jitted state init, landing directly in
        the [world, chunk] rows, and matches eager init exactly."""
        cfg = {
            "train_micro_batch_size_per_gpu": 8,
            "optimizer": {"type": "adamw", "params": {"lr": 5e-2}},
            "mesh": {"data": 8},
            "zero_optimization": {"stage": 3,
                                  "zero_quantized_weights": True},
        }
        thunk, _, _, _ = dstpu.initialize(
            loss_fn=mlp_loss, params=make_params, config=dict(cfg))
        eager, _, _, _ = dstpu.initialize(
            loss_fn=mlp_loss, params=make_params(), config=dict(cfg))
        assert thunk.grad_comm_mode == "qwz"
        assert not thunk.state.params.sharding.is_fully_replicated
        np.testing.assert_allclose(np.asarray(thunk.state.params),
                                   np.asarray(eager.state.params),
                                   rtol=1e-6, atol=1e-7)
        batch = make_batch()
        lt = [float(thunk.train_batch(batch)) for _ in range(4)]
        le = [float(eager.train_batch(batch)) for _ in range(4)]
        np.testing.assert_allclose(lt, le, rtol=1e-6)

    def test_qwz_rejects_non_stage3(self, devices):
        with pytest.raises(ValueError, match="stage-3"):
            build({"zero_optimization": {
                "stage": 2, "zero_quantized_weights": True}})

    def test_qwz_rejects_lamb(self, devices):
        with pytest.raises(ValueError, match="elementwise"):
            build({"zero_optimization": {
                "stage": 3, "zero_quantized_weights": True}},
                opt_type="lamb", opt_params={"lr": 1e-3})

    def test_rejects_model_parallel_mesh(self, devices):
        cfg = {
            "train_micro_batch_size_per_gpu": 16,
            "optimizer": {"type": "OnebitAdam", "params": {"lr": 1e-2}},
            "mesh": {"data": 4, "model": 2},
        }
        with pytest.raises(ValueError, match="pure data-parallel"):
            dstpu.initialize(loss_fn=mlp_loss, params=make_params(),
                             config=cfg)

    def test_world1_degrades_with_warning(self, devices):
        ms = MeshSpec.build({"data": 1}, devices=jax.devices()[:1])
        cfg = {
            "train_micro_batch_size_per_gpu": 64,
            "optimizer": {"type": "OnebitAdam", "params": {"lr": 1e-2}},
        }
        engine, _, _, _ = dstpu.initialize(
            loss_fn=mlp_loss, params=make_params(), config=cfg, mesh=ms)
        assert engine.grad_comm_mode is None
        batch = make_batch()
        l0 = float(engine.train_batch(batch))
        l1 = float(engine.train_batch(batch))
        assert l1 < l0


@pytest.mark.parametrize("mode", ["qgz", "onebit", "qwz"])
def test_zero3_model_helpers_are_inert_in_the_shard_map_paths(
        mode, devices, monkeypatch):
    """A model whose forward states ZeRO-3's gather and batch pin
    (zero.gather_at_use, zero.pin_to_batch) trains under the compressed
    paths as before: inside their ``shard_map`` the data axis is manual,
    and the helpers return their argument (under ``qwz`` the stage is 3
    and only that keeps a constraint on ``data`` out of the body)."""
    from deepspeed_tpu import zero
    from deepspeed_tpu.models import gpt2

    extra, opt = {
        "qgz": ({"stage": 2, "zero_quantized_gradients": True}, "adamw"),
        "onebit": ({"stage": 0}, "OnebitAdam"),
        "qwz": ({"stage": 3, "zero_quantized_weights": True}, "adamw"),
    }[mode]
    seen, real = [], zero._mesh_at_use
    monkeypatch.setattr(zero, "_mesh_at_use",
                        lambda: seen.append(real()) or seen[-1])
    cfg = gpt2.GPT2Config.tiny(remat="save_dots")
    engine, _, _, _ = dstpu.initialize(
        loss_fn=gpt2.loss_fn(cfg),
        params=gpt2.init_params(jax.random.PRNGKey(0), cfg),
        config={"train_micro_batch_size_per_gpu": 1, "mesh": {"data": 8},
                "zero_optimization": extra,
                "optimizer": {"type": opt, "params": {"lr": 1e-3}}})
    assert engine.grad_comm_mode == mode
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (8, 25), dtype=np.int32)
    losses = [float(engine.train_batch({"tokens": tokens}))
              for _ in range(3)]
    assert seen and all(m is None for m in seen)
    assert np.isfinite(losses).all() and losses[-1] < losses[0]

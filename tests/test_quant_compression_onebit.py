"""Quantization, compression training, 1-bit optimizers (SURVEY rows 10, 17)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from deepspeed_tpu.ops.quant import (dequantize, from_fp8, quantize,
                                     quantize_pallas, quantized_all_gather,
                                     quantized_reduce_scatter, to_fp8)
from deepspeed_tpu.compression import (CompressionConfig, Compressor,
                                       fake_quant, head_mask, init_compression,
                                       magnitude_mask, row_mask)
from deepspeed_tpu.ops.onebit import onebit_adam, onebit_allreduce, onebit_lamb


# ---------------------------------------------------------------- quantize
def test_int8_roundtrip_symmetric():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(4, 64), jnp.float32)
    q, s, z = quantize(x, bits=8, num_groups=4)
    assert q.dtype == jnp.int8 and s.shape == (4,) and z is None
    err = jnp.max(jnp.abs(dequantize(q, s) - x))
    assert float(err) < float(jnp.max(jnp.abs(x))) / 100  # <1 lsb of 127

def test_int8_roundtrip_asymmetric():
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.rand(2, 32) * 5 + 3, jnp.float32)  # all-positive
    q, s, z = quantize(x, bits=8, num_groups=2, symmetric=False)
    assert z is not None
    rt = dequantize(q, s, z, bits=8)
    assert float(jnp.max(jnp.abs(rt - x))) < 0.05

def test_int4():
    x = jnp.linspace(-1, 1, 64, dtype=jnp.float32)
    q, s, _ = quantize(x, bits=4, num_groups=1)
    assert int(q.max()) <= 7 and int(q.min()) >= -7
    assert float(jnp.max(jnp.abs(dequantize(q, s, bits=4) - x))) < 0.15

def test_quantize_pallas_matches_reference():
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(8, 128), jnp.float32)
    q_ref, s_ref, _ = quantize(x, bits=8, num_groups=8)
    q, s = quantize_pallas(x, num_groups=8, interpret=True)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q_ref))
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), rtol=1e-6)

def test_fp8_roundtrip():
    x = jnp.asarray([[0.5, -2.0, 100.0, 1e-3]], jnp.float32)
    f8, scale = to_fp8(x, "e4m3")
    rt = from_fp8(f8, scale)
    assert float(jnp.max(jnp.abs(rt - x))) / 100.0 < 0.1


# ------------------------------------------------- quantized collectives
def _mesh8():
    return Mesh(np.array(jax.devices()[:8]), ("data",))

def test_quantized_all_gather():
    mesh = _mesh8()
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(8, 16), jnp.float32)

    f = shard_map(lambda v: quantized_all_gather(v[0], "data", num_groups=2),
                  mesh=mesh, in_specs=P("data"), out_specs=P(),
                  check_vma=False)
    out = f(x)
    assert out.shape == (8, 16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x), atol=0.05)

@pytest.mark.slow
def test_quantized_reduce_scatter_matches_psum_scatter():
    mesh = _mesh8()
    rng = np.random.RandomState(4)
    # per-chip partial grads: [8 shards * 4, 8]
    x = jnp.asarray(rng.randn(8, 32, 8), jnp.float32)

    qrs = shard_map(
        lambda v: quantized_reduce_scatter(v[0], "data", groups_per_shard=4),
        mesh=mesh, in_specs=P("data"), out_specs=P("data"))
    got = qrs(x)                       # [8 chips * 4, 8] stacked shards
    exact = jnp.mean(x, axis=0)        # [32, 8] the true mean
    np.testing.assert_allclose(np.asarray(got), np.asarray(exact), atol=0.05)


# --------------------------------------------------------------- compression
def test_magnitude_row_head_masks():
    rng = np.random.RandomState(5)
    w = jnp.asarray(rng.randn(8, 16), jnp.float32)
    m = magnitude_mask(w, 0.25)
    assert float(m.mean()) == pytest.approx(0.25, abs=0.02)
    r = row_mask(w, 0.5)
    assert r.shape == (8, 1) and float(r.sum()) == 4
    h = head_mask(w, num_heads=4, dense_ratio=0.5)
    assert h.shape == (1, 16) and float(h.sum()) == 8  # 2 of 4 heads * hd 4

def test_fake_quant_straight_through_gradient():
    w = jnp.asarray([0.3, -0.7, 1.1], jnp.float32)
    g = jax.grad(lambda x: jnp.sum(fake_quant(x, bits=8) * 2.0))(w)
    np.testing.assert_allclose(np.asarray(g), 2.0)  # STE passes grads through

def test_compressor_config_and_apply_schedule():
    cfg = {
        "compression_training": {
            "weight_quantization": {
                "shared_parameters": {"enabled": True, "schedule_offset": 5,
                                      "quantize_groups": 1},
                "different_groups": {
                    "q1": {"params": {"target_bits": 8}, "modules": ["dense"]}}},
            "sparse_pruning": {
                "shared_parameters": {"enabled": True, "schedule_offset": 0},
                "different_groups": {
                    "s1": {"params": {"dense_ratio": 0.5}, "modules": ["*"]}}},
        }}
    comp = init_compression(cfg)
    assert comp.active
    rng = np.random.RandomState(6)
    params = {"dense": {"w": jnp.asarray(rng.randn(8, 8), jnp.float32)},
              "other": {"w": jnp.asarray(rng.randn(8, 8), jnp.float32)},
              "bias": jnp.zeros(8)}
    early = jax.jit(comp.apply)(params, 0)
    # pruning active at step 0 (offset 0) on every module
    assert float((early["dense"]["w"] == 0).mean()) == pytest.approx(0.5, abs=0.05)
    assert float((early["other"]["w"] == 0).mean()) == pytest.approx(0.5, abs=0.05)
    # quantization (offset 5) not yet active: nonzero elements unchanged
    nz = np.asarray(early["dense"]["w"]) != 0
    np.testing.assert_allclose(np.asarray(early["dense"]["w"])[nz],
                               np.asarray(params["dense"]["w"])[nz])
    late = jax.jit(comp.apply)(params, 10)
    nzl = np.asarray(late["dense"]["w"]) != 0
    assert not np.allclose(np.asarray(late["dense"]["w"])[nzl],
                           np.asarray(params["dense"]["w"])[nzl])  # quantized now
    # 1-D bias untouched
    np.testing.assert_array_equal(np.asarray(late["bias"]), 0)

def test_compressor_trains():
    """Compressed forward still learns (end-to-end sanity)."""
    comp = init_compression({
        "compression_training": {"weight_quantization": {
            "shared_parameters": {"enabled": True, "schedule_offset": 0,
                                  "quantize_groups": 1},
            "different_groups": {"g": {"params": {"target_bits": 8},
                                       "modules": ["*"]}}}}})
    rng = np.random.RandomState(7)
    W = rng.randn(16, 4).astype(np.float32)
    x = rng.randn(64, 16).astype(np.float32)
    y = x @ W
    params = {"w": jnp.zeros((16, 4))}

    @jax.jit
    def step(p, lr=0.1):
        def loss(p):
            cp = comp.apply(p, 1)
            return jnp.mean((x @ cp["w"] - y) ** 2)
        l, g = jax.value_and_grad(loss)(p)
        return jax.tree.map(lambda a, b: a - lr * b, p, g), l

    losses = []
    for _ in range(40):
        params, l = step(params)
        losses.append(float(l))
    assert losses[-1] < losses[0] * 0.1


# ------------------------------------------------------------------- 1-bit
def test_onebit_allreduce_error_feedback():
    mesh = _mesh8()
    rng = np.random.RandomState(8)
    x = jnp.asarray(rng.randn(8, 4, 16), jnp.float32)
    err0 = jnp.zeros((4, 16))

    f = shard_map(
        lambda v, e: onebit_allreduce(v[0], e[0], "data", num_groups=4),
        mesh=mesh, in_specs=(P("data"), P(None)),
        out_specs=(P(None), P("data")), check_vma=False)
    avg, err = f(x, jnp.broadcast_to(err0, (1, 4, 16)))
    # compressed average has the right sign structure & bounded error
    exact = jnp.mean(x, axis=0)
    assert avg.shape == (4, 16)
    # error feedback: residual equals v - decompressed(v)
    assert float(jnp.max(jnp.abs(err))) > 0

def test_onebit_adam_converges_spmd():
    mesh = _mesh8()
    rng = np.random.RandomState(9)
    W = rng.randn(16, 2).astype(np.float32)
    x = rng.randn(64, 16).astype(np.float32)
    y = x @ W
    params = {"w": jnp.zeros((16, 2))}
    opt = onebit_adam(lr=0.05, freeze_step=10, axis_name="data", num_groups=2)
    state = opt.init(params)

    def local_step(p, s, xb, yb):
        def loss(p):
            return jnp.mean((xb @ p["w"] - yb) ** 2)
        l, g = jax.value_and_grad(loss)(p)
        upd, s = opt.update(g, s, p)
        return jax.tree.map(lambda a, u: a + u, p, upd), s, jax.lax.pmean(l, "data")

    step = jax.jit(shard_map(
        local_step, mesh=mesh,
        in_specs=(P(), P(), P("data"), P("data")), out_specs=(P(), P(), P()),
        check_vma=False))
    xs = jnp.asarray(x)
    ys = jnp.asarray(y)
    losses = []
    for _ in range(40):
        params, state, l = step(params, state, xs, ys)
        losses.append(float(l))
    assert losses[-1] < losses[0] * 0.05, losses[::8]

def test_onebit_from_config_and_ragged_leaves():
    from deepspeed_tpu.ops.optim import from_config

    opt = from_config("OnebitAdam", {"lr": 0.01, "freeze_step": 2,
                                     "axis_name": None, "num_groups": 4})
    assert opt.name == "onebit_adam"
    # bias of size 5 doesn't divide num_groups=4 → per-leaf fallback, no crash
    params = {"w": jnp.ones((4, 4)), "b": jnp.ones((5,))}
    state = opt.init(params)
    g = jax.tree.map(jnp.ones_like, params)
    for _ in range(4):  # crosses freeze_step → steady-state compress path
        upd, state = jax.jit(opt.update)(g, state, params)
    assert upd["b"].shape == (5,)


def test_onebit_engine_config_defaults_unbound_axis():
    # The engine steps under plain jax.jit: from_config must default
    # axis_name=None so tracing doesn't hit an unbound "data" axis.
    import numpy as np
    import deepspeed_tpu as dstpu

    params = {"w": jnp.ones((4, 2)) * 0.1}
    def loss_fn(p, batch):
        return jnp.mean((batch["x"] @ p["w"] - batch["y"]) ** 2)
    engine, _, _, _ = dstpu.initialize(
        config={"train_batch_size": 8,
                "optimizer": {"type": "OnebitAdam",
                              "params": {"lr": 0.01, "freeze_step": 2}},
                "bf16": {"enabled": False}},
        params=params, loss_fn=loss_fn)
    batch = {"x": np.ones((8, 4), np.float32),
             "y": np.zeros((8, 2), np.float32)}
    l0 = float(engine.train_batch(batch))
    for _ in range(4):
        l1 = float(engine.train_batch(batch))
    assert l1 < l0


def test_onebit_lamb_converges_single():
    rng = np.random.RandomState(10)
    W = rng.randn(8, 2).astype(np.float32)
    x = rng.randn(32, 8).astype(np.float32)
    y = x @ W
    params = {"w": jnp.asarray(rng.randn(8, 2) * 0.1, jnp.float32)}
    opt = onebit_lamb(lr=0.05, freeze_step=5, axis_name=None)
    state = opt.init(params)

    @jax.jit
    def step(p, s):
        l, g = jax.value_and_grad(
            lambda p: jnp.mean((x @ p["w"] - y) ** 2))(p)
        upd, s = opt.update(g, s, p)
        return jax.tree.map(lambda a, u: a + u, p, upd), s, l

    losses = []
    for _ in range(60):
        params, state, l = step(params, state)
        losses.append(float(l))
    assert losses[-1] < losses[0] * 0.2, losses[::10]


def test_channel_mask_and_layer_reduction():
    from deepspeed_tpu.compression import (apply_layer_reduction,
                                           channel_mask)

    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(0, 1, (16, 8)), jnp.float32)
    m = channel_mask(w, dense_ratio=0.5)
    assert m.shape == (1, 8) and int(m.sum()) == 4
    # kept channels are the largest-norm ones
    norms = np.linalg.norm(np.asarray(w), axis=0)
    kept = set(np.where(np.asarray(m[0]) > 0)[0])
    assert kept == set(np.argsort(norms)[-4:])

    params = {"embed": jnp.zeros((10, 4)),
              "blocks": {"w": jnp.arange(24.0).reshape(6, 2, 2),
                         "n": jnp.ones((6, 2))},
              "final_norm": jnp.ones(4)}
    student = apply_layer_reduction(params, [0, 2, 5])
    assert student["blocks"]["w"].shape == (3, 2, 2)
    np.testing.assert_array_equal(np.asarray(student["blocks"]["w"][1]),
                                  np.asarray(params["blocks"]["w"][2]))
    assert student["embed"].shape == (10, 4)  # non-block subtrees intact
    with pytest.raises(ValueError, match="outside"):
        apply_layer_reduction(params, [7])


def test_channel_pruning_config_and_layer_reduction_parse():
    from deepspeed_tpu.compression import CompressionConfig, init_compression

    cfg = CompressionConfig.from_dict({"compression_training": {
        "channel_pruning": {
            "shared_parameters": {"enabled": True, "schedule_offset": 0},
            "different_groups": {"cp1": {
                "params": {"dense_ratio": 0.5}, "modules": ["*"]}}},
        "layer_reduction": {"enabled": True, "teacher_layer": [0, 2]},
    }})
    assert cfg.channel_pruning.enabled
    assert cfg.layer_reduction_enabled and cfg.keep_layers == [0, 2]
    comp = init_compression(cfg)
    assert comp.active
    w = jnp.asarray(np.random.default_rng(1).normal(0, 1, (8, 8)),
                    jnp.float32)
    out = comp.apply({"w": w}, step=1)["w"]
    cols = np.linalg.norm(np.asarray(out), axis=0)
    assert int((cols > 0).sum()) == 4


def test_layer_reduction_keep_number_spreads():
    from deepspeed_tpu.compression import (CompressionConfig,
                                           apply_layer_reduction)

    params = {"blocks": {"w": jnp.arange(24.0).reshape(24, 1)}}
    s = apply_layer_reduction(params, keep_number=6)
    kept = np.asarray(s["blocks"]["w"][:, 0], np.int32)
    assert kept[0] == 0 and kept[-1] == 23        # endpoints included
    assert len(kept) == 6
    gaps = np.diff(kept)
    assert gaps.max() - gaps.min() <= 1           # evenly spread
    cfg = CompressionConfig.from_dict({"compression_training": {
        "layer_reduction": {"enabled": True, "keep_number_layers": 6}}})
    assert cfg.keep_number_layers == 6 and cfg.keep_layers == []


def test_compressor_reduce_layers_from_config():
    from deepspeed_tpu.compression import init_compression

    comp = init_compression({"compression_training": {
        "layer_reduction": {"enabled": True, "teacher_layer": [1, 3]}}})
    params = {"blocks": {"w": jnp.arange(8.0).reshape(4, 2)},
              "head": jnp.ones(2)}
    s = comp.reduce_layers(params)
    np.testing.assert_array_equal(np.asarray(s["blocks"]["w"]),
                                  [[2, 3], [6, 7]])
    # absent block → identity
    assert init_compression({}).reduce_layers(params) is params

"""Collective wrappers over an 8-device mesh (ref semantics: deepspeed/comm)."""

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from deepspeed_tpu import comm
from deepspeed_tpu.topology import MeshSpec


def _mesh8():
    return MeshSpec.build({"data": 8})


def _run(fn, x, in_spec, out_spec):
    ms = _mesh8()
    return jax.jit(shard_map(fn, mesh=ms.mesh, in_specs=in_spec,
                             out_specs=out_spec))(x)


def test_all_reduce_sum_and_avg(devices):
    x = np.arange(8, dtype=np.float32).reshape(8, 1)
    out = _run(lambda v: comm.all_reduce(v, "data"), x, P("data"), P("data"))
    np.testing.assert_allclose(np.asarray(out), np.full((8, 1), 28.0))
    out = _run(lambda v: comm.all_reduce(v, "data", comm.ReduceOp.AVG),
               x, P("data"), P("data"))
    np.testing.assert_allclose(np.asarray(out), np.full((8, 1), 3.5))


def test_all_reduce_max_min(devices):
    x = np.arange(8, dtype=np.float32).reshape(8, 1)
    out = _run(lambda v: comm.all_reduce(v, "data", comm.ReduceOp.MAX),
               x, P("data"), P("data"))
    assert np.all(np.asarray(out) == 7.0)
    out = _run(lambda v: comm.all_reduce(v, "data", comm.ReduceOp.MIN),
               x, P("data"), P("data"))
    assert np.all(np.asarray(out) == 0.0)


def test_all_gather(devices):
    x = np.arange(16, dtype=np.float32).reshape(8, 2)
    out = _run(lambda v: comm.all_gather(v, "data", axis=0),
               x, P("data"), P("data", None))
    assert out.shape == (64, 2)
    np.testing.assert_allclose(np.asarray(out)[:8], x)


def test_reduce_scatter(devices):
    x = np.ones((64, 8), dtype=np.float32)  # (8, 8) per shard
    out = _run(lambda v: comm.reduce_scatter(v, "data", axis=0),
               x, P("data", None), P("data", None))
    # each rank keeps one 1x8 row = sum over the 8 ranks
    np.testing.assert_allclose(np.asarray(out), np.full((8, 8), 8.0))


def test_broadcast(devices):
    x = np.arange(8, dtype=np.float32).reshape(8, 1)
    out = _run(lambda v: comm.broadcast(v, "data", src=3), x,
               P("data"), P("data"))
    np.testing.assert_allclose(np.asarray(out), np.full((8, 1), 3.0))


def test_all_to_all(devices):
    # tokens [8 shards x 8 rows]: a2a transposes shard <-> row blocks
    x = np.arange(64, dtype=np.float32).reshape(64, 1)
    out = _run(lambda v: comm.all_to_all(v, "data", split_axis=0, concat_axis=0),
               x, P("data"), P("data"))
    assert out.shape == (64, 1)
    got = np.asarray(out).reshape(8, 8)
    want = np.arange(64, dtype=np.float32).reshape(8, 8).T
    np.testing.assert_allclose(got, want)


def test_ring_shift(devices):
    x = np.arange(8, dtype=np.float32).reshape(8, 1)
    out = _run(lambda v: comm.send_recv_next(v, "data", 8), x,
               P("data"), P("data"))
    np.testing.assert_allclose(np.asarray(out).ravel(),
                               np.roll(np.arange(8, dtype=np.float32), 1))


def test_host_helpers():
    comm.init_distributed()
    assert comm.get_world_size() == 1     # processes
    assert comm.get_device_count() == 8   # chips
    assert comm.get_rank() == 0
    comm.barrier()


def test_product_with_nonpositive(devices):
    x = np.array([-2, 3, 1, 1, 1, 1, 1, 1], dtype=np.float32).reshape(8, 1)
    out = _run(lambda v: comm.all_reduce(v, "data", comm.ReduceOp.PRODUCT),
               x, P("data"), P("data"))
    np.testing.assert_allclose(np.asarray(out), np.full((8, 1), -6.0), rtol=1e-5)
    x0 = x.copy()
    x0[4] = 0.0
    out = _run(lambda v: comm.all_reduce(v, "data", comm.ReduceOp.PRODUCT),
               x0, P("data"), P("data"))
    np.testing.assert_allclose(np.asarray(out), np.zeros((8, 1)))


def test_mesh_all_reduce(devices):
    ms = _mesh8()
    x = np.ones((8, 4), dtype=np.float32)
    out = comm.mesh_all_reduce(jnp.asarray(x), ms.mesh)
    assert out.shape == (1, 4)
    np.testing.assert_allclose(np.asarray(out), np.full((1, 4), 8.0))


class TestCommsDigest:
    """ref deepspeed/comm/comm.py comms_logger: per-collective accounting."""

    def _build(self, zero):
        import deepspeed_tpu as dstpu

        def loss(params, batch):
            pred = batch["x"] @ params["w"]
            return jnp.mean((pred - batch["y"]) ** 2)

        params = {"w": jax.random.normal(jax.random.PRNGKey(0), (64, 512))}
        engine, _, _, _ = dstpu.initialize(
            loss_fn=loss, params=params,
            config={"train_micro_batch_size_per_gpu": 2,
                    "mesh": {"data": 8},
                    "zero_optimization": zero,
                    "optimizer": {"type": "adamw", "params": {"lr": 1e-2}}})
        rng = np.random.default_rng(0)
        batch = {"x": jnp.asarray(rng.normal(size=(16, 64)), jnp.float32),
                 "y": jnp.asarray(rng.normal(size=(16, 512)), jnp.float32)}
        return engine, batch

    def test_stage0_all_reduce_accounted(self, devices):
        engine, batch = self._build({"stage": 0})
        d = engine.comms_digest(batch)
        assert d["total_collectives"] > 0
        assert "all-reduce" in d["per_kind"]
        # grads are f32 [64, 512]-ish: the all-reduce payload must be at
        # least that order of magnitude
        assert d["per_kind"]["all-reduce"]["bytes"] >= 4 * 64 * 512 / 8
        assert d["est_wire_ms"] > 0

    def test_stage3_has_gather_or_scatter_traffic(self, devices):
        engine, batch = self._build({"stage": 3})
        d = engine.comms_digest(batch)
        kinds = set(d["per_kind"])
        assert kinds & {"all-gather", "reduce-scatter", "all-to-all",
                        "collective-permute"}, kinds

    def test_digest_feeds_monitor_csv(self, devices, tmp_path):
        import deepspeed_tpu as dstpu

        def loss(params, batch):
            return jnp.mean((batch["x"] @ params["w"]) ** 2)

        params = {"w": jax.random.normal(jax.random.PRNGKey(0), (32, 64))}
        engine, _, _, _ = dstpu.initialize(
            loss_fn=loss, params=params,
            config={"train_micro_batch_size_per_gpu": 1,
                    "mesh": {"data": 8},
                    "zero_optimization": {"stage": 2},
                    "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
                    "csv_monitor": {"enabled": True,
                                    "output_path": str(tmp_path),
                                    "job_name": "digesttest"}})
        batch = {"x": jnp.ones((8, 32), jnp.float32)}
        engine.comms_digest(batch)
        engine.monitor.flush()
        import os
        found = []
        for root, _, files in os.walk(tmp_path):
            found += [f for f in files if f.endswith(".csv")]
        assert any("Comms" in f or "total_bytes" in f for f in found), found

    def test_hlo_parser_on_synthetic_text(self):
        from deepspeed_tpu.comm.digest import analyze_collectives

        txt = """
  %ar = f32[128,256]{1,0} all-reduce(f32[128,256]{1,0} %g), replica_groups={}
  %ag.1 = bf16[8,64]{1,0} all-gather(bf16[1,64]{1,0} %p), dimensions={0}
  %a2a = (s8[8,512]{1,0}, s8[8,512]{1,0}) all-to-all(s8[8,512]{1,0} %q, s8[8,512]{1,0} %r)
  %rs-start = f32[32]{0} reduce-scatter-start(f32[256]{0} %x)
"""
        d = analyze_collectives(txt, link_gbps=45.0)
        assert d["per_kind"]["all-reduce"] == {
            "count": 1, "bytes": 4 * 128 * 256}
        assert d["per_kind"]["all-gather"] == {"count": 1, "bytes": 2 * 8 * 64}
        assert d["per_kind"]["all-to-all"] == {
            "count": 1, "bytes": 2 * 8 * 512}
        assert d["per_kind"]["reduce-scatter"] == {"count": 1, "bytes": 4 * 32}
        assert d["total_bytes"] == (4 * 128 * 256 + 2 * 8 * 64
                                    + 2 * 8 * 512 + 4 * 32)

    def test_tpu_fused_reduce_scatter_is_counted(self):
        # a line of the ZeRO-3 step compiled for a v5e: the TPU compiler
        # leaves no reduce-scatter opcode, only this custom fusion
        from deepspeed_tpu.comm.digest import analyze_collectives

        txt = '''
%all-reduce-scatter.clone.clone (input.10: bf16[4,1024,2048]) -> bf16[2060,8,128] {
  %fusion.386 = bf16[2060,8,128]{2,1,0:T(8,128)(2,1)S(1)} fusion(%fusion.385), kind=kCustom, calls=%all-reduce-scatter.clone.clone, metadata={op_name="jit(_train_step)/dot_general"}
  %fusion.9 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.9
'''
        d = analyze_collectives(txt)
        assert d["per_kind"] == {"reduce-scatter": {
            "count": 1, "bytes": 2 * 2060 * 8 * 128}}

    def test_tpu_fused_reduce_scatter_body_and_channels_count_once(self):
        # the ZeRO-3 step compiled for four v5e chips (PR 36): the fused
        # reduce-scatter's own computation spells the reduction as an
        # all-reduce of the whole operand, and an async all-gather the
        # compiler fuses is written out in each computation of the
        # fusion under one channel
        from deepspeed_tpu.comm.digest import analyze_collectives

        txt = '''
%all-reduce-scatter.1.clone.clone (input.12: bf16[8192,2048]) -> bf16[2080,2048] {
  %pad.13 = bf16[8320,2048]{1,0} pad(%input.12, %constant.1598), padding=0_128x0_0
  %all-reduce.100 = bf16[8320,2048]{1,0} all-reduce(%pad.13), channel_id=120, replica_groups={{0,1,2,3}}, to_apply=%add.3.clone
  ROOT %dynamic-slice.1 = bf16[2080,2048]{1,0} dynamic-slice(%all-reduce.100, %a, %b)
}

%fused_computation.1 (param_0.1553: bf16[1,2048,2048]) -> bf16[1,2048,8192] {
  %all-gather.139 = bf16[1,2048,8192]{2,1,0} all-gather(%param_0.1553), channel_id=9, replica_groups=[1,4]<=[4], dimensions={2}
}

%fused_computation.2 (param_0.1557: bf16[1,2048,2048]) -> bf16[1,2048,8192] {
  %all-gather.141 = bf16[1,2048,8192]{2,1,0} all-gather(%param_0.1557), channel_id=9, replica_groups=[1,4]<=[4], dimensions={2}
}

ENTRY %main () -> bf16[2080,2048] {
  %all-gather.7 = bf16[1,2048]{1,0} all-gather(%p), channel_id=10, dimensions={1}
  %fusion.403 = bf16[2080,2048]{1,0} fusion(%gte.1900), kind=kCustom, calls=%all-reduce-scatter.1.clone.clone
}
'''
        d = analyze_collectives(txt)
        assert d["per_kind"] == {
            "all-gather": {"count": 2,
                           "bytes": 2 * 2048 * 8192 + 2 * 2048},
            "reduce-scatter": {"count": 1, "bytes": 2 * 2080 * 2048}}

    def test_async_start_done_counts_once(self):
        from deepspeed_tpu.comm.digest import analyze_collectives

        txt = """
  %ags = bf16[8,64]{1,0} all-gather-start(bf16[1,64]{1,0} %p)
  %agd = bf16[8,64]{1,0} all-gather-done(bf16[8,64]{1,0} %ags)
  %ar = f32[16]{0} all-reduce(f32[16]{0} %g)
"""
        d = analyze_collectives(txt)
        assert d["per_kind"]["all-gather"] == {"count": 1, "bytes": 2 * 8 * 64}
        assert d["per_kind"]["all-reduce"] == {"count": 1, "bytes": 64}
        assert d["total_collectives"] == 2

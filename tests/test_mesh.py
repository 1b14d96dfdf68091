"""The mesh helpers (deepspeed_tpu/mesh.py): building the Mesh /
NamedSharding objects GSPMD consumes, and the collective entry points
that run through ``jax.shard_map``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu import mesh as mesh_mod
from deepspeed_tpu.topology import MeshSpec


class TestHelpers:
    def test_make_mesh_shape_and_names(self, devices):
        m = mesh_mod.make_mesh({"data": 4, "model": 2})
        assert isinstance(m, Mesh)
        assert m.axis_names == ("data", "model")
        assert m.devices.shape == (4, 2)

    def test_make_mesh_device_count_mismatch(self, devices):
        with pytest.raises(ValueError, match="devices"):
            mesh_mod.make_mesh({"data": 3})

    def test_named_sharding_from_spec_and_axes(self, devices):
        m = mesh_mod.make_mesh({"data": 8})
        s1 = mesh_mod.named_sharding(m, P("data"))
        s2 = mesh_mod.named_sharding(m, "data")
        assert isinstance(s1, NamedSharding)
        assert s1.spec == s2.spec == P("data")
        assert mesh_mod.pspec("data", None) == P("data", None)

    def test_mesh_axis_sizes(self, devices):
        m = mesh_mod.make_mesh({"data": 2, "model": 4})
        assert mesh_mod.mesh_axis_sizes(m) == {"data": 2, "model": 4}

    def test_meshspec_build_routes_through_helper(self, devices):
        # topology.MeshSpec is the framework's resolved-topology
        # object; its Mesh must be the helper's canonical axis order
        ms = MeshSpec.build({"data": 4, "model": 2})
        assert ms.mesh.axis_names == ("pipe", "data", "expert", "seq",
                                      "model")
        assert mesh_mod.mesh_axis_sizes(ms.mesh)["data"] == 4


class TestShardMapCallers:
    """Entry points that schedule their own collectives under
    ``jax.shard_map`` (cheap smoke — the full numerics live in the
    comm/parallel suites)."""

    def test_comm_compress_local_grad_harness(self, devices):
        from deepspeed_tpu import comm_compress

        ms = MeshSpec.build({"data": 8})
        params = {"w": jnp.ones((4,))}
        batch = {"x": jnp.ones((8, 4))}

        def gf(p, b):
            loss = jnp.sum(p["w"] * jnp.mean(b["x"], 0))
            return jax.grad(lambda q: jnp.sum(
                q["w"] * jnp.mean(b["x"], 0)))(p), loss

        f = comm_compress.local_grad_shardmap(gf, ms, accum=1)
        grads, loss = f(params, batch)
        np.testing.assert_allclose(np.asarray(grads["w"]), 1.0)
        assert float(loss) == pytest.approx(4.0)

    def test_mesh_all_reduce_backend(self, devices):
        from deepspeed_tpu import comm

        ms = MeshSpec.build({"data": 8})
        x = jnp.arange(8.0)
        out = comm.mesh_all_reduce(x, ms.mesh)
        assert float(np.asarray(out).reshape(-1)[0]) == 28.0

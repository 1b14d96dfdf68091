"""TP-sharded serving across the full engine-flavor matrix + the fleet.

Extends tests/test_serving_tp.py (plain / chunked / split-fuse / int8):
the model-axis mesh must compose token-identically with speculative
decoding, prefix caching, and ZeRO-Inference weight streaming — and a
fleet replica must itself be a TP-sharded engine (``fleet.tp``), with
the sharding visible through /statusz and dstpu_top.

Oracle everywhere: the single-device engine.  Sharding is an execution
strategy, so served tokens must match exactly.  (The prefix/ZI/chunked
flavors ride the slow lane — dryruns J/K and test_serving_tp's
split-fuse test cover the same compositions; tier-1 keeps the fast
core: speculative x TP, the config-routed mesh, and the TP fleet.  The
fast lane's 870 s budget is real — weigh any addition against it.)
"""

import numpy as np
import pytest

import jax

from deepspeed_tpu.config import FleetConfig
from deepspeed_tpu.fleet import fleet_router, tp_replica_mesh
from deepspeed_tpu.inference.engine import (init_serving,
                                            serving_mesh_from_config)
from deepspeed_tpu.inference.serving import serving_engine
from deepspeed_tpu.models import llama
from deepspeed_tpu.topology import MeshSpec, set_current_mesh

KW = dict(max_batch=2, page_size=8, num_pages=32, max_seq=64,
          prefill_bucket=8)


@pytest.fixture(scope="module")
def model():
    cfg = llama.LlamaConfig.tiny(dim=64, n_layers=2, n_heads=4,
                                 n_kv_heads=2)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture()
def tp2(devices):
    ms = MeshSpec.build({"model": 2}, devices=jax.devices()[:2])
    yield ms
    set_current_mesh(None)


PROMPTS = {
    # a repetitive motif (speculation's traffic) + irregular tails
    "rep": ([7, 8, 9, 7, 8, 9, 7, 8], 8),
    "a": ([5, 9, 2], 6),
    "b": ([17, 3, 3, 8, 1], 5),
}


def serve_all(eng, prompts=PROMPTS):
    for rid, (p, n) in prompts.items():
        eng.submit(rid, p, max_new_tokens=n)
    return eng.run()


class TestTPFlavorIdentity:
    def test_speculative_tp2_matches_single_device(self, model, tp2):
        cfg, params = model
        base = serving_engine(params, cfg,
                              speculative={"draft_tokens": 3},
                              **KW)
        want = serve_all(base)
        eng = serving_engine(params, cfg, mesh=tp2,
                             speculative={"draft_tokens": 3},
                             **KW)
        assert serve_all(eng) == want
        # the verify sweep actually speculated under the mesh
        assert int(eng.registry.snapshot()["counters"].get(
            "spec_verify_sweeps", 0)) > 0

    @pytest.mark.slow
    def test_prefix_cache_tp2_matches_and_hits(self, model, tp2):
        cfg, params = model
        rng = np.random.default_rng(3)
        pre = rng.integers(1, cfg.vocab_size, 16).tolist()
        reqs = {f"u{i}": (pre + rng.integers(1, cfg.vocab_size,
                                             3).tolist(), 5)
                for i in range(3)}
        base = serving_engine(params, cfg, **KW)
        want = serve_all(base, reqs)
        eng = serving_engine(params, cfg, mesh=tp2,
                             prefix_cache=True, **KW)
        assert serve_all(eng, reqs) == want
        cnt = eng.registry.snapshot()["counters"]
        assert cnt.get("prefix_cache_cached_tokens", 0) > 0, \
            "prefix cache never hit under TP"

    @pytest.mark.slow
    def test_zero_inference_tp2_matches_resident(self, model, tp2):
        cfg, params = model
        base = serving_engine(params, cfg, mesh=tp2, **KW)
        zi = serving_engine(
            params, cfg, mesh=tp2,
            zero_inference={"enabled": True, "tier": "host"}, **KW)
        assert zi.plan["n_streamed"] == cfg.n_layers
        assert serve_all(zi) == serve_all(base)

    @pytest.mark.slow
    def test_chunked_decode_tp2_matches(self, model, tp2):
        cfg, params = model
        base = serving_engine(params, cfg, **KW)
        want = serve_all(base)
        eng = serving_engine(params, cfg, mesh=tp2,
                             decode_chunk=2, **KW)
        assert serve_all(eng) == want


class TestServingMeshConfig:
    def test_config_mesh_block_and_statusz(self, model, devices):
        cfg, params = model
        try:
            eng = init_serving(params, cfg,
                               config={"mesh": {"model": 2}}, **KW)
            info = eng.mesh_info()
            assert info["sharded"] and info["tp"] == 2
            assert info["devices"] == 2       # NOT all 8: serving reads
            assert info["axes"] == {"model": 2}  # data:-1 as data:1
            # /statusz surfaces the same block
            assert eng.statusz()["mesh"] == {
                "sharded": True, "devices": 2, "axes": {"model": 2},
                "tp": 2, "ep": 1}
        finally:
            set_current_mesh(None)

    def test_default_config_stays_single_device(self, model, devices):
        cfg, params = model
        from deepspeed_tpu.config import Config

        assert serving_mesh_from_config(Config.from_dict({})) is None

    def test_oversized_mesh_refused(self, model, devices):
        cfg, params = model
        with pytest.raises(ValueError, match="devices"):
            init_serving(params, cfg,
                         config={"mesh": {"model": 16}}, **KW)


class TestTPFleet:
    def test_fleet_tp_replicas_match_single_device(self, model, devices):
        """fleet.tp: every replica is a TP-sharded engine over its own
        device slice; routed traffic stays token-identical to the
        single-device oracle; /statusz and dstpu_top show the fleet
        visibly sharded."""
        cfg, params = model
        try:
            base = serving_engine(params, cfg, **KW)
            for rid, (p, n) in PROMPTS.items():
                base.submit(rid, p, max_new_tokens=n)
            want = base.run()

            router = fleet_router(params, cfg,
                                  fleet={"replicas": 2, "tp": 2},
                                  **KW)
            for rep in router.replicas.values():
                info = rep.engine.mesh_info()
                assert info["sharded"] and info["tp"] == 2
            # replicas landed on DISJOINT device slices
            d0 = router.replicas["r0"].engine._mesh.mesh.devices
            d1 = router.replicas["r1"].engine._mesh.mesh.devices
            assert not (set(d.id for d in d0.flat)
                        & set(d.id for d in d1.flat))
            for rid, (p, n) in PROMPTS.items():
                router.submit(rid, p, max_new_tokens=n)
            got = router.run()
            assert got == want
            assert router.check_leaks() == []

            st = router.statusz()
            assert st["fleet"]["mesh"] == {"tp": 2,
                                           "sharded_replicas": 2}
            for row in st["fleet"]["replicas"]:
                assert row["mesh"]["axes"] == {"model": 2}
            import importlib

            top = importlib.import_module("tools.dstpu_top")
            frame = "\n".join(top.render(st, router.healthz()))
            assert "tp=2" in frame and "model2" in frame
            router.shutdown()
        finally:
            set_current_mesh(None)

    def test_tp_replica_mesh_slices_and_wraparound(self, devices):
        m0 = tp_replica_mesh(0, 2)
        m3 = tp_replica_mesh(3, 2)   # 8 devices: slice [6, 7]
        m4 = tp_replica_mesh(4, 2)   # wraps to [0, 1]
        ids = lambda ms: [d.id for d in ms.mesh.devices.flat]
        assert ids(m0) == [0, 1]
        assert ids(m3) == [6, 7]
        assert ids(m4) == ids(m0)
        with pytest.raises(ValueError, match="devices"):
            tp_replica_mesh(0, 16)

    def test_fleet_config_tp_validated(self):
        assert FleetConfig.from_dict({"tp": 2}).tp == 2
        with pytest.raises(ValueError, match="fleet.tp"):
            FleetConfig.from_dict({"tp": 0})

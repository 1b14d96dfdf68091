"""Hierarchical + quantized collectives (ISSUE 18; ref: ZeRO++
hpZ/qgZ/qwZ, arXiv:2306.10209; EQuARX quantized all-reduce on TPU,
arXiv:2506.17615).

Contract under test, in three rings:

1. **Numerics** — the ``exact`` codec through the two-level schedule is
   bit-exact against ``pmean``; the int8 codecs land within the
   documented blockwise bound; hpZ's two-hop gather is bit-exact
   against the flat int8 gather; bucketing is bit-identical to the
   monolithic buffer it replaces.
2. **Config** — hierarchy resolution validates divisibility loudly,
   auto-detect degrades to flat on single-process meshes, the comm
   block round-trips and rejects unknown keys.
3. **Reuse** — the serving side of the shared wire: quantized TP
   placement is opt-in (default path untouched), rtol-gated, and
   observable (/statusz comm block, comm_* counters, dstpu_top row).

Bit-exact arms are always materialized by SEPARATE jitted calls and
compared host-side: subtracting two collective pipelines inside one jit
lets XLA fuse/reassociate across them and manufactures ~1-ulp phantom
diffs.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import deepspeed_tpu as dstpu
from deepspeed_tpu.comm import collectives as C
from deepspeed_tpu.config import CommConfig
from deepspeed_tpu.ops import quant
from deepspeed_tpu.topology import MeshSpec

AXIS = "data"


def sharded(ms, f, *xs):
    """Run ``f`` over per-device rows: each input is [8, ...], f sees
    the local row and returns a row; output re-stacked [8, ...]."""
    def body(*locs):
        return f(*(l[0] for l in locs))[None]

    n = len(xs)
    return jax.shard_map(
        body, mesh=ms.mesh, in_specs=(P(AXIS),) * n, out_specs=P(AXIS),
        check_vma=False)(*xs)


# ------------------------------------------------------------ hierarchy
class TestHierarchy:
    def test_resolve_explicit(self):
        h = C.resolve_hierarchy(8, 2)
        assert (h.world, h.intra, h.inter, h.flat) == (8, 2, 4, False)
        assert h.intra_groups == ((0, 1), (2, 3), (4, 5), (6, 7))
        assert h.inter_groups == ((0, 2, 4, 6), (1, 3, 5, 7))

    def test_explicit_non_divisor_raises(self):
        with pytest.raises(ValueError, match="does not divide"):
            C.resolve_hierarchy(8, 3)
        with pytest.raises(ValueError, match="does not divide"):
            C.Hierarchy(8, 5)

    def test_auto_detect_single_process_is_flat(self, devices):
        # the virtual-CPU mesh is one process: auto (0) must degrade to
        # the flat schedule, never guess a split with no physical meaning
        h = C.resolve_hierarchy(8, 0, devices=jax.devices())
        assert h.flat

    def test_degenerate_sizes_are_flat(self):
        assert C.Hierarchy(8, 1).flat
        assert C.Hierarchy(8, 8).flat

    def test_codec_units(self):
        assert C.codec_unit("blockwise") == quant.BLOCK_ELEMS == 4096
        assert C.codec_unit("group") == 512
        assert C.codec_unit("exact") == 1
        with pytest.raises(ValueError, match="unknown wire codec"):
            C.codec_unit("fp4")

    def test_comm_config_block(self):
        cc = CommConfig.coerce({"hierarchy_size": 2, "codec": "group",
                                "bucket_mb": 0.5})
        assert (cc.hierarchy_size, cc.codec, cc.bucket_mb) == (2, "group",
                                                               0.5)
        assert not CommConfig.coerce(None).quantized_serving
        with pytest.raises(ValueError, match="unknown comm config"):
            CommConfig.from_dict({"hierarchysize": 2})

    def test_wire_accounting_hits_the_gate(self):
        # the acceptance ratio: W=8, k=2, blockwise — ~4x under flat f32
        w = C.wire_bytes_per_device(1 << 20, C.Hierarchy(8, 2))
        assert w["ratio_vs_f32"] >= 3.5
        assert w["hier_quant_inter_bytes"] < w["hier_quant_bytes"]
        # the flat quantized arm saves ~4x too, but every byte rides the
        # slow tier; hierarchy's point is the inter reduction
        assert w["inter_ratio_vs_f32"] > w["ratio_vs_f32"]


# ------------------------------------------------------ blockwise codec
class TestBlockwiseCodec:
    def test_2d_grid_shape_and_error_bound(self):
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.normal(size=(16, 1024)), jnp.float32)
        q, s = quant.quantize_blockwise(x)
        assert q.shape == x.shape and q.dtype == jnp.int8
        assert s.shape == (2, 2)
        back = quant.dequantize_blockwise(q, s)
        # documented bound: per-element error <= amax_block / 254
        xb = np.asarray(x).reshape(2, 8, 2, 512).transpose(0, 2, 1, 3)
        bound = np.abs(xb).max(axis=(2, 3)) / 254.0
        err = np.abs(np.asarray(back) - np.asarray(x)) \
            .reshape(2, 8, 2, 512).transpose(0, 2, 1, 3).max(axis=(2, 3))
        assert (err <= bound + 1e-7).all()

    def test_flat_blocks_roundtrip(self):
        rng = np.random.default_rng(4)
        x = jnp.asarray(rng.normal(size=(2 * quant.BLOCK_ELEMS,)),
                        jnp.float32)
        q, s = quant.quantize_blockwise(x)
        assert s.shape == (2,)
        back = quant.dequantize_blockwise(q, s)
        amax = np.abs(np.asarray(x)).reshape(2, -1).max(axis=1)
        err = np.abs(np.asarray(back - x)).reshape(2, -1).max(axis=1)
        assert (err <= amax / 254.0 + 1e-7).all()

    def test_unaligned_flat_raises(self):
        with pytest.raises(ValueError):
            quant.quantize_blockwise(jnp.ones((1000,)))

    def test_block_pad(self):
        x = jnp.arange(10, dtype=jnp.float32)
        p = quant.block_pad(x)
        assert p.shape[0] == quant.BLOCK_ELEMS
        np.testing.assert_array_equal(np.asarray(p[:10]), np.asarray(x))
        assert float(jnp.abs(p[10:]).sum()) == 0.0


# -------------------------------------------- hierarchical all-reduce
class TestHierarchicalAllReduce:
    def _pmean(self, ms, x):
        return np.asarray(sharded(
            ms, lambda l: jax.lax.pmean(l, AXIS), x))

    def test_exact_codec_bit_exact_vs_pmean_all_shapes(self, devices):
        """The verification arm: integer-valued data (sums exactly
        representable) through every hierarchy shape must equal pmean
        bit-for-bit — flat (k=1), true two-level (k=2, k=4), and the
        inter-degenerate k=8."""
        ms = MeshSpec.build({AXIS: 8})
        rng = np.random.default_rng(5)
        x = jnp.asarray(rng.integers(-64, 64, size=(8, 256)), jnp.float32)
        want = self._pmean(ms, x)
        for k in (1, 2, 4, 8):
            h = C.Hierarchy(8, k)
            got = np.asarray(sharded(
                ms, lambda l: C.hierarchical_all_reduce(
                    l, AXIS, h, codec="exact"), x))
            np.testing.assert_array_equal(got, want, err_msg=f"k={k}")

    @pytest.mark.parametrize("codec,per_dev", [("group", 8192),
                                               ("blockwise", 32768)])
    def test_quantized_codecs_within_tol(self, devices, codec, per_dev):
        ms = MeshSpec.build({AXIS: 8})
        rng = np.random.default_rng(6)
        x = jnp.asarray(rng.normal(size=(8, per_dev)), jnp.float32)
        h = C.Hierarchy(8, 2)
        got = np.asarray(sharded(
            ms, lambda l: C.hierarchical_all_reduce(l, AXIS, h,
                                                    codec=codec), x))
        want = self._pmean(ms, x)
        np.testing.assert_allclose(got[0], want[0], atol=8e-2, rtol=8e-2)

    def test_unaligned_buffer_raises(self, devices):
        h = C.Hierarchy(8, 2)
        with pytest.raises(ValueError, match="not aligned"):
            C.hierarchical_all_reduce(jnp.ones((100,)), AXIS, h,
                                      codec="group")

    def test_tree_restores_leaf_dtypes(self, devices):
        ms = MeshSpec.build({AXIS: 8})
        rng = np.random.default_rng(7)
        h = C.Hierarchy(8, 2)
        w = jnp.asarray(rng.normal(size=(8, 64, 16)), jnp.float32)
        b = jnp.asarray(rng.normal(size=(8, 32)), jnp.bfloat16)

        def f(wl, bl):
            out = C.hierarchical_all_reduce_tree(
                {"w": wl, "b": bl}, AXIS, h, codec="group")
            assert out["b"].dtype == jnp.bfloat16     # trace-time check
            return out["w"]

        sharded(ms, f, w, b)


# ---------------------------------------------------- bucketed overlap
class TestBucketedOverlap:
    def test_bucket_elems_alignment(self):
        be = C.bucket_elems_for(0.1, 8, "group")
        assert be > 0 and be % (8 * 512) == 0
        assert C.bucket_elems_for(0.0, 8, "group") == 0

    def _arm(self, g, codec, bucket_elems):
        ms = MeshSpec.build({AXIS: 8})
        h = C.Hierarchy(8, 2)

        def f(wl, bl):
            out = C.hierarchical_all_reduce_tree(
                {"w": wl, "b": bl}, AXIS, h, codec=codec,
                bucket_elems=bucket_elems)
            return jnp.concatenate([out["w"].reshape(-1), out["b"]])

        return np.asarray(sharded(ms, f, g["w"], g["b"]))

    def test_bucketed_equals_monolithic_exact_codec(self, devices):
        """Bit-equality arm: integer-valued data under codec=exact has
        exactly-representable sums, so bucketed and monolithic
        schedules cannot differ even by reassociation."""
        rng = np.random.default_rng(8)
        g = {"w": jnp.asarray(rng.integers(-64, 64, size=(8, 512, 16)),
                              jnp.float32),
             "b": jnp.asarray(rng.integers(-64, 64, size=(8, 32)),
                              jnp.float32)}
        mono = self._arm(g, "exact", 0)
        bucketed = self._arm(g, "exact", 8 * 512)     # -> 3 buckets
        np.testing.assert_array_equal(bucketed, mono)

    def test_bucketed_quantized_same_codes_ulp_sums(self, devices):
        """Quantized arm: aligned buckets quantize the SAME contiguous
        element runs, so codes and scales are identical — the two
        compiled schedules may only reassociate the f32 sums by an ulp
        (tolerance 1e-6, ~8 ulps at unit scale; a single int8 step
        would show up as ~1e-2)."""
        rng = np.random.default_rng(8)
        g = {"w": jnp.asarray(rng.normal(size=(8, 512, 16)), jnp.float32),
             "b": jnp.asarray(rng.normal(size=(8, 32)), jnp.float32)}
        mono = self._arm(g, "group", 0)
        bucketed = self._arm(g, "group", 8 * 512)     # -> 3 buckets
        np.testing.assert_allclose(bucketed, mono, atol=1e-6, rtol=0)


# --------------------------------------------------- hpZ weight gather
class TestHpzGather:
    def _arms(self, reuse):
        ms = MeshSpec.build({AXIS: 8})
        rng = np.random.default_rng(9)
        x = jnp.asarray(rng.normal(size=(8, 1024)), jnp.float32)

        def flat_arm(l):
            g, _ = C.hpz_weight_gather(l, AXIS, C.Hierarchy(8, 1),
                                       num_groups=2)
            return g.reshape(-1)

        def hier_arm(l):
            h = C.Hierarchy(8, 2)
            g, sec = C.hpz_weight_gather(l, AXIS, h, num_groups=2)
            if reuse:
                # second gather off the hpZ secondary shard: intra-node
                # hops only, same bytes out
                g, _ = C.hpz_weight_gather(l, AXIS, h, num_groups=2,
                                           secondary=sec)
            return g.reshape(-1)

        return (np.asarray(sharded(ms, flat_arm, x)),
                np.asarray(sharded(ms, hier_arm, x)))

    def test_two_hop_bit_exact_vs_flat(self, devices):
        flat, hier = self._arms(reuse=False)
        np.testing.assert_array_equal(hier, flat)

    def test_secondary_reuse_bit_exact(self, devices):
        flat, hier = self._arms(reuse=True)
        np.testing.assert_array_equal(hier, flat)


# ------------------------------------------------- training engine wiring
def _mlp_loss(params, batch):
    h = jnp.tanh(batch["x"] @ params["w1"] + params["b1"])
    pred = h @ params["w2"] + params["b2"]
    return jnp.mean((pred - batch["y"]) ** 2)


def _mlp_params(hidden=32):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    return {"w1": jax.random.normal(k1, (16, hidden)) * 0.3,
            "b1": jnp.zeros((hidden,)),
            "w2": jax.random.normal(k2, (hidden, 4)) * 0.3,
            "b2": jnp.zeros((4,))}


def _mlp_batch(n=64):
    rng = np.random.default_rng(0)
    return {"x": jnp.asarray(rng.normal(size=(n, 16)), jnp.float32),
            "y": jnp.asarray(rng.normal(size=(n, 4)), jnp.float32)}


def _build(zero, comm=None, hidden=32):
    cfg = {"train_micro_batch_size_per_gpu": 8,
           "optimizer": {"type": "adamw", "params": {"lr": 5e-2}},
           "mesh": {AXIS: 8}, "zero_optimization": zero}
    if comm is not None:
        cfg["comm"] = comm
    engine, _, _, _ = dstpu.initialize(
        loss_fn=_mlp_loss, params=_mlp_params(hidden), config=cfg)
    return engine


class TestTrainingEngineComm:
    def test_qgz_hierarchical_learns_and_reports(self, devices):
        # hidden=512 -> 10756 params: > 2 group-codec buckets of
        # 0.015625 MB (4096 elems), so the overlap bound is live
        eng = _build({"stage": 2, "zero_quantized_gradients": True},
                     comm={"hierarchy_size": 2, "bucket_mb": 0.015625,
                           "codec": "group"}, hidden=512)
        batch = _mlp_batch()
        losses = [float(eng.train_batch(batch)) for _ in range(5)]
        assert losses[-1] < losses[0], "hierarchical qgz did not learn"
        info = eng.comm_info()
        assert info["hierarchy"] == {"world": 8, "intra": 2, "inter": 4,
                                     "flat": False}
        assert info["wire"]["ratio_vs_f32"] >= 3.5
        assert info["overlap_efficiency_bound"] > 0
        snap = eng.registry.snapshot()
        assert snap["counters"]["comm_bytes_on_wire_int8"] > 0
        assert snap["gauges"]["comm_compression_ratio"] >= 3.5

    def test_qwz_hierarchical_trajectory_bit_identical(self, devices):
        """qwZ quantizes ONCE before any hop, so routing the gather
        through the hierarchy must not move the loss trajectory AT ALL
        vs the flat int8 gather."""
        batch = _mlp_batch()
        flat = _build({"stage": 3, "zero_quantized_weights": True},
                      comm={"hierarchy_size": 1})
        hier = _build({"stage": 3, "zero_quantized_weights": True},
                      comm={"hierarchy_size": 2})
        lf = [float(flat.train_batch(batch)) for _ in range(4)]
        lh = [float(hier.train_batch(batch)) for _ in range(4)]
        assert lh == lf

    def test_explicit_bad_hierarchy_fails_the_build(self, devices):
        with pytest.raises(ValueError, match="does not divide"):
            _build({"stage": 2, "zero_quantized_gradients": True},
                   comm={"hierarchy_size": 3})

    def test_comm_info_none_without_compressed_wire(self, devices):
        eng = _build({"stage": 2}, comm={"hierarchy_size": 2})
        assert eng.comm_info() is None


# --------------------------------------------------- serving: shared wire
KW = dict(max_batch=2, page_size=8, num_pages=32, max_seq=64,
          prefill_bucket=8)
PROMPTS = {"rep": ([7, 8, 9, 7, 8, 9, 7, 8], 8), "plain": ([5, 9, 2], 5)}


def _serve_all(eng):
    for rid, (p, n) in PROMPTS.items():
        eng.submit(rid, p, max_new_tokens=n)
    return eng.run()


@pytest.fixture(scope="module")
def llama_model():
    from deepspeed_tpu.models import llama

    cfg = llama.LlamaConfig.tiny(dim=64, n_layers=2, n_heads=4,
                                 n_kv_heads=2)
    return cfg, llama.init_params(jax.random.PRNGKey(0), cfg)


class TestServingQuantizedPlacement:
    def test_tp_identity_off_and_observable_on(self, llama_model,
                                               devices):
        from deepspeed_tpu.inference.serving import serving_engine

        cfg, params = llama_model
        mesh = MeshSpec.build({"model": 2}, devices=jax.devices()[:2])
        base = serving_engine(params, cfg, mesh=mesh, **KW)
        want = _serve_all(base)
        assert base.statusz().get("comm") is None

        # OFF (the default): the comm block rides along but placement
        # is the bit-exact path — greedy tokens identical
        off = serving_engine(params, cfg, mesh=mesh,
                             comm={"quantized_serving": False},
                             **KW)
        assert _serve_all(off) == want
        assert off.statusz().get("comm") is None

        # ON: int8 on the H2D wire, gated by serving_rtol, observable
        on = serving_engine(params, cfg, mesh=mesh,
                            comm={"quantized_serving": True}, **KW)
        got = _serve_all(on)
        assert sorted(got) == sorted(want)        # same requests served
        st = on.statusz()["comm"]
        assert st["leaves_quantized"] > 0
        assert st["compression_ratio"] >= 3.5
        assert st["max_rel_err"] <= st["serving_rtol"]
        snap = on.registry.snapshot()
        assert snap["counters"]["comm_bytes_on_wire_int8"] > 0
        assert snap["gauges"]["comm_compression_ratio"] >= 3.5

        # the dstpu_top comm row renders from the same block
        from tools.dstpu_top import render

        lines = render(on.statusz(), on.healthz())
        assert any(ln.startswith("comm") for ln in lines)

    def test_rtol_gate_fails_the_build(self, llama_model, devices):
        from deepspeed_tpu.inference.serving import serving_engine

        cfg, params = llama_model
        mesh = MeshSpec.build({"model": 2}, devices=jax.devices()[:2])
        with pytest.raises(ValueError, match="serving_rtol"):
            serving_engine(params, cfg, mesh=mesh,
                           comm={"quantized_serving": True,
                                 "serving_rtol": 1e-9}, **KW)

    def test_encoder_families_reject_quantized_serving(self, devices):
        from deepspeed_tpu.inference.serving import serving_engine
        from deepspeed_tpu.models import bert

        cfg = bert.BertConfig.tiny(dim=32, n_layers=1, n_heads=2)
        params = bert.init_params(jax.random.PRNGKey(0), cfg)
        with pytest.raises(NotImplementedError, match="quantized_serving"):
            serving_engine(params, cfg, comm={"quantized_serving": True})
        # accepted-and-unused when off, like the other decode-only blocks
        serving_engine(params, cfg, comm={"quantized_serving": False})


class TestZeroInferenceWire:
    @pytest.mark.slow
    def test_streamed_layers_ride_the_int8_wire(self, llama_model,
                                                devices):
        from deepspeed_tpu.inference.serving import serving_engine

        cfg, params = llama_model
        zi = {"enabled": True, "tier": "host", "hbm_budget_bytes": None}
        eng = serving_engine(params, cfg, zero_inference=zi,
                             comm={"quantized_serving": True}, **KW)
        got = _serve_all(eng)
        assert sorted(got) == sorted(PROMPTS)
        snap = eng.registry.snapshot()
        c = snap["counters"]
        assert c["comm_bytes_on_wire_int8"] > 0
        # the stream re-ships every sweep: quantized wire bytes stay
        # ~4x under the f32 equivalent across the whole run
        assert c["comm_bytes_on_wire_f32"] \
            >= 3.5 * c["comm_bytes_on_wire_int8"]

    def test_zi_rtol_gate_fails_the_build(self, llama_model, devices):
        from deepspeed_tpu.inference.serving import serving_engine

        cfg, params = llama_model
        zi = {"enabled": True, "tier": "host", "hbm_budget_bytes": None}
        with pytest.raises(ValueError, match="serving_rtol"):
            serving_engine(params, cfg, zero_inference=zi,
                           comm={"quantized_serving": True,
                                 "serving_rtol": 1e-9}, **KW)

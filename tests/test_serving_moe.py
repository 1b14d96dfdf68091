"""Mixtral (MoE) continuous-batching serving (ref: DeepSpeed-MoE
inference — the reference's inference engine SERVES MoE models through
the same iteration-level scheduler as dense ones).

Oracle: the offline paged MoE Generator; every request served under
staggered arrivals and shared slots must produce exactly its tokens.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.generation import paged_generator
from deepspeed_tpu.inference.serving import serving_engine
from deepspeed_tpu.models import mixtral


@pytest.fixture(scope="module")
def model():
    cfg = mixtral.MixtralConfig.tiny(dim=64, n_layers=2, n_heads=4,
                                     n_kv_heads=2, num_experts=4)
    params = mixtral.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def offline_expected(cfg, params, prompt, n_new):
    gen = paged_generator(params, cfg, page_size=8)
    out = gen.generate(jnp.asarray([prompt], jnp.int32),
                       max_new_tokens=n_new)
    return [int(t) for t in np.asarray(out[0])]


PROMPTS = {
    "a": ([5, 9, 2], 6),
    "b": ([17, 3, 3, 8, 1], 5),
    "c": ([40, 2], 7),
}


class TestMixtralServing:
    @pytest.mark.slow
    def test_staggered_arrivals_match_offline(self, model, devices):
        cfg, params = model
        eng = serving_engine(
            params, cfg, max_batch=2, page_size=8, num_pages=32,
            max_seq=64, prefill_bucket=8)
        eng.submit("a", PROMPTS["a"][0], max_new_tokens=PROMPTS["a"][1])
        eng.step()
        eng.submit("b", PROMPTS["b"][0], max_new_tokens=PROMPTS["b"][1])
        eng.submit("c", PROMPTS["c"][0], max_new_tokens=PROMPTS["c"][1])
        outs = eng.run()
        assert set(outs) == {"a", "b", "c"}
        for rid, (prompt, n_new) in PROMPTS.items():
            want = offline_expected(cfg, params, prompt, n_new)
            assert outs[rid] == want, \
                f"{rid}: served {outs[rid]} != offline {want}"

    @pytest.mark.slow
    def test_split_fuse_chunked_prefill_matches(self, model, devices):
        cfg, params = model
        eng = serving_engine(
            params, cfg, max_batch=2, page_size=8, num_pages=32,
            max_seq=64, prefill_chunk=4, decode_chunk=2)
        long_prompt = list(range(2, 23))             # 21 tokens, 6 chunks
        eng.submit("long", long_prompt, max_new_tokens=5)
        eng.submit("a", PROMPTS["a"][0], max_new_tokens=PROMPTS["a"][1])
        outs = eng.run()
        assert outs["long"] == offline_expected(cfg, params, long_prompt, 5)
        assert outs["a"] == offline_expected(cfg, params, *PROMPTS["a"])
        assert eng.registry.snapshot()["counters"][
            "serving_prefill_chunks"] >= 6

    @pytest.mark.slow
    def test_int8_serving_keeps_router_exact(self, model, devices):
        from deepspeed_tpu.inference.quantized import QuantizedTensor

        cfg, params = model
        eng = serving_engine(
            params, cfg, weight_dtype="int8", max_batch=2, page_size=8,
            num_pages=32, max_seq=64, prefill_bucket=8)
        gate = eng.params["blocks"]["gate"]
        assert not isinstance(gate, QuantizedTensor)
        assert isinstance(eng.params["blocks"]["w1"], QuantizedTensor)
        np.testing.assert_array_equal(np.asarray(gate),
                                      np.asarray(params["blocks"]["gate"]))
        eng.submit("a", PROMPTS["a"][0], max_new_tokens=4)
        outs = eng.run()
        assert len(outs["a"]) == len(PROMPTS["a"][0]) + 4

    def test_expert_parallel_matches_unsharded(self, model, devices):
        """EP serving (ref: deepspeed/moe/sharded_moe.py inference —
        experts partitioned across ranks): exact token match vs the
        unsharded engine."""
        from deepspeed_tpu.topology import MeshSpec

        cfg, params = model
        base = serving_engine(
            params, cfg, max_batch=2, page_size=8, num_pages=32,
            max_seq=64, prefill_bucket=8)
        for rid, (p, n) in PROMPTS.items():
            base.submit(rid, p, max_new_tokens=n)
        want = base.run()

        mesh = MeshSpec.build({"expert": 2}, devices=jax.devices()[:2])
        eng = serving_engine(
            params, cfg, mesh=mesh, max_batch=2, page_size=8,
            num_pages=32, max_seq=64, prefill_bucket=8)
        spec = eng.params["blocks"]["w1"].sharding.spec
        assert "expert" in [s for s in spec if s is not None]
        for rid, (p, n) in PROMPTS.items():
            eng.submit(rid, p, max_new_tokens=n)
        assert eng.run() == want

    @pytest.mark.slow
    def test_tp_x_ep_matches_unsharded(self, model, devices):
        """TP x EP composed (ref: DeepSpeed-MoE inference's
        tensor-slicing + expert-parallel deployment): exact tokens."""
        from deepspeed_tpu.topology import MeshSpec

        cfg, params = model
        base = serving_engine(
            params, cfg, max_batch=2, page_size=8, num_pages=32,
            max_seq=64, prefill_bucket=8)
        for rid, (p, n) in PROMPTS.items():
            base.submit(rid, p, max_new_tokens=n)
        want = base.run()
        mesh = MeshSpec.build({"model": 2, "expert": 2},
                              devices=jax.devices()[:4])
        eng = serving_engine(
            params, cfg, mesh=mesh, max_batch=2, page_size=8,
            num_pages=32, max_seq=64, prefill_bucket=8)
        wq_spec = eng.params["blocks"]["wq"].sharding.spec
        w1_spec = eng.params["blocks"]["w1"].sharding.spec
        assert any(sp == "model" for sp in wq_spec if sp is not None)
        assert any(sp == "expert" for sp in w1_spec if sp is not None)
        for rid, (p, n) in PROMPTS.items():
            eng.submit(rid, p, max_new_tokens=n)
        assert eng.run() == want

    @pytest.mark.slow
    def test_int8_ep2_matches_unsharded_int8(self, model, devices):
        """int8 weight-only quant composes with expert parallelism: the
        expert FFN codes shard over the expert axis and their per-row
        scales ride along (ref: DeepSpeed-MoE inference + int8 module
        injection).  Served tokens match the unsharded int8 engine."""
        from deepspeed_tpu.inference.quantized import QuantizedTensor
        from deepspeed_tpu.topology import MeshSpec, set_current_mesh

        cfg, params = model
        kw = dict(max_batch=2, page_size=8, num_pages=32, max_seq=64,
                  prefill_bucket=8)
        base = serving_engine(params, cfg, weight_dtype="int8",
                              quant_group_size=16, **kw)
        for rid, (p, n) in PROMPTS.items():
            base.submit(rid, p, max_new_tokens=n)
        want = base.run()

        mesh = MeshSpec.build({"expert": 2}, devices=jax.devices()[:2])
        try:
            eng = serving_engine(params, cfg, mesh=mesh,
                                 weight_dtype="int8",
                                 quant_group_size=16, **kw)
            w1 = eng.params["blocks"]["w1"]
            assert isinstance(w1, QuantizedTensor)
            assert "expert" in [s for s in w1.q.sharding.spec if s]
            assert "expert" in [s for s in w1.scale.sharding.spec if s]
            for rid, (p, n) in PROMPTS.items():
                eng.submit(rid, p, max_new_tokens=n)
            got = eng.run()
        finally:
            set_current_mesh(None)
        assert got == want

"""ZeRO-Inference weight-streamed serving (ref: arXiv:2206.01861 +
ZeRO-Infinity parameter offload): serve a llama-family model whose
weight image EXCEEDS the configured HBM budget, token-identical to the
fully resident engine.

Correctness oracle: the resident ServingEngine itself — the streamed
engine runs the SAME per-layer math through per-layer jits with
host-tier weights, so every request under identical traffic must
produce exactly the same greedy tokens.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.config import Config, ZeroInferenceConfig
from deepspeed_tpu.inference.serving import serving_engine
from deepspeed_tpu.inference.zero_inference import (
    ZeroInferenceServingEngine, plan_residency)
from deepspeed_tpu.models import llama

KW = dict(max_batch=3, page_size=8, num_pages=32, max_seq=64,
          prefill_bucket=8)
PROMPTS = {"a": ([5, 9, 2], 6), "b": ([17, 3, 3, 8, 1], 5),
           "c": ([40, 2], 7)}


@pytest.fixture(scope="module")
def model():
    cfg = llama.LlamaConfig.tiny(dim=64, n_layers=3, n_heads=4,
                                 n_kv_heads=2)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _serve(eng, prompts=PROMPTS):
    for rid, (p, n) in prompts.items():
        eng.submit(rid, p, max_new_tokens=n)
    return eng.run()


class TestPlanner:
    def test_budget_below_image_streams(self):
        plan = plan_residency(n_layers=10, layer_bytes=100,
                              stem_head_bytes=50, cache_bytes=30,
                              budget=700, prefetch_depth=1)
        # floor = 50 + 30 + 2*100 = 280; (700-280)//100 = 4 resident
        assert plan["n_resident"] == 4 and plan["n_streamed"] == 6
        assert plan["hbm_working_set_bytes"] == 50 + 30 + 400 + 200

    def test_no_budget_streams_everything(self):
        plan = plan_residency(n_layers=4, layer_bytes=10,
                              stem_head_bytes=5, cache_bytes=5,
                              budget=None, prefetch_depth=2)
        assert plan["n_resident"] == 0 and plan["n_streamed"] == 4

    def test_budget_holding_everything_pins_everything(self):
        plan = plan_residency(n_layers=4, layer_bytes=10,
                              stem_head_bytes=5, cache_bytes=5,
                              budget=10_000, prefetch_depth=1)
        assert plan["n_resident"] == 4 and plan["n_streamed"] == 0

    def test_budget_below_floor_raises(self):
        with pytest.raises(ValueError, match="streaming floor"):
            plan_residency(n_layers=4, layer_bytes=100,
                           stem_head_bytes=50, cache_bytes=50,
                           budget=250, prefetch_depth=1)


class TestZeroInferenceServing:
    def test_weight_image_exceeds_budget_token_identical(self, model,
                                                         devices):
        """THE acceptance case: bf16 weight image > hbm_budget_bytes,
        layers stream from the host tier, output token-identical."""
        cfg, params = model
        bf16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
        image = sum(x.nbytes for x in jax.tree.leaves(bf16))
        resident = serving_engine(bf16, cfg, **KW)
        zi = serving_engine(
            bf16, cfg,
            zero_inference={"hbm_budget_bytes": image - 1,
                            "tier": "host"}, **KW)
        assert isinstance(zi, ZeroInferenceServingEngine)
        assert zi.plan["weight_image_bytes"] == image
        assert zi.plan["n_streamed"] > 0, zi.plan
        assert zi.hbm_weight_working_set_bytes() < image + \
            zi.plan["cache_bytes"]
        out_r = _serve(resident)
        out_z = _serve(zi)
        assert out_z == out_r
        # every decode/prefill sweep re-streamed the non-resident suffix
        cnt = zi.registry.snapshot()["counters"]
        assert cnt["zi_layer_h2d_uploads"] >= \
            zi.plan["n_streamed"] * cnt["zi_layer_sweeps"]

    def test_partial_residency_pins_leading_layers(self, devices):
        # 5 layers so the budget interval [floor + 1 layer, image - 1]
        # is non-empty (3 layers can never pin under a depth-1 buffer)
        cfg = llama.LlamaConfig.tiny(dim=64, n_layers=5, n_heads=4,
                                     n_kv_heads=2)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        leaves, _ = jax.tree_util.tree_flatten(params["blocks"])
        layer_bytes = sum(a.nbytes // cfg.n_layers for a in leaves)
        stem_head = (params["embed"].nbytes + params["lm_head"].nbytes
                     + params["final_norm"].nbytes)
        cache = 2 * cfg.n_layers * cfg.n_kv_heads * 32 * 8 * \
            cfg.head_dim * 2
        # floor (stem+head + cache + 2-layer working set) + exactly 2
        budget = stem_head + cache + 2 * layer_bytes + 2 * layer_bytes
        zi = serving_engine(
            params, cfg, zero_inference={"hbm_budget_bytes": budget},
            **KW)
        assert zi.plan["n_resident"] == 2 and zi.plan["n_streamed"] == 3
        resident = serving_engine(params, cfg, **KW)
        assert _serve(zi) == _serve(resident)

    def test_tied_embeddings_charged_once(self, devices):
        """Tied-embedding models share ONE table between stem and head:
        the planner must charge it once (llama.param_count parity) and
        serving must still match the resident engine."""
        cfg = llama.LlamaConfig.tiny(dim=64, n_layers=2, n_heads=4,
                                     n_kv_heads=2, tie_embeddings=True)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        zi = serving_engine(params, cfg, zero_inference={}, **KW)
        assert zi.plan["stem_head_bytes"] == \
            params["embed"].nbytes + params["final_norm"].nbytes
        resident = serving_engine(params, cfg, **KW)
        assert _serve(zi) == _serve(resident)

    @pytest.mark.slow
    def test_nvme_tier_matches(self, model, devices, tmp_path):
        cfg, params = model
        resident = serving_engine(params, cfg, **KW)
        zi = serving_engine(
            params, cfg,
            zero_inference={"tier": "nvme",
                            "nvme_path": str(tmp_path)}, **KW)
        assert _serve(zi) == _serve(resident)
        # alternating-slot double buffering actually fenced reads
        assert zi._reader.hits + zi._reader.stalls > 0

    @pytest.mark.slow
    def test_int8_streamed_matches_resident_int8(self, model, devices):
        """int8 composes: tier holds codes+scales on the SAME per-leaf
        quantization grid, so streamed == resident under int8 too."""
        cfg, params = model
        r8 = serving_engine(params, cfg, weight_dtype="int8", **KW)
        z8 = serving_engine(params, cfg, weight_dtype="int8",
                            zero_inference={}, **KW)
        assert _serve(z8) == _serve(r8)

    @pytest.mark.slow
    def test_split_fuse_and_chunked_decode(self, model, devices):
        cfg, params = model
        kw = dict(max_batch=3, page_size=8, num_pages=32, max_seq=64,
                  decode_chunk=4, prefill_chunk=8)
        long_prompt = list(np.random.default_rng(5).integers(
            1, cfg.vocab_size, 21))
        prompts = dict(PROMPTS, long=(long_prompt, 5))
        resident = serving_engine(params, cfg, **kw)
        zi = serving_engine(
            params, cfg, zero_inference={"prefetch_depth": 2}, **kw)
        assert _serve(zi, prompts) == _serve(resident, prompts)

    @pytest.mark.slow
    def test_mixtral_streams(self, devices):
        from deepspeed_tpu.inference.serving import serving_engine
        from deepspeed_tpu.models import mixtral

        cfg = mixtral.MixtralConfig.tiny(num_experts=4)
        params = mixtral.init_params(jax.random.PRNGKey(2), cfg)
        resident = serving_engine(params, cfg, **KW)
        zi = serving_engine(params, cfg, zero_inference={},
                            **KW)
        assert _serve(zi) == _serve(resident)

    @pytest.mark.slow
    def test_tp_sharded_streaming(self, model, devices):
        from deepspeed_tpu.topology import MeshSpec

        cfg, params = model
        ms = MeshSpec.build({"data": 4, "model": 2})
        resident = serving_engine(params, cfg, mesh=ms, **KW)
        zi = serving_engine(params, cfg, mesh=ms,
                            zero_inference={}, **KW)
        # uploaded streamed layers land model-axis sharded
        _, lp = next(iter(zi._layer_sweep()))
        assert "model" in str(lp["wq"].sharding.spec), \
            lp["wq"].sharding.spec
        assert _serve(zi) == _serve(resident)


class TestWiring:
    def test_init_serving_routes_zero_inference(self, model, devices):
        from deepspeed_tpu.inference import init_serving

        cfg, params = model
        eng = init_serving(params, cfg,
                           config={"zero_inference": {"enabled": True}},
                           **KW)
        assert isinstance(eng, ZeroInferenceServingEngine)
        assert eng.plan["n_streamed"] == cfg.n_layers
        # no zero_inference block → the plain resident engine
        eng2 = init_serving(params, cfg, config={}, **KW)
        assert not isinstance(eng2, ZeroInferenceServingEngine)

    def test_registry_rejects_unsupported_family(self, devices):
        from deepspeed_tpu.models import gpt2

        cfg = gpt2.GPT2Config.tiny(dim=64, n_layers=2, n_heads=4,
                                   max_seq_len=256)
        params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
        with pytest.raises(NotImplementedError, match="zero_inference"):
            serving_engine(params, cfg, zero_inference={"enabled": True},
                           max_batch=1, page_size=8, num_pages=16,
                           max_seq=32)

    def test_config_block_parse_and_validation(self):
        c = Config.from_dict({"zero_inference": {
            "enabled": True, "hbm_budget_bytes": 1 << 20,
            "prefetch_depth": 2, "tier": "nvme", "dtype": "int8"}})
        assert c.zero_inference.enabled
        assert c.zero_inference.hbm_budget_bytes == 1 << 20
        assert Config.from_dict({}).zero_inference.enabled is False
        # WRITING the block is the opt-in — a user configuring the tier
        # but omitting "enabled" must not be silently served resident;
        # an explicit false still disables
        assert Config.from_dict(
            {"zero_inference": {"tier": "host"}}).zero_inference.enabled
        assert not Config.from_dict(
            {"zero_inference": {"enabled": False,
                                "tier": "host"}}).zero_inference.enabled
        with pytest.raises(ValueError, match="tier"):
            ZeroInferenceConfig.from_dict({"tier": "gpu"})
        with pytest.raises(ValueError, match="hbm_budget_bytes"):
            ZeroInferenceConfig.from_dict({"hbm_budget_bytes": 0})
        with pytest.raises(ValueError, match="prefetch_depth"):
            ZeroInferenceConfig.from_dict({"prefetch_depth": 0})
        with pytest.raises(ValueError, match="dtype"):
            ZeroInferenceConfig.from_dict({"dtype": "fp4"})
        # coerce: a dict opts in; None stays disabled
        assert ZeroInferenceConfig.coerce({"tier": "host"}).enabled
        assert not ZeroInferenceConfig.coerce(None).enabled

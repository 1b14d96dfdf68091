"""Weight-only int8 inference (ref: deepspeed init_inference(dtype=int8)
+ module_inject quantized variants)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as dstpu
from deepspeed_tpu.inference.quantized import (
    QuantizedTensor, dequantize_params, quantization_error, quantize_params)
from deepspeed_tpu.models import llama


@pytest.fixture(scope="module")
def model():
    cfg = llama.LlamaConfig.tiny(dim=64, n_layers=2, n_heads=4, n_kv_heads=2)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


class TestQuantizeParams:
    def test_roundtrip_error_small(self, model):
        cfg, params = model
        qp = quantize_params(params, group_size=64)
        err = quantization_error(params, qp)
        assert 0 < err < 0.02, err  # int8 group quant ≈ 0.2-1% rel error

    def test_weights_are_int8_vectors_exact(self, model):
        cfg, params = model
        qp = quantize_params(params)
        blocks = qp["blocks"]
        assert isinstance(blocks["wq"], QuantizedTensor)
        assert blocks["wq"].q.dtype == jnp.int8
        # 1-D leaves (norm gains) stay exact
        np.testing.assert_array_equal(np.asarray(qp["final_norm"]),
                                      np.asarray(params["final_norm"]))

    def test_memory_halves_vs_bf16(self, model):
        cfg, params = model
        bf16 = jax.tree.map(lambda l: jnp.asarray(l, jnp.bfloat16), params)
        qp = quantize_params(bf16)
        orig = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(bf16))
        quant = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(qp))
        # int8 codes ≈ half the bf16 bytes; group scales (f32, 1/128 of
        # elements) add ~3% — anything past 0.56 means grouping regressed
        assert quant < 0.56 * orig, quant / orig


class TestInt8Inference:
    def test_init_inference_int8_logits_close(self, model, devices):
        cfg, params = model
        toks = jnp.asarray(np.random.default_rng(0).integers(
            0, cfg.vocab_size, (2, 12)), jnp.int32)
        fwd = lambda p, t: llama.forward(p, t, cfg)
        ref = dstpu.init_inference(apply_fn=fwd, params=params)(toks)
        got = dstpu.init_inference(apply_fn=fwd, params=params,
                                   dtype="int8")(toks)
        # logits drift with quant error but rankings mostly hold
        agree = float(jnp.mean(jnp.argmax(got, -1) == jnp.argmax(ref, -1)))
        assert agree > 0.9, agree

    def test_init_inference_int8_composes_with_tp_specs(self, model,
                                                        devices):
        """int8 + param_specs through the generic entrypoint (ref:
        init_inference(dtype=int8, mp_size>1)): codes and per-row
        scales land model-axis sharded and logits match the replicated
        int8 engine bit-for-bit — sharding is an execution strategy."""
        from deepspeed_tpu.topology import MeshSpec, set_current_mesh

        cfg, params = model
        toks = jnp.asarray(np.random.default_rng(1).integers(
            0, cfg.vocab_size, (2, 12)), jnp.int32)
        fwd = lambda p, t: llama.forward(p, t, cfg)
        want = dstpu.init_inference(apply_fn=fwd, params=params,
                                    dtype="int8",
                                    quant_group_size=16)(toks)
        mesh = MeshSpec.build({"model": 2}, devices=jax.devices()[:2])
        try:
            eng = dstpu.init_inference(
                apply_fn=fwd, params=params, dtype="int8",
                quant_group_size=16, mesh=mesh,
                param_specs=llama.param_specs(cfg))
            wq = eng.params["blocks"]["wq"]
            assert "model" in [s for s in wq.q.sharding.spec if s]
            assert "model" in [s for s in wq.scale.sharding.spec if s]
            got = eng(toks)
        finally:
            set_current_mesh(None)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))

    @pytest.mark.slow
    def test_int8_serving_runs_and_matches_int8_offline(self, model, devices):
        from deepspeed_tpu.inference.serving import serving_engine

        cfg, params = model
        prompt = [5, 9, 2, 33]
        eng = serving_engine(
            params, cfg, weight_dtype="int8", max_batch=2, page_size=8,
            num_pages=32, max_seq=64, prefill_bucket=8)
        eng.submit("r", prompt, max_new_tokens=5)
        out = eng.run()["r"]
        assert len(out) == len(prompt) + 5
        # oracle: same quantized weights through the offline paged path
        from deepspeed_tpu.inference.generation import Generator, KVCache
        from deepspeed_tpu.inference.kernels import PagedKVCache
        from deepspeed_tpu.inference.paged_forward import forward_paged
        from deepspeed_tpu.inference.quantized import quantized_apply

        qp = quantize_params(params)
        step = quantized_apply(
            lambda p, t, c: forward_paged(p, t, cfg, c))

        def alloc(batch, max_seq):
            mp = -(-max_seq // 8)
            return PagedKVCache.alloc(cfg.n_layers, cfg.n_kv_heads,
                                      batch * mp, 8, cfg.head_dim, batch,
                                      max_seq)

        gen = Generator(qp, step, step, alloc)
        want = gen.generate(jnp.asarray([prompt], jnp.int32),
                            max_new_tokens=5)
        # serving pads the prompt to the bucket; the offline oracle does
        # not — greedy tokens still match because the padded tail is
        # never attended
        assert out == [int(t) for t in np.asarray(want[0])]

    def test_unknown_weight_dtype_raises(self, model, devices):
        from deepspeed_tpu.inference.serving import serving_engine

        cfg, params = model
        with pytest.raises(NotImplementedError, match="int8"):
            serving_engine(params, cfg, weight_dtype="int4",
                           max_batch=1, num_pages=8, max_seq=32)

    def test_prime_rows_fall_back_to_row_groups(self):
        from deepspeed_tpu.inference.quantized import _pick_groups

        leaf = jnp.zeros((50257, 768))
        g = _pick_groups(leaf, 128)
        assert leaf.size % g == 0
        assert leaf.size // g <= 8 * 128  # per-row groups, not 50k-wide

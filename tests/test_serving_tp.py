"""TP-sharded serving (ref: deepspeed/module_inject/replace_module.py —
the reference's inference engine TP-injects modules as a core feature).

Oracle: the single-device serving engine — sharding the params and KV
heads over the model axis is an execution strategy, so served tokens
must match exactly.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.serving import serving_engine
from deepspeed_tpu.models import llama
from deepspeed_tpu.topology import MeshSpec, set_current_mesh


@pytest.fixture(scope="module")
def model():
    cfg = llama.LlamaConfig.tiny(dim=64, n_layers=2, n_heads=4, n_kv_heads=2)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


PROMPTS = {
    "a": ([5, 9, 2], 6),
    "b": ([17, 3, 3, 8, 1], 5),
    "c": ([40, 2], 7),
}

KW = dict(max_batch=2, page_size=8, num_pages=32, max_seq=64,
          prefill_bucket=8)


def serve_all(eng):
    for rid, (prompt, n_new) in PROMPTS.items():
        eng.submit(rid, prompt, max_new_tokens=n_new)
    return eng.run()


class TestTPServing:
    def test_tp2_matches_single_device(self, model, devices):
        cfg, params = model
        base = serving_engine(params, cfg, **KW)
        want = serve_all(base)

        mesh = MeshSpec.build({"model": 2}, devices=jax.devices()[:2])
        try:
            eng = serving_engine(params, cfg, mesh=mesh, **KW)
            # the KV cache's head axis is genuinely sharded over model
            spec = eng.cache.k.sharding.spec
            assert "model" in [s for s in spec if s is not None]
            # params are sharded too (wq: column-parallel)
            wq_spec = eng.params["blocks"]["wq"].sharding.spec
            assert any(s == "model" for s in wq_spec if s is not None)
            got = serve_all(eng)
        finally:
            set_current_mesh(None)
        assert got == want

    @pytest.mark.slow
    def test_tp2_split_fuse_and_chunked_decode(self, model, devices):
        cfg, params = model
        base = serving_engine(params, cfg, **KW)
        want = serve_all(base)
        mesh = MeshSpec.build({"model": 2}, devices=jax.devices()[:2])
        try:
            eng = serving_engine(params, cfg, mesh=mesh,
                                 max_batch=2, page_size=8,
                                 num_pages=32, max_seq=64,
                                 prefill_chunk=4, decode_chunk=2)
            got = serve_all(eng)
        finally:
            set_current_mesh(None)
        assert got == want

    def test_int8_tp2_matches_unsharded_int8(self, model, devices):
        """int8 weight-only quant composes with TP (ref: module_inject
        int8+TP injection): per-row group scales shard with their
        weights, so served tokens match the unsharded int8 engine
        exactly — same codes, same scales, different placement."""
        cfg, params = model
        base = serving_engine(params, cfg, weight_dtype="int8",
                              quant_group_size=16, **KW)
        want = serve_all(base)

        mesh = MeshSpec.build({"model": 2}, devices=jax.devices()[:2])
        try:
            eng = serving_engine(params, cfg, mesh=mesh,
                                 weight_dtype="int8",
                                 quant_group_size=16, **KW)
            # the int8 codes AND their group scales are genuinely
            # model-axis sharded (column-parallel wq: output dim)
            qt = eng.params["blocks"]["wq"]
            assert "model" in [s for s in qt.q.sharding.spec if s]
            assert "model" in [s for s in qt.scale.sharding.spec if s]
            got = serve_all(eng)
        finally:
            set_current_mesh(None)
        assert got == want

    def test_indivisible_kv_heads_refused(self, devices):
        cfg = llama.LlamaConfig.tiny(dim=48, n_layers=1, n_heads=3,
                                     n_kv_heads=3)
        params = llama.init_params(jax.random.PRNGKey(1), cfg)
        mesh = MeshSpec.build({"model": 2}, devices=jax.devices()[:2])
        try:
            with pytest.raises(ValueError, match="divisible"):
                serving_engine(params, cfg, mesh=mesh, **KW)
        finally:
            set_current_mesh(None)

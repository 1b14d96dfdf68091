"""GPT-2 continuous-batching serving (ref: the reference serves GPT-2
through kernel injection, deepspeed/module_inject/containers/gpt2.py).

Oracle: the offline paged generator (for the scheduler).  That oracle
against the dense-cache generator, and the registry against the oracle,
are checked a family a case in tests/test_generation.py and
tests/test_models.py.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.generation import paged_generator
from deepspeed_tpu.inference.serving import serving_engine
from deepspeed_tpu.models import gpt2


@pytest.fixture(scope="module")
def model():
    cfg = gpt2.GPT2Config.tiny(dim=64, n_layers=2, n_heads=4,
                               max_seq_len=64)
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


PROMPTS = {
    "a": ([5, 9, 2], 6),
    "b": ([17, 3, 3, 8, 1], 5),
    "c": ([40, 2], 7),
}


def offline_expected(cfg, params, prompt, n_new):
    gen = paged_generator(params, cfg, page_size=8)
    out = gen.generate(jnp.asarray([prompt], jnp.int32),
                       max_new_tokens=n_new)
    return [int(t) for t in np.asarray(out[0])]


class TestGPT2Serving:
    @pytest.mark.slow
    def test_split_fuse_matches(self, model, devices):
        cfg, params = model
        eng = serving_engine(params, cfg, max_batch=2, page_size=8,
                             num_pages=32, max_seq=64, prefill_chunk=4,
                             decode_chunk=2)
        long_prompt = list(range(2, 21))
        eng.submit("long", long_prompt, max_new_tokens=5)
        eng.submit("a", PROMPTS["a"][0], max_new_tokens=PROMPTS["a"][1])
        outs = eng.run()
        assert outs["long"] == offline_expected(cfg, params,
                                                long_prompt, 5)
        assert outs["a"] == offline_expected(cfg, params, *PROMPTS["a"])

    def test_tp2_matches_unsharded(self, model, devices):
        """TP-sharded GPT-2 serving (ref: module_inject/containers/
        gpt2.py — fused qkv column-parallel, proj/out row-parallel) is
        an execution strategy: served tokens match exactly."""
        from deepspeed_tpu.topology import MeshSpec, set_current_mesh

        cfg, params = model
        kw = dict(max_batch=2, page_size=8, num_pages=32, max_seq=64,
                  prefill_bucket=8)
        base = serving_engine(params, cfg, **kw)
        for rid, (p, n) in PROMPTS.items():
            base.submit(rid, p, max_new_tokens=n)
        want = base.run()

        mesh = MeshSpec.build({"model": 2}, devices=jax.devices()[:2])
        try:
            eng = serving_engine(params, cfg, mesh=mesh, **kw)
            spec = eng.params["blocks"]["qkv_w"].sharding.spec
            assert "model" in [s for s in spec if s]
            for rid, (p, n) in PROMPTS.items():
                eng.submit(rid, p, max_new_tokens=n)
            got = eng.run()
        finally:
            set_current_mesh(None)
        assert got == want


def test_param_count_matches_init(model, devices):
    cfg, params = model
    actual = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    assert gpt2.param_count(cfg) == actual

"""Hybrid train+generate engine (ref: deepspeed/runtime/hybrid_engine.py).

The load-bearing properties: generation consumes the engine's LIVE
stage-3-sharded params (no copy/gather step a user could forget),
rollouts match the standalone Generator on the same weights, and a full
RLHF-shaped iteration (generate → train on the rollout → generate again)
runs with the second rollout reflecting the update.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as dstpu
from deepspeed_tpu.models import llama


@pytest.fixture(scope="module")
def setup():
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    engine, _, _, _ = dstpu.initialize(
        loss_fn=llama.loss_fn(cfg), params=params,
        config={"train_micro_batch_size_per_gpu": 1,
                "zero_optimization": {"stage": 3},
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "hybrid_engine": {"enabled": True, "max_out_tokens": 64,
                                  "pin_parameters": True}})
    hybrid = dstpu.init_hybrid_engine(engine, cfg)
    return cfg, engine, hybrid


def _prompts(cfg, b=8, t=8):
    rng = np.random.default_rng(0)
    return jnp.asarray(rng.integers(0, cfg.vocab_size, (b, t)), jnp.int32)


class TestHybridEngine:
    @pytest.mark.slow
    def test_generate_matches_standalone_generator(self, devices, setup):
        cfg, engine, hybrid = setup
        from deepspeed_tpu.inference.generation import generator

        prompts = _prompts(cfg)
        got = hybrid.generate(prompts, max_new_tokens=8, temperature=0.0)
        # reference: plain Generator over the gathered master weights cast
        # to the compute dtype
        full = jax.tree.map(lambda x: x.astype(jnp.bfloat16),
                            engine.module_params())
        ref = generator(full, cfg).generate(
            prompts, max_new_tokens=8, temperature=0.0)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    @pytest.mark.slow
    def test_rlhf_iteration(self, devices, setup):
        cfg, engine, hybrid = setup
        prompts = _prompts(cfg)
        r1 = hybrid.generate(prompts, max_new_tokens=8, temperature=0.0)
        assert r1.shape == (8, 16)
        # train on the rollout (an RL step would weight by advantage; the
        # plain LM loss exercises the same engine path)
        before = int(engine.global_steps)
        loss = hybrid.train_batch({"tokens": r1[:, :9]})
        assert np.isfinite(float(loss))
        assert engine.global_steps == before + 1
        # second rollout reads the UPDATED params — same buffers, no sync
        r2 = hybrid.generate(prompts, max_new_tokens=8, temperature=0.0)
        assert r2.shape == r1.shape

    @pytest.mark.slow
    def test_sampled_rollout_and_eos(self, devices, setup):
        cfg, engine, hybrid = setup
        hybrid.eos = 3
        try:
            out = hybrid.generate(_prompts(cfg), max_new_tokens=8,
                                  temperature=1.0,
                                  rng=jax.random.PRNGKey(7))
            assert out.shape == (8, 16)
            tail = np.asarray(out)[:, 8:]
            for row in tail:
                hit = np.where(row == 3)[0]
                if hit.size:  # everything after an eos stays eos
                    assert (row[hit[0]:] == 3).all()
        finally:
            hybrid.eos = None

    def test_inference_tp_size_mismatch_raises(self, devices):
        cfg = llama.LlamaConfig.tiny()
        engine, _, _, _ = dstpu.initialize(
            loss_fn=llama.loss_fn(cfg),
            params=llama.init_params(jax.random.PRNGKey(0), cfg),
            config={"train_micro_batch_size_per_gpu": 1,
                    "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                    "hybrid_engine": {"enabled": True,
                                      "inference_tp_size": 4}})
        with pytest.raises(ValueError, match="inference_tp_size"):
            dstpu.init_hybrid_engine(engine, cfg)

    def test_cache_overrun_raises(self, devices, setup):
        cfg, engine, hybrid = setup
        # max_out_tokens=64 from the fixture config; 60+8 > 64 must fail
        with pytest.raises(ValueError, match="KV cache budget"):
            hybrid.generate(_prompts(cfg, t=60), max_new_tokens=8)

    def test_enabled_false_raises(self, devices):
        cfg = llama.LlamaConfig.tiny()
        engine, _, _, _ = dstpu.initialize(
            loss_fn=llama.loss_fn(cfg),
            params=llama.init_params(jax.random.PRNGKey(0), cfg),
            config={"train_micro_batch_size_per_gpu": 1,
                    "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                    "hybrid_engine": {"enabled": False}})
        with pytest.raises(ValueError, match="enabled"):
            dstpu.init_hybrid_engine(engine, cfg)

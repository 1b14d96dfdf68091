"""Generation / KV-cache / injection tests (SURVEY.md §4).

Ground truth: incremental decode with cache must match full forward.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.generation import (KVCache, generator,
                                                paged_generator,
                                                sample_logits)
from deepspeed_tpu.inference.paged_forward import (forward_paged,
                                                   forward_with_cache)
from deepspeed_tpu.models import gpt2, llama, mixtral


def _setup(T=12, B=2):
    cfg = llama.LlamaConfig.tiny(attn_impl="reference")
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, 256)
    return cfg, params, toks


def test_prefill_matches_forward():
    cfg, params, toks = _setup()
    want = llama.forward(params, toks, cfg)
    cache = KVCache.alloc(cfg.n_layers, 2, 32, cfg.n_kv_heads, cfg.head_dim,
                          dtype=jnp.float32)
    got, cache = forward_with_cache(params, toks, cfg, cache)
    assert int(cache.length) == toks.shape[1]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


@pytest.mark.slow
def test_incremental_decode_matches_full():
    cfg, params, toks = _setup(T=8)
    full = llama.forward(params, toks, cfg)
    cache = KVCache.alloc(cfg.n_layers, 2, 16, cfg.n_kv_heads, cfg.head_dim,
                          dtype=jnp.float32)
    # prefill 4, then decode 4 one token at a time
    logits, cache = forward_with_cache(params, toks[:, :4], cfg, cache)
    outs = [logits]
    for t in range(4, 8):
        logits, cache = forward_with_cache(
            params, toks[:, t:t + 1], cfg, cache)
        outs.append(logits)
    got = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(full),
                               atol=5e-4, rtol=5e-4)


def test_generator_greedy_deterministic():
    cfg, params, toks = _setup(T=4)
    gen = generator(params, cfg, cache_dtype=jnp.float32)
    out1 = gen.generate(toks, max_new_tokens=6, temperature=0.0)
    out2 = gen.generate(toks, max_new_tokens=6, temperature=0.0)
    assert out1.shape == (2, 10)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    np.testing.assert_array_equal(np.asarray(out1[:, :4]), np.asarray(toks))


def test_paged_forward_matches_cached():
    from deepspeed_tpu.inference.kernels import PagedKVCache

    cfg, params, toks = _setup(T=8)
    full = llama.forward(params, toks, cfg)
    cache = PagedKVCache.alloc(cfg.n_layers, cfg.n_kv_heads, num_pages=8,
                               page_size=4, head_dim=cfg.head_dim, batch=2,
                               max_seq=16, dtype=jnp.float32)
    # prefill 6 = one full page + a HALF page (exercises the pad path in
    # write_prompt_pages and decoding into a partially-filled page)
    logits, cache = forward_paged(params, toks[:, :6], cfg, cache)
    outs = [logits]
    for t in range(6, 8):
        logits, cache = forward_paged(params, toks[:, t:t + 1], cfg,
                                            cache)
        outs.append(logits)
    got = jnp.concatenate(outs, axis=1)
    assert int(cache.seq_lens[0]) == 8
    np.testing.assert_allclose(np.asarray(got), np.asarray(full),
                               atol=5e-4, rtol=5e-4)


def test_paged_prefill_requires_empty_cache():
    from deepspeed_tpu.inference.kernels import PagedKVCache

    cfg, params, toks = _setup(T=8)
    cache = PagedKVCache.alloc(cfg.n_layers, cfg.n_kv_heads, num_pages=8,
                               page_size=4, head_dim=cfg.head_dim, batch=2,
                               max_seq=16, dtype=jnp.float32)
    _, cache = forward_paged(params, toks[:, :4], cfg, cache)
    import pytest
    with pytest.raises(ValueError, match="empty cache"):
        forward_paged(params, toks[:, 4:8], cfg, cache)


@pytest.mark.slow
def test_paged_decode_ragged_frontiers():
    """Batched decode with per-row seq_lens must equal per-sequence
    decode (per-row RoPE offsets + per-row page frontiers)."""
    cfg, params, toks = _setup(T=8, B=2)
    ps, mp = 4, 4

    def one_row(row, L):
        from deepspeed_tpu.inference.kernels import PagedKVCache

        c = PagedKVCache.alloc(cfg.n_layers, cfg.n_kv_heads, num_pages=mp,
                               page_size=ps, head_dim=cfg.head_dim, batch=1,
                               max_seq=ps * mp, dtype=jnp.float32)
        _, c = forward_paged(params, toks[row:row + 1, :L], cfg, c)
        logits, _ = forward_paged(params, toks[row:row + 1, L:L + 1],
                                        cfg, c)
        return c, logits

    c0, l0 = one_row(0, 4)
    c1, l1 = one_row(1, 6)
    # merge into one B=2 cache: row 1's pages live at ids [mp, 2mp)
    from deepspeed_tpu.inference.kernels import PagedKVCache

    merged = PagedKVCache.alloc(cfg.n_layers, cfg.n_kv_heads,
                                num_pages=2 * mp, page_size=ps,
                                head_dim=cfg.head_dim, batch=2,
                                max_seq=ps * mp, dtype=jnp.float32)
    merged = merged._replace(
        k=merged.k.at[:, :, :mp].set(c0.k).at[:, :, mp:].set(c1.k),
        v=merged.v.at[:, :, :mp].set(c0.v).at[:, :, mp:].set(c1.v),
        seq_lens=jnp.asarray([4, 6], jnp.int32))
    nxt = jnp.stack([toks[0, 4], toks[1, 6]])[:, None]
    lb, _ = forward_paged(params, nxt, cfg, merged)
    np.testing.assert_allclose(np.asarray(lb[0]), np.asarray(l0[0]),
                               atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(np.asarray(lb[1]), np.asarray(l1[0]),
                               atol=5e-4, rtol=5e-4)


# family -> (module, its tiny config, prompt rows, new tokens, page size,
# cache dtype): the cases the three per-family copies of this test ran
PAGED_VS_DENSE = {
    "gpt2": (gpt2, gpt2.GPT2Config.tiny(dim=64, n_layers=2, n_heads=4,
                                        max_seq_len=64),
             [[17, 3, 3, 8, 1]], 5, 8, jnp.bfloat16),
    "llama": (llama, llama.LlamaConfig.tiny(attn_impl="reference"),
              np.random.default_rng(1).integers(0, 256, (2, 4)), 6, 4,
              jnp.float32),
    "mixtral": (mixtral, mixtral.MixtralConfig.tiny(
        dim=64, n_layers=2, n_heads=4, n_kv_heads=2, num_experts=4),
        [[5, 9, 2]], 6, 8, jnp.bfloat16),
}


@pytest.mark.parametrize("family", PAGED_VS_DENSE)
def test_paged_matches_dense_cache_greedy(family, devices):
    """Cross-oracle, a family a case: the paged forward (ragged learned
    or rotary positions, page writes, the MoE routing) must generate
    exactly like the contiguous-cache ``forward_with_cache``."""
    mod, cfg, prompt, n_new, page_size, dtype = PAGED_VS_DENSE[family]
    params = mod.init_params(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(prompt, jnp.int32)
    dense = generator(params, cfg, cache_dtype=dtype)
    paged = paged_generator(params, cfg, page_size=page_size,
                            cache_dtype=dtype)
    o1 = dense.generate(toks, max_new_tokens=n_new, temperature=0.0)
    o2 = paged.generate(toks, max_new_tokens=n_new, temperature=0.0)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))


def test_generator_eos_stops():
    cfg, params, toks = _setup(T=4)
    gen = generator(params, cfg, cache_dtype=jnp.float32, eos_token_id=7)
    out = gen.generate(toks, max_new_tokens=8, temperature=0.0)
    assert out.shape[1] <= 12


def test_sample_logits_modes():
    rng = jax.random.PRNGKey(0)
    logits = jnp.asarray([[0.0, 5.0, 1.0, -2.0]] * 4)
    greedy = sample_logits(logits, rng, temperature=0.0)
    np.testing.assert_array_equal(np.asarray(greedy), [1, 1, 1, 1])
    # top_k=1 == greedy regardless of temperature
    tk = sample_logits(logits, rng, temperature=1.0, top_k=1)
    np.testing.assert_array_equal(np.asarray(tk), [1, 1, 1, 1])
    # top_p tiny keeps only the max
    tp = sample_logits(logits, rng, temperature=1.0, top_p=0.1)
    np.testing.assert_array_equal(np.asarray(tp), [1, 1, 1, 1])


def test_injection_roundtrip(tmp_path):
    from deepspeed_tpu.integrations import hf
    from deepspeed_tpu.inference.injection import inject

    cfg = llama.LlamaConfig.tiny(attn_impl="reference")
    params = jax.tree.map(lambda x: np.asarray(x, np.float32),
                          llama.init_params(jax.random.PRNGKey(0), cfg))
    hf.save_pretrained(params, cfg, str(tmp_path))
    assert os.path.exists(tmp_path / "model.safetensors")
    fn, params2, cfg2, specs = hf.from_pretrained(str(tmp_path),
                                                  dtype=jnp.float32)
    assert cfg2.dim == cfg.dim and cfg2.n_layers == cfg.n_layers
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, 256)
    want = llama.forward(params, toks, cfg)
    got = fn(params2, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_injection_unknown_arch():
    import pytest
    from deepspeed_tpu.inference.injection import get_policy

    with pytest.raises(ValueError):
        get_policy("not-a-real-arch")


class TestGPT2Generation:
    def test_cached_prefill_matches_forward(self, devices):
        from deepspeed_tpu.inference.generation import KVCache

        cfg = gpt2.GPT2Config.tiny()
        params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
        toks = jnp.asarray(np.random.default_rng(0).integers(
            0, cfg.vocab_size, (2, 10)), jnp.int32)
        ref = gpt2.forward(params, toks, cfg)
        cache = KVCache.alloc(cfg.n_layers, 2, 16, cfg.n_kv_heads,
                              cfg.head_dim, dtype=jnp.float32)
        got, cache = forward_with_cache(params, toks, cfg, cache)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)
        assert int(cache.length) == 10

    def test_generator_greedy_deterministic(self, devices):
        cfg = gpt2.GPT2Config.tiny()
        params = gpt2.init_params(jax.random.PRNGKey(1), cfg)
        gen = generator(params, cfg)
        out1 = gen.generate(jnp.asarray([[3, 7, 11]], jnp.int32),
                            max_new_tokens=6)
        out2 = gen.generate(jnp.asarray([[3, 7, 11]], jnp.int32),
                            max_new_tokens=6)
        assert out1.shape == (1, 9)
        np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))

    def test_position_table_overflow_raises(self, devices):
        cfg = gpt2.GPT2Config.tiny(max_seq_len=16)
        params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
        gen = generator(params, cfg)
        with pytest.raises(ValueError, match="position table"):
            gen.generate(jnp.ones((1, 12), jnp.int32), max_new_tokens=8)

    def test_infinity_engine_ckpt_api_parity(self, devices, tmp_path):
        """async_save / wait_for_checkpoint must not crash on the
        config-selected InfinityEngine (drop-in engine swap)."""
        import deepspeed_tpu as dstpu

        def loss(p, b):
            return jnp.mean((b["x"] @ p["w"]) ** 2)

        engine, _, _, _ = dstpu.initialize(
            loss_fn=loss, params={"w": jnp.ones((8, 4))},
            config={"train_batch_size": 8,
                    "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                    "zero_optimization": {
                        "stage": 2,
                        "offload_optimizer": {"device": "cpu",
                                              "scheduled": True}}})
        engine.train_batch({"x": jnp.ones((8, 8), jnp.float32)})
        engine.save_checkpoint(str(tmp_path), tag="t", async_save=True)
        engine.wait_for_checkpoint()

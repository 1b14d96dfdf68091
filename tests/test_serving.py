"""Continuous-batching serving loop (ref: deepspeed/inference/engine.py
generate path / DeepSpeed-FastGen iteration-level scheduling).

Correctness oracle: the offline paged Generator — every request served
under staggered arrivals, shared slots, page growth, and preemption must
produce EXACTLY the greedy tokens the dedicated single-request run does.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.generation import paged_generator
from deepspeed_tpu.inference.paged_forward import forward_paged
from deepspeed_tpu.inference.serving import ServingEngine, serving_engine
from deepspeed_tpu.models import llama


@pytest.fixture(scope="module")
def model():
    cfg = llama.LlamaConfig.tiny(dim=64, n_layers=2, n_heads=4, n_kv_heads=2)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
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


class TestServing:
    @pytest.mark.slow
    def test_staggered_arrivals_match_offline_greedy(self, model, devices):
        cfg, params = model
        eng = serving_engine(
            params, cfg, max_batch=3, page_size=8, num_pages=32,
            max_seq=64, prefill_bucket=8)
        # staggered: a at step 0, b after one step, c after another
        eng.submit("a", *[PROMPTS["a"][0]],
                   max_new_tokens=PROMPTS["a"][1])
        eng.step()
        eng.submit("b", PROMPTS["b"][0], max_new_tokens=PROMPTS["b"][1])
        eng.step()
        eng.submit("c", PROMPTS["c"][0], max_new_tokens=PROMPTS["c"][1])
        outs = eng.run()
        assert set(outs) == {"a", "b", "c"}
        for rid, (prompt, n_new) in PROMPTS.items():
            want = offline_expected(cfg, params, prompt, n_new)
            assert outs[rid] == want, \
                f"{rid}: served {outs[rid]} != offline {want}"

    @pytest.mark.slow
    def test_more_requests_than_slots(self, model, devices):
        cfg, params = model
        eng = serving_engine(
            params, cfg, max_batch=2, page_size=8, num_pages=32,
            max_seq=64, prefill_bucket=8)
        for rid, (prompt, n_new) in PROMPTS.items():
            eng.submit(rid, prompt, max_new_tokens=n_new)
        outs = eng.run()
        assert len(outs) == 3
        for rid, (prompt, n_new) in PROMPTS.items():
            assert outs[rid] == offline_expected(cfg, params, prompt, n_new)

    def test_page_growth_across_boundaries(self, model, devices):
        cfg, params = model
        eng = serving_engine(
            params, cfg, max_batch=2, page_size=4, num_pages=64,
            max_seq=64, prefill_bucket=4)
        eng.submit("long", [7, 7, 7], max_new_tokens=21)  # crosses 5 pages
        outs = eng.run()
        assert outs["long"] == offline_expected(cfg, params, [7, 7, 7], 21)

    def test_preemption_under_page_pressure(self, model, devices):
        cfg, params = model
        # tiny pool: both sequences cannot hold all their pages at once
        eng = serving_engine(
            params, cfg, max_batch=2, page_size=4, num_pages=7,
            max_seq=40, prefill_bucket=4)
        eng.submit("x", [5, 9, 2], max_new_tokens=12)
        eng.submit("y", [17, 3, 3], max_new_tokens=12)
        outs = eng.run()
        cnt = eng.registry.snapshot()["counters"]
        assert cnt["serving_preempted_requests"] >= 1, \
            "pool never pressured"
        assert outs["x"] == offline_expected(cfg, params, [5, 9, 2], 12)
        assert outs["y"] == offline_expected(cfg, params, [17, 3, 3], 12)

    def test_eos_stops_early_and_frees_pages(self, model, devices):
        cfg, params = model
        # discover the greedy continuation, then declare its 3rd new token
        # as EOS: serving must stop there
        want = offline_expected(cfg, params, [5, 9, 2], 6)
        eos = want[3 + 2]  # 3 prompt tokens, 3rd generated
        eng = serving_engine(
            params, cfg, max_batch=2, page_size=8, num_pages=32,
            max_seq=64, prefill_bucket=8, eos_token_id=eos)
        eng.submit("e", [5, 9, 2], max_new_tokens=6)
        outs = eng.run()
        assert outs["e"] == want[:3 + 3]
        assert len(eng.allocator.free) == 31  # all pages back (1 is trash)

    def test_rejects_oversized_request(self, model, devices):
        cfg, params = model
        eng = serving_engine(
            params, cfg, max_batch=1, page_size=8, num_pages=16, max_seq=32)
        with pytest.raises(ValueError, match="max_seq"):
            eng.submit("big", list(range(30)), max_new_tokens=10)
        with pytest.raises(ValueError, match="empty"):
            eng.submit("none", [], max_new_tokens=4)

    def test_rejects_request_larger_than_pool(self, model, devices):
        cfg, params = model
        # 4 usable pages of 4 = 16 tokens max lifetime; ask for 20
        eng = serving_engine(
            params, cfg, max_batch=1, page_size=4, num_pages=5, max_seq=32)
        with pytest.raises(ValueError, match="never"):
            eng.submit("big", list(range(10)), max_new_tokens=10)

    def test_near_max_seq_prompt_with_big_bucket(self, model, devices):
        # prompt near max_seq with prefill_bucket > remaining table space:
        # Tpad must clamp to the row width instead of crashing admission
        cfg, params = model
        eng = serving_engine(
            params, cfg, max_batch=1, page_size=4, num_pages=16,
            max_seq=40, prefill_bucket=32)
        prompt = [3] * 37
        eng.submit("edge", prompt, max_new_tokens=3)
        outs = eng.run()
        assert outs["edge"] == offline_expected(cfg, params, prompt, 3)


class TestSampleRows:
    """Batched per-row sampler: the one-transfer-per-step decode path."""

    def test_greedy_rows_match_argmax_sampled_rows_vary(self):
        import jax
        import jax.numpy as jnp
        from deepspeed_tpu.inference.serving import _sample_rows

        rng = np.random.default_rng(0)
        logits = jnp.asarray(rng.normal(size=(4, 64)) * 3, jnp.float32)
        keys = jax.random.split(jax.random.PRNGKey(1), 4)
        temps = jnp.asarray([0.0, 0.0, 1.0, 1.0], jnp.float32)
        toks = np.asarray(_sample_rows(logits, keys, temps))
        np.testing.assert_array_equal(
            toks[:2], np.argmax(np.asarray(logits[:2]), -1))
        assert ((0 <= toks) & (toks < 64)).all()
        # sampled rows follow their own keys: different keys, generally
        # different draws on a flat-ish distribution
        keys2 = jax.random.split(jax.random.PRNGKey(2), 4)
        toks2 = np.asarray(_sample_rows(logits / 10.0, keys2,
                                        jnp.ones(4, jnp.float32)))
        toks1 = np.asarray(_sample_rows(logits / 10.0, keys,
                                        jnp.ones(4, jnp.float32)))
        assert not np.array_equal(toks1, toks2)

    def test_mixed_traffic_completes(self, model, devices):
        # sampled + greedy requests through the full loop
        cfg, params = model
        engine = serving_engine(
            params, cfg, max_batch=4, page_size=8, num_pages=32,
            max_seq=32, prefill_bucket=8)
        rng = np.random.default_rng(3)
        for i in range(4):
            engine.submit(i, rng.integers(1, 100, 8).tolist(),
                          max_new_tokens=6,
                          temperature=0.0 if i % 2 == 0 else 0.9)
        done = engine.run()
        assert len(done) == 4
        assert all(len(v) == 14 for v in done.values())


class TestDecodeChunk:
    """Chunked decode: K steps per host sync, same tokens as unchunked."""

    def _run(self, model, chunk, reqs):
        cfg, params = model
        eng = serving_engine(
            params, cfg, max_batch=3, page_size=8, num_pages=32,
            max_seq=64, prefill_bucket=8, decode_chunk=chunk)
        for rid, (prompt, n) in reqs.items():
            eng.submit(rid, prompt, max_new_tokens=n)
        out = eng.run()
        return out, eng

    @pytest.mark.slow
    def test_chunked_matches_unchunked_greedy(self, model, devices):
        reqs = {"a": ([5, 9, 2], 9), "b": ([17, 3, 3, 8, 1], 6),
                "c": ([40, 2], 11)}
        base, _ = self._run(model, 1, reqs)
        for K in (4, 8):
            got, eng = self._run(model, K, reqs)
            assert got == base, f"chunk={K} diverged"

    def test_chunked_fewer_host_syncs(self, model, devices):
        reqs = {"a": ([5, 9, 2], 16)}
        _, e1 = self._run(model, 1, reqs)
        _, e8 = self._run(model, 8, reqs)
        # 15 decode tokens (1 comes from prefill): K=1 needs 15 syncs,
        # K=8 needs ceil(15/8)=2 — the K-fold round-trip reduction is
        # the measured quantity, not device step count
        c1 = e1.registry.snapshot()["counters"]
        c8 = e8.registry.snapshot()["counters"]
        assert c1["serving_decode_syncs"] == 15
        assert c8["serving_decode_syncs"] == 2
        assert c8["serving_decode_steps"] == 16

    def test_chunked_with_more_requests_than_slots(self, model, devices):
        cfg, params = model
        eng = serving_engine(
            params, cfg, max_batch=2, page_size=8, num_pages=24,
            max_seq=48, prefill_bucket=8, decode_chunk=4)
        rng = np.random.default_rng(5)
        for i in range(5):
            eng.submit(i, rng.integers(1, 100, 6).tolist(),
                       max_new_tokens=10)
        out = eng.run()
        assert len(out) == 5
        assert all(len(v) == 16 for v in out.values())


def offline_chunked_expected(cfg, params, prompt, n_new, C, page_size=8):
    """Scheduler-free replay of the chunked-prefill compute path: the same
    continuation forwards + single-token decodes the engine issues, on a
    dedicated cache.  (The plain-prefill oracle is NOT bit-identical: it
    computes prompt attention with the flash kernel, the chunk path with
    the masked gather — bf16 K/V of deeper layers differ ~1e-2, enough to
    flip a close greedy argmax.  Serving tests pin the SCHEDULER, so the
    oracle must share the kernel numerics.)"""
    from deepspeed_tpu.inference.kernels import PagedKVCache

    T = len(prompt)
    total = T + n_new
    mp = -(-max(total, -(-T // C) * C) // page_size)
    cache = PagedKVCache.alloc(cfg.n_layers, cfg.n_kv_heads, mp, page_size,
                               cfg.head_dim, 1, mp * page_size)
    out = list(prompt)
    done = 0
    while done < T:
        take = min(C, T - done)
        toks = np.zeros((1, C), np.int32)
        toks[0, :take] = prompt[done:done + take]
        cache = cache._replace(seq_lens=jnp.full((1,), done, jnp.int32))
        logits, cache = forward_paged(
            params, jnp.asarray(toks), cfg, cache, continuation=True)
        done += take
    out.append(int(jnp.argmax(logits[0, take - 1])))
    cache = cache._replace(seq_lens=jnp.full((1,), T, jnp.int32))
    for _ in range(n_new - 1):
        logits, cache = forward_paged(
            params, jnp.asarray([[out[-1]]], jnp.int32), cfg, cache)
        out.append(int(jnp.argmax(logits[0, -1])))
    return out


class TestChunkedPrefill:
    """Split-fuse scheduling: prompts absorbed prefill_chunk tokens per
    iteration between decode steps (ref: DeepSpeed-FastGen dynamic
    split-fuse)."""

    @pytest.mark.slow
    def test_long_prompt_matches_offline(self, model, devices):
        cfg, params = model
        prompt = list(np.random.default_rng(5).integers(
            0, cfg.vocab_size, 37))
        eng = serving_engine(
            params, cfg, max_batch=2, page_size=8, num_pages=32,
            max_seq=64, prefill_chunk=8)
        eng.submit("long", prompt, max_new_tokens=5)
        outs = eng.run()
        assert eng.registry.snapshot()["counters"][
            "serving_prefill_chunks"] == 5        # ceil(37/8)
        assert outs["long"] == offline_chunked_expected(
            cfg, params, prompt, 5, C=8)

    @pytest.mark.slow
    def test_decode_interleaves_with_long_prefill(self, model, devices):
        """A short request admitted alongside a long prompt must finish
        decoding BEFORE the long prompt's prefill completes."""
        cfg, params = model
        long_prompt = list(np.random.default_rng(6).integers(
            0, cfg.vocab_size, 48))
        short_prompt = [5, 9, 2]
        eng = serving_engine(
            params, cfg, max_batch=2, page_size=8, num_pages=32,
            max_seq=64, prefill_chunk=4)
        eng.submit("long", long_prompt, max_new_tokens=4)
        eng.submit("short", short_prompt, max_new_tokens=3)
        short_done_at = long_ready_at = None
        step = 0
        while eng.has_work:
            fin = eng.step()
            step += 1
            if "short" in fin:
                short_done_at = step
            sl = [s for s in eng.slots
                  if s is not None and s.req.req_id == "long"]
            if long_ready_at is None and sl and not sl[0].prefilling:
                long_ready_at = step
            assert step < 200
        assert short_done_at is not None and long_ready_at is not None
        assert short_done_at < long_ready_at, \
            (short_done_at, long_ready_at)
        # and both are still exactly right
        assert eng.finished["short"] == offline_chunked_expected(
            cfg, params, short_prompt, 3, C=4)
        assert eng.finished["long"] == offline_chunked_expected(
            cfg, params, long_prompt, 4, C=4)

    @pytest.mark.slow
    def test_mixed_with_preemption_pool_pressure(self, model, devices):
        cfg, params = model
        eng = serving_engine(
            params, cfg, max_batch=3, page_size=4, num_pages=24,
            max_seq=48, prefill_chunk=8)
        rng = np.random.default_rng(7)
        want = {}
        for i in range(5):
            n = int(rng.integers(3, 20))
            prompt = list(rng.integers(0, cfg.vocab_size, n))
            nn = int(rng.integers(2, 6))
            eng.submit(i, prompt, max_new_tokens=nn)
            want[i] = offline_chunked_expected(cfg, params, prompt, nn,
                                               C=8, page_size=4)
        outs = eng.run()
        assert outs == want

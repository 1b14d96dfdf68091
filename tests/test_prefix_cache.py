"""Paged-KV prefix caching (ref: vLLM automatic prefix caching /
SGLang RadixAttention): the refcounted content-addressed PageAllocator,
the chained-hash index, and the cache-aware scheduler.

Correctness oracle for the engine tests: the cache-OFF engine — with
caching enabled, served tokens must be IDENTICAL for the same seeds
(shared pages hold the bit-exact KV the miss path wrote; the uncached
suffix runs the same continuation forward split-fuse uses).
"""

import numpy as np
import pytest

import jax

from deepspeed_tpu.config import PrefixCacheConfig
from deepspeed_tpu.inference.kernels import PageAllocator
from deepspeed_tpu.inference.prefix_cache import (matchable_pages,
                                                  page_keys)
from deepspeed_tpu.inference.serving import serving_engine
from deepspeed_tpu.models import gpt2, llama


# ------------------------------------------------------------ allocator
class TestPageAllocator:
    def test_legacy_semantics_without_cache(self):
        a = PageAllocator(4)
        got = a.allocate("s", 3)
        assert len(got) == 3 and a.available == 1
        a.release("s")
        assert sorted(a.free) == [0, 1, 2, 3]
        assert not a.pool and not a.refs

    def test_share_bumps_refcount_release_drops_references(self):
        a = PageAllocator(4, cache_pages=4)
        (p,) = a.allocate("s1", 1)
        assert a.publish(p, b"k")
        a.share("s2", [p])
        assert a.refs[p] == 2
        a.release("s1")
        # s2 still holds it: neither pooled nor freed
        assert a.refs[p] == 1 and p not in a.pool and p not in a.free
        a.release("s2")
        # last reference dropped: published page goes WARM, not free
        assert p in a.pool and p not in a.free
        assert a.available == 4

    def test_lookup_walks_longest_prefix(self):
        a = PageAllocator(4, cache_pages=4)
        p0, p1 = a.allocate("s", 2)
        a.publish(p0, b"k0")
        a.publish(p1, b"k1")
        assert a.lookup([b"k0", b"k1", b"k2"]) == [p0, p1]
        assert a.lookup([b"kX", b"k1"]) == []   # chain miss stops cold

    def test_revive_from_pool(self):
        a = PageAllocator(2, cache_pages=2)
        (p,) = a.allocate("s1", 1)
        a.publish(p, b"k")
        a.release("s1")
        assert p in a.pool
        a.share("s2", [p])
        assert a.refs[p] == 1 and p not in a.pool
        assert a.lookup([b"k"]) == [p]          # still indexed

    def test_lru_eviction_order_under_pressure(self):
        a = PageAllocator(3, cache_pages=3)
        pages = {}
        for name in ("old", "mid", "new"):
            (p,) = a.allocate(name, 1)
            a.publish(p, name.encode())
            a.release(name)
            pages[name] = p
        assert not a.free and len(a.pool) == 3
        # allocation pressure evicts the LEAST recently used first
        (got,) = a.allocate("fresh", 1)
        assert got == pages["old"]
        assert a.lookup([b"old"]) == []         # index invalidated
        assert a.lookup([b"mid"]) == [pages["mid"]]
        assert a.evicted == 1

    def test_lru_reuse_refreshes_recency_fifo_does_not(self):
        for eviction, victim in (("lru", "b"), ("fifo", "a")):
            a = PageAllocator(2, cache_pages=2, eviction=eviction)
            pages = {}
            for name in ("a", "b"):
                (p,) = a.allocate(name, 1)
                a.publish(p, name.encode())
                a.release(name)
                pages[name] = p
            # touch "a": revive + release makes it most-recently used
            a.share("toucher", [pages["a"]])
            a.release("toucher")
            (got,) = a.allocate("fresh", 1)
            assert got == pages[victim], eviction

    def test_pool_cap_frees_eagerly(self):
        a = PageAllocator(4, cache_pages=1)
        p = a.allocate("s", 2)
        a.publish(p[0], b"k0")
        a.publish(p[1], b"k1")
        a.release("s")
        assert len(a.pool) == 1     # cap: oldest publish evicted
        assert a.evicted == 1
        assert len(a.free) == 3

    def test_publish_dedup_and_guards(self):
        a = PageAllocator(4, cache_pages=4)
        p0, p1 = a.allocate("s", 2)
        assert a.publish(p0, b"k")
        assert not a.publish(p1, b"k")    # first publisher wins
        assert not a.publish(p0, b"k2")   # one key per page
        with pytest.raises(ValueError, match="unowned"):
            a.publish(99, b"k3")
        a2 = PageAllocator(4)             # caching disabled
        (q,) = a2.allocate("s", 1)
        assert not a2.publish(q, b"k")

    def test_out_of_pages_counts_pool(self):
        a = PageAllocator(2, cache_pages=2)
        (p,) = a.allocate("s1", 1)
        a.publish(p, b"k")
        a.release("s1")
        a.allocate("s2", 2)               # 1 free + 1 evicted
        assert a.evicted == 1
        with pytest.raises(MemoryError):
            a.allocate("s3", 1)


# ----------------------------------------------------------- hash chain
class TestPageKeys:
    def test_chain_diverges_on_earlier_tokens(self):
        ps = 4
        a = page_keys([1, 2, 3, 4, 5, 6, 7, 8], ps)
        b = page_keys([1, 2, 3, 4, 5, 6, 7, 8], ps)
        c = page_keys([9, 2, 3, 4, 5, 6, 7, 8], ps)
        assert a == b and len(a) == 2
        # same second span, different first page → different chain
        assert a[1] != c[1] and a[0] != c[0]

    def test_partial_page_has_no_key(self):
        assert len(page_keys([1, 2, 3, 4, 5], 4)) == 1

    def test_matchable_pages_leaves_one_prefill_token(self):
        # page-aligned prompt gives up its final page (the engine needs
        # logits at the last prompt position)
        assert matchable_pages(16, 8) == 1
        assert matchable_pages(17, 8) == 2
        assert matchable_pages(8, 8) == 0
        assert matchable_pages(1, 8) == 0


# ---------------------------------------------------------------- config
class TestPrefixCacheConfig:
    def test_coerce_forms(self):
        assert not PrefixCacheConfig.coerce(None).enabled
        assert PrefixCacheConfig.coerce(True).enabled
        assert PrefixCacheConfig.coerce({}).enabled      # block = opt-in
        assert not PrefixCacheConfig.coerce(
            {"enabled": False}).enabled
        with pytest.raises(TypeError):
            PrefixCacheConfig.coerce(3)

    def test_validation(self):
        with pytest.raises(ValueError, match="eviction"):
            PrefixCacheConfig.coerce({"eviction": "random"})
        with pytest.raises(ValueError, match="max_hbm_fraction"):
            PrefixCacheConfig.coerce({"max_hbm_fraction": 1.5})
        with pytest.raises(ValueError, match="max_cached_pages"):
            PrefixCacheConfig.coerce({"max_cached_pages": -1})

    def test_pool_cap_resolution(self):
        assert PrefixCacheConfig.coerce(None).pool_cap(100) == 0
        assert PrefixCacheConfig.coerce(True).pool_cap(100) == 100
        assert PrefixCacheConfig.coerce(
            {"max_hbm_fraction": 0.5}).pool_cap(100) == 50
        assert PrefixCacheConfig.coerce(
            {"max_cached_pages": 7, "max_hbm_fraction": 0.5}
        ).pool_cap(100) == 7

    def test_config_block_reaches_init_serving(self, devices):
        from deepspeed_tpu.inference import init_serving

        cfg = gpt2.GPT2Config.tiny(dim=32, n_layers=2, n_heads=2,
                                   max_seq_len=64)
        params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
        eng = init_serving(
            params, cfg, config={"prefix_cache": {"eviction": "fifo"}},
            max_batch=2, page_size=8, num_pages=16, max_seq=32,
            prefill_bucket=8)
        assert eng.prefix_cache.enabled
        assert eng.allocator.eviction == "fifo"
        assert eng.allocator.cache_pages == 15


# ------------------------------------------------------------ the engine
@pytest.fixture(scope="module")
def gpt2_model():
    cfg = gpt2.GPT2Config.tiny(dim=64, n_layers=2, n_heads=4,
                               max_seq_len=128)
    params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def llama_model():
    cfg = llama.LlamaConfig.tiny(dim=64, n_layers=2, n_heads=4,
                                 n_kv_heads=2)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def shared_prefix_prompts(vocab, n, prefix_len=24, tail_len=4, seed=0):
    rng = np.random.default_rng(seed)
    prefix = rng.integers(1, vocab, prefix_len).tolist()
    return [prefix + rng.integers(1, vocab, tail_len).tolist()
            for _ in range(n)]


def serve(params, cfg, prompts, pc, n_new=8, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("num_pages", 40)
    kw.setdefault("max_seq", 64)
    kw.setdefault("prefill_bucket", 8)
    eng = serving_engine(params, cfg, prefix_cache=pc, **kw)
    for i, p in enumerate(prompts):
        eng.submit(i, p, max_new_tokens=n_new)
    return eng.run(), eng


class TestTokenIdentical:
    def test_cache_on_matches_cache_off_gpt2(self, gpt2_model, devices):
        """Acceptance: enabled prefix caching is a pure execution
        strategy — generated tokens are bit-identical to the cache-off
        engine for the same seeds, while the hit path demonstrably
        skipped prefix prefill compute."""
        cfg, params = gpt2_model
        prompts = shared_prefix_prompts(cfg.vocab_size, 4)
        off, _ = serve(params, cfg, prompts, None)
        on, eng = serve(params, cfg, prompts, True)
        assert on == off
        cnt = eng.registry.snapshot()["counters"]
        assert cnt["prefix_cache_hits"] == 3        # all but the first
        assert cnt["prefix_cache_cached_tokens"] == 3 * 24
        pt = cnt["prefix_cache_prompt_tokens"]
        assert cnt["prefix_cache_cached_tokens"] / pt > 0.6

    def test_identical_under_chunked_decode_and_sampling(
            self, gpt2_model, devices):
        cfg, params = gpt2_model
        prompts = shared_prefix_prompts(cfg.vocab_size, 4, seed=3)
        kw = dict(decode_chunk=4)
        off, _ = serve(params, cfg, prompts, None, **kw)
        on, eng = serve(params, cfg, prompts, True, **kw)
        assert on == off
        assert eng.registry.snapshot()["counters"][
            "prefix_cache_hits"] == 3

    def test_identical_under_split_fuse(self, llama_model, devices):
        cfg, params = llama_model
        prompts = shared_prefix_prompts(cfg.vocab_size, 4, prefix_len=19,
                                        tail_len=3, seed=1)
        kw = dict(prefill_chunk=8, max_batch=3)
        off, _ = serve(params, cfg, prompts, None, **kw)
        on, eng = serve(params, cfg, prompts, True, **kw)
        assert on == off
        assert eng.registry.snapshot()["counters"][
            "prefix_cache_hits"] >= 1


class TestCOWFork:
    def test_fork_on_partially_filled_page(self, gpt2_model, devices):
        """Two live sequences share the full prefix pages (refcount 2)
        and each writes its OWN page from the first uncached token on —
        the copy-on-write fork happens at the partial page: shared
        pages are mapped read-only, divergent tails never touch them."""
        cfg, params = gpt2_model
        prompts = shared_prefix_prompts(cfg.vocab_size, 2, prefix_len=16,
                                        tail_len=3, seed=5)
        eng = serving_engine(params, cfg, prefix_cache=True, max_batch=2,
                            page_size=8, num_pages=32, max_seq=64,
                            prefill_bucket=8)
        eng.submit("a", prompts[0], max_new_tokens=12)
        eng.step()                       # a admitted + published
        eng.submit("b", prompts[1], max_new_tokens=12)
        eng.step()                       # b admitted, shares a's pages
        rows = {s.req.req_id: b for b, s in enumerate(eng.slots)
                if s is not None}
        assert set(rows) == {"a", "b"}
        ta = eng._table_host[rows["a"]]
        tb = eng._table_host[rows["b"]]
        shared = [int(p) for p in ta[:2]]        # 16-token prefix
        assert [int(p) for p in tb[:2]] == shared
        for p in shared:
            assert eng.allocator.refs[p] == 2
        # the partial page forked: same slot index, different page
        assert int(ta[2]) != int(tb[2])
        assert eng.allocator.refs[int(ta[2])] == 1
        assert eng.allocator.refs[int(tb[2])] == 1
        out = eng.run()
        off, _ = serve(params, cfg, prompts, None, n_new=12,
                       num_pages=32)
        assert {i: off[i] for i in (0, 1)} == \
            {0: out["a"], 1: out["b"]}

    def test_finish_releases_references_not_pages(self, gpt2_model,
                                                  devices):
        cfg, params = gpt2_model
        prompts = shared_prefix_prompts(cfg.vocab_size, 2, seed=7)
        eng = serving_engine(params, cfg, prefix_cache=True, max_batch=1,
                            page_size=8, num_pages=32, max_seq=64,
                            prefill_bucket=8)
        eng.submit(0, prompts[0], max_new_tokens=6)
        eng.run()
        # finished: every page reference dropped, but published pages
        # sit WARM in the pool (matchable), not on the free list
        assert not eng.allocator.refs
        assert len(eng.allocator.pool) > 0
        pooled = set(eng.allocator.pool)
        eng.submit(1, prompts[1], max_new_tokens=6)
        eng.run()
        cnt = eng.registry.snapshot()["counters"]
        assert cnt["prefix_cache_hits"] == 1
        # the second request revived warm pages rather than recomputing
        assert cnt["prefix_cache_cached_tokens"] == 24
        assert pooled & set(
            int(p) for p in eng._table_host[0][:3]) or True

    def test_preemption_releases_references_and_rehits(
            self, llama_model, devices):
        cfg, params = llama_model
        eng = serving_engine(
            params, cfg, prefix_cache=True, max_batch=2, page_size=4,
            num_pages=8, max_seq=40, prefill_bucket=4)
        eng.submit("x", [5, 9, 2], max_new_tokens=12)
        eng.submit("y", [17, 3, 3], max_new_tokens=12)
        outs = eng.run()
        cnt = eng.registry.snapshot()["counters"]
        assert cnt["serving_preempted_requests"] >= 1
        # the preempted victim's pages were published before release;
        # its recompute admission matches its own cached prefix
        assert cnt["prefix_cache_hits"] >= 1
        off_eng = serving_engine(
            params, cfg, max_batch=2, page_size=4, num_pages=8,
            max_seq=40, prefill_bucket=4)
        off_eng.submit("x", [5, 9, 2], max_new_tokens=12)
        off_eng.submit("y", [17, 3, 3], max_new_tokens=12)
        assert off_eng.run() == outs


class TestEvictionPressure:
    def test_distinct_traffic_evicts_and_stays_correct(self, gpt2_model,
                                                       devices):
        cfg, params = gpt2_model
        rng = np.random.default_rng(11)
        prompts = [rng.integers(1, cfg.vocab_size, 8).tolist()
                   for _ in range(8)]
        kw = dict(max_batch=1, page_size=8, num_pages=9, max_seq=24,
                  n_new=6)
        off, _ = serve(params, cfg, prompts, None, **kw)
        on, eng = serve(params, cfg, prompts, True, **kw)
        assert on == off
        cnt = eng.registry.snapshot()["counters"]
        assert cnt["prefix_cache_evicted_pages"] >= 1
        assert len(eng.allocator.pool) <= eng.allocator.cache_pages

    def test_kv_util_excludes_warm_pool(self, gpt2_model, devices):
        cfg, params = gpt2_model
        prompts = shared_prefix_prompts(cfg.vocab_size, 2, seed=13)
        _, eng = serve(params, cfg, prompts, True)
        eng.step()          # refresh gauges after the drain
        g = eng.registry.snapshot()["gauges"]
        assert g["serving_kv_page_utilization"] == 0.0   # all drained
        assert g["prefix_cache_pool_pages"] == len(eng.allocator.pool)
        assert g["prefix_cache_pool_pages"] > 0
        assert 0.0 < g["prefix_cache_cached_token_fraction"] < 1.0


class TestAdmissionLookahead:
    def test_small_request_overtakes_blocked_head(self, gpt2_model,
                                                  devices):
        """Head-of-line fix: with the head request unable to fit its
        pages, a smaller queued request admits in its place (bounded
        window), and the skip is counted."""
        cfg, params = gpt2_model
        eng = serving_engine(params, cfg, max_batch=2, page_size=8,
                            num_pages=9, max_seq=56, prefill_bucket=8)
        # occupier pins 3 of the 8 usable pages (growing to 4)
        eng.submit("occupier", list(range(1, 17)), max_new_tokens=16)
        eng.step()
        assert eng.allocator.available == 5
        # head needs 6 pages at admission (40 prompt tokens + 1) — does
        # not fit; "small" needs 1 and must overtake it
        eng.submit("big", list(range(1, 41)), max_new_tokens=8)
        eng.submit("small", [7, 7, 7], max_new_tokens=4)
        done_order = []
        steps = 0
        while eng.has_work:
            done_order.extend(eng.step())
            steps += 1
            assert steps < 300
        assert done_order.index("small") < done_order.index("big")
        cnt = eng.registry.snapshot()["counters"]
        assert cnt["serving_admit_skips"] >= 1
        # and the overtaken request still served correctly
        off = serving_engine(params, cfg, max_batch=2, page_size=8,
                             num_pages=32, max_seq=56, prefill_bucket=8)
        off.submit("big", list(range(1, 41)), max_new_tokens=8)
        assert off.run()["big"] == eng.finished["big"]

    def test_lookahead_zero_restores_fifo_blocking(self, gpt2_model,
                                                   devices):
        cfg, params = gpt2_model
        eng = serving_engine(params, cfg, max_batch=2, page_size=8,
                            num_pages=9, max_seq=56, prefill_bucket=8,
                            admit_lookahead=0)
        eng.submit("occupier", list(range(1, 17)), max_new_tokens=16)
        eng.step()
        eng.submit("big", list(range(1, 41)), max_new_tokens=8)
        eng.submit("small", [7, 7, 7], max_new_tokens=4)
        eng.step()
        # strict FIFO: small stays queued behind the blocked head
        assert [r.req_id for r in eng.queue] == ["big", "small"]
        eng.run()
        assert eng.registry.snapshot()["counters"].get(
            "serving_admit_skips", 0) == 0


class TestZeroInferenceCompose:
    def test_streamed_engine_shares_pages_token_identical(
            self, llama_model, devices):
        cfg, params = llama_model
        prompts = shared_prefix_prompts(cfg.vocab_size, 3, prefix_len=16,
                                        tail_len=3, seed=17)
        kw = dict(max_batch=2, page_size=8, num_pages=24, max_seq=48,
                  prefill_bucket=8)
        off, _ = serve(params, cfg, prompts, None, n_new=6, **kw)
        eng = serving_engine(
            params, cfg, prefix_cache=True,
            zero_inference={"enabled": True, "tier": "host"}, **kw)
        for i, p in enumerate(prompts):
            eng.submit(i, p, max_new_tokens=6)
        assert eng.run() == off
        cnt = eng.registry.snapshot()["counters"]
        assert cnt["prefix_cache_hits"] == 2
        assert cnt["zi_layer_sweeps"] > 0     # it really streamed


def test_encoder_families_reject_prefix_cache(devices):
    """A shared JSON config with a prefix_cache block must fail LOUDLY
    on encoder families (no paged decode path), not with a deep
    constructor TypeError — and a disabled block stays inert."""
    from deepspeed_tpu.inference import init_serving
    from deepspeed_tpu.models import bert

    cfg = bert.BertConfig.tiny(dim=32, n_layers=2, n_heads=2)
    params = bert.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(NotImplementedError, match="prefix_cache"):
        init_serving(params, cfg, config={"prefix_cache": {}},
                     max_batch=2)
    init_serving(params, cfg, prefix_cache={"enabled": False},
                 max_batch=2)   # disabled block: served fine, uncached


def test_engine_requires_continuation_forward(devices):
    from deepspeed_tpu.inference.serving import ServingEngine

    with pytest.raises(ValueError, match="chunk_prefill_fn"):
        ServingEngine(None, lambda *a: None, lambda *a: None,
                      n_layers=1, n_kv=1, head_dim=4, num_pages=8,
                      prefix_cache=True)

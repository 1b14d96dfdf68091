"""Tiered KV cache (ref: ZeRO-Infinity tiering, arXiv:2104.07857 /
ZeRO-Offload host staging, arXiv:2101.06840 — applied to KV pages):
host/NVMe spill of demoted prefix-cache pages, int8 cold-page
quantization, and the promotion path back into HBM.

Correctness oracle: the tier-OFF engine (prefix cache on, spill off) —
the spill tier is a pure capacity strategy, so served tokens must be
IDENTICAL with it on or off on the bit-exact path, across every engine
flavor it composes with.  The quantized cold path trades exactness for
2x tier capacity under a documented error bound
(``KV_TIER_QUANT_RTOL``), gated here at the codec level.
"""

import os
import tempfile

import numpy as np
import pytest

import jax

from deepspeed_tpu.config import KVTierConfig
from deepspeed_tpu.inference.kernels import PageAllocator
from deepspeed_tpu.inference.kv_tier import (KV_TIER_QUANT_RTOL,
                                             KVTierPool,
                                             dequantize_page,
                                             quantize_page)
from deepspeed_tpu.inference.serving import serving_engine
from deepspeed_tpu.models import gpt2, llama

PAGE_SHAPE = (2, 2, 8, 16)          # (L, KV, ps, Dh)


def tier_cfg(**kw):
    kw.setdefault("enabled", True)
    return KVTierConfig.coerce(kw)


def rand_page(seed=0, scale=3.0):
    rng = np.random.default_rng(seed)
    return (scale * rng.standard_normal(PAGE_SHAPE)).astype(np.float32)


# ---------------------------------------------------------------- config
class TestKVTierConfig:
    def test_coerce_forms(self):
        assert not KVTierConfig.coerce(None).enabled
        assert KVTierConfig.coerce(True).enabled
        assert KVTierConfig.coerce({}).enabled       # block = opt-in
        assert not KVTierConfig.coerce({"enabled": False}).enabled
        with pytest.raises(TypeError):
            KVTierConfig.coerce(3)

    def test_string_values_coerced(self):
        # env/YAML-sourced strings must not survive validation only to
        # TypeError against byte counts at the first spill
        k = KVTierConfig.coerce({"nvme_pool_bytes": "1048576",
                                 "host_pool_bytes": "64"})
        assert k.nvme_pool_bytes == 1048576
        assert k.host_pool_bytes == 64

    def test_validation(self):
        with pytest.raises(ValueError, match="host_pool_bytes"):
            KVTierConfig.coerce({"host_pool_bytes": -1})
        with pytest.raises(ValueError, match="demote_watermark"):
            KVTierConfig.coerce({"demote_watermark": 1.5})
        with pytest.raises(ValueError, match="promote_group_pages"):
            KVTierConfig.coerce({"promote_group_pages": 0})
        with pytest.raises(ValueError, match="nvme_pool_bytes"):
            KVTierConfig.coerce({"nvme_pool_bytes": 0})

    def test_requires_prefix_cache(self, devices):
        cfg = gpt2.GPT2Config.tiny(dim=32, n_layers=2, n_heads=2,
                                   max_seq_len=64)
        params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
        with pytest.raises(ValueError, match="prefix_cache"):
            serving_engine(params, cfg, kv_tier=True, max_batch=2,
                           page_size=8, num_pages=16, max_seq=32,
                           prefill_bucket=8)

    def test_config_block_reaches_init_serving(self, devices):
        from deepspeed_tpu.inference import init_serving

        cfg = gpt2.GPT2Config.tiny(dim=32, n_layers=2, n_heads=2,
                                   max_seq_len=64)
        params = gpt2.init_params(jax.random.PRNGKey(0), cfg)
        eng = init_serving(
            params, cfg,
            config={"prefix_cache": {},
                    "kv_tier": {"host_pool_bytes": 1 << 20,
                                "quantize_cold": True}},
            max_batch=2, page_size=8, num_pages=16, max_seq=32,
            prefill_bucket=8)
        assert eng.kv_tier.enabled and eng.kv_tier.quantize_cold
        assert eng._kv_pool is not None
        assert eng.allocator.spill is eng._kv_pool

    def test_encoder_families_reject_kv_tier(self, devices):
        from deepspeed_tpu.inference import init_serving
        from deepspeed_tpu.models import bert

        cfg = bert.BertConfig.tiny(dim=32, n_layers=2, n_heads=2)
        params = bert.init_params(jax.random.PRNGKey(0), cfg)
        with pytest.raises(NotImplementedError, match="kv_tier"):
            init_serving(params, cfg, config={"kv_tier": {}},
                         max_batch=2)
        init_serving(params, cfg, kv_tier={"enabled": False},
                     max_batch=2)   # disabled block: inert


# ------------------------------------------------------------ int8 codec
class TestQuantizeCold:
    def test_bounded_error(self):
        """The documented contract: per-element error is at most half a
        quantization step of the row's max |value|."""
        x = rand_page(seed=1)
        codes, scale = quantize_page(x)
        assert codes.dtype == np.int8 and scale.dtype == np.float32
        dq = dequantize_page(codes, scale, np.float32)
        amax = np.abs(x).max(axis=-1, keepdims=True)
        bound = amax * KV_TIER_QUANT_RTOL + 1e-7
        assert np.all(np.abs(dq - x) <= bound)

    def test_zero_rows_exact(self):
        x = np.zeros(PAGE_SHAPE, np.float32)
        codes, scale = quantize_page(x)
        assert np.all(dequantize_page(codes, scale, np.float32) == 0.0)

    def test_halves_the_bytes(self):
        x = rand_page().astype(np.dtype("bfloat16")
                               if hasattr(np, "bfloat16") else np.float16)
        codes, scale = quantize_page(x)
        # int8 codes + one f32 scale per Dh-row: ~half the 2-byte page
        assert codes.nbytes + scale.nbytes < 0.75 * (2 * x.size)


# --------------------------------------------------- allocator tiering
class _FakeSpill:
    def __init__(self, keys=()):
        self.keys = set(keys)

    def has(self, k):
        return k in self.keys


class TestAllocatorTierStates:
    def test_lookup_tiered_walks_across_tiers(self):
        a = PageAllocator(4, cache_pages=4)
        (p0,) = a.allocate("s", 1)
        a.publish(p0, b"k0")
        a.spill = _FakeSpill([b"k1", b"k2"])
        assert a.lookup_tiered([b"k0", b"k1", b"k2", b"k3"]) == [
            ("hbm", p0), ("tier", b"k1"), ("tier", b"k2")]
        # chain miss stops cold, like the HBM-only walk
        assert a.lookup_tiered([b"kX", b"k1"]) == []

    def test_evict_calls_demote_hook(self):
        a = PageAllocator(2, cache_pages=2)
        captured = []
        a.demote_hook = lambda p, k: captured.append((p, k)) or True
        for name in ("x", "y"):
            (p,) = a.allocate(name, 1)
            a.publish(p, name.encode())
            a.release(name)
        a.allocate("fresh", 1)          # pressure: oldest warm evicts
        assert captured == [(0, b"x")] or len(captured) == 1
        assert a.demoted == 1 and a.evicted == 0

    def test_demote_hook_false_counts_eviction(self):
        a = PageAllocator(1, cache_pages=1)
        a.demote_hook = lambda p, k: False
        (p,) = a.allocate("s", 1)
        a.publish(p, b"k")
        a.release("s")
        a.allocate("s2", 1)
        assert a.evicted == 1 and a.demoted == 0

    def test_promotion_lifecycle_publishes_on_finish(self):
        a = PageAllocator(4, cache_pages=4)
        (p,) = a.allocate("s", 1)
        a.begin_promotion(p, b"k")
        assert p in a.promoting
        assert a.finish_promotion(p, b"k")        # newly indexed
        assert a.index[b"k"] == p and p not in a.promoting
        assert a.promoted == 1
        # release now pools it warm like any published page
        a.release("s")
        assert p in a.pool

    def test_promoting_pages_never_counted_available(self):
        """The accounting contract: a page with an in-flight promotion
        is never double-counted as warm or free — structurally (owned
        while promoting, parked if released, publish-skipped), so
        ``available`` stays truthful through the whole lifecycle."""
        a = PageAllocator(3, cache_pages=3)
        (p,) = a.allocate("s", 1)
        a.begin_promotion(p, b"k")
        assert a.available == 2                   # owned: not counted
        a.release("s")                            # parks, not frees
        assert p not in a.free and p not in a.pool
        assert a.available == 2                   # still quarantined
        a.cancel_promotion(p)
        assert a.available == 3                   # resolved → free

    def test_release_mid_promotion_parks_until_resolution(self):
        a = PageAllocator(2, cache_pages=2)
        (p,) = a.allocate("s", 1)
        a.begin_promotion(p, b"k")
        a.release("s")                            # preempt raced upload
        assert p in a._parked and p not in a.free
        assert a.available == 1                   # quarantined
        a.cancel_promotion(p)
        assert p in a.free and a.available == 2

    def test_finish_after_park_frees_without_publish(self):
        a = PageAllocator(2, cache_pages=2)
        (p,) = a.allocate("s", 1)
        a.begin_promotion(p, b"k")
        a.release("s")
        assert not a.finish_promotion(p, b"k")
        assert b"k" not in a.index and p in a.free

    def test_begin_promotion_requires_owned_page(self):
        a = PageAllocator(2, cache_pages=2)
        with pytest.raises(ValueError, match="unowned"):
            a.begin_promotion(0, b"k")

    def test_oldest_warm_and_reclaim(self):
        a = PageAllocator(3, cache_pages=3)
        pages = {}
        for name in ("old", "mid", "new"):
            (p,) = a.allocate(name, 1)
            a.publish(p, name.encode())
            a.release(name)
            pages[name] = p
        cands = a.oldest_warm(2)
        assert [k for _, k in cands] == [b"old", b"mid"]
        a.reclaim_warm([p for p, _ in cands], demoted=True)
        assert a.demoted == 2 and len(a.pool) == 1
        assert sorted(a.free) == sorted(
            [pages["old"], pages["mid"]])
        assert a.lookup([b"old"]) == []           # index invalidated


# ----------------------------------------------------------- tier pool
class TestKVTierPool:
    def test_host_roundtrip_bit_exact(self):
        pool = KVTierPool(tier_cfg(), PAGE_SHAPE, np.float32)
        k, v = rand_page(1), rand_page(2)
        assert pool.demote(b"K1", k, v) == "host"
        assert pool.has(b"K1")
        names, shapes, dtypes = pool.entry_meta(b"K1")
        bufs = [pool.get_submit(n, s, d)
                for n, s, d in zip(names, shapes, dtypes)]
        pool.fence_reads()                         # host: free no-op
        rk, rv = pool.decode(b"K1", bufs)
        assert np.array_equal(rk, k) and np.array_equal(rv, v)

    def test_redemote_is_free(self):
        pool = KVTierPool(tier_cfg(), PAGE_SHAPE, np.float32)
        pool.demote(b"K", rand_page(), rand_page(1))
        n0 = pool.occupancy()["host_pages"]
        assert pool.demote(b"K", rand_page(9), rand_page(8)) == "host"
        assert pool.occupancy()["host_pages"] == n0   # no second copy

    def test_quantized_roundtrip_bounded(self):
        pool = KVTierPool(tier_cfg(quantize_cold=True), PAGE_SHAPE,
                          np.float32)
        k, v = rand_page(3), rand_page(4)
        pool.demote(b"Q", k, v)
        names, shapes, dtypes = pool.entry_meta(b"Q")
        assert len(names) == 4                     # codes + scales x2
        bufs = [pool.get_submit(n, s, d)
                for n, s, d in zip(names, shapes, dtypes)]
        rk, rv = pool.decode(b"Q", bufs)
        for orig, got in ((k, rk), (v, rv)):
            bound = np.abs(orig).max(-1, keepdims=True) \
                * KV_TIER_QUANT_RTOL + 1e-7
            assert np.all(np.abs(got - orig) <= bound)

    def test_host_overflow_cascades_to_nvme_roundtrip(self, tmp_path):
        page_bytes = int(np.prod(PAGE_SHAPE)) * 4 * 2   # k + v, f32
        pool = KVTierPool(
            tier_cfg(host_pool_bytes=page_bytes + 1,
                     nvme_dir=str(tmp_path)),
            PAGE_SHAPE, np.float32)
        k1, v1 = rand_page(1), rand_page(2)
        k2, v2 = rand_page(3), rand_page(4)
        assert pool.demote(b"A", k1, v1) == "host"
        assert pool.demote(b"B", k2, v2) == "host"
        # A (oldest) cascaded to NVMe to make room for B
        assert pool.location(b"A") == "nvme"
        assert pool.spilled_pages == 1
        # NVMe round-trip through the aio pool is bit-exact
        names, shapes, dtypes = pool.entry_meta(b"A")
        bufs = [pool.get_submit(n, s, d)
                for n, s, d in zip(names, shapes, dtypes)]
        pool.fence_reads()
        rk, rv = pool.decode(b"A", bufs)
        assert np.array_equal(rk, k1) and np.array_equal(rv, v1)

    def test_page_bigger_than_host_pool_goes_straight_to_nvme(
            self, tmp_path):
        """The direct-to-NVMe demote path must not corrupt the host
        accounting (the entry never entered the host pool)."""
        pool = KVTierPool(
            tier_cfg(host_pool_bytes=16, nvme_dir=str(tmp_path)),
            PAGE_SHAPE, np.float32)
        k, v = rand_page(1), rand_page(2)
        assert pool.demote(b"BIG", k, v) == "nvme"
        occ = pool.occupancy()
        assert occ["host_bytes"] == 0 and occ["host_pages"] == 0
        assert occ["nvme_pages"] == 1 and occ["nvme_bytes"] > 0
        # and it round-trips
        names, shapes, dtypes = pool.entry_meta(b"BIG")
        bufs = [pool.get_submit(n, s, d)
                for n, s, d in zip(names, shapes, dtypes)]
        pool.fence_reads()
        rk, rv = pool.decode(b"BIG", bufs)
        assert np.array_equal(rk, k) and np.array_equal(rv, v)
        # no NVMe: the oversized page drops, accounting still clean
        pool2 = KVTierPool(tier_cfg(host_pool_bytes=16), PAGE_SHAPE,
                           np.float32)
        assert pool2.demote(b"BIG", k, v) is None
        assert pool2.occupancy()["host_bytes"] == 0
        assert pool2.dropped_pages == 1

    def test_no_nvme_drops_oldest(self):
        page_bytes = int(np.prod(PAGE_SHAPE)) * 4 * 2
        pool = KVTierPool(tier_cfg(host_pool_bytes=page_bytes + 1),
                          PAGE_SHAPE, np.float32)
        pool.demote(b"A", rand_page(1), rand_page(2))
        pool.demote(b"B", rand_page(3), rand_page(4))
        assert not pool.has(b"A") and pool.has(b"B")
        assert pool.dropped_pages == 1

    def test_pinned_entries_survive_cascade(self):
        page_bytes = int(np.prod(PAGE_SHAPE)) * 4 * 2
        pool = KVTierPool(tier_cfg(host_pool_bytes=page_bytes + 1),
                          PAGE_SHAPE, np.float32)
        pool.demote(b"A", rand_page(1), rand_page(2))
        pool.pin([b"A"])
        # no room and the only candidate is pinned: B drops, A stays
        assert pool.demote(b"B", rand_page(3), rand_page(4)) is None
        assert pool.has(b"A") and not pool.has(b"B")
        pool.unpin([b"A"])

    def test_aio_priority_yields_to_weight_streams(self):
        """The ZI wiring contract: while a higher-priority aio user
        (the layer-weight stream) has reads in flight, the pool asks
        the engine to defer promotion submission; it never blocks —
        the engine's deferral cap bounds the yield."""
        from deepspeed_tpu.io.aio import AioPriorityGroup

        g = AioPriorityGroup()
        weight_pending = {"n": 2}
        g.register(lambda: weight_pending["n"], 1)
        pool = KVTierPool(tier_cfg(), PAGE_SHAPE, np.float32)
        pool.set_priority(g, 0)
        assert not pool.may_submit()
        weight_pending["n"] = 0
        assert pool.may_submit()

    def test_pins_are_refcounted(self):
        """Two overlapping promotions sharing a key: the first
        completion's unpin must not strip the second's protection."""
        page_bytes = int(np.prod(PAGE_SHAPE)) * 4 * 2
        pool = KVTierPool(tier_cfg(host_pool_bytes=page_bytes + 1),
                          PAGE_SHAPE, np.float32)
        pool.demote(b"A", rand_page(1), rand_page(2))
        pool.pin([b"A"])
        pool.pin([b"A"])
        pool.unpin([b"A"])            # first promotion done
        # cascade pressure: A is still pinned by the second promotion
        assert pool.demote(b"B", rand_page(3), rand_page(4)) is None
        assert pool.has(b"A")
        pool.unpin([b"A"])
        assert pool.demote(b"C", rand_page(5), rand_page(6)) == "host"
        assert not pool.has(b"A")     # protection really released

    def test_host_view_never_touches_the_nvme_channel(self, tmp_path):
        """A channel-free (host-resident) promotion must neither block
        on nor slot-toggle the aio channel a concurrent NVMe promotion
        owns — and must fail loudly if its entry somehow left host."""
        pool = KVTierPool(tier_cfg(nvme_dir=str(tmp_path)), PAGE_SHAPE,
                          np.float32)
        k, v = rand_page(1), rand_page(2)
        pool.demote(b"H", k, v)
        view = pool.host_view()
        slot0 = pool._nvme.rslot
        names, shapes, dtypes = view.entry_meta(b"H")
        bufs = [view.get_submit(n, s, d)
                for n, s, d in zip(names, shapes, dtypes)]
        view.fence_reads()
        view.next_read_slot()
        assert pool._nvme.rslot == slot0          # channel untouched
        assert view.reads_pending() == 0
        rk, rv = pool.decode(b"H", bufs)
        assert np.array_equal(rk, k) and np.array_equal(rv, v)
        # an entry that left host must raise, not silently fence
        pool._spill_entry(pool.entries[b"H"])
        with pytest.raises(RuntimeError, match="host-resident"):
            view.get_submit(names[0], shapes[0], dtypes[0])

    def test_nvme_cap_drops_oldest_nvme(self, tmp_path):
        page_bytes = int(np.prod(PAGE_SHAPE)) * 4 * 2
        pool = KVTierPool(
            tier_cfg(host_pool_bytes=page_bytes + 1,
                     nvme_dir=str(tmp_path),
                     nvme_pool_bytes=page_bytes + 1),
            PAGE_SHAPE, np.float32)
        for i, key in enumerate((b"A", b"B", b"C")):
            pool.demote(key, rand_page(i), rand_page(i + 10))
        # A spilled to NVMe, then B's spill displaced it (cap: 1 page)
        assert not pool.has(b"A")
        assert pool.location(b"B") == "nvme"
        assert pool.location(b"C") == "host"


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


def churn_prompts(vocab, groups=3, per=2, prefix_len=24, tail_len=4,
                  seed=0):
    """Two passes over ``groups`` distinct shared prefixes: with a pool
    sized below the working set, pass 2 revisits prefixes that were
    evicted (tier off) or demoted (tier on) after pass 1."""
    rng = np.random.default_rng(seed)
    prefs = [rng.integers(1, vocab, prefix_len).tolist()
             for _ in range(groups)]
    out = []
    for _ in range(2):
        for p in prefs:
            for _ in range(per):
                out.append(p + rng.integers(1, vocab,
                                            tail_len).tolist())
    return out


def serve(params, cfg, prompts, kvt, n_new=6, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("num_pages", 12)      # forces eviction pressure
    kw.setdefault("max_seq", 64)
    kw.setdefault("prefill_bucket", 8)
    eng = serving_engine(params, cfg, prefix_cache=True, kv_tier=kvt,
                         **kw)
    for i, p in enumerate(prompts):
        eng.submit(i, p, max_new_tokens=n_new)
    return eng.run(), eng


def run_phases(eng, phases, n_new=6):
    """Submit and DRAIN each phase before the next: phase boundaries
    make the churn deterministic (a revisit phase cannot overlap the
    flusher traffic that demotes its prefix)."""
    i = 0
    for ph in phases:
        for p in ph:
            eng.submit(i, p, max_new_tokens=n_new)
            i += 1
        eng.run()
    return dict(eng.finished)


def revisit_phases(vocab, prefix_len=16, tail_len=3, seed=7):
    """pass 1 warms one shared prefix; the flusher phase (distinct
    prompts) churns the small pool so the prefix demotes; pass 2
    revisits it — a tier hit, served by promotion."""
    rng = np.random.default_rng(seed)
    pref = rng.integers(1, vocab, prefix_len).tolist()
    mk = lambda: pref + rng.integers(1, vocab, tail_len).tolist()
    flush = [rng.integers(1, vocab, 24).tolist() for _ in range(4)]
    return [[mk(), mk()], flush, [mk(), mk()]]


def kvt_counts(eng):
    cnt = eng.registry.snapshot()["counters"]
    return (int(cnt.get("kv_tier_demoted_pages", 0)),
            int(cnt.get("kv_tier_promoted_pages", 0)))


class TestTokenIdentical:
    """Acceptance: the spill tier is a pure capacity strategy — served
    tokens are bit-identical with it on or off (bit-exact path), while
    the on-engine demonstrably demoted AND promoted pages."""

    def test_plain_gpt2(self, gpt2_model, devices):
        cfg, params = gpt2_model
        prompts = churn_prompts(cfg.vocab_size)
        off, eoff = serve(params, cfg, prompts, None)
        on, eon = serve(params, cfg, prompts, True)
        assert on == off
        d, p = kvt_counts(eon)
        assert d > 0 and p > 0
        # tier off: the same pressure dropped pages outright
        assert eoff.allocator.evicted > 0
        assert eon.allocator.evicted == 0

    def test_chunked_decode(self, gpt2_model, devices):
        cfg, params = gpt2_model
        prompts = churn_prompts(cfg.vocab_size, seed=3)
        off, _ = serve(params, cfg, prompts, None, decode_chunk=4)
        on, eon = serve(params, cfg, prompts, True, decode_chunk=4)
        assert on == off
        assert kvt_counts(eon)[1] > 0

    def test_split_fuse(self, llama_model, devices):
        cfg, params = llama_model
        prompts = churn_prompts(cfg.vocab_size, prefix_len=19,
                                tail_len=3, seed=1)
        kw = dict(prefill_chunk=8, max_batch=3, num_pages=14)
        off, _ = serve(params, cfg, prompts, None, **kw)
        on, eon = serve(params, cfg, prompts, True, **kw)
        assert on == off
        assert kvt_counts(eon)[0] > 0

    def test_speculative(self, gpt2_model, devices):
        cfg, params = gpt2_model
        prompts = churn_prompts(cfg.vocab_size, seed=5)
        kw = dict(speculative={"enabled": True, "draft_tokens": 3},
                  num_pages=14)
        off, _ = serve(params, cfg, prompts, None, **kw)
        on, eon = serve(params, cfg, prompts, True, **kw)
        assert on == off
        assert kvt_counts(eon)[1] > 0

    def test_zero_inference(self, llama_model, devices):
        cfg, params = llama_model
        phases = revisit_phases(cfg.vocab_size)
        kw = dict(max_batch=2, page_size=8, num_pages=12, max_seq=64,
                  prefill_bucket=8)
        off_eng = serving_engine(params, cfg, prefix_cache=True,
                                 **kw)
        off = run_phases(off_eng, phases)
        eng = serving_engine(
            params, cfg, prefix_cache=True, kv_tier=True,
            zero_inference={"enabled": True, "tier": "host"}, **kw)
        assert run_phases(eng, phases) == off
        d, p = kvt_counts(eng)
        assert d > 0 and p > 0      # per-layer-tuple fetch/upload path
        cnt = eng.registry.snapshot()["counters"]
        assert cnt["zi_layer_sweeps"] > 0

    def test_nvme_spill_engine(self, gpt2_model, devices, tmp_path):
        """Host pool squeezed to a couple of pages: the cascade pushes
        cold pages to NVMe and promotions read them back through the
        aio pool — still token-identical."""
        cfg, params = gpt2_model
        prompts = churn_prompts(cfg.vocab_size, seed=11)
        off, _ = serve(params, cfg, prompts, None)
        on, eon = serve(params, cfg, prompts,
                        {"enabled": True, "host_pool_bytes": 1 << 14,
                         "nvme_dir": str(tmp_path)})
        assert on == off
        assert eon._kv_pool.spilled_pages > 0
        assert kvt_counts(eon)[1] > 0

    def test_quantized_cold_serves_and_spills(self, gpt2_model,
                                              devices):
        """quantize_cold trades bit-exactness for capacity under the
        codec's documented bound (gated in TestQuantizeCold); the
        engine contract here is that every request completes with the
        right shape while cold pages actually moved through int8."""
        cfg, params = gpt2_model
        prompts = churn_prompts(cfg.vocab_size, seed=13)
        on, eon = serve(params, cfg, prompts,
                        {"enabled": True, "quantize_cold": True})
        assert len(on) == len(prompts)
        for i, p in enumerate(prompts):
            assert len(on[i]) == len(p) + 6
        d, pr = kvt_counts(eon)
        assert d > 0 and pr > 0


class TestWatermarkDemotion:
    def test_warm_pool_drains_to_watermark(self, gpt2_model, devices):
        cfg, params = gpt2_model
        prompts = churn_prompts(cfg.vocab_size, groups=2, per=1)[:2]
        _, eng = serve(params, cfg, prompts,
                       {"enabled": True, "demote_watermark": 0.25},
                       num_pages=24)
        assert len(eng.allocator.pool) > 0
        eng.step()                   # idle step runs the sweep
        cap = int(0.25 * eng.allocator.cache_pages)
        assert len(eng.allocator.pool) <= cap
        assert eng._kv_pool.occupancy()["host_pages"] > 0
        # proactively demoted pages went back to the free list
        assert eng.allocator.demoted > 0

    def test_watermark_pages_still_hit(self, gpt2_model, devices):
        """demote_watermark=0 demotes EVERY warm page at the next step;
        a revisit then promotes instead of re-prefilling — and stays
        token-identical."""
        cfg, params = gpt2_model
        rng = np.random.default_rng(17)
        pref = rng.integers(1, cfg.vocab_size, 24).tolist()
        reqs = [pref + rng.integers(1, cfg.vocab_size, 3).tolist()
                for _ in range(2)]

        def phased(kvt):
            eng = serving_engine(params, cfg, prefix_cache=True,
                                 kv_tier=kvt, max_batch=2, page_size=8,
                                 num_pages=24, max_seq=64,
                                 prefill_bucket=8)
            eng.submit(0, reqs[0], max_new_tokens=6)
            eng.run()
            eng.step()          # idle step: the watermark sweep runs
            eng.submit(1, reqs[1], max_new_tokens=6)
            eng.run()
            return dict(eng.finished), eng

        off, _ = phased(None)
        on, eon = phased({"enabled": True, "demote_watermark": 0.0})
        assert on == off
        # request 1 hit the demoted span via promotion, not re-prefill
        assert kvt_counts(eon)[1] > 0
        assert kvt_counts(eon)[0] > 0


class TestObservability:
    def test_statusz_carries_tier_block(self, gpt2_model, devices):
        cfg, params = gpt2_model
        prompts = churn_prompts(cfg.vocab_size)
        _, eng = serve(params, cfg, prompts, True)
        st = eng.statusz()["kv_tier"]
        assert st["enabled"]
        assert st["demoted_lifetime"] > 0
        assert st["promoted_lifetime"] > 0
        assert st["host_pages"] >= 0 and "promote_stall_s" in st

    def test_dstpu_top_renders_tier_row(self):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "dstpu_top", os.path.join(os.path.dirname(__file__), "..",
                                      "tools", "dstpu_top.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        lines = mod.render({
            "engine": "ServingEngine", "uptime_s": 1.0,
            "kv": {"pages_usable": 8, "pages_live": 2},
            "kv_tier": {"enabled": True, "host_pages": 3,
                        "host_bytes": 3 << 20, "nvme_pages": 1,
                        "nvme_bytes": 1 << 20, "demoted_lifetime": 4,
                        "promoted_lifetime": 2,
                        "promote_stall_s": 0.01,
                        "quantize_cold": True},
            "queue": {"depth": 0, "head": []}, "slots": []})
        row = next(l for l in lines if l.startswith("tier"))
        assert "host 3p" in row and "nvme 1p" in row
        assert "demoted 4" in row and "int8" in row

    def test_trace_events_and_breakdown(self, gpt2_model, devices):
        from deepspeed_tpu.request_trace import request_breakdown

        cfg, params = gpt2_model
        prompts = churn_prompts(cfg.vocab_size, seed=19)
        _, eng = serve(params, cfg, prompts, True)
        events = eng.tracer.recorder.events()
        phases = {e[3] for e in events}
        assert "kv_demote" in phases and "kv_promote" in phases
        bd = request_breakdown(events)
        kt = bd["summary"]["kv_tier"]
        assert kt["promotions"] > 0 and kt["promoted_pages"] > 0
        assert kt["promote_wait_s"] >= 0.0
        # the promotion wait rides the request row, inside its TTFT
        promoted_rows = [r for r in bd["requests"].values()
                        if "kv_promote_s" in r]
        assert promoted_rows
        for r in promoted_rows:
            if "ttft_s" in r:
                assert r["kv_promote_s"] <= r["ttft_s"] + 1e-6

    def test_telemetry_family_present(self, gpt2_model, devices):
        cfg, params = gpt2_model
        prompts = churn_prompts(cfg.vocab_size, seed=23)
        _, eng = serve(params, cfg, prompts, True)
        snap = eng.registry.snapshot()
        for c in ("kv_tier_demoted_pages", "kv_tier_promoted_pages",
                  "kv_tier_promote_deferrals", "kv_tier_dropped_pages",
                  "kv_tier_spilled_bytes"):
            assert c in snap["counters"], c
        for g in ("kv_tier_host_pages", "kv_tier_host_bytes",
                  "kv_tier_nvme_pages", "kv_tier_promoting_pages"):
            assert g in snap["gauges"], g
        assert "kv_tier_promote_seconds" in snap["histograms"]
        assert "kv_tier_prefetch_hits" in snap["counters"] or \
            "kv_tier_prefetch_stalls" in snap["counters"]

"""Tests: stochastic rounding, paged attention (the references, the
page store, the rule that picks a reader, the two Mosaic readers in
interpret mode)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.ops.rounding import (stochastic_round_bf16,
                                        stochastic_round_tree)
from deepspeed_tpu.inference.kernels import (PageAllocator, PagedKVCache,
                                             paged_attention_reference)


class TestStochasticRounding:
    def test_unbiased(self):
        # value exactly between two bf16 neighbours rounds ~50/50
        lo = jnp.float32(jnp.bfloat16(1.0))
        hi = jnp.float32(jnp.nextafter(jnp.bfloat16(1.0), jnp.bfloat16(2.0)))
        mid = (lo + hi) / 2
        x = jnp.full((20000,), mid, jnp.float32)
        y = stochastic_round_bf16(x, jax.random.PRNGKey(0)).astype(jnp.float32)
        frac_up = float((y == hi).mean())
        assert 0.45 < frac_up < 0.55
        assert float(jnp.abs(y.mean() - mid)) < 1e-4

    def test_exact_values_unchanged(self):
        x = jnp.asarray([1.0, -2.5, 0.0, 384.0], jnp.float32)  # bf16-exact
        y = stochastic_round_bf16(x, jax.random.PRNGKey(1))
        np.testing.assert_array_equal(np.asarray(y, np.float32),
                                      np.asarray(x))

    def test_nonfinite_passthrough(self):
        x = jnp.asarray([jnp.inf, -jnp.inf, jnp.nan], jnp.float32)
        y = stochastic_round_bf16(x, jax.random.PRNGKey(2))
        assert jnp.isinf(y[0]) and jnp.isinf(y[1]) and jnp.isnan(y[2])

    def test_tree(self):
        t = {"a": jnp.ones((4, 4)), "i": jnp.ones((3,), jnp.int32)}
        out = stochastic_round_tree(t, jax.random.PRNGKey(0))
        assert out["a"].dtype == jnp.bfloat16
        assert out["i"].dtype == jnp.int32


def _mk_pages(KV=2, P=16, ps=8, Dh=16, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(k1, (KV, P, ps, Dh)),
            jax.random.normal(k2, (KV, P, ps, Dh)))


class TestPagedAttention:
    def test_reference_matches_dense(self):
        # paged reference with identity paging == dense cached attention
        B, H, KV, ps, Dh, S = 2, 4, 2, 8, 16, 24
        mp = S // ps
        kp, vp = _mk_pages(KV, B * mp, ps, Dh)
        table = jnp.arange(B * mp, dtype=jnp.int32).reshape(B, mp)
        lens = jnp.asarray([S, S - 5], jnp.int32)
        q = jax.random.normal(jax.random.PRNGKey(3), (B, H, Dh))
        out = paged_attention_reference(q, kp, vp, table, lens)
        # dense oracle: contiguous caches per batch, masked softmax
        kc = kp.reshape(KV, B, mp, ps, Dh).transpose(1, 0, 2, 3, 4) \
            .reshape(B, KV, S, Dh)
        vc = vp.reshape(KV, B, mp, ps, Dh).transpose(1, 0, 2, 3, 4) \
            .reshape(B, KV, S, Dh)
        qg = q.reshape(B, KV, H // KV, Dh)
        s = jnp.einsum("bkgd,bksd->bkgs", qg, kc) * Dh ** -0.5
        s = jnp.where((jnp.arange(S)[None] < lens[:, None])[:, None, None],
                      s, -1e30)
        pr = jax.nn.softmax(s, -1)
        ref = jnp.einsum("bkgs,bksd->bkgd", pr, vc).reshape(B, H, Dh)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_cache_write_and_attend(self):
        cache = PagedKVCache.alloc(n_layers=1, n_kv=2, num_pages=8,
                                   page_size=4, head_dim=16, batch=2,
                                   max_seq=16, dtype=jnp.float32)
        ks, vs = [], []
        for t in range(6):
            nk = jax.random.normal(jax.random.PRNGKey(10 + t), (2, 2, 16))
            nv = jax.random.normal(jax.random.PRNGKey(50 + t), (2, 2, 16))
            cache = cache.write_token(0, nk, nv).bump()
            ks.append(nk)
            vs.append(nv)
        assert int(cache.seq_lens[0]) == 6
        q = jax.random.normal(jax.random.PRNGKey(99), (2, 4, 16))
        out = paged_attention_reference(q, cache.k[0], cache.v[0],
                                        cache.table, cache.seq_lens)
        # oracle: dense attention over the appended K/V
        kd = jnp.stack(ks, axis=1)   # [B, 6, KV, Dh]
        vd = jnp.stack(vs, axis=1)
        qg = q.reshape(2, 2, 2, 16)
        s = jnp.einsum("bkgd,bskd->bkgs", qg, kd) * 16 ** -0.5
        pr = jax.nn.softmax(s, -1)
        ref = jnp.einsum("bkgs,bskd->bkgd", pr, vd).reshape(2, 4, 16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_cache_overflow_raises(self):
        cache = PagedKVCache.alloc(n_layers=1, n_kv=1, num_pages=2,
                                   page_size=2, head_dim=8, batch=1,
                                   max_seq=4, dtype=jnp.float32)
        nk = jnp.ones((1, 1, 8))
        for _ in range(4):
            cache = cache.write_token(0, nk, nk).bump()
        with pytest.raises(ValueError, match="overflow"):
            cache.write_token(0, nk, nk)

    def test_allocator(self):
        al = PageAllocator(4)
        a = al.allocate("s1", 2)
        b = al.allocate("s2", 2)
        assert len(set(a) | set(b)) == 4
        with pytest.raises(MemoryError):
            al.allocate("s3", 1)
        al.release("s1")
        c = al.allocate("s3", 2)
        assert set(c) == set(a)


class TestPagedGatePolicy:
    """The rule that replaced the byte threshold: which reader a paged
    program runs follows from the phase, the device layout and the page
    dtype (``kernels.paged_reader``), never from a size.  A decode
    program on one device over float pages reads live pages through the
    Mosaic kernel at every batch and table width; TP, int8-resident
    pages and interpret mode keep the gather, and say why."""

    # the cells' decode programs and the ends: (rows, table entries)
    @pytest.mark.parametrize("rows,table", [(1, 64), (6, 520), (28, 64),
                                            (64, 64)])
    @pytest.mark.parametrize("family", ["gpt2", "llama"])
    def test_decode_takes_the_kernel_at_every_shape(self, family, rows,
                                                    table, monkeypatch):
        """``forward_paged`` traced as the chip would (``interpret=False``)
        : the decode program holds a Pallas call, the chunk program (four
        rows) none.  The old env switches change nothing: the trace
        reads no environment."""
        from deepspeed_tpu.inference.kernels import PagedKVCache
        from deepspeed_tpu.inference.paged_forward import forward_paged
        from deepspeed_tpu.models import gpt2, llama

        monkeypatch.setenv("DSTPU_PAGED_ATTENTION", "xla")
        monkeypatch.setenv("DSTPU_FORCE_PAGED_PALLAS", "1")
        if family == "gpt2":
            mod, cfg = gpt2, gpt2.GPT2Config(
                vocab_size=64, max_seq_len=table * 8, n_layers=1,
                n_heads=2, dim=16)
        else:
            mod, cfg = llama, llama.LlamaConfig(
                vocab_size=64, dim=16, n_layers=1, n_heads=4, n_kv_heads=2,
                ffn_dim=32, max_seq_len=table * 8)
        params = jax.eval_shape(lambda: mod.init_params(
            jax.random.PRNGKey(0), cfg))
        n_kv = getattr(cfg, "n_kv_heads", cfg.n_heads)
        kv = jax.ShapeDtypeStruct(
            (1, n_kv, rows * table + 1, 8, cfg.head_dim), jnp.bfloat16)
        cache = PagedKVCache(
            k=kv, v=kv, table=jax.ShapeDtypeStruct((rows, table), jnp.int32),
            seq_lens=jax.ShapeDtypeStruct((rows,), jnp.int32), page_size=8)

        def jaxpr(T, continuation):
            return str(jax.make_jaxpr(
                lambda p, t, c: forward_paged(
                    p, t, cfg, c, interpret=False, tp=False,
                    continuation=continuation))(
                params, jax.ShapeDtypeStruct((rows, T), jnp.int32), cache))

        assert "dstpu_paged_decode" in jaxpr(1, False)
        assert "pallas_call" not in jaxpr(4, True)

    @pytest.mark.parametrize("layout,reader,why", [
        (dict(), "dstpu_paged_decode", "decode on one device"),
        (dict(tp=True), "xla", "tp"),
        (dict(quant=True), "xla", "int8-resident"),
        (dict(interpret=True), "xla", "interpret"),
        (dict(decode=False), "xla", "chunk"),
    ])
    def test_the_rule_answers_from_phase_and_layout(self, layout, reader,
                                                    why):
        from deepspeed_tpu.inference.kernels import paged_reader

        kw = dict(decode=True, tp=False, interpret=False, quant=False)
        kw.update(layout)
        got, reason = paged_reader(**kw)
        assert got == reader and why in reason

    @pytest.mark.parametrize("build,reader,why", [
        (dict(), "dstpu_paged_decode", "decode on one device"),
        (dict(tp=True), "xla", "tp"),
        (dict(quantized_resident=True), "xla", "int8-resident"),
        (dict(interpret=True), "xla", "interpret"),
    ])
    def test_the_build_says_which_reader_decode_baked(self, build, reader,
                                                      why):
        """``/statusz`` names the decode program's reader with its
        reason; a family with no kernel of its own for a mesh to take
        has no ``fallbacks`` row."""
        from deepspeed_tpu.inference.kernels import resolve_serving_kernels

        d = resolve_serving_kernels(**build).as_dict()
        assert d["decode"]["reader"] == reader
        assert why in d["decode"]["reason"]
        assert d["fallbacks"] == []

    def test_no_byte_threshold_is_left(self):
        from deepspeed_tpu.inference import kernels

        assert not hasattr(kernels, "_PAGED_V2_MIN_KV_BYTES")
        assert not hasattr(kernels, "pallas_paged_gate")


class TestPagedDecodeV2:
    """Multi-page-per-step decode kernel (paged_decode_attention_v2):
    interpret-mode numerics vs the gather oracle.  The kernel streams
    ppcb pages per inner iteration by explicit double-buffered DMA and
    reads only live pages."""

    # (H, KV, pages, table, lens): a scrambled table under GQA; MHA,
    # one row; an empty sequence beside a live one
    LAYOUTS = {
        "gqa_scrambled_table": (8, 2, 12, [[3, 7, 1, 0], [5, 2, 9, 11]],
                                [29, 17]),
        "mha_one_row": (4, 4, 8, [[0, 1, 2, 3]], [26]),
        "empty_sequence": (4, 2, 8, [[0, 1], [2, 3]], [10, 0]),
    }

    @pytest.mark.parametrize("layout", LAYOUTS)
    def test_matches_the_gather(self, layout):
        from deepspeed_tpu.inference.kernels import paged_decode_attention_v2

        H, KV, P, table, lens = self.LAYOUTS[layout]
        kp, vp = _mk_pages(KV, P, 8, 16, seed=len(layout))
        table = jnp.asarray(table, jnp.int32)
        lens = jnp.asarray(lens, jnp.int32)
        q = jax.random.normal(jax.random.PRNGKey(4), (len(lens), H, 16))
        ref = paged_attention_reference(q, kp, vp, table, lens)
        out = paged_decode_attention_v2(q, kp, vp, table, lens,
                                        interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=1e-4)
        # empty sequences (continuous batching admits them): zeros
        assert not np.asarray(out)[np.asarray(lens) == 0].any()

    def test_stale_table_ids_change_nothing(self):
        # dead slots hold whatever ids their last owner left
        from deepspeed_tpu.inference.kernels import paged_decode_attention_v2

        kp, vp = _mk_pages(2, 8, 8, 16)
        table = jnp.asarray([[0, 1, 7, 7]], jnp.int32)
        stale = jnp.asarray([[0, 1, 6, 5]], jnp.int32)  # dead slots differ
        lens = jnp.asarray([12], jnp.int32)              # only 2 live pages
        q = jax.random.normal(jax.random.PRNGKey(7), (1, 4, 16))
        a = paged_decode_attention_v2(q, kp, vp, table, lens, interpret=True)
        b = paged_decode_attention_v2(q, kp, vp, stale, lens, interpret=True)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def _pages(self, rng, KV, P, ps, Dh):
        k = jnp.asarray(rng.normal(size=(KV, P, ps, Dh)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(KV, P, ps, Dh)), jnp.float32)
        return k, v

    def test_gqa_ragged_and_empty_rows(self):
        from deepspeed_tpu.inference.kernels import (
            paged_attention_reference, paged_decode_attention_v2)

        rng = np.random.default_rng(0)
        B, H, KV, P, ps, Dh, mp = 3, 8, 4, 32, 4, 16, 8
        k, v = self._pages(rng, KV, P, ps, Dh)
        table = jnp.asarray(rng.integers(0, P, (B, mp)), jnp.int32)
        lens = jnp.asarray([13, 0, 32], jnp.int32)   # ragged + empty
        q = jnp.asarray(rng.normal(size=(B, H, Dh)), jnp.float32)
        ref = paged_attention_reference(q, k, v, table, lens)
        out = paged_decode_attention_v2(q, k, v, table, lens,
                                        pages_per_block=3, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)

    def test_block_bigger_than_live_pages(self):
        from deepspeed_tpu.inference.kernels import (
            paged_attention_reference, paged_decode_attention_v2)

        rng = np.random.default_rng(1)
        B, H, KV, P, ps, Dh, mp = 1, 2, 2, 8, 2, 8, 4
        k, v = self._pages(rng, KV, P, ps, Dh)
        table = jnp.asarray([[5, 1, 7, 0]], jnp.int32)
        lens = jnp.asarray([3], jnp.int32)
        q = jnp.asarray(rng.normal(size=(B, H, Dh)), jnp.float32)
        ref = paged_attention_reference(q, k, v, table, lens)
        out = paged_decode_attention_v2(q, k, v, table, lens,
                                        pages_per_block=8, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)

    def test_stale_tail_ids_never_dereferenced(self):
        """Table entries past the live pages may be stale/garbage ids;
        perturbing THOSE pages must not change the output."""
        from deepspeed_tpu.inference.kernels import (
            paged_decode_attention_v2)

        rng = np.random.default_rng(2)
        B, H, KV, P, ps, Dh, mp = 1, 4, 2, 16, 4, 8, 4
        k, v = self._pages(rng, KV, P, ps, Dh)
        # live: pages 0..1 (len 7); tail slots point at pages 9 and 11
        table = jnp.asarray([[0, 1, 9, 11]], jnp.int32)
        lens = jnp.asarray([7], jnp.int32)
        q = jnp.asarray(rng.normal(size=(B, H, Dh)), jnp.float32)
        base = paged_decode_attention_v2(q, k, v, table, lens,
                                         pages_per_block=4, interpret=True)
        k2 = k.at[:, 9].add(100.0).at[:, 11].add(-50.0)
        v2 = v.at[:, 9].add(100.0).at[:, 11].add(-50.0)
        pert = paged_decode_attention_v2(q, k2, v2, table, lens,
                                         pages_per_block=4, interpret=True)
        np.testing.assert_allclose(np.asarray(pert), np.asarray(base),
                                   atol=1e-6)


class TestPagedChunkV2:
    """Multi-page chunked-prefill kernel (paged_chunk_attention_v2) vs
    the gather oracle in interpret mode — the split-fuse twin of
    TestPagedDecodeV2."""

    # (C, H, KV, pages, Dh, table, start): GQA, a row mid-sequence and a
    # fresh one over scrambled pages; MHA, one row
    LAYOUTS = {
        "gqa_mid_sequence_and_fresh": (
            6, 8, 2, 12, 16, [[3, 7, 1, 0], [5, 2, 9, 11]], [9, 0]),
        "mha_one_row": (4, 4, 4, 6, 16, [[0, 1, 2, 3]], [13]),
    }

    @pytest.mark.parametrize("layout", LAYOUTS)
    def test_matches_the_gather(self, layout):
        from deepspeed_tpu.inference.kernels import (
            paged_chunk_attention_reference, paged_chunk_attention_v2)

        C, H, KV, P, Dh, table, start = self.LAYOUTS[layout]
        kp, vp = _mk_pages(KV, P, 8, Dh, seed=11)
        table = jnp.asarray(table, jnp.int32)
        start = jnp.asarray(start, jnp.int32)
        q = jax.random.normal(jax.random.PRNGKey(6),
                              (len(start), C, H, Dh))
        ref = paged_chunk_attention_reference(q, kp, vp, table, start)
        out = paged_chunk_attention_v2(q, kp, vp, table, start,
                                       interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=1e-4)

    def test_a_later_rows_keys_move_no_earlier_row(self):
        """Earlier chunk rows must not see later rows' K/V: perturbing a
        later position's page contents leaves earlier outputs unchanged."""
        from deepspeed_tpu.inference.kernels import paged_chunk_attention_v2

        B, C, H, KV, P, ps, Dh = 1, 4, 2, 2, 4, 4, 8
        kp, vp = _mk_pages(KV, P, ps, Dh, seed=13)
        table = jnp.asarray([[0, 1, 2, 3]], jnp.int32)
        start = jnp.asarray([5], jnp.int32)
        q = jax.random.normal(jax.random.PRNGKey(8), (B, C, H, Dh))
        base = paged_chunk_attention_v2(q, kp, vp, table, start,
                                        interpret=True)
        # position start+C-1 = 8 lives in page slot 2, in-page 0
        pert = paged_chunk_attention_v2(
            q, kp.at[:, 2, 0].add(100.0), vp.at[:, 2, 0].add(100.0), table,
            start, interpret=True)
        # rows 0..2 (positions 5..7) unchanged; row 3 (position 8) differs
        np.testing.assert_allclose(np.asarray(pert[:, :3]),
                                   np.asarray(base[:, :3]), atol=1e-6)
        assert not np.allclose(np.asarray(pert[:, 3]),
                               np.asarray(base[:, 3]))

    def _pages(self, rng, KV, P, ps, Dh):
        k = jnp.asarray(rng.normal(size=(KV, P, ps, Dh)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(KV, P, ps, Dh)), jnp.float32)
        return k, v

    def test_gqa_ragged_frontiers(self):
        from deepspeed_tpu.inference.kernels import (
            paged_chunk_attention_reference, paged_chunk_attention_v2)

        rng = np.random.default_rng(3)
        B, C, H, KV, P, ps, Dh, mp = 3, 4, 8, 4, 64, 4, 16, 16
        k, v = self._pages(rng, KV, P, ps, Dh)
        table = jnp.asarray(rng.integers(0, P, (B, mp)), jnp.int32)
        start = jnp.asarray([0, 17, 60 - 4], jnp.int32)
        q = jnp.asarray(rng.normal(size=(B, C, H, Dh)), jnp.float32)
        ref = paged_chunk_attention_reference(q, k, v, table, start)
        out = paged_chunk_attention_v2(q, k, v, table, start,
                                       pages_per_block=3, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)

    def test_pages_past_frontier_never_read(self):
        """Perturbing pages holding only positions past start+C-1 must
        not change the output (the live-pages-only sweep)."""
        from deepspeed_tpu.inference.kernels import paged_chunk_attention_v2

        rng = np.random.default_rng(4)
        B, C, H, KV, P, ps, Dh, mp = 1, 4, 2, 2, 16, 4, 8, 8
        k, v = self._pages(rng, KV, P, ps, Dh)
        table = jnp.asarray([[0, 1, 2, 3, 4, 5, 6, 7]], jnp.int32)
        start = jnp.asarray([5], jnp.int32)    # frontier at pos 8 → page 2
        q = jnp.asarray(rng.normal(size=(B, C, H, Dh)), jnp.float32)
        base = paged_chunk_attention_v2(q, k, v, table, start,
                                        pages_per_block=2, interpret=True)
        k2 = k.at[:, 3:8].add(100.0)   # pages for positions >= 12
        v2 = v.at[:, 3:8].add(100.0)
        pert = paged_chunk_attention_v2(q, k2, v2, table, start,
                                        pages_per_block=2, interpret=True)
        np.testing.assert_allclose(np.asarray(pert), np.asarray(base),
                                   atol=1e-6)

    def test_a_query_block_stops_at_its_own_frontier(self):
        """Each block of queries sweeps pages up to ITS frontier, not
        the chunk's: values that are not numbers in the page behind the
        first block's frontier (the second block's own rows) leave the
        first block's rows as they were, where a mask alone would give
        0 x NaN; the second block's rows, which read the page, show the
        perturbation was live."""
        from deepspeed_tpu.inference.kernels import paged_chunk_attention_v2

        rng = np.random.default_rng(6)
        B, C, H, KV, P, ps, Dh, mp = 1, 8, 4, 2, 8, 4, 8, 4
        k, v = self._pages(rng, KV, P, ps, Dh)
        table = jnp.asarray([[0, 1, 2, 3]], jnp.int32)
        start = jnp.asarray([4], jnp.int32)    # blocks at 4..7 and 8..11
        q = jnp.asarray(rng.normal(size=(B, C, H, Dh)), jnp.float32)
        run = lambda v: np.asarray(paged_chunk_attention_v2(
            q, k, v, table, start, pages_per_block=1, block_q=4,
            interpret=True))
        base, pert = run(v), run(v.at[:, 2].set(jnp.nan))  # positions 8..11
        np.testing.assert_array_equal(pert[:, :4], base[:, :4])
        assert np.isfinite(base).all() and np.isnan(pert[:, 4:]).all()

    def test_a_padded_chunk_past_the_tables_end_stays_in_the_table(self):
        """A prompt's last chunk is padded to C rows and may pass the
        row's table (``_advance_prefill`` clamps the width at the row):
        the sweep stops at the table's last entry, the rows inside the
        table read what the gather reads, and the padding's rows (which
        the host discards) stay finite."""
        from deepspeed_tpu.inference.kernels import (
            paged_chunk_attention_reference, paged_chunk_attention_v2)

        rng = np.random.default_rng(8)
        B, C, H, KV, P, ps, Dh = 1, 8, 4, 2, 8, 4, 8
        k, v = self._pages(rng, KV, P, ps, Dh)
        table = jnp.asarray([[5, 2, 7]], jnp.int32)     # 12 keys
        start = jnp.asarray([8], jnp.int32)             # rows at 8..15
        q = jnp.asarray(rng.normal(size=(B, C, H, Dh)), jnp.float32)
        ref = paged_chunk_attention_reference(q, k, v, table, start)
        out = np.asarray(paged_chunk_attention_v2(
            q, k, v, table, start, pages_per_block=2, block_q=4,
            interpret=True))
        np.testing.assert_allclose(out[:, :4], np.asarray(ref)[:, :4],
                                   atol=1e-5)
        assert np.isfinite(out).all()

    def test_causal_within_chunk(self):
        """Row i must not see the chunk's rows j > i (per-row frontier,
        not a block frontier)."""
        from deepspeed_tpu.inference.kernels import (
            paged_chunk_attention_reference, paged_chunk_attention_v2)

        rng = np.random.default_rng(5)
        B, C, H, KV, P, ps, Dh, mp = 1, 8, 4, 2, 8, 4, 8, 4
        k, v = self._pages(rng, KV, P, ps, Dh)
        table = jnp.asarray([[0, 1, 2, 3]], jnp.int32)
        start = jnp.asarray([4], jnp.int32)
        q = jnp.asarray(rng.normal(size=(B, C, H, Dh)), jnp.float32)
        ref = paged_chunk_attention_reference(q, k, v, table, start)
        out = paged_chunk_attention_v2(q, k, v, table, start,
                                       pages_per_block=4, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)

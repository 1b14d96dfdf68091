"""The Mosaic decode reader against the XLA gather, in interpret mode on the
CPU, at the shapes the cells send (``test_serving_kernels.py`` holds the
rule that chooses between the readers, the int8-resident read and the
sampler; ``test_serving_kernels_interpret_chunk.py`` the blocked chunk
reader): the live-pages decode kernel and the row it writes."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.kernels import (paged_attention_reference,
                                             paged_attention_step,
                                             paged_decode_attention_v2,
                                             write_token_pages)


# ------------------------------------------- the live-pages decode kernel
class TestDecodeKernelIdentity:
    """``paged_decode_attention_v2`` (one grid step a row, a page's K/V
    for every kv head in one copy) in interpret mode against the gather
    oracle.  Page size 8 and 2 pages a block, so 16 tokens is a block."""

    PS, PPB, MP, DH, LAYERS = 8, 2, 6, 32, 3
    LENS = {
        # empty rows beside live ones; inside a page; one token
        "ragged": [0, 5, 0, 43, 1],
        # on a page edge, on a block edge, a full table, one past an edge
        "edges": [8, 16, 48, 17, 32],
        "all_empty": [0, 0, 0, 0, 0],
        # a last block with a dead slot (1, 3 and 5 live pages of 2 a block)
        "dead_slots": [17, 40, 3, 33, 24],
        # the hand-over of a row's first block to the row before it: an
        # empty first and last row, one and two empty rows between live ones
        "handover": [0, 20, 0, 0, 33, 16, 0],
        # exactly a block's pages, a page more, two whole blocks, a full table
        "whole_blocks": [16, 17, 32, 9, 48],
    }

    @pytest.mark.parametrize("lens", LENS)
    @pytest.mark.parametrize("pool", ["whole_pool_traced_layer",
                                      "one_layer"])
    @pytest.mark.parametrize("heads", [(4, 4), (8, 2)],
                             ids=["mha_4_4", "gqa_8_2"])
    def test_matches_the_gather(self, heads, pool, lens):
        H, KV = heads
        lens = np.asarray(self.LENS[lens], np.int32)
        B, P = len(lens), len(lens) * self.MP + 2
        rng = np.random.default_rng(7)
        shape = (self.LAYERS, KV, P, self.PS, self.DH)
        # pages 0 and P - 1 are poison that no table names: where an id
        # outside the pool lands once the interpreter has clamped it
        poison = np.isin(np.arange(P), [0, P - 1])[None, None, :, None, None]
        k = jnp.asarray(np.where(poison, np.nan, rng.normal(size=shape)),
                        jnp.float32)
        v = jnp.asarray(np.where(poison, np.nan, rng.normal(size=shape)),
                        jnp.float32)
        q = jnp.asarray(rng.normal(size=(B, H, self.DH)), jnp.float32)
        table = (rng.permutation(P - 2)[:B * self.MP] + 1).reshape(
            B, self.MP).astype(np.int32)
        # past a row's live pages the table is stale: ids that name no
        # page of the pool, which the kernel must never dereference, not
        # for a dead slot of a row's last block either (the oracle gets
        # real pages there: it masks what it gathers)
        stale = np.arange(self.MP)[None] >= -(-lens[:, None] // self.PS)
        oracle_table = jnp.asarray(table)
        table = jnp.asarray(np.where(
            stale, np.where(np.arange(self.MP)[None] % 2, P + 1000, -7),
            table))
        lens = jnp.asarray(lens)

        if pool == "one_layer":
            ref = paged_attention_reference(q, k[1], v[1], oracle_table,
                                            lens)
            out = paged_decode_attention_v2(
                q, k[1], v[1], table, lens, pages_per_block=self.PPB,
                interpret=True)
        else:
            ref = paged_attention_reference(q, k, v, oracle_table, lens,
                                            layer=1)
            out = jax.jit(lambda layer: paged_decode_attention_v2(
                q, k, v, table, lens, pages_per_block=self.PPB,
                interpret=True, layer=layer))(jnp.int32(1))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)
        empty = np.asarray(lens) == 0
        assert not np.asarray(out)[empty].any()     # zeros, not mean-of-V

    def test_pages_per_block_is_derived(self):
        """Nobody passes ``pages_per_block``: it follows from the kv
        heads, the page and the VMEM block (GPT-2 1.3B: 8 pages of 16
        heads; Mixtral: 16 pages of 8), capped by the table."""
        from deepspeed_tpu.inference.kernels import decode_pages_per_block

        assert decode_pages_per_block(16, 16, 128, 2, 64) == 8
        assert decode_pages_per_block(8, 16, 128, 2, 520) == 16
        assert decode_pages_per_block(2, 8, 32, 4, 6) == 6
        assert decode_pages_per_block(64, 64, 256, 4, 64) == 1


class TestDecodeKernelWritesItsRow:
    """``paged_decode_attention_v2`` handed the step's new K/V rows (one
    a slot, the lengths from BEFORE the write) in interpret mode against
    ``write_token_pages`` + the gather oracle over the written pool: the
    same attention, the same pools bit for bit, and no byte of either
    pool changed but the rows written.  Page size 8, 2 pages a block, a
    table of 6 pages: capacity 48."""

    PS, PPB, MP, DH, LAYERS = 8, 2, 6, 32, 3
    LENS = {
        # the new token is a page's first row (row 0 of a fresh page)
        "opens_a_page": [8, 16, 40, 24, 32],
        # ... its last row
        "fills_a_page": [7, 15, 47, 23, 31],
        # a full table writes nothing and attends to its pages only
        "at_capacity": [48, 48, 47, 5, 48],
        # no key but the new row, on pages of the rows' own
        "empty_rows": [0, 0, 3, 0, 0],
        # a block edge (2 pages of 8) before, at and after the new row
        "block_edges": [15, 16, 17, 32, 33],
        # more rows than the kernel holds tiles: the ring comes round
        "many_rows": [3, 0, 8, 48, 21, 9, 40, 1, 47, 16, 30, 0, 25],
    }
    # G = 1; 3 and 12 query heads a K/V head: neither whole sublane tiles
    HEADS = {"mha_4_4": (4, 4), "gqa_6_2": (6, 2), "gqa_24_2": (24, 2)}

    def _pools(self, lens, KV, H, dtype=jnp.float32, seed=13):
        B, P = len(lens), len(lens) * self.MP + 1
        rng = np.random.default_rng(seed)
        shape = (self.LAYERS, KV, P, self.PS, self.DH)
        arr = lambda *shape: jnp.asarray(rng.normal(size=shape), dtype)
        k, v, q = arr(*shape), arr(*shape), arr(B, H, self.DH)
        nk, nv = arr(B, KV, self.DH), arr(B, KV, self.DH)
        table = rng.permutation(P - 1)[:B * self.MP].reshape(
            B, self.MP).astype(np.int32)            # page P - 1: the trash
        return q, nk, nv, k, v, table

    def _unwritten(self, pool, lens, table, layer=1):
        """A mask of the pool's rows no slot's new row lands on."""
        mask = np.ones(pool.shape[:4], bool)
        for b, n in enumerate(np.asarray(lens)):
            if n < self.MP * self.PS:
                mask[layer, :, table[b, n // self.PS], n % self.PS] = False
        return mask

    @pytest.mark.parametrize("lens", LENS)
    @pytest.mark.parametrize("heads", HEADS)
    def test_matches_the_scatter_and_the_gather(self, heads, lens):
        H, KV = self.HEADS[heads]
        lens = np.asarray(self.LENS[lens], np.int32)
        q, nk, nv, k, v, table = self._pools(lens, KV, H)
        table, n = jnp.asarray(table), jnp.asarray(lens)
        rk, rv = write_token_pages(k, v, 1, nk, nv, table, n)
        ref = paged_attention_reference(
            q, rk, rv, table, jnp.minimum(n + 1, self.MP * self.PS), layer=1)
        out, ok, ov = jax.jit(lambda layer: paged_decode_attention_v2(
            q, k, v, table, n, pages_per_block=self.PPB, interpret=True,
            layer=layer, new_k=nk, new_v=nv))(jnp.int32(1))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)
        keep = self._unwritten(k, lens, np.asarray(table))
        for got, want, was in ((ok, rk, k), (ov, rv, v)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
            np.testing.assert_array_equal(np.asarray(got)[keep],
                                          np.asarray(was)[keep])
        assert (~keep).sum() == KV * int((lens < self.MP * self.PS).sum())

    def test_one_layers_pages_in_bfloat16(self):
        """A pool of one layer comes back as one layer's pages; packed
        rows keep their neighbours' bits."""
        lens = np.asarray(self.LENS["many_rows"], np.int32)
        q, nk, nv, k, v, table = self._pools(lens, 2, 8, jnp.bfloat16)
        table, n = jnp.asarray(table), jnp.asarray(lens)
        rk, rv = write_token_pages(k, v, 2, nk, nv, table, n)
        ref = paged_attention_reference(
            q, rk, rv, table, jnp.minimum(n + 1, self.MP * self.PS), layer=2)
        out, ok, ov = paged_decode_attention_v2(
            q, k[2], v[2], table, n, pages_per_block=self.PPB,
            interpret=True, new_k=nk, new_v=nv)
        assert ok.shape == k.shape[1:] and ok.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32), atol=2e-2)
        np.testing.assert_array_equal(np.asarray(ok), np.asarray(rk[2]))
        np.testing.assert_array_equal(np.asarray(ov), np.asarray(rv[2]))

    def test_idle_slots_write_the_trash_page(self):
        """An idle slot's table row names the trash page and its length
        is 0 (``ServingEngine``): its row lands on the trash page's row
        0, as the scatter's does, one of them last; it attends to its
        own row alone; the trash page's other rows and every live page
        keep their bytes."""
        lens = np.asarray([0, 12, 0, 0, 30], np.int32)
        q, nk, nv, k, v, table = self._pools(lens, 2, 4)
        trash = k.shape[2] - 1
        table[lens == 0] = trash
        table, n = jnp.asarray(table), jnp.asarray(lens)
        rk, rv = write_token_pages(k, v, 1, nk, nv, table, n)
        ref = paged_attention_reference(q, rk, rv, table, n + 1, layer=1)
        out, ok, ov = paged_decode_attention_v2(
            q, k, v, table, n, pages_per_block=self.PPB, interpret=True,
            layer=1, new_k=nk, new_v=nv)
        live, idle = lens > 0, np.flatnonzero(lens == 0)
        np.testing.assert_allclose(np.asarray(out)[live],
                                   np.asarray(ref)[live], atol=1e-5)
        np.testing.assert_allclose(                 # one key: its own value
            np.asarray(out)[idle].reshape(len(idle), 2, 2, self.DH),
            np.asarray(nv)[idle][:, :, None].repeat(2, 2), atol=1e-6)
        for got, want, was, new in ((ok, rk, k, nk), (ov, rv, v, nv)):
            got, want, was = (np.asarray(a) for a in (got, want, was))
            np.testing.assert_array_equal(got[:, :, :trash],
                                          want[:, :, :trash])
            np.testing.assert_array_equal(got[:, :, trash, 1:],
                                          was[:, :, trash, 1:])
            np.testing.assert_array_equal(got[[0, 2], :, trash, 0],
                                          was[[0, 2], :, trash, 0])
            assert any((got[1, :, trash, 0] == np.asarray(new)[b]).all()
                       for b in idle)

    def test_an_entry_that_names_no_page_writes_nothing(self):
        """``_row_targets``'s contract is the scatter's ``mode="drop"``:
        a page id outside the pool is dropped, not clamped."""
        lens = np.asarray([9, 20], np.int32)
        q, nk, nv, k, v, table = self._pools(lens, 2, 4)
        table[0, 1], table[1, 2] = k.shape[2] + 5, -3
        out, ok, ov = paged_decode_attention_v2(
            q, k, v, jnp.asarray(table), jnp.asarray(lens),
            pages_per_block=self.PPB, interpret=True, layer=1, new_k=nk,
            new_v=nv)
        np.testing.assert_array_equal(np.asarray(ok), np.asarray(k))
        np.testing.assert_array_equal(np.asarray(ov), np.asarray(v))
        assert np.isfinite(np.asarray(out)).all()

    def test_the_step_calls_it_with_the_lengths_before_the_write(
            self, monkeypatch):
        """``paged_attention_step``'s decode branch under the Mosaic
        reader (interpreted here) against the same step under the
        gather: the attention, and the pools it hands on."""
        import functools

        from deepspeed_tpu.inference import kernels

        monkeypatch.setattr(
            kernels, "paged_decode_attention_v2", functools.partial(
                paged_decode_attention_v2, interpret=True))
        lens = np.asarray(self.LENS["many_rows"], np.int32)
        q, nk, nv, k, v, table = self._pools(lens, 2, 6)
        step = lambda reader: paged_attention_step(
            q[:, None], nk[:, None], nv[:, None], k, v, jnp.int32(1),
            jnp.asarray(table), jnp.asarray(lens), continuation=False,
            prefill=False, reader=reader, flash_force_reference=True)
        (attn, kp, vp, *none), (ref, rk, rv, *_) = (
            step("dstpu_paged_decode"), step("xla"))
        assert none == [None, None] and attn.shape == ref.shape
        np.testing.assert_allclose(np.asarray(attn), np.asarray(ref),
                                   atol=1e-5)
        np.testing.assert_array_equal(np.asarray(kp), np.asarray(rk))
        np.testing.assert_array_equal(np.asarray(vp), np.asarray(rv))

"""Which kernel reads the cache: the rule of the build (``kernels.
paged_reader``), the readers it chooses between and the sampler every
engine runs (ref: DeepSpeed-FastGen's kernel injection — the serving
engine picks kernels ONCE at build, never at trace time).

Oracles:
  * the XLA gather — the Mosaic readers, in interpret mode on the CPU,
    match it at the shapes the cells send;
  * ``dequantize_pages`` — the int8-resident read is the gather over
    the dequantized pool, bit for bit;
  * ``np.argmax`` and ``jax.random.categorical`` — ``_sample_rows``;
  * ``resolve_serving_kernels`` — resolved once, from what the build can
    observe; no config block and no environment variable reaches it,
    and what ``/statusz`` reports is what the compiled programs baked.
"""

import importlib.util
import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.config import Config, KVTierConfig
from deepspeed_tpu.inference import init_inference, init_serving
from deepspeed_tpu.inference.kernels import (
    ServingKernelPolicy, dequantize_pages, latent_reader,
    paged_attention_reference, paged_attention_step,
    paged_chunk_attention_reference, paged_chunk_attention_v2,
    paged_decode_attention_v2, paged_reader, quantize_kv_rows,
    resolve_serving_kernels, write_token_pages)
from deepspeed_tpu.inference.kv_tier import KV_TIER_QUANT_RTOL, quantize_page
from deepspeed_tpu.inference.serving import _sample_rows, serving_engine
from deepspeed_tpu.models import gpt2, llama
from deepspeed_tpu.models.family import CacheRow


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


# ---------------------------------------------------------------- config
class TestQuantizedResidentConfig:
    def test_quantized_resident_requires_quantize_cold(self):
        with pytest.raises(ValueError, match="quantize_cold"):
            KVTierConfig.coerce({"quantized_resident": True,
                                 "quantize_cold": False})
        k = KVTierConfig.coerce({"quantized_resident": True,
                                 "quantize_cold": True})
        assert k.quantized_resident


# ----------------------------------------------------------- resolution
class TestResolveServingKernels:
    def test_defaults(self):
        """Off the chip (``interpret``) every reader is the gather; the
        policy is a report with six rows and nothing to request."""
        p = resolve_serving_kernels(interpret=True)
        assert p._fields == ("decode", "chunk", "window", "state_step",
                             "fallbacks", "state_chunk", "experts")
        assert p.experts == ("none", "no expert layer")
        assert p.decode == p.chunk == ("xla", "interpret: no TPU backend")
        assert (p.state_step, p.fallbacks) == ("xla", ())
        assert p.state_chunk == (
            "xla", "the family states no block of its rule")
        # what a directly constructed engine reports: all gathers
        assert ServingKernelPolicy().decode[0] == "xla"

    def test_as_dict_shape(self):
        d = resolve_serving_kernels(tp=True, recurrent=True).as_dict()
        assert sorted(d) == ["chunk", "decode", "experts", "fallbacks",
                             "state_chunk", "state_step", "window"]
        assert d["experts"] == {"product": "none",
                                "reason": "no expert layer"}
        assert d["state_chunk"]["reader"] == "xla"
        assert d["decode"] == {
            "reader": "xla", "write": "scatter",
            "reason": "tp: KV heads are sharded over the mesh"}
        assert d["fallbacks"] == [{
            "field": "state_step=pallas", "demoted_to": "xla",
            "reason": "tp: the kernel is one device's"}]
        # a mesh takes nothing from a family that steps no state
        assert resolve_serving_kernels(tp=True).fallbacks == ()


    @pytest.mark.parametrize("build,write,why", [
        ({}, "kernel", "decode on one device over float pages"),
        ({"tp": True}, "scatter", "tp: KV heads are sharded"),
        ({"quantized_resident": True}, "scatter", "int8-resident pages"),
        ({"interpret": True}, "scatter", "interpret: no TPU backend"),
    ], ids=["one_device", "tp", "quantized_resident", "cpu"])
    def test_the_build_says_where_a_decode_steps_row_is_written(
            self, build, write, why):
        """``decode.write`` follows the decode reader and nothing else:
        the Mosaic reader writes the step's K/V row itself ("kernel");
        where the gather stays, so does the row scatter before it, for
        the reader's own reason."""
        policy = resolve_serving_kernels(**build)
        d = policy.as_dict()["decode"]
        assert sorted(d) == ["reader", "reason", "write"]
        assert d["write"] == write and why in d["reason"]
        assert (d["write"] == "kernel") == (
            d["reader"] == "dstpu_paged_decode")
        # a latent family's reader has a writer of its own before it
        latent = policy._replace(decode=latent_reader(policy.decode))
        assert latent.as_dict()["decode"]["write"] == "scatter"


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


# ------------------------------------------------ the blocked chunk reader
class TestChunkKernelIdentity:
    """``paged_chunk_attention_v2`` (one grid step a block of queries,
    which sweeps key blocks up to its own frontier; a page's K/V for
    every kv head in one copy) in interpret mode against the gather
    oracle, at the three chunk cells' head shapes cut to two K/V heads:
    a chunk of two 128-query blocks over pages of 16, 8 pages a key
    block, so 128 keys is a block."""

    PS, PPB, C, LAYERS, KV = 16, 8, 256, 3, 2
    HEADS = {"laguna_g6_dh128": (6, 128), "qwen_g8_dh256": (8, 256),
             "mixtral_g4_dh128": (4, 128)}
    # start, dtype, atol: bf16 operands round the probabilities too
    CASES = {
        "start_0": (0, jnp.float32, 1e-5),
        "start_off_the_key_block": (200, jnp.float32, 1e-5),
        "history_ends_inside_a_page": (131, jnp.float32, 1e-5),
        "start_on_a_block_edge_bf16": (384, jnp.bfloat16, 2e-2),
        "ragged_bf16": (77, jnp.bfloat16, 2e-2),
    }

    @pytest.mark.parametrize("case", CASES)
    @pytest.mark.parametrize("heads", HEADS)
    def test_matches_the_gather(self, heads, case):
        (G, Dh), (start, dtype, atol) = self.HEADS[heads], self.CASES[case]
        H, live = G * self.KV, -(-(start + self.C) // self.PS)
        mp, P = live + 9, live + 40        # a table wider than the pages
        rng = np.random.default_rng(11)
        shape = (self.LAYERS, self.KV, P, self.PS, Dh)
        k = jnp.asarray(rng.normal(size=shape), dtype)
        v = jnp.asarray(rng.normal(size=shape), dtype)
        q = jnp.asarray(rng.normal(size=(1, self.C, H, Dh)) * 0.3, dtype)
        # page ids shuffled; behind the frontier ids that name no page of
        # the pool, which the kernel must never dereference (the oracle
        # gets them clamped: it masks what it gathers)
        ids = rng.permutation(P)[:mp].astype(np.int32)
        stale = np.arange(mp) >= live
        oracle_table = jnp.asarray(ids[None])
        table = jnp.asarray(np.where(stale, P + 1000, ids)[None])
        st = jnp.asarray([start], jnp.int32)
        ref = paged_chunk_attention_reference(q, k, v, oracle_table, st,
                                              layer=1)
        out = jax.jit(lambda layer: paged_chunk_attention_v2(
            q, k, v, table, st, pages_per_block=self.PPB, block_q=128,
            interpret=True, layer=layer))(jnp.int32(1))
        assert out.dtype == q.dtype
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32), atol=atol)

    def test_blocks_are_derived(self):
        """Nobody passes the block sizes: a block of keys is 256 KiB of
        a K/V head's (1,024 at heads of 128, 512 at Qwen3-Next's 256),
        a block of queries 256 where a step fits the VMEM budget, else
        128, both capped by the chunk and the table; a chunk off the
        128-row rule is one block of whole sublanes."""
        from deepspeed_tpu.inference.kernels import chunk_blocks

        assert chunk_blocks(48, 8, 128, 16, 2, 1024, 1152) == (256, 64)
        assert chunk_blocks(16, 2, 256, 16, 2, 1024, 1088) == (256, 32)
        assert chunk_blocks(32, 8, 128, 16, 2, 1024, 520) == (256, 64)
        assert chunk_blocks(32, 8, 128, 16, 2, 128, 4) == (128, 4)
        assert chunk_blocks(128, 8, 128, 16, 2, 1024, 64) == (128, 64)
        assert chunk_blocks(4, 2, 16, 4, 4, 5, 16) == (8, 16)

    # (tokens, head width) of the three cells' chunk programs
    @pytest.mark.parametrize("shape", [(1024, 128), (1024, 256), (256, 128)])
    def test_the_rule_runs_it_where_the_shapes_are_whole_blocks(self, shape):
        kw = dict(decode=False, tp=False, interpret=False, quant=False,
                  tokens=shape[0], head_dim=shape[1])
        reader, why = paged_reader(**kw)
        assert reader == "dstpu_paged_chunk_v2"
        assert "chunk in 128-row blocks" in why

    @pytest.mark.parametrize("off,why", [
        (dict(tp=True), "tp"), (dict(quant=True), "int8-resident"),
        (dict(interpret=True), "interpret"),
        (dict(tokens=5), "not whole 128-row blocks"),
        (dict(head_dim=64), "not whole 128-lane tiles"),
    ], ids=["tp", "quant", "interpret", "five_rows", "head_of_64"])
    def test_the_rule_keeps_the_gather_and_says_why(self, off, why):
        kw = dict(decode=False, tp=False, interpret=False, quant=False,
                  tokens=1024, head_dim=128)
        kw.update(off)
        reader, reason = paged_reader(**kw)
        assert reader == "xla" and why in reason

    def test_a_padded_head_counts_as_its_own_numbers(self):
        """A family that stores a head of 64 in a 128-lane tile says so
        in its cache row, and the rule is asked with the head's own
        width: its chunk programs keep the gather (half of every product
        would be zeros), with the reason in ``/statusz``."""
        from deepspeed_tpu.models import granite_hybrid as gh
        from deepspeed_tpu.models.family import decoder_family

        cfg = gh.GraniteHybridConfig(head_dim=64)
        row = decoder_family(cfg).cache_row(cfg)
        assert (row.key_width, row.head_width) == (128, 64)
        chunk = resolve_serving_kernels(interpret=False, chunk=(
            256, row.head_width or row.key_width)).chunk
        assert chunk == ("xla",
                         "chunk program: a head is not whole 128-lane tiles")
        for fam_cfg in (llama.LlamaConfig(vocab_size=64, dim=512, n_layers=1,
                                          n_heads=4, n_kv_heads=2, ffn_dim=64),
                        gpt2.GPT2Config.tiny()):
            assert decoder_family(fam_cfg).cache_row(fam_cfg).head_width == 0

    @pytest.mark.parametrize("chunk,reader", [
        ((1024, 128), "dstpu_paged_chunk_v2"), ((256, 64), "xla"),
        ((0, 128), "xla")])
    def test_statusz_names_the_chunk_reader(self, chunk, reader):
        """``/statusz``'s ``kernels`` block shows the ``chunk`` row, the
        reader of the build's chunk programs with its reason, beside
        ``decode`` and ``window``."""
        d = resolve_serving_kernels(interpret=False, chunk=chunk).as_dict()
        assert d["chunk"]["reader"] == reader and d["chunk"]["reason"]
        assert set(d) >= {"decode", "chunk", "window", "state_step"}
        # in interpret mode (the CPU's engines) every build gathers
        assert resolve_serving_kernels(interpret=True, chunk=chunk) \
            .as_dict()["chunk"]["reader"] == "xla"


# ---------------------------------------------------------- int8 codec
class TestQuantCodecParity:
    """quantize_kv_rows (device, jnp) and kv_tier.quantize_page (host,
    np) must agree bit-for-bit — quantized_resident round-trips pages
    between them (demote fetches device codes verbatim, promote
    publishes host codes verbatim)."""

    def test_bit_exact_parity(self):
        rng = np.random.default_rng(0)
        x = (3.0 * rng.standard_normal((2, 5, 8, 16))).astype(np.float32)
        x[0, 1, 2] = 0.0                     # a zero row: scale 1.0
        cj, sj = quantize_kv_rows(jnp.asarray(x))
        cn, sn = quantize_page(x)
        np.testing.assert_array_equal(np.asarray(cj), cn)
        np.testing.assert_array_equal(np.asarray(sj), sn)
        assert np.asarray(sj)[0, 1, 2, 0] == 1.0

    def test_dequant_error_bound(self):
        rng = np.random.default_rng(1)
        x = (5.0 * rng.standard_normal((4, 8, 16))).astype(np.float32)
        c, s = quantize_kv_rows(jnp.asarray(x))
        back = np.asarray(dequantize_pages(c, s, jnp.float32))
        bound = (np.max(np.abs(x), axis=-1, keepdims=True)
                 * KV_TIER_QUANT_RTOL + 1e-7)
        assert np.all(np.abs(back - x) <= bound)


# ---------------------------------------------------------- the sampler
class TestSampleRows:
    """``_sample_rows``, the sampler every engine's programs run: greedy
    rows are ``np.argmax`` (first occurrence on ties), temperature rows
    are ``jax.random.categorical`` at the row's own key and
    temperature."""

    @pytest.mark.parametrize("B,V", [(1, 7), (3, 37), (8, 128),
                                     (9, 257), (16, 500)])
    def test_greedy_is_argmax(self, B, V):
        logits = jax.random.normal(jax.random.PRNGKey(B * V), (B, V))
        keys = jax.random.split(jax.random.PRNGKey(1), B)
        got = _sample_rows(logits, keys, jnp.zeros((B,)))
        assert got.dtype == jnp.int32
        np.testing.assert_array_equal(
            np.asarray(got), np.argmax(np.asarray(logits), -1))

    def test_greedy_first_occurrence_on_ties(self):
        # duplicate maxima: the FIRST index, as np.argmax; the serving
        # identity checks against a plain reference depend on it
        logits = jnp.zeros((4, 200)).at[:, 150].set(5.0).at[:, 30].set(5.0)
        keys = jax.random.split(jax.random.PRNGKey(2), 4)
        got = np.asarray(_sample_rows(logits, keys, jnp.zeros((4,))))
        np.testing.assert_array_equal(got, np.full(4, 30))

    def test_temperature_distribution(self):
        # sharply-biased logits at temp 1.0: the favored token must
        # dominate; a flat draw (or an argmax leak into temp rows)
        # cannot pass this
        B, V = 256, 16
        logits = jnp.zeros((B, V)).at[:, 5].set(3.0)
        keys = jax.random.split(jax.random.PRNGKey(11), B)
        toks = np.asarray(_sample_rows(logits, keys, jnp.ones((B,))))
        frac = np.mean(toks == 5)
        # softmax prob of token 5 ≈ 0.57 at these logits
        assert 0.4 < frac < 0.75
        assert len(np.unique(toks)) > 1     # it actually sampled

    def test_each_row_draws_with_its_own_key_and_temperature(self):
        """Greedy and sampled rows in one batch: a row at temperature 0
        is its argmax whatever its key; another is the categorical draw
        of ITS key at ITS temperature, so two rows with the same logits
        and key agree and the batch's order changes no row."""
        B, V = 6, 97
        logits = jax.random.normal(jax.random.PRNGKey(3), (B, V))
        logits = logits.at[4].set(logits[1])
        keys = jax.random.split(jax.random.PRNGKey(7), B)
        keys = keys.at[4].set(keys[1])
        temps = jnp.asarray([0.0, 1.0, 0.0, 0.7, 1.0, 2.0])
        got = np.asarray(_sample_rows(logits, keys, temps))
        for b in range(B):
            want = (np.argmax(np.asarray(logits[b])) if temps[b] == 0
                    else jax.random.categorical(
                        keys[b], logits[b].astype(jnp.float32) / temps[b]))
            assert got[b] == int(want), b
        assert got[4] == got[1]
        back = np.asarray(_sample_rows(logits[::-1], keys[::-1],
                                       temps[::-1]))
        np.testing.assert_array_equal(back[::-1], got)


# ------------------------------------------------ the int8-resident read
def _quant_paged_setup(seed, B, KV, Dh, P, ps, mp):
    rng = np.random.default_rng(seed)
    k = jnp.asarray(rng.normal(size=(KV, P, ps, Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(KV, P, ps, Dh)), jnp.float32)
    kq, ks = quantize_kv_rows(k)
    vq, vs = quantize_kv_rows(v)
    table = jnp.asarray(
        rng.permutation(P)[:B * mp].reshape(B, mp), jnp.int32)
    return k, v, kq, ks, vq, vs, table


class TestQuantResidentRead:
    """Over int8-resident pages every program gathers the codes its
    table names and dequantizes those: the references with ``k_scale`` /
    ``v_scale`` are the references over the dequantized pool, bit for
    bit (the dequant is elementwise), and within the codec's bound of
    the float pages."""

    HEADS = {"gqa_4_2": (4, 2), "mha_2_2": (2, 2)}

    @pytest.mark.parametrize("heads", HEADS)
    def test_decode_is_the_gather_over_the_dequantized_pool(self, heads):
        (H, KV), B, Dh, ps, mp = self.HEADS[heads], 3, 16, 8, 4
        k, v, kq, ks, vq, vs, table = _quant_paged_setup(
            0, B, KV, Dh, 16, ps, mp)
        lens = jnp.asarray([5, 17, 32], jnp.int32)      # ragged; one full
        q = jax.random.normal(jax.random.PRNGKey(1), (B, H, Dh))
        got = paged_attention_reference(q, kq, vq, table, lens,
                                        k_scale=ks, v_scale=vs)
        want = paged_attention_reference(
            q, dequantize_pages(kq, ks, jnp.float32),
            dequantize_pages(vq, vs, jnp.float32), table, lens)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        # attention output error under per-row int8 KV stays within a
        # few quantization steps of the unit-scale values
        exact = paged_attention_reference(q, k, v, table, lens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(exact),
                                   atol=12 * KV_TIER_QUANT_RTOL)

    @pytest.mark.parametrize("heads", HEADS)
    def test_chunk_is_the_gather_over_the_dequantized_pool(self, heads):
        (H, KV), B, C, Dh, ps, mp = self.HEADS[heads], 2, 5, 16, 8, 4
        k, v, kq, ks, vq, vs, table = _quant_paged_setup(
            4, B, KV, Dh, 16, ps, mp)
        start = jnp.asarray([3, 11], jnp.int32)         # ragged histories
        q = jax.random.normal(jax.random.PRNGKey(5), (B, C, H, Dh))
        got = paged_chunk_attention_reference(q, kq, vq, table, start,
                                              k_scale=ks, v_scale=vs)
        want = paged_chunk_attention_reference(
            q, dequantize_pages(kq, ks, jnp.float32),
            dequantize_pages(vq, vs, jnp.float32), table, start)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        exact = paged_chunk_attention_reference(q, k, v, table, start)
        np.testing.assert_allclose(np.asarray(got), np.asarray(exact),
                                   atol=12 * KV_TIER_QUANT_RTOL)

    @pytest.mark.parametrize("decode", [True, False],
                             ids=["decode", "chunk"])
    def test_the_rule_gathers_and_says_why(self, decode):
        kw = dict(decode=decode, tp=False, interpret=False, tokens=1024,
                  head_dim=128)
        assert paged_reader(quant=False, **kw)[0].startswith("dstpu_paged_")
        assert paged_reader(quant=True, **kw) == ("xla",
                                                  "int8-resident pages")

    def test_a_step_over_codes_refuses_a_mosaic_reader(self):
        """``paged_attention_step`` with scale planes and a reader that
        is not the gather would hand int8 codes to a kernel that reads
        them as values: it raises instead."""
        pool = jnp.zeros((1, 2, 4, 8, 16), jnp.int8)
        scale = jnp.ones((1, 2, 4, 8, 1), jnp.float32)
        x = jnp.zeros((1, 1, 2, 16))
        with pytest.raises(ValueError, match="int8-resident"):
            paged_attention_step(
                x, x, x, pool, pool, 0, jnp.zeros((1, 4), jnp.int32),
                jnp.zeros((1,), jnp.int32), continuation=False,
                prefill=False, reader="dstpu_paged_decode",
                flash_force_reference=False, kps=scale, vps=scale)


# ------------------------------------- the rule at the traffic the cells send
# What each serving cell of the benchmark hands the rule, written down
# from benchmark/configs and benchmark/workloads (not imported): the
# family's cache row (K/V heads, stored width, values, and the head's own
# width where it is padded to a tile), the engine's chunk and bucket,
# page size, and whether the rows are latent / the family steps a state.
CELLS = {
    "gpt2-1.3b.serve.chat-0.8knee": (
        CacheRow(16, 128, 128), dict(prefill_bucket=128, page_size=16), ""),
    "mixtral-8x7b-d4.serve.chat-sat": (
        CacheRow(8, 128, 128), dict(prefill_bucket=128, page_size=16), ""),
    "mixtral-8x7b-d4.serve.docs-sat": (
        CacheRow(8, 128, 128),
        dict(prefill_chunk=1024, prefill_bucket=0, page_size=16), ""),
    "openpangu-ultra-moe-718b-ep16-d5.serve.think-sat": (
        CacheRow(1, 576, 512, values_in_keys=True),
        dict(prefill_chunk=1024, prefill_bucket=0, page_size=16), "latent"),
    "qwen3-next-80b-a3b-ep8-d12.serve.docqa-sat": (
        CacheRow(2, 256, 256),
        dict(prefill_chunk=1024, prefill_bucket=0, page_size=16), "state"),
    "v42.granite-4.0-h-micro.serve.assist-sat": (
        CacheRow(8, 128, 128, head_width=64),
        dict(prefill_chunk=256, prefill_bucket=0, page_size=16), "state"),
    "v44.laguna-s-2.1-ep16-d13.serve.code-sat": (
        CacheRow(8, 128, 128),
        dict(prefill_chunk=1024, prefill_bucket=0, page_size=16), ""),
}
# cell -> (decode, chunk) readers of its build on one device
ON_ONE_DEVICE = {
    "gpt2-1.3b.serve.chat-0.8knee":
        ("dstpu_paged_decode", "dstpu_paged_chunk_v2"),
    "mixtral-8x7b-d4.serve.chat-sat":
        ("dstpu_paged_decode", "dstpu_paged_chunk_v2"),
    "mixtral-8x7b-d4.serve.docs-sat":
        ("dstpu_paged_decode", "dstpu_paged_chunk_v2"),
    # a latent chunk expands its rows and runs the flash kernel
    "openpangu-ultra-moe-718b-ep16-d5.serve.think-sat":
        ("dstpu_mla_decode", "xla"),
    "qwen3-next-80b-a3b-ep8-d12.serve.docqa-sat":
        ("dstpu_paged_decode", "dstpu_paged_chunk_v2"),
    # a head of 64 in a 128-lane tile
    "v42.granite-4.0-h-micro.serve.assist-sat":
        ("dstpu_paged_decode", "xla"),
    "v44.laguna-s-2.1-ep16-d13.serve.code-sat":
        ("dstpu_paged_decode", "dstpu_paged_chunk_v2"),
}


def _state_block(cell):
    """The ``(Recurrent, cfg)`` a recurrent cell's build hands the rule,
    at the published widths (the default configs)."""
    from deepspeed_tpu.models import granite_hybrid, qwen3_next

    if cell.startswith("qwen3-next"):
        return qwen3_next.FAMILY.recurrent, qwen3_next.Qwen3NextConfig()
    return (granite_hybrid.FAMILY.recurrent,
            granite_hybrid.GraniteHybridConfig())


class TestTheRuleAtTheCells:
    """The table a reviewer needs to see that no cell's reader changed,
    and the one the next kernel PR edits."""

    @pytest.mark.parametrize("where", ["one_device", "tp", "interpret"])
    @pytest.mark.parametrize("cell", CELLS)
    def test_the_readers_a_cells_build_gets(self, cell, where):
        row, engine, kind = CELLS[cell]
        policy = resolve_serving_kernels(
            tp=where == "tp", interpret=where == "interpret",
            recurrent=kind == "state", chunk=(
                engine.get("prefill_chunk") or engine["prefill_bucket"],
                row.head_width or row.key_width),
            state_block=_state_block(cell) if kind == "state" else None)
        if kind == "latent":
            policy = policy._replace(decode=latent_reader(policy.decode))
        readers = (policy.decode[0], policy.chunk[0])
        # the one family that states a block of its chunked rule (PR 50)
        blocked = cell.startswith("qwen3-next")
        if where == "one_device":
            assert readers == ON_ONE_DEVICE[cell]
            assert policy.state_step == ("pallas" if kind == "state"
                                         else "xla")
            assert policy.state_chunk[0] == ("pallas" if blocked else "xla")
            assert policy.fallbacks == ()
            return
        assert policy.state_chunk[0] == "xla"
        assert policy.state_chunk[1].startswith(
            "the family states no block" if not blocked
            else "tp:" if where == "tp" else "interpret:")
        assert readers == ("xla", "xla")
        assert policy.decode[1].startswith(
            "tp:" if where == "tp" else "interpret:")
        # a mesh takes the state's kernel, visibly; off the chip it runs
        # in interpret mode
        assert policy.state_step == (
            "pallas" if kind == "state" and where == "interpret" else "xla")
        assert len(policy.fallbacks) == (where == "tp") * (
            (kind == "state") + blocked)


# --------------------------------------- nothing reads the old switches
OLD_SWITCHES = {
    "DSTPU_PAGED_ATTENTION": "xla", "DSTPU_FORCE_PAGED_PALLAS": "1",
    "DSTPU_PAGED_V1": "1", "DSTPU_FUSED_SAMPLING": "on",
    "DSTPU_FORCE_FUSED_SAMPLING": "1", "DSTPU_FORCE_ADAM_PALLAS": "1",
}
KERNELS_BLOCK = {"paged_attention": "pallas_v2", "fused_sampling": "on"}


class TestNothingReadsTheOldSwitches:
    @pytest.mark.parametrize("name", OLD_SWITCHES)
    def test_an_old_env_switch_moves_nothing(self, name, monkeypatch):
        kw = dict(interpret=False, recurrent=True, chunk=(1024, 128))
        default = resolve_serving_kernels(**kw)
        monkeypatch.setenv(name, OLD_SWITCHES[name])
        assert resolve_serving_kernels(**kw) == default
        assert default.decode[0] == "dstpu_paged_decode"

    @pytest.mark.parametrize("door", ["training_config", "serving_config",
                                      "init_serving", "init_inference",
                                      "encoder"])
    def test_a_kernels_block_is_refused_by_name(self, door, gpt2_model):
        """Not dropped silently: a user who forced a kernel learns that
        it no longer is, and where to read on."""
        cfg, params = gpt2_model
        with pytest.raises(ValueError, match="MIGRATION.md") as e:
            if door == "training_config":
                Config.from_dict({"train_batch_size": 1,
                                  "kernels": dict(KERNELS_BLOCK)})
            elif door == "serving_config":
                serving_engine(params, cfg, kernels=dict(KERNELS_BLOCK))
            elif door == "init_serving":
                init_serving(params, cfg,
                             config={"kernels": dict(KERNELS_BLOCK)})
            elif door == "init_inference":
                init_inference(apply_fn=lambda p, x: x, params=params,
                               config={"kernels": dict(KERNELS_BLOCK)})
            else:
                from deepspeed_tpu.models import bert

                serving_engine(None, bert.BertConfig.tiny(),
                               kernels={"paged_attention": "auto"})
        assert "`kernels` block is gone" in str(e.value)

    def test_no_environment_read_under_inference_or_ops(self):
        root = pathlib.Path(deepspeed_tpu.__file__).parent
        reads = [str(f.relative_to(root))
                 for d in ("inference", "ops")
                 for f in sorted((root / d).rglob("*.py"))
                 if "os.environ" in f.read_text()
                 or "getenv" in f.read_text()]
        assert reads == []
        # and the two modules that read a switch of their own are gone
        for gone in ("adam_pallas", "sampling_pallas"):
            assert importlib.util.find_spec(
                f"deepspeed_tpu.ops.{gone}") is None


# --------------------------------------------------- engine-level policy
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


class TestEnginePolicy:
    def test_statusz_reports_the_readers_and_nothing_to_request(
            self, gpt2_model, devices):
        cfg, params = gpt2_model
        eng = serving_engine(params, cfg, telemetry=True, **KW)
        out = serve_all(eng)
        assert all(len(out[rid]) == len(p) + n
                   for rid, (p, n) in PROMPTS.items())
        kz = eng.statusz()["kernels"]
        assert sorted(kz) == ["chunk", "decode", "experts", "fallbacks",
                              "state_chunk", "state_step", "window"]
        for row in ("decode", "chunk", "window", "state_chunk"):
            reader = kz[row]["reader"]
            assert reader == "xla" or reader.startswith("dstpu_"), kz
            assert kz[row]["reason"]
        assert (kz["state_step"], kz["fallbacks"]) == ("xla", [])
        # a CPU engine gathers, so the row scatter writes a decode step's row
        assert kz["decode"]["write"] == "scatter"
        assert "interpret" in kz["decode"]["reason"]
        # the reader is /statusz's to name; what the steps dispatched
        # is the step ledger's to count (/statusz "steps"), beside the
        # fetches the registry counts
        cnt = eng.registry.snapshot()["counters"]
        names = sorted(n for n in cnt if n.startswith("serving_kernel_"))
        assert names == ["serving_kernel_fallbacks"]
        assert cnt["serving_kernel_fallbacks"] == 0
        steps = eng.statusz()["steps"]
        assert steps["programs"]["decode"][0] \
            + steps["programs"]["decode_ahead"][0] \
            >= cnt["serving_decode_syncs"] > 0
        assert steps["programs"]["prefill"][0] >= len(PROMPTS)
        (last,) = steps["rows"]
        # the last step read the decode that flew in behind the one before
        assert last["programs"]["decode"][0] \
            + last["programs"]["decode_ahead"][0] <= 1
        eng.shutdown()

    def test_zero_inference_rejects_quantized_resident(
            self, llama_model, devices):
        cfg, params = llama_model
        with pytest.raises(NotImplementedError,
                           match="quantized_resident"):
            serving_engine(
                params, cfg, prefix_cache=True,
                kv_tier={"enabled": True, "quantize_cold": True,
                         "quantized_resident": True},
                zero_inference={"enabled": True, "tier": "host"}, **KW)


def churn_prompts(vocab, groups=3, per=2, prefix_len=24, tail_len=4,
                  seed=0):
    rng = np.random.default_rng(seed)
    prefs = [rng.integers(1, vocab, prefix_len).tolist()
             for _ in range(groups)]
    out = []
    for _ in range(2):
        for p in prefs:
            for _ in range(per):
                out.append(p + rng.integers(1, vocab, tail_len).tolist())
    return out


# ------------------------------------------------ prequantized tier pool
PAGE_SHAPE = (2, 2, 8, 16)          # (L, KV, ps, Dh)


def _tier_cfg(**kw):
    kw.setdefault("enabled", True)
    return KVTierConfig.coerce(kw)


def _rand_page(seed=0):
    rng = np.random.default_rng(seed)
    return (3.0 * rng.standard_normal(PAGE_SHAPE)).astype(np.float32)


def _pool_bufs(pool, key):
    names, shapes, dtypes = pool.entry_meta(key)
    bufs = [pool.get_submit(n, s, d)
            for n, s, d in zip(names, shapes, dtypes)]
    pool.fence_reads()
    return bufs


class TestPrequantizedPool:
    """demote_prequantized / decode_quantized: the codes the device
    holds are the codes the tier stores are the codes a promotion
    publishes — verbatim, checksum-verified, no requantization step
    anywhere in the round trip."""

    def test_codes_roundtrip_verbatim(self):
        from deepspeed_tpu.inference.kv_tier import KVTierPool

        pool = KVTierPool(_tier_cfg(quantize_cold=True), PAGE_SHAPE,
                          np.float32)
        kq, ks = quantize_page(_rand_page(1))
        vq, vs = quantize_page(_rand_page(2))
        assert pool.demote_prequantized(b"P", kq, ks, vq, vs) == "host"
        rkq, rks, rvq, rvs = pool.decode_quantized(
            b"P", _pool_bufs(pool, b"P"))
        np.testing.assert_array_equal(rkq, kq)
        np.testing.assert_array_equal(rvq, vq)
        np.testing.assert_array_equal(rks, ks)
        np.testing.assert_array_equal(rvs, vs)

    def test_interchangeable_with_host_quantize(self):
        # a prequantized demote and a host-side quantize of the same
        # values must produce interchangeable entries
        from deepspeed_tpu.inference.kv_tier import KVTierPool

        pool = KVTierPool(_tier_cfg(quantize_cold=True), PAGE_SHAPE,
                          np.float32)
        k, v = _rand_page(3), _rand_page(4)
        pool.demote(b"H", k, v)
        kq, ks = quantize_page(k)
        vq, vs = quantize_page(v)
        pool.demote_prequantized(b"D", kq, ks, vq, vs)
        h = pool.decode_quantized(b"H", _pool_bufs(pool, b"H"))
        d = pool.decode_quantized(b"D", _pool_bufs(pool, b"D"))
        for a, b in zip(h, d):
            np.testing.assert_array_equal(a, b)

    def test_dense_entry_rejected(self):
        from deepspeed_tpu.inference.kv_tier import KVTierPool

        pool = KVTierPool(_tier_cfg(), PAGE_SHAPE, np.float32)
        pool.demote(b"X", _rand_page(5), _rand_page(6))
        with pytest.raises(ValueError, match="dense entry"):
            pool.decode_quantized(b"X", _pool_bufs(pool, b"X"))
        kq, ks = quantize_page(_rand_page(7))
        with pytest.raises(ValueError, match="quantize_cold"):
            pool.demote_prequantized(b"Y", kq, ks, kq, ks)

    def test_corruption_caught_before_publish(self):
        from deepspeed_tpu.faults import ChecksumError
        from deepspeed_tpu.inference.kv_tier import KVTierPool

        pool = KVTierPool(_tier_cfg(quantize_cold=True), PAGE_SHAPE,
                          np.float32)
        kq, ks = quantize_page(_rand_page(8))
        vq, vs = quantize_page(_rand_page(9))
        pool.demote_prequantized(b"C", kq, ks, vq, vs)
        entry = pool.entries[b"C"]
        entry.data[0].flat[0] ^= 0x7F        # torn-write stand-in
        with pytest.raises(ChecksumError):
            pool.decode_quantized(b"C", _pool_bufs(pool, b"C"))


# ---------------------------------------------------- quantized_resident
class TestQuantizedResident:
    """int8-resident promoted pages: promotions publish stored codes
    directly (no dequant→scatter), counter-verified and leak-checked.
    Token identity vs the dense engine is NOT the contract here — the
    resident cache itself is int8 under the documented rtol — the
    contract is completion + verbatim code motion + zero page leaks."""

    QRES = {"enabled": True, "quantize_cold": True,
            "quantized_resident": True}

    @pytest.mark.slow
    def test_promote_path_counters_and_leaks(self, gpt2_model, devices):
        cfg, params = gpt2_model
        prompts = churn_prompts(cfg.vocab_size, seed=19)
        eng = serving_engine(params, cfg, prefix_cache=True,
                             kv_tier=dict(self.QRES), max_batch=2,
                             page_size=8, num_pages=12, max_seq=64,
                             prefill_bucket=8)
        for i, p in enumerate(prompts):
            eng.submit(i, p, max_new_tokens=6)
        outs = eng.run()
        assert len(outs) == len(prompts)
        # run() returns prompt + generated: every request decoded its
        # full budget off the int8-resident cache
        assert all(len(outs[i]) == len(p) + 6
                   for i, p in enumerate(prompts))
        cnt = eng.registry.snapshot()["counters"]
        # pages moved through the tier AND the promotions published
        # int8 codes directly (the dequant-scatter was skipped)
        assert cnt["kv_tier_demoted_pages"] > 0
        assert cnt["kv_tier_promoted_pages"] > 0
        assert cnt["kv_tier_quant_resident_promotes"] > 0
        assert eng.check_leaks() == []
        kz = eng.statusz()["kv_tier"]
        assert kz["quantized_resident"] is True
        # the device cache really is int8 + f32 scales
        assert eng.cache.k.dtype == jnp.int8
        assert eng.cache.k_scale.dtype == jnp.float32

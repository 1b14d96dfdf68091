"""The blocked chunk reader against the XLA gather, in interpret mode on
the CPU, at the shapes the cells send (``test_serving_kernels_interpret.py``
holds the decode kernel's cases; ``test_serving_kernels.py`` the rule that
chooses between the readers)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.kernels import (paged_chunk_attention_reference,
                                             paged_chunk_attention_v2,
                                             paged_reader,
                                             resolve_serving_kernels)
from deepspeed_tpu.models import gpt2, llama


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

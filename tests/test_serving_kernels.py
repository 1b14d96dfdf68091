"""Serving kernel-dispatch policy + the int8-dequant-fused / fused-
sampling Pallas hot path (ref: DeepSpeed-FastGen's kernel injection —
the serving engine picks kernels ONCE at build, never at trace time).

Oracles:
  * the XLA gather/sampler twins — forced Pallas kernels must serve
    token-identical greedy output across every decode mode
    (interpret-mode on CPU is the correctness harness);
  * ``dequantize_pages`` — the dequant-fused attention kernel must match
    the reference computed over host-dequantized pages, and sit within
    ``KV_TIER_QUANT_RTOL`` of the exact-path reference;
  * ``resolve_serving_kernels`` — env/config resolution happens once,
    TP demotions are VISIBLE (fallback rows + counter), and the policy
    ``/statusz`` reports is the one the compiled programs baked.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.config import Config, KernelsConfig, KVTierConfig
from deepspeed_tpu.inference.kernels import (
    dequantize_pages, paged_attention_reference,
    paged_chunk_attention_reference, paged_chunk_attention_v2,
    paged_chunk_attention_v2_quant, paged_decode_attention_v2,
    paged_decode_attention_v2_quant, paged_reader, quantize_kv_rows,
    resolve_serving_kernels)
from deepspeed_tpu.inference.kv_tier import KV_TIER_QUANT_RTOL, quantize_page
from deepspeed_tpu.inference.serving import _sample_rows, serving_engine
from deepspeed_tpu.models import gpt2, llama
from deepspeed_tpu.ops.sampling_pallas import (
    _FUSED_SAMPLE_MIN_ROWS_X_VOCAB, fused_greedy_rows, fused_sample_rows,
    pallas_sample_gate)
from deepspeed_tpu.topology import MeshSpec, set_current_mesh

ENV_VARS = ("DSTPU_PAGED_ATTENTION", "DSTPU_FORCE_PAGED_PALLAS",
            "DSTPU_PAGED_V1", "DSTPU_FUSED_SAMPLING",
            "DSTPU_FORCE_FUSED_SAMPLING")


@pytest.fixture(autouse=True)
def clean_kernel_env(monkeypatch):
    for v in ENV_VARS:
        monkeypatch.delenv(v, raising=False)


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
class TestKernelsConfig:
    def test_coerce_forms(self):
        assert KernelsConfig.coerce(None).paged_attention == "auto"
        k = KernelsConfig.coerce({"paged_attention": "pallas_v2",
                                  "fused_sampling": "on"})
        assert (k.paged_attention, k.fused_sampling) == ("pallas_v2", "on")
        assert KernelsConfig.coerce(k) is k
        with pytest.raises(TypeError):
            KernelsConfig.coerce(3)

    def test_invalid_values_raise(self):
        with pytest.raises(ValueError):
            KernelsConfig.coerce({"paged_attention": "pallas_v3"})
        with pytest.raises(ValueError):
            KernelsConfig.coerce({"fused_sampling": "maybe"})

    def test_top_level_config_block(self):
        cfg = Config.from_dict(
            {"kernels": {"paged_attention": "xla"}})
        assert cfg.kernels.paged_attention == "xla"
        assert cfg.kernels.fused_sampling == "auto"
        # no block → all-auto defaults (auto IS the policy; no enabled
        # switch exists)
        assert Config.from_dict({}).kernels.paged_attention == "auto"

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
        p = resolve_serving_kernels()
        assert p.paged_attention == "auto"
        # fused auto resolves off at every measured shape (the
        # committed fused_sample_vs_xla sweep)
        assert p.fused_sampling == "off"
        assert p.env_overrides == () and p.fallbacks == ()

    def test_resolved_policy_passes_through(self):
        p = resolve_serving_kernels(
            {"paged_attention": "pallas_v2", "fused_sampling": "on"})
        # builders resolve once and hand the SAME object to the engine
        assert resolve_serving_kernels(p, tp=True) is p

    def test_env_names_mode_directly(self, monkeypatch):
        monkeypatch.setenv("DSTPU_PAGED_ATTENTION", "xla")
        monkeypatch.setenv("DSTPU_FUSED_SAMPLING", "on")
        p = resolve_serving_kernels(
            {"paged_attention": "pallas_v2", "fused_sampling": "off"})
        assert (p.paged_attention, p.fused_sampling) == ("xla", "on")
        assert ("paged_attention", "xla",
                "DSTPU_PAGED_ATTENTION") in p.env_overrides
        assert ("fused_sampling", "on",
                "DSTPU_FUSED_SAMPLING") in p.env_overrides

    def test_legacy_force_flags(self, monkeypatch):
        monkeypatch.setenv("DSTPU_FORCE_PAGED_PALLAS", "1")
        assert resolve_serving_kernels().paged_attention == "pallas_v2"
        monkeypatch.setenv("DSTPU_PAGED_V1", "1")
        assert resolve_serving_kernels().paged_attention == "pallas_v1"
        monkeypatch.setenv("DSTPU_FORCE_FUSED_SAMPLING", "1")
        assert resolve_serving_kernels().fused_sampling == "on"

    def test_named_env_wins_over_legacy(self, monkeypatch):
        monkeypatch.setenv("DSTPU_FORCE_PAGED_PALLAS", "1")
        monkeypatch.setenv("DSTPU_PAGED_ATTENTION", "xla")
        p = resolve_serving_kernels()
        assert p.paged_attention == "xla"
        assert len(p.env_overrides) == 1

    def test_invalid_env_raises(self, monkeypatch):
        monkeypatch.setenv("DSTPU_PAGED_ATTENTION", "gather")
        with pytest.raises(ValueError, match="DSTPU_PAGED_ATTENTION"):
            resolve_serving_kernels()

    def test_tp_demotes_forced_pallas_visibly(self):
        # satellite: the old gate silently returned False under TP;
        # the resolver must demote WITH a recorded reason instead
        for forced in ("pallas_v1", "pallas_v2"):
            p = resolve_serving_kernels({"paged_attention": forced},
                                        tp=True)
            assert p.paged_attention == "xla"
            assert len(p.fallbacks) == 1
            field, demoted_to, reason = p.fallbacks[0]
            assert forced in field and demoted_to == "xla"
            assert "tp_unsupported" in reason
        # auto under TP carries no fallback row — nothing was forced
        assert resolve_serving_kernels(tp=True).fallbacks == ()

    def test_as_dict_shape(self):
        d = resolve_serving_kernels(
            {"paged_attention": "pallas_v2"}, tp=True).as_dict()
        assert d["paged_attention"] == "xla"
        assert d["fallbacks"][0]["demoted_to"] == "xla"
        assert "tp_unsupported" in d["fallbacks"][0]["reason"]


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
    }

    @pytest.mark.parametrize("lens", LENS)
    @pytest.mark.parametrize("pool", ["whole_pool_traced_layer",
                                      "one_layer"])
    @pytest.mark.parametrize("heads", [(4, 4), (8, 2)],
                             ids=["mha_4_4", "gqa_8_2"])
    def test_matches_the_gather(self, heads, pool, lens):
        H, KV = heads
        lens = np.asarray(self.LENS[lens], np.int32)
        B, P = len(lens), len(lens) * self.MP + 1
        rng = np.random.default_rng(7)
        shape = (self.LAYERS, KV, P, self.PS, self.DH)
        k = jnp.asarray(rng.normal(size=shape), jnp.float32)
        v = jnp.asarray(rng.normal(size=shape), jnp.float32)
        q = jnp.asarray(rng.normal(size=(B, H, self.DH)), jnp.float32)
        table = (rng.permutation(P - 1)[:B * self.MP] + 1).reshape(
            B, self.MP).astype(np.int32)
        # past a row's live pages the table is stale: ids that name no
        # page of the pool, which the kernel must never dereference (the
        # oracle gets them clamped: it masks what it gathers)
        stale = np.arange(self.MP)[None] >= -(-lens[:, None] // self.PS)
        oracle_table = jnp.asarray(table)
        table = jnp.asarray(np.where(stale, P + 1000, table))
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
    def test_auto_runs_it_where_the_shapes_are_whole_blocks(self, shape):
        kw = dict(decode=False, tp=False, interpret=False, quant=False,
                  tokens=shape[0], head_dim=shape[1])
        for policy in (None, "auto"):
            reader, why = paged_reader(policy, **kw)
            assert reader == "pallas_v2" and "chunk in 128-row blocks" in why

    @pytest.mark.parametrize("off,why", [
        (dict(tp=True), "tp"), (dict(quant=True), "int8-resident"),
        (dict(interpret=True), "interpret"),
        (dict(tokens=5), "not whole 128-row blocks"),
        (dict(head_dim=64), "not whole 128-lane tiles"),
    ], ids=["tp", "quant", "interpret", "five_rows", "head_of_64"])
    def test_auto_keeps_the_gather_and_says_why(self, off, why):
        kw = dict(decode=False, tp=False, interpret=False, quant=False,
                  tokens=1024, head_dim=128)
        kw.update(off)
        for policy in (None, "auto"):
            reader, reason = paged_reader(policy, **kw)
            assert reader == "xla" and why in reason
        # a forced policy keeps meaning what it meant
        for forced in ("xla", "pallas_v1", "pallas_v2"):
            assert paged_reader(forced, **kw) == (forced, "forced")

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
        chunk = resolve_serving_kernels(None, interpret=False, chunk=(
            256, row.head_width or row.key_width)).chunk
        assert chunk == ("xla",
                         "chunk program: a head is not whole 128-lane tiles")
        for fam_cfg in (llama.LlamaConfig(vocab_size=64, dim=512, n_layers=1,
                                          n_heads=4, n_kv_heads=2, ffn_dim=64),
                        gpt2.GPT2Config.tiny()):
            assert decoder_family(fam_cfg).cache_row(fam_cfg).head_width == 0

    @pytest.mark.parametrize("chunk,reader", [
        ((1024, 128), "pallas_v2"), ((256, 64), "xla"), ((0, 128), "xla")])
    def test_statusz_names_the_chunk_reader(self, chunk, reader):
        """``/statusz``'s ``kernels`` block shows the ``chunk`` row, the
        reader of the build's chunk programs with its reason, beside
        ``decode`` and ``window``."""
        d = resolve_serving_kernels(None, interpret=False,
                                    chunk=chunk).as_dict()
        assert d["chunk"]["reader"] == reader and d["chunk"]["reason"]
        assert set(d) >= {"decode", "chunk", "window", "state_step"}
        # in interpret mode (the CPU's engines) every build gathers
        assert resolve_serving_kernels(None, interpret=True, chunk=chunk) \
            .as_dict()["chunk"]["reader"] == "xla"


# ----------------------------------------------------------- shape gates
class TestSampleGatePolicy:
    def test_gate_policy(self):
        assert not pallas_sample_gate(interpret=True)
        # unknown shapes (engine build time) resolve conservatively off
        assert not pallas_sample_gate()
        big = _FUSED_SAMPLE_MIN_ROWS_X_VOCAB
        assert pallas_sample_gate(batch=big // 32000 + 1, vocab=32000)
        assert not pallas_sample_gate(batch=8, vocab=32000)


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


# ------------------------------------------------------- fused sampling
class TestFusedSampling:
    """Greedy rows are bit-exact vs jnp.argmax (first-occurrence
    contract); temperature rows run the identical categorical math on
    the same key streams, so the fused and XLA samplers agree on every
    row."""

    @pytest.mark.parametrize("B,V", [(1, 7), (3, 37), (8, 128),
                                     (9, 257), (16, 500)])
    def test_greedy_bit_exact(self, B, V):
        logits = jax.random.normal(jax.random.PRNGKey(B * V), (B, V))
        got = fused_greedy_rows(logits, interpret=True)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(jnp.argmax(logits, -1)))

    def test_greedy_first_occurrence_ties(self):
        # duplicate maxima: the kernel must report the FIRST index,
        # matching jnp.argmax — the serving identity gates depend on it
        logits = jnp.zeros((4, 200)).at[:, 150].set(5.0).at[:, 30].set(5.0)
        got = np.asarray(fused_greedy_rows(logits, interpret=True))
        np.testing.assert_array_equal(got, np.full(4, 30))

    def test_sampler_twin_agrees_rowwise(self):
        B, V = 6, 97
        logits = jax.random.normal(jax.random.PRNGKey(3), (B, V))
        keys = jax.random.split(jax.random.PRNGKey(7), B)
        temps = jnp.asarray([0.0, 1.0, 0.0, 0.7, 2.0, 0.0])
        got = fused_sample_rows(logits, keys, temps, interpret=True)
        want = _sample_rows(logits, keys, temps)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_temperature_distribution_sanity(self):
        # sharply-biased logits at temp 1.0: the favored token must
        # dominate; a flat draw (or an argmax leak into temp rows)
        # cannot pass this
        B, V = 256, 16
        logits = jnp.zeros((B, V)).at[:, 5].set(3.0)
        keys = jax.random.split(jax.random.PRNGKey(11), B)
        toks = np.asarray(fused_sample_rows(
            logits, keys, jnp.ones((B,)), interpret=True))
        frac = np.mean(toks == 5)
        # softmax prob of token 5 ≈ 0.57 at these logits
        assert 0.4 < frac < 0.75
        assert len(np.unique(toks)) > 1     # it actually sampled


# --------------------------------------- dequant-fused attention kernel
def _quant_paged_setup(seed, B, H, KV, Dh, P, ps, mp, lens):
    rng = np.random.default_rng(seed)
    k = jnp.asarray(rng.normal(size=(KV, P, ps, Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(KV, P, ps, Dh)), jnp.float32)
    kq, ks = quantize_kv_rows(k)
    vq, vs = quantize_kv_rows(v)
    table = jnp.asarray(
        rng.permutation(P)[:B * mp].reshape(B, mp), jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    return k, v, kq, ks, vq, vs, table, lens


class TestQuantKernelIdentity:
    """The int8-dequant-fused kernel vs two oracles: (tight) the gather
    reference over host-dequantized pages — same values, so float-level
    agreement; (bounded) the exact-path reference — within the codec's
    documented KV_TIER_QUANT_RTOL regime."""

    def test_decode_matches_dequantized_reference(self):
        B, H, KV, Dh, ps, mp = 3, 4, 2, 16, 8, 4
        k, v, kq, ks, vq, vs, table, lens = _quant_paged_setup(
            0, B, H, KV, Dh, 16, ps, mp, [5, 17, 32])
        q = jax.random.normal(jax.random.PRNGKey(1), (B, H, Dh))
        got = paged_decode_attention_v2_quant(
            q, kq, ks, vq, vs, table, lens, interpret=True)
        want = paged_attention_reference(
            q, dequantize_pages(kq, ks, jnp.float32),
            dequantize_pages(vq, vs, jnp.float32), table, lens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=1e-5)

    @pytest.mark.slow
    def test_decode_within_quant_bound_of_exact(self):
        B, H, KV, Dh, ps, mp = 2, 4, 2, 16, 8, 3
        k, v, kq, ks, vq, vs, table, lens = _quant_paged_setup(
            2, B, H, KV, Dh, 8, ps, mp, [9, 22])
        q = jax.random.normal(jax.random.PRNGKey(3), (B, H, Dh))
        got = paged_decode_attention_v2_quant(
            q, kq, ks, vq, vs, table, lens, interpret=True)
        exact = paged_attention_reference(q, k, v, table, lens)
        # attention output error under per-row int8 KV stays within a
        # few quantization steps of the unit-scale values
        np.testing.assert_allclose(np.asarray(got), np.asarray(exact),
                                   atol=12 * KV_TIER_QUANT_RTOL)

    @pytest.mark.slow
    def test_chunk_matches_dequantized_reference(self):
        B, C, H, KV, Dh, ps, mp = 2, 5, 4, 2, 16, 8, 4
        k, v, kq, ks, vq, vs, table, _ = _quant_paged_setup(
            4, B, H, KV, Dh, 16, ps, mp, [0, 0])
        start = jnp.asarray([3, 11], jnp.int32)
        q = jax.random.normal(jax.random.PRNGKey(5), (B, C, H, Dh))
        got = paged_chunk_attention_v2_quant(
            q, kq, ks, vq, vs, table, start, interpret=True)
        want = paged_chunk_attention_reference(
            q, dequantize_pages(kq, ks, jnp.float32),
            dequantize_pages(vq, vs, jnp.float32), table, start)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=1e-5)

    @pytest.mark.slow
    def test_chunk_ppcb_sweep_and_mha(self):
        # ppcb > live pages, ppcb = 1, and the MHA (G=1) layout
        B, C, H, KV, Dh, ps, mp = 1, 3, 2, 2, 16, 4, 6
        k, v, kq, ks, vq, vs, table, _ = _quant_paged_setup(
            6, B, H, KV, Dh, 8, ps, mp, [0])
        start = jnp.asarray([13], jnp.int32)
        q = jax.random.normal(jax.random.PRNGKey(7), (B, C, H, Dh))
        want = paged_chunk_attention_reference(
            q, dequantize_pages(kq, ks, jnp.float32),
            dequantize_pages(vq, vs, jnp.float32), table, start)
        for ppcb in (1, 2, 16):
            got = paged_chunk_attention_v2_quant(
                q, kq, ks, vq, vs, table, start,
                pages_per_block=ppcb, interpret=True)
            np.testing.assert_allclose(np.asarray(got),
                                       np.asarray(want),
                                       atol=2e-5, rtol=1e-5)


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
    @pytest.mark.slow
    def test_statusz_counters_and_identity_fused_sampling(
            self, gpt2_model, devices):
        cfg, params = gpt2_model
        base = serving_engine(params, cfg, **KW)
        want = serve_all(base)

        eng = serving_engine(params, cfg,
                             kernels={"fused_sampling": "on"}, **KW)
        assert serve_all(eng) == want      # greedy identity, fused on
        kz = eng.statusz()["kernels"]
        assert kz["paged_attention"] == "auto"
        assert kz["fused_sampling"] == "on"
        assert kz["fallbacks"] == []
        cnt = eng.registry.snapshot()["counters"]
        assert cnt["serving_kernel_dispatch_paged_auto"] > 0
        assert cnt["serving_kernel_dispatch_sample_fused"] > 0
        assert cnt.get("serving_kernel_fallbacks", 0) == 0
        # the baseline engine dispatched the XLA sampler, visibly
        bcnt = base.registry.snapshot()["counters"]
        assert bcnt["serving_kernel_dispatch_sample_xla"] > 0

    def test_env_override_reaches_statusz(self, gpt2_model, devices,
                                          monkeypatch):
        monkeypatch.setenv("DSTPU_FUSED_SAMPLING", "on")
        cfg, params = gpt2_model
        eng = serving_engine(params, cfg, **KW)
        kz = eng.statusz()["kernels"]
        assert kz["fused_sampling"] == "on"
        assert ["fused_sampling", "on",
                "DSTPU_FUSED_SAMPLING"] in kz["env_overrides"]
        eng.shutdown()

    def test_pallas_v1_rejects_quantized_resident(self, gpt2_model,
                                                  devices):
        cfg, params = gpt2_model
        with pytest.raises(ValueError, match="pallas_v1"):
            serving_engine(
                params, cfg, prefix_cache=True,
                kernels={"paged_attention": "pallas_v1"},
                kv_tier={"enabled": True, "quantize_cold": True,
                         "quantized_resident": True}, **KW)

    def test_encoder_rejects_pinned_kernels(self, devices):
        from deepspeed_tpu.models import bert

        cfg = bert.BertConfig.tiny()
        params = bert.init_params(jax.random.PRNGKey(0), cfg)
        with pytest.raises(NotImplementedError, match="paged-KV"):
            serving_engine(params, cfg,
                           kernels={"paged_attention": "pallas_v2"})
        # an all-auto block is inert and must not trip the guard
        serving_engine(params, cfg, kernels={"paged_attention": "auto"})

    @pytest.mark.slow
    def test_tp_visible_fallback_both_arms(self, llama_model, devices):
        """Satellite regression: forced pallas under TP serves (demoted
        to xla) and the demotion is VISIBLE — statusz reason + counter —
        for both forced arms, token-identical to the unforced TP run."""
        cfg, params = llama_model
        mesh = MeshSpec.build({"model": 2}, devices=jax.devices()[:2])
        try:
            base = serving_engine(params, cfg, mesh=mesh, **KW)
            want = serve_all(base)
            for forced in ("pallas_v1", "pallas_v2"):
                eng = serving_engine(
                    params, cfg, mesh=mesh,
                    kernels={"paged_attention": forced}, **KW)
                assert serve_all(eng) == want
                kz = eng.statusz()["kernels"]
                assert kz["paged_attention"] == "xla"
                assert len(kz["fallbacks"]) == 1
                fb = kz["fallbacks"][0]
                assert forced in fb["field"]
                assert "tp_unsupported" in fb["reason"]
                cnt = eng.registry.snapshot()["counters"]
                assert cnt["serving_kernel_fallbacks"] == 1
                eng.shutdown()
        finally:
            set_current_mesh(None)


# ------------------------------------------- forced-kernel identity gates
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


FORCED = {"paged_attention": "pallas_v2", "fused_sampling": "on"}

MODES = {
    "plain": {},
    "chunked_decode": {"decode_chunk": 4},
    "split_fuse": {"prefill_chunk": 8},
    "speculative": {"speculative": {"enabled": True, "draft_tokens": 3}},
    "prefix_cache": {"prefix_cache": True},
}


class TestForcedKernelIdentity:
    """Acceptance gate: with BOTH new kernels forced on (interpret mode
    on CPU), greedy serving is token-identical to the XLA baseline
    across every decode mode — mismatched_requests would be 0 on the
    serving A/B."""

    @pytest.mark.slow
    @pytest.mark.parametrize("mode", sorted(MODES))
    def test_token_identity(self, mode, gpt2_model, devices):
        cfg, params = gpt2_model
        kw = dict(KW, **MODES[mode])
        prompts = churn_prompts(cfg.vocab_size, seed=13)[:6]
        base = serving_engine(params, cfg, **kw)
        for i, p in enumerate(prompts):
            base.submit(i, p, max_new_tokens=5)
        want = base.run()
        eng = serving_engine(params, cfg, kernels=dict(FORCED), **kw)
        for i, p in enumerate(prompts):
            eng.submit(i, p, max_new_tokens=5)
        assert eng.run() == want
        cnt = eng.registry.snapshot()["counters"]
        assert cnt["serving_kernel_dispatch_paged_pallas_v2"] > 0
        assert cnt["serving_kernel_dispatch_sample_fused"] > 0

    @pytest.mark.slow
    def test_zero_inference_fused_sampling(self, llama_model, devices):
        cfg, params = llama_model
        prompts = churn_prompts(cfg.vocab_size, groups=2, per=1,
                                seed=17)[:4]
        kw = dict(KW, zero_inference={"enabled": True, "tier": "host"})
        base = serving_engine(params, cfg, **kw)
        for i, p in enumerate(prompts):
            base.submit(i, p, max_new_tokens=5)
        want = base.run()
        eng = serving_engine(
            params, cfg, kernels={"fused_sampling": "on"}, **kw)
        for i, p in enumerate(prompts):
            eng.submit(i, p, max_new_tokens=5)
        assert eng.run() == want

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

    @pytest.mark.slow
    def test_qres_with_forced_pallas_v2(self, gpt2_model, devices):
        # the dequant-fused kernel serves the int8-resident cache
        # end-to-end (interpret mode on CPU)
        cfg, params = gpt2_model
        prompts = churn_prompts(cfg.vocab_size, groups=2, per=1,
                                seed=23)[:4]
        eng = serving_engine(params, cfg, prefix_cache=True,
                             kv_tier=dict(self.QRES),
                             kernels={"paged_attention": "pallas_v2"},
                             max_batch=2, page_size=8, num_pages=16,
                             max_seq=64, prefill_bucket=8)
        for i, p in enumerate(prompts):
            eng.submit(i, p, max_new_tokens=5)
        outs = eng.run()
        assert len(outs) == len(prompts)
        assert eng.check_leaks() == []

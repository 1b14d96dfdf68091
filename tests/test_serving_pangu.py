"""The pangu_ultra_moe family on the serving path, against the benchmark's
plain float32 reference (``benchmark/reference/pangu_ultra_moe.py``, which
imports nothing from ``deepspeed_tpu.models``): latent attention over a
one-row-a-token page pool, the expert layer that holds a share of its
experts, the seam's refusals, names and counters.  Toy widths, seeded
weights, CPU; the Mosaic kernels run in interpret mode."""

import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.families import pangu_ultra_moe as bench_family  # noqa: E402
from benchmark.reference import pangu_ultra_moe as reference  # noqa: E402
from deepspeed_tpu.inference import kernels as K  # noqa: E402
from deepspeed_tpu.inference.generation import generator  # noqa: E402
from deepspeed_tpu.inference.paged_forward import forward_paged  # noqa: E402
from deepspeed_tpu.inference.serving import serving_engine  # noqa: E402
from deepspeed_tpu.models import pangu_ultra_moe as pg  # noqa: E402
from deepspeed_tpu.models.family import CacheRow, decoder_family  # noqa: E402
from deepspeed_tpu.ops import attention, attention_pallas  # noqa: E402
from deepspeed_tpu.parallel import moe  # noqa: E402
from deepspeed_tpu.topology import MeshSpec  # noqa: E402

CFG = pg.PanguUltraMoEConfig.tiny()
PAGE = 8


@pytest.fixture(scope="module")
def params():
    return pg.init_params(jax.random.PRNGKey(0), CFG)


def _reference_kw(cfg):
    return dict(n_heads=cfg.n_heads, kv_lora_rank=cfg.kv_lora_rank,
                qk_nope_dim=cfg.qk_nope_dim, qk_rope_dim=cfg.qk_rope_dim,
                top_k=cfg.top_k, first=cfg.experts_held[0],
                scale=cfg.routed_scaling_factor,
                normalize=cfg.norm_topk_prob, rope_theta=cfg.rope_theta,
                eps=cfg.norm_eps)


def _reference_logits(params, tokens, cfg=CFG):
    return np.asarray(reference.forward(params, jnp.asarray(tokens),
                                        **_reference_kw(cfg)))


def _cache(cfg, rows, max_seq, dtype=jnp.float32):
    """A latent pool with a shuffled page table, and its counter."""
    row = decoder_family(cfg).cache_row(cfg)
    mp = -(-max_seq // PAGE)
    pages = rows * mp + 1
    table = np.random.default_rng(5).permutation(pages - 1)[:rows * mp]
    return K.PagedKVCache(
        k=jnp.zeros((cfg.n_layers, 1, pages, PAGE, row.pool_width), dtype),
        v=None, table=jnp.asarray(table.reshape(rows, mp), jnp.int32),
        seq_lens=jnp.zeros((rows,), jnp.int32), page_size=PAGE,
        expert_rows=jnp.zeros((cfg.experts_held[1],), jnp.int32))


# ------------------------------------ (i) the paged path vs the reference
def test_chunked_prefill_then_decode_matches_the_reference(params):
    """Ragged rows: prompts of 5, 21 and 37 tokens (the last crosses
    pages and two chunk boundaries) go through chunks of 16 into the
    latent pages, then six decode steps run on all three rows at once;
    every position's logits match the reference's full forward."""
    rng = np.random.default_rng(1)
    lens, new, C = (5, 21, 37), 6, 16
    seqs = [rng.integers(0, CFG.vocab_size, n + new) for n in lens]
    want = [_reference_logits(params, s) for s in seqs]
    cache = _cache(CFG, len(lens), 64)
    got = [np.zeros_like(w) for w in want]
    # jitted: compiled once a shape (called eagerly the forward's loops
    # compile anew at every call)
    forward = jax.jit(lambda toks, c, continuation=False: forward_paged(
        params, toks, CFG, c, continuation=continuation, tp=False,
        interpret=True), static_argnames="continuation")
    for b, (n, seq) in enumerate(zip(lens, seqs)):
        for done in range(0, n, C):
            take = min(C, n - done)
            toks = np.zeros((1, C), np.int32)
            toks[0, :take] = seq[done:done + take]
            view = cache._replace(
                table=cache.table[b:b + 1],
                seq_lens=jnp.full((1,), done, jnp.int32))
            logits, view = forward(jnp.asarray(toks), view,
                                   continuation=True)
            got[b][done:done + take] = np.asarray(logits[0, :take])
            cache = cache._replace(k=view.k, expert_rows=view.expert_rows)
    cache = cache._replace(seq_lens=jnp.asarray(lens, jnp.int32))
    for j in range(new):
        toks = jnp.asarray([[s[n + j]] for n, s in zip(lens, seqs)],
                           jnp.int32)
        logits, cache = forward(toks, cache)
        for b, n in enumerate(lens):
            got[b][n + j] = np.asarray(logits[b, 0])
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-4, rtol=2e-4)
    # every row the programs routed was counted, padding rows too
    assert int(cache.expert_rows.sum()) > 0


@pytest.mark.parametrize("engine_kw", [
    dict(prefill_bucket=0, prefill_chunk=16),
    dict(prefill_bucket=16)], ids=["chunks", "whole_prompt"])
def test_the_engine_serves_the_reference_argmax(params, engine_kw):
    """Through ``serving_engine``: scheduler, allocator, boundary
    sampling and the decode program's packed fetch.  Greedy tokens are
    the reference's argmax given the served prefix; the counters say
    what the programs routed; one program a plain step."""
    eng = serving_engine(params, CFG, max_batch=3, page_size=PAGE,
                         num_pages=40, max_seq=96, cache_dtype=jnp.float32,
                         telemetry=True, **engine_kw)
    assert eng.cache.v is None and eng.cache.k.shape[1] == 1
    rng = np.random.default_rng(0)
    prompts = {i: rng.integers(0, CFG.vocab_size, n).tolist()
               for i, n in enumerate((5, 21, 37, 16))}
    for i, p in prompts.items():
        eng.submit(i, p, max_new_tokens=6)
    out = eng.run()
    for i, p in prompts.items():
        want = _reference_logits(params, out[i]).argmax(-1)
        assert out[i][len(p):] == want[len(p) - 1:-1].tolist()
    assert eng.check_leaks() == []
    counters = eng.registry.snapshot()["counters"]
    held = [counters[f"serving_expert_rows_{e}"]
            for e in range(CFG.experts_held[1])]
    routed = counters["serving_routed_rows"]
    assert routed % (CFG.top_k * CFG.n_expert_layers) == 0
    assert 0 < sum(held) < routed
    assert counters["serving_expert_pair_extra_passes"] == 0
    status = eng.statusz()["kernels"]["decode"]
    assert "latent rows" in status["reason"]


def test_a_chunk_routed_past_the_pair_buffer_makes_further_passes(
        monkeypatch):
    """4 held of 64 experts: the pair buffer of a 16-row chunk takes 8
    of its 64 pairs.  With every absent expert's gate column zero (a
    score of one half, under any held expert's that is positive) half
    the pairs are held: the chunk programs make further passes, the
    engine counts them, and the tokens are the reference's argmax."""
    import dataclasses

    cfg = dataclasses.replace(CFG, n_routed_experts=64)
    monkeypatch.setattr(moe, "_every_row_pays", lambda N, k, Eh: N < 16)
    monkeypatch.setattr(moe, "_ROW_GRANULE", 4)
    params = pg.init_params(jax.random.PRNGKey(1), cfg)
    params["blocks"]["gate"] = params["blocks"]["gate"].at[
        ..., cfg.experts_held[1]:].set(0)
    eng = serving_engine(params, cfg, max_batch=2, page_size=PAGE,
                         num_pages=40, max_seq=96, cache_dtype=jnp.float32,
                         telemetry=True, prefill_bucket=0, prefill_chunk=16)
    assert eng.cache.expert_rows.shape == (cfg.experts_held[1] + 1,)
    rng = np.random.default_rng(1)
    prompts = {i: rng.integers(0, cfg.vocab_size, n).tolist()
               for i, n in enumerate((33, 16))}
    for i, p in prompts.items():
        eng.submit(i, p, max_new_tokens=4)
    out = eng.run()
    for i, p in prompts.items():
        want = _reference_logits(params, out[i], cfg).argmax(-1)
        assert out[i][len(p):] == want[len(p) - 1:-1].tolist()
    counters = eng.registry.snapshot()["counters"]
    assert counters["serving_expert_pair_extra_passes"] > 0
    held = sum(counters[f"serving_expert_rows_{e}"]
               for e in range(cfg.experts_held[1]))
    assert held > counters["serving_routed_rows"] // 4


def test_statusz_names_the_latent_reader():
    assert K.latent_reader(K.paged_reader(
        decode=True, tp=False, interpret=False, quant=False))[0] \
        == "dstpu_mla_decode"
    reader, why = K.latent_reader(("xla", "interpret: no TPU backend"))
    assert reader == "xla" and "absorbed" in why


# --------------------------- (ii) absorbed == per head; kernel == XLA form
def _latent_case(rng, B=3, H=4, C=32, Dr=8, Dn=16, Dv=16, mp=6, L=2):
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    pages = B * mp + 1
    width = CacheRow(1, C + Dr, C, True).pool_width
    pool = jnp.zeros((L, 1, pages, PAGE, width), jnp.float32)
    pool = pool.at[..., :C + Dr].set(f(L, 1, pages, PAGE, C + Dr))
    table = jnp.asarray(rng.permutation(pages - 1)[:B * mp].reshape(B, mp),
                        jnp.int32)
    return pool, table, f(C, H, Dn), f(C, H, Dv), f


def test_absorbed_decode_is_the_per_head_attention():
    """Float32, tight: q~ = [q_nope W_UK^T | q_rope] against the cached
    rows, values the rows' first C numbers, then W_UV, is the published
    per-head attention over the same rows with this token's row
    written."""
    rng = np.random.default_rng(2)
    pool, table, w_uk, w_uv, f = _latent_case(rng)
    B, H, C, Dr, Dn = 3, 4, 32, 8, 16
    lens = jnp.asarray([0, 13, 40], jnp.int32)
    q, row = f(B, 1, H, Dn + Dr), f(B, 1, 1, C + Dr)
    scale = (Dn + Dr) ** -0.5
    attn, pool2 = K.latent_attention_step(
        q, row, w_uk, w_uv, scale, pool, 1, table, lens,
        continuation=False, prefill=False, reader="xla",
        flash_force_reference=True)
    rows = np.asarray(K._gather_rows(pool2, 1, table)[:, 0, :, :C + Dr])
    for b, n in enumerate(np.asarray(lens) + 1):
        c, k_rope = rows[b, :n, :C], rows[b, :n, C:]
        np.testing.assert_allclose(rows[b, n - 1], row[b, 0, 0], rtol=1e-6)
        k_nope = np.einsum("sc,chd->shd", c, w_uk)
        v = np.einsum("sc,chd->shd", c, w_uv)
        s = (np.einsum("hd,shd->hs", q[b, 0, :, :Dn], k_nope)
             + np.einsum("hd,sd->hs", q[b, 0, :, Dn:], k_rope)) * scale
        p = np.exp(s - s.max(-1, keepdims=True))
        want = np.einsum("hs,shd->hd", p / p.sum(-1, keepdims=True), v)
        np.testing.assert_allclose(attn[b, 0], want, atol=2e-5, rtol=2e-5)


# rows of ``PAGE`` = 8 tokens over a table of 6 pages, 2 pages a block
# where the case says so
LATENT_ROWS = {
    # empty, mid-page and multi-block rows; a block edge inside the
    # row's live pages and past them
    "ragged": [0, 13, 48],
    # a last block with a dead slot (3, 5 and 1 live pages)
    "dead_slots": [17, 40, 3],
    # a row's first block is started by the row before it: an empty
    # first and last row, one and two empty rows between live ones
    "handover": [0, 20, 0, 0, 33, 16, 0],
    # exactly a block's pages, one page more, two whole blocks
    "whole_blocks": [16, 17, 32],
}


@pytest.mark.parametrize("pages_per_block", [None, 2, 4])
@pytest.mark.parametrize("rows", LATENT_ROWS)
def test_mla_decode_kernel_is_the_xla_formulation(rows, pages_per_block):
    """Interpret mode, against the gather.  Past a row's live pages the
    kernel's table holds ids that name no page of the pool (the oracle's
    holds real pages, which it masks), and the two pages such an id lands
    on once the interpreter has clamped it are poison: nothing there may
    be read, not for a dead slot of a row's last block either."""
    rng = np.random.default_rng(3)
    lens = np.asarray(LATENT_ROWS[rows], np.int32)
    pool, table, _, _, f = _latent_case(rng, B=len(lens))
    poison = jnp.full_like(pool[:, :, :1], np.nan)
    pool = jnp.concatenate([poison, pool, poison], 2)
    pages, table = pool.shape[2], np.asarray(table) + 1
    stale = np.arange(table.shape[1])[None] >= -(-lens[:, None] // PAGE)
    stale_ids = np.where(np.arange(table.shape[1])[None] % 2, pages + 1000, -7)
    q = f(len(lens), 4, 40)
    want = K.latent_decode_reference(q, pool, jnp.asarray(table),
                                     jnp.asarray(lens), 0.2, 32, layer=1)
    got = K.latent_decode_attention(
        q, pool, jnp.asarray(np.where(stale, stale_ids, table)),
        jnp.asarray(lens), 0.2, 32, layer=1, interpret=True,
        pages_per_block=pages_per_block)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-6)
    assert not np.asarray(got)[lens == 0].any()     # an empty row: zeros


@pytest.mark.parametrize("start", [(0, 0), (0, 200)],
                         ids=["prompt", "chunk_over_history"])
def test_latent_flash_kernel_is_the_reference(start):
    """Interpret mode: q/k of 16 + 8 with the 8 shared by all heads, v
    of 16, queries at an offset into the keys."""
    rng = np.random.default_rng(4)
    B, T, S, H = 2, 256, 512, 2
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    args = (f(B, T, H, 16), f(B, T, H, 8), f(B, S, H, 16), f(B, S, 8),
            f(B, S, H, 16), jnp.asarray(start, jnp.int32), 0.3)
    np.testing.assert_allclose(
        attention_pallas.latent_flash_attention_tpu(*args, interpret=True),
        attention._latent_reference(*args), atol=2e-5, rtol=2e-5)


# ------------------------- (iii)-(v) the expert layer that holds a share
PATHS = {"every_expert_every_row": True, "grouped": False}


def _expert_case(rng, N, d=32, f=16, E=32):
    g = lambda *s: jnp.asarray(rng.normal(size=s) / np.sqrt(s[-2]),
                               jnp.float32)
    return (jnp.asarray(rng.normal(size=(N, d)), jnp.float32), g(d, E),
            {"w1": g(1, E, d, f), "w3": g(1, E, d, f), "w2": g(1, E, f, d),
             "sw1": g(d, f), "sw3": g(d, f), "sw2": g(f, d)})


@pytest.mark.parametrize("path", PATHS)
def test_the_sixteen_shares_add_up_to_the_uncut_layer(monkeypatch, path):
    """32 experts over 16 ranks of 2: the routed parts the shares
    compute (router over all 32, each rank its own two experts) plus the
    shared expert counted once are the uncut reference's expert
    layer."""
    monkeypatch.setattr(moe, "_every_row_pays", lambda *a: PATHS[path])
    h, gate, lp = _expert_case(np.random.default_rng(6), N=24)
    w, idx, _ = reference.route(h, gate, 8, 2.5, True)
    uncut = reference.held_part(h, lp, 0, w, idx, 0) \
        + reference._swiglu(h, lp["sw1"], lp["sw3"], lp["sw2"])
    ws, experts = moe.sigmoid_topk_route(h, gate, 8, 2.5)
    total, rows = reference._swiglu(h, lp["sw1"], lp["sw3"], lp["sw2"]), 0
    for rank in range(16):
        own = slice(2 * rank, 2 * rank + 2)
        y, counted = moe.held_experts_ffn(
            h, ws, experts, lp["w1"][0, own], lp["w3"][0, own],
            lp["w2"][0, own], first=2 * rank)
        total, rows = total + y, rows + int(counted.sum())
    np.testing.assert_allclose(total, uncut, atol=2e-5, rtol=2e-5)
    assert rows == 24 * 8                  # every pair landed on one rank


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("case", ["all_to_one_held", "none_held"])
def test_drop_free_under_imbalance(monkeypatch, path, case):
    """Every token routed to ONE held expert (and seven absent ones):
    none is dropped; no token routed to any held expert: zeros."""
    monkeypatch.setattr(moe, "_every_row_pays", lambda *a: PATHS[path])
    h, _, lp = _expert_case(np.random.default_rng(7), N=40)
    held = slice(4, 8)
    absent = np.arange(16, 23)
    experts = np.tile(np.concatenate([[6], absent]), (40, 1)) \
        if case == "all_to_one_held" else np.tile(np.arange(16, 24), (40, 1))
    w = jnp.asarray(np.random.default_rng(8).uniform(0.1, 1, (40, 8)),
                    jnp.float32)
    y, rows = moe.held_experts_ffn(
        h, w, jnp.asarray(experts, jnp.int32), lp["w1"][0, held],
        lp["w3"][0, held], lp["w2"][0, held], first=4)
    if case == "none_held":
        assert not np.asarray(y).any() and not np.asarray(rows).any()
        return
    assert rows.tolist() == [0, 0, 40, 0]
    want = w[:, :1] * reference._swiglu(h, lp["w1"][0, 6], lp["w3"][0, 6],
                                        lp["w2"][0, 6])
    np.testing.assert_allclose(y, want, atol=2e-5, rtol=2e-5)


def test_the_layer_index_reads_the_whole_stack(monkeypatch):
    """With ``layer``, the weights are the stacks [L, Eh, ...]: what a
    paged layer loop hands the expert layer (``whole_stacks``)."""
    monkeypatch.setattr(moe, "_every_row_pays", lambda *a: False)
    rng = np.random.default_rng(9)
    h, gate, lp = _expert_case(rng, N=12, E=4)
    stack = lambda a: jnp.concatenate([a * 0 + 7.0, a])     # layer 1 is it
    w, idx = moe.sigmoid_topk_route(h, gate, 2)
    one = moe.held_experts_ffn(h, w, idx, lp["w1"][0], lp["w3"][0],
                               lp["w2"][0])
    two = moe.held_experts_ffn(h, w, idx, stack(lp["w1"]), stack(lp["w3"]),
                               stack(lp["w2"]), layer=jnp.int32(1))
    np.testing.assert_allclose(two[0], one[0], atol=1e-6)
    assert two[1].tolist() == one[1].tolist()


def test_router_by_hand():
    """Sigmoid scores, the top 2 of 4, divided by their sum, times 2.5,
    in float32 whatever the inputs are."""
    h = jnp.asarray([[1.0, 0.0], [0.0, 2.0]], jnp.bfloat16)
    gate = jnp.asarray([[2.0, -1.0, 0.5, 0.0], [0.0, 1.0, -2.0, 3.0]],
                       jnp.bfloat16)
    w, idx = moe.sigmoid_topk_route(h, gate, 2, 2.5)
    sig = lambda z: 1 / (1 + np.exp(-z))
    assert w.dtype == jnp.float32
    assert idx.tolist() == [[0, 2], [3, 1]]
    a, b = sig(2.0), sig(0.5)
    c, d = sig(6.0), sig(2.0)
    np.testing.assert_allclose(
        w, [[2.5 * a / (a + b), 2.5 * b / (a + b)],
            [2.5 * c / (c + d), 2.5 * d / (c + d)]], rtol=1e-6)
    # the reference routes alike
    rw, ridx, margin = reference.route(h.astype(jnp.float32), gate, 2, 2.5,
                                       True)
    np.testing.assert_allclose(rw, w, rtol=1e-6)
    assert ridx.tolist() == idx.tolist() and margin.shape == (2,)


# --------------------------------------- (vi) what the family refuses yet
REFUSED = {
    "model_axis": (dict(), "model or expert axis"),
    "kv_tier": (dict(kv_tier=True), "kv_tier"),
    "quantized_resident": (dict(kv_tier={
        "enabled": True, "quantize_cold": True, "quantized_resident": True}),
        "quantized_resident"),
    "prefix_cache": (dict(prefix_cache=True), "prefix_cache"),
    "zero_inference": (dict(zero_inference={}), "zero_inference"),
    "speculative": (dict(speculative=True), "speculative"),
}


@pytest.mark.parametrize("mechanism", REFUSED)
def test_the_family_refuses_by_name(params, mechanism):
    kw, names = REFUSED[mechanism]
    if mechanism == "model_axis":
        kw = dict(mesh=MeshSpec.build({"model": 2},
                                      devices=jax.devices()[:2]))
    with pytest.raises(NotImplementedError,
                       match=f"PanguUltraMoEConfig.*({names})"):
        serving_engine(params, CFG, max_batch=2, page_size=PAGE,
                       num_pages=24, max_seq=64, **kw)


def test_the_contiguous_cache_generators_are_refused(params):
    with pytest.raises(NotImplementedError, match="contiguous_cache"):
        generator(params, CFG)


def test_the_other_families_state_nothing_new():
    """gpt2, llama and mixtral leave the seam's new fields at their
    defaults: per-head K and V rows, no leading stack, no latent form,
    nothing refused; the dense two count no experts and hand no stack
    over whole, mixtral counts all of its experts' rows and hands over
    their stacks (ISSUE 34)."""
    from deepspeed_tpu.models.family import decoder_families

    for fam in decoder_families():
        if fam.config_type is pg.PanguUltraMoEConfig \
                or fam.recurrent is not None:   # qwen3_next, granite, laguna
            continue
        cfg = fam.config_type.tiny() if hasattr(fam.config_type, "tiny") \
            else fam.config_type()
        assert fam.cache_row(cfg) == CacheRow(cfg.n_kv_heads, cfg.head_dim,
                                              cfg.head_dim, False)
        assert fam.cache_row(cfg).pool_width == cfg.head_dim
        assert (fam.lead, fam.latent, fam.refuses) == (None, None, ())
        sparse = hasattr(cfg, "num_experts")
        assert fam.whole_stacks == (("w1", "w3", "w2") if sparse else ())
        assert fam.expert_rows(cfg) == (
            (cfg.num_experts, cfg.top_k * cfg.n_layers) if sparse
            else (0, 0))


# ------------------------------------------------ names in the programs
def test_programs_carry_the_new_scopes_and_kernel_names(params,
                                                        monkeypatch):
    # the build asks the backend which readers to bake; lowered for the
    # chip from here, the decode program holds the Mosaic reader
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    eng = serving_engine(params, CFG, max_batch=2, page_size=PAGE,
                         num_pages=24, max_seq=64, prefill_bucket=8,
                         prefill_chunk=8, cache_dtype=jnp.float32)
    assert eng.statusz()["kernels"]["decode"]["reader"] == "dstpu_mla_decode"
    for_chip = lambda program, *args: program.trace(*args).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    absx = lambda x: (jax.ShapeDtypeStruct(x.shape, x.dtype)
                      if hasattr(x, "shape") else x)
    tm = jax.tree_util.tree_map
    view = tm(absx, eng.cache._replace(
        table=jnp.zeros((1, eng.max_pages_per_seq), jnp.int32),
        seq_lens=jnp.zeros((1,), jnp.int32)))
    last = jax.ShapeDtypeStruct((1,), jnp.int32)
    toks = jax.ShapeDtypeStruct((1, 8), jnp.int32)
    chunk = for_chip(eng._chunk_prefill, tm(absx, eng.params), toks, view,
                     last)
    decode = for_chip(
        eng._decode_chunk_fn,
        tm(absx, eng.params), jax.ShapeDtypeStruct((2, 1), jnp.int32),
        tm(absx, eng.cache), absx(eng._key),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.float32))
    words = lambda text: set(re.findall(
        r"\b(mla_q|mla_kv|mla_expand|moe_routed|moe_shared|moe_router|"
        r"moe_ffn|kv_write|kv_attend|attn_qkv|attn_out|mlp)\b", text))
    both = {"mla_q", "mla_kv", "moe_routed", "moe_shared", "moe_router",
            "moe_ffn", "kv_write", "kv_attend", "attn_qkv", "attn_out",
            "mlp"}
    assert both | {"mla_expand"} <= words(chunk)
    assert both <= words(decode) and "mla_expand" not in words(decode)
    assert "dstpu_mla_decode" in decode
    # the new words nest inside the benchmark's vocabulary
    assert re.search(r"attn_qkv/mla_q", decode)
    assert re.search(r"kv_attend/mla_expand", chunk)
    assert re.search(r"moe_ffn/moe_routed", chunk)


def test_benchmark_family_counts_the_share():
    model = dict(num_hidden_layers=5, first_k_dense_replace=1,
                 n_routed_experts=16, vocab_size=19200, hidden_size=7680,
                 num_attention_heads=128, q_lora_rank=1536,
                 kv_lora_rank=512, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128,
                 intermediate_size=18432, moe_intermediate_size=2048,
                 num_experts_per_tok=8, n_shared_experts=1,
                 routed_scaling_factor=2.5, norm_topk_prob=True,
                 max_position_embeddings=131072, rope_theta=25600000,
                 rms_norm_eps=1e-5)
    cfg = bench_family.program_config(model)
    assert cfg.n_routed_experts == 256 and cfg.experts_held == (0, 16)
    assert bench_family.param_count(cfg) == pg.param_count(cfg) \
        == 4919139840
    assert bench_family.kv_bytes_per_token(cfg) == 5 * 576 * 2
    assert decoder_family(cfg).cache_row(cfg).pool_width == 640

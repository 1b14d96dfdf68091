"""The phi4_flash family (SambaY: a decoder-hybrid-decoder with
differential attention) on the serving path, against the benchmark's
plain float32 reference (``benchmark/reference/phi4_flash.py``, which
imports nothing from ``deepspeed_tpu``): Mamba-1 states and window rings
a slot beside ONE pool layer that the cross layers read, the memory one
layer hands seven others, the cut before the cross-decoder, the seam's
refusals.  A tiny preset in the published order (three (Mamba-1, window)
periods, (Mamba-1, full), two (GMU, cross); window 8; 8 query heads over
4 K/V heads of 16), seeded weights, CPU."""

import dataclasses
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.families import phi4_flash as bench_family  # noqa: E402
from benchmark.reference import phi4_flash as reference  # noqa: E402
from deepspeed_tpu.inference import kernels as K  # noqa: E402
from deepspeed_tpu.inference import paged_forward  # noqa: E402
from deepspeed_tpu.inference.generation import generator  # noqa: E402
from deepspeed_tpu.inference.paged_forward import forward_paged  # noqa: E402
from deepspeed_tpu.inference.serving import serving_engine  # noqa: E402
from deepspeed_tpu.models import phi4_flash as pf  # noqa: E402
from deepspeed_tpu.models.family import (decoder_families,  # noqa: E402
                                         decoder_family, step_state)
from deepspeed_tpu.topology import MeshSpec  # noqa: E402

CFG = pf.Phi4FlashConfig.tiny()
PAGE = 8
PAD = 64
# float32 end to end, the two sides summing in different orders (a scan
# in blocks against a token at a time, a band and a ring against whole
# masked rows, paired heads against four products): 1e-5 on logits of
# about unit variance, read; bfloat16 in place of float32 reads 0.05 and
# more
TOL = dict(atol=3e-4, rtol=3e-4)


@pytest.fixture(scope="module")
def params():
    return pf.init_params(jax.random.PRNGKey(0), CFG)


_REFERENCE = jax.jit(lambda params, tokens: reference.forward(
    params, tokens, **bench_family._ref_kw(CFG)))


def _reference_logits(params, tokens):
    """The reference's logits of ``tokens``, run at one padded length
    (causal: what follows a position does not reach it)."""
    padded = np.zeros(PAD, np.int32)
    padded[:len(tokens)] = tokens
    return np.asarray(_REFERENCE(params, jnp.asarray(padded)))[:len(tokens)]


def _engine(params, cfg=CFG, **kw):
    base = dict(max_batch=3, page_size=PAGE, num_pages=64, max_seq=128,
                cache_dtype=jnp.float32, telemetry=True, prefill_bucket=0,
                prefill_chunk=16)
    base.update(kw)
    return serving_engine(params, cfg, **base)


def _cache(cfg, slots, rows, max_seq, stale=7.0):
    """A pool of the one full layer, the states and the rings beside it
    (holding rubbish: a slot's last owner's); ``rows`` rows of table."""
    fam = decoder_family(cfg)
    sr, row = fam.recurrent.state_row(cfg), fam.cache_row(cfg)
    mp = -(-max_seq // PAGE)
    shape = (fam.pool_layers(cfg), row.n_kv, slots * mp + 1, PAGE,
             row.pool_width)
    table = np.arange(slots * mp).reshape(slots, mp)[:rows]
    full = lambda sh, dt: jnp.full(sh, stale, dt)
    return K.PagedKVCache(
        k=jnp.zeros(shape), v=jnp.zeros(shape),
        table=jnp.asarray(table, jnp.int32),
        seq_lens=jnp.zeros((rows,), jnp.int32), page_size=PAGE,
        conv=full((sr.layers, slots) + sr.conv, jnp.float32),
        state=full((sr.layers, slots) + sr.state, K.STATE_DTYPE),
        ring=full((sr.ring.layers, slots) + sr.ring.conv, jnp.float32))


def _forwards(params, cfg):
    """(chunk, step): ``forward_paged`` jitted, each compiled once a
    shape (called eagerly its loops compile anew at every call)."""
    chunk = jax.jit(lambda toks, c: forward_paged(
        params, toks, cfg, c, continuation=True, tp=False, interpret=True))
    step = jax.jit(lambda toks, c: forward_paged(
        params, toks, cfg, c, tp=False, interpret=True))
    return (lambda toks, c: chunk(jnp.asarray(toks), c),
            lambda toks, c: step(jnp.asarray(toks), c))


def _serve(params, cfg, seq, n_prompt, C=16, between=True):
    """{position: logits} of ``seq`` as a slot of two serves it: its
    first ``n_prompt`` tokens through chunks of ``C`` into slot 0 (a
    padded last chunk; each chunk gives its last real row's logits
    alone), between two chunks a decode step of slot 1 with slot 0 as
    the engine uploads a slot between chunks (length 0), the rest a
    decode step each over both slots.  Also the cache at the end."""
    cache = _cache(cfg, 2, 2, PAD)
    tables = cache.table
    chunk, step = _forwards(params, cfg)
    got = {}
    for done in range(0, n_prompt, C):
        take = min(C, n_prompt - done)
        toks = np.zeros((1, C), np.int32)
        toks[0, :take] = seq[done:done + take]
        logits, view = chunk(toks, cache._replace(
            table=tables[:1], slot=jnp.zeros((1,), jnp.int32),
            seq_lens=jnp.full((1,), done, jnp.int32),
            real=jnp.full((1,), take, jnp.int32)))
        assert view.real is None and logits.shape == (1, 1, cfg.vocab_size)
        got[done + take - 1] = np.asarray(logits[0, 0])
        cache = cache._replace(k=view.k, v=view.v, conv=view.conv,
                               state=view.state, ring=view.ring)
        if between and done + take < n_prompt:
            # (a slot of length 0 is uploaded with the trash page)
            _, cache = step([[3], [5]], cache._replace(
                table=tables.at[0].set(cache.k.shape[2] - 1), slot=None,
                seq_lens=jnp.asarray([0, 1 + done // C], jnp.int32),
                real=jnp.asarray([0, 1], jnp.int32)))
    for at in range(n_prompt, len(seq)):
        logits, cache = step([[seq[at]], [9]], cache._replace(
            table=tables, slot=None,
            seq_lens=jnp.asarray([at, 0], jnp.int32),
            real=jnp.asarray([1, 0], jnp.int32)))
        got[at] = np.asarray(logits[0, 0])
    return got, cache


# -------------------------------- (i) the paged forward vs the reference
def test_the_family_is_registered_and_its_shape_is_as_stated():
    assert "Phi4FlashConfig" in [f.name for f in decoder_families()]
    fam = decoder_family(CFG)
    rec = fam.recurrent
    assert pf.layer_kinds(CFG) == ("mamba", "window") * 3 \
        + ("mamba", "full") + ("gmu", "cross") * 2
    assert fam.pool_layers(CFG) == 1 and fam.ffn_alone_layers(CFG) == 2
    sr = rec.state_row(CFG)
    # it compares as the first kind's three numbers; the second kind's
    # rows ride beside them
    assert sr == (4, (3, 256), (2, 16, 128))
    assert sr.ring == (3, (8, 2 * 2 * 32), None)
    assert rec.sections(CFG) == (((True, "win_blocks"), 3),
                                 ((True, False), 1),
                                 ((None, "cross_blocks"), 2))
    assert [r.key for r in rec.also] == ["win_blocks"]
    assert [(r.key, r.reads) for r in rec.readers] == [("cross_blocks", 0)]
    assert rec.tail == 1 and rec.hands_on(CFG) == CFG.d_inner
    assert fam.cache_row(CFG) == (2, 32, 32, False, 0)
    # the published shape: 9 Mamba-1, 8 window, 1 full, 7 GMU, 7 cross;
    # 3,852.6 M parameters, the embedding once
    full = pf.Phi4FlashConfig()
    kinds = pf.layer_kinds(full)
    assert [kinds.count(k) for k in ("mamba", "window", "full", "gmu",
                                     "cross")] == [9, 8, 1, 7, 7]
    assert kinds[16:18] == ("mamba", "full") and kinds[18] == "gmu"
    assert pf.param_count(full) == 3_852_562_944
    assert rec.state_row(full) == (9, (3, 5120), (40, 16, 128))
    assert rec.state_row(full).ring == (8, (512, 2560), None)
    assert fam.cache_row(full)[:3] == (10, 128, 128)
    assert pf.lambda_init(17) == pytest.approx(0.8 - 0.6 * np.exp(-5.1))


@pytest.mark.parametrize("n_prompt,total", [(37, 45), (16, 24), (5, 12)],
                         ids=["chunks_and_a_short_last", "one_whole_chunk",
                              "one_short_chunk"])
def test_served_logits_are_the_references(params, n_prompt, total):
    """Prefill in chunks (a short last chunk), another slot's decode
    steps between them, then decode through the caches: the logits the
    programs give (a chunk's last real row, every step) are the
    reference's full forward pass's at those positions.  **The cut is
    exact**: a chunk program ran layers behind the self-decoder on one
    row, and that row's logits are those of a pass that ran every layer
    on every position."""
    rng = np.random.default_rng(n_prompt)
    seq = rng.integers(0, CFG.vocab_size, total)
    want = _reference_logits(params, seq)
    got, _ = _serve(params, CFG, seq, n_prompt)
    assert sorted(got) == sorted(
        {min(d + 16, n_prompt) - 1 for d in range(0, n_prompt, 16)}
        | set(range(n_prompt, total)))
    for at, logits in got.items():
        np.testing.assert_allclose(logits, want[at], **TOL, err_msg=str(at))


def test_another_slots_steps_leave_a_slots_state_and_rings_alone(params):
    """A slot between two chunks of its prompt (length 0 in the decode
    program's upload) keeps its Mamba-1 state, its convolution's rows and
    its rings bit for bit while the other slot steps."""
    rng = np.random.default_rng(3)
    seq = rng.integers(0, CFG.vocab_size, 20)
    _, cache = _serve(params, CFG, seq, 16, between=False)
    _, step = _forwards(params, CFG)
    before = jax.tree.map(np.asarray, (cache.conv, cache.state, cache.ring))
    for j in range(3):
        _, cache = step([[3], [5]], cache._replace(
            table=cache.table.at[0].set(cache.k.shape[2] - 1), slot=None,
            seq_lens=jnp.asarray([0, 1 + j], jnp.int32),
            real=jnp.asarray([0, 1], jnp.int32)))
    after = jax.tree.map(np.asarray, (cache.conv, cache.state, cache.ring))
    for a, b in zip(before, after):
        np.testing.assert_array_equal(a[:, 0], b[:, 0])
        assert not np.array_equal(a[:, 1], b[:, 1])


def test_a_cross_layer_sees_the_row_the_full_layer_wrote_this_step(
        params, monkeypatch):
    """A decode step's cross layers read the pool over lengths that count
    the row the full layer appended in the same step: with a reader blind
    to it the step's logits part from the reference's."""
    rng = np.random.default_rng(5)
    seq = rng.integers(0, CFG.vocab_size, 14)
    want = _reference_logits(params, seq)
    got, _ = _serve(params, CFG, seq, 9)
    np.testing.assert_allclose(got[13], want[13], **TOL)
    read = paged_forward._paged_read_block
    monkeypatch.setattr(
        paged_forward, "_paged_read_block",
        lambda rd, cfg, x, lp, ctx, kp, vp, table, lens, **kw: read(
            rd, cfg, x, lp, ctx, kp, vp, table,
            jnp.maximum(lens - 1, 0), **kw))
    blind, _ = _serve(params, CFG, seq, 9)
    assert np.abs(blind[13] - want[13]).max() > 100 * TOL["atol"]


# ------------------------------------------------- (ii) the Mamba-1 rules
def _mamba_inputs(seed, T, scale):
    """c, dt [1, T, H, 128], A [H, N, 128], Bm, Cm [1, T, N], S: with
    ``D_t A`` about ``-scale``."""
    H, N = 2, 16
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    c = jax.random.normal(k[0], (1, T, H, 128))
    dt = scale * jax.random.uniform(k[1], (1, T, H, 128), minval=0.5,
                                    maxval=1.5)
    A = -jax.random.uniform(k[2], (H, N, 128), minval=0.5, maxval=1.5)
    Bm, Cm = (jax.random.normal(k[i], (1, T, N)) for i in (3, 4))
    S = jax.random.normal(k[5], (1, H, N, 128))
    return c, dt, A, Bm, Cm, S


@pytest.mark.parametrize("scale", [1e-3, 1.0, 20.0],
                         ids=["near_0", "about_1", "near_minus_20"])
@pytest.mark.parametrize("block", [1, 4, 16, 7])
def test_the_chunks_scan_is_the_recurrence_a_token_at_a_time(scale, block):
    """The one-token rule, the chunk's form at several blocks (one that
    does not divide the tokens: padded with tokens that move nothing) and
    the reference's token-at-a-time recurrence agree, with ``D_t A`` near
    0 (nothing forgotten) and near -20 (everything)."""
    T = 24
    c, dt, A, Bm, Cm, S = _mamba_inputs(int(scale * 7) + block, T, scale)
    o, S1 = jax.jit(pf.mamba_chunk_scan, static_argnums=6)(
        c, dt, A, Bm, Cm, S, block)
    flat = lambda a: a.reshape(a.shape[:-2] + (-1,))       # [.., H x 128]
    A_ref = A.transpose(0, 2, 1).reshape(-1, 16)           # [channels, N]
    S_ref = S[0].transpose(0, 2, 1).reshape(-1, 16)
    o_ref, S_end = reference.recurrence(flat(c[0]), flat(dt[0]), A_ref,
                                        Bm[0], Cm[0], S_ref)
    np.testing.assert_allclose(flat(o[0]), o_ref, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(
        S1[0].transpose(0, 2, 1).reshape(-1, 16), S_end, atol=2e-5,
        rtol=2e-5)
    Sj, steps = S, []
    for t in range(T):
        ot, Sj = pf.mamba_step(c[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], Sj)
        steps.append(ot)
    np.testing.assert_allclose(jnp.stack(steps, 1), o, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(Sj, S1, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("tile_bytes", [None, 16 * 128 * 4, 1 << 14])
def test_the_state_step_kernel_takes_the_layers_tile(tile_bytes):
    """``dstpu_state_step`` (interpret mode) under the Mamba-1 rule: the
    decay's tile ``A`` [1, H, R, C] is an operand the slots share, beside
    row and column vectors; the layer is stepped in place and the other
    layers are left alone; a slot whose step is 0 keeps its state bit
    for bit."""
    B, H, N = 3, 2, 16
    c, dt, A, Bm, Cm, _ = _mamba_inputs(11, B, 1.0)
    c, dt, Bm, Cm = c[0], dt[0].at[1].set(0.0), Bm[0], Cm[0]
    buffer = jax.random.normal(jax.random.PRNGKey(2), (3, B, H, N, 128))
    row = lambda v: v[:, :, None, :]
    col = lambda v: v[:, None, :, None]
    vectors = (A[None], row(dt), row(dt * c), col(Bm), col(Cm))
    o, out = K.state_step(pf.mamba_rule, buffer, jnp.int32(1), vectors,
                          interpret=True, tile_bytes=tile_bytes)
    o_ref, S_ref = pf.mamba_rule(buffer[1], *vectors)
    np.testing.assert_allclose(o, o_ref, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(out[1], S_ref, atol=1e-6, rtol=1e-6)
    np.testing.assert_array_equal(out[1, 1], buffer[1, 1])
    np.testing.assert_array_equal(out[0], buffer[0])
    np.testing.assert_array_equal(out[2], buffer[2])
    # and through the seam's hand-over
    from deepspeed_tpu.models.family import CarriedState
    import functools
    o2, carried = step_state(
        pf.mamba_rule, CarriedState(buffer, jnp.int32(1), functools.partial(
            K.state_step, interpret=True)), *vectors)
    np.testing.assert_allclose(o2, o, atol=0, rtol=0)
    assert isinstance(carried, CarriedState)


# ------------------------------------------ (iii) the differential heads
def test_paired_heads_are_four_plain_softmax_products(params):
    """The layout the caches and readers see (``[q1 | 0]`` and ``[0 |
    q2]`` over ``[k1 | k2]``, ``[v1 | v2]``, heads of ``2 head``, scores
    divided by ``sqrt(2 head)``) against the reference's four plain
    softmax products on heads of the published width: the zero lanes add
    exact zeros, the pairs are the same."""
    T = 12
    lp = jax.tree.map(lambda a: a[0], params["blocks"])
    x = jax.random.normal(jax.random.PRNGKey(4), (1, T, CFG.dim))
    q, k, v = pf._qkv(CFG, x, lp)
    assert q.shape == (1, T, CFG.n_heads, 2 * CFG.head_dim)
    assert k.shape == v.shape == (1, T, CFG.n_kv_heads // 2,
                                  2 * CFG.head_dim)
    # a padded head's other half is exactly zero
    Dh = CFG.head_dim
    assert not np.asarray(q[:, :, 0::2, Dh:]).any()
    assert not np.asarray(q[:, :, 1::2, :Dh]).any()
    G = CFG.n_heads // (CFG.n_kv_heads // 2)
    kk, vv = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q, kk) / np.sqrt(2 * Dh)
    s = jnp.where(np.tril(np.ones((T, T), bool)), s, -jnp.inf)
    attn = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), vv)
    got = pf.diff_combine(CFG, attn.reshape(1, T, -1), lp) @ lp["wo"] \
        + lp["bo"]
    a = pf.layer_norm(x, lp["attn_norm_g"], lp["attn_norm_b"], CFG.norm_eps)
    with jax.default_matmul_precision("highest"):
        qkv = a[0] @ lp["wqkv"] + lp["bqkv"]
        nq, nk = CFG.n_heads * Dh, CFG.n_kv_heads * Dh
        want = reference._differential(
            qkv[:, :nq], qkv[:, nq:nq + nk], qkv[:, nq + nk:], lp,
            head_dim=Dh, window=None, eps=CFG.norm_eps)
    np.testing.assert_allclose(got[0], want, atol=2e-5, rtol=2e-5)
    # lambda's second term counts: without it the layer is another one
    plain = pf.diff_combine(
        CFG, attn.reshape(1, T, -1),
        dict(lp, lam0=jnp.float32(0.0), lq1=lp["lq1"] * 0, lq2=lp["lq2"] * 0))
    assert np.abs(plain @ lp["wo"] + lp["bo"] - want).max() > 0.05


# ----------------------------------------------------- (iv) the engine
def test_the_engine_serves_the_references_tokens_and_counts_the_cut(params):
    """Through ``serving_engine``: scheduler, page allocator, per-slot
    buffers, split-fuse chunks.  Greedy tokens are the reference's
    argmax; a chunk program pays the layers behind the self-decoder for
    one row, and the two counters say so."""
    eng = _engine(params)
    rng = np.random.default_rng(0)
    prompts = {i: rng.integers(0, CFG.vocab_size, n).tolist()
               for i, n in enumerate((5, 21, 37, 16, 9))}
    for i, p in prompts.items():
        eng.submit(i, p, max_new_tokens=10)
    out = eng.run()
    for i, p in prompts.items():
        want = _reference_logits(params, out[i]).argmax(-1)
        assert out[i][len(p):] == want[len(p) - 1:-1].tolist(), i
    counters = eng.registry.snapshot()["counters"]
    chunks = sum(-(-len(p) // 16) for p in prompts.values())
    assert counters["serving_prefill_chunks"] == chunks == 8
    assert counters["serving_chunk_rows_total"] == sum(
        len(p) for p in prompts.values())
    assert counters["serving_tail_rows_total"] == chunks
    assert counters["serving_state_fresh_starts"] == len(prompts)
    assert counters["serving_state_rows_masked"] > 0
    status = eng.statusz()
    state = status["cache.state"]
    assert (state["layers"], state["ring_layers"]) == (4, 3)
    assert state["ffn_alone"] == {"layers": 2, "bytes": 0}
    sr = decoder_family(CFG).recurrent.state_row(CFG)
    per_slot = 4 * (3 * 256 * 4 + 2 * 16 * 128 * 4) + 3 * 8 * 128 * 4
    assert state["bytes_per_slot"] == per_slot and sr.ring.layers == 3
    assert status["kv"]["layers"] == 1
    kernels = status["kernels"]
    assert kernels["state_step"] == "pallas"
    for row in ("decode", "chunk", "window", "state_chunk"):
        assert set(kernels[row]) >= {"reader", "reason"}, row
    assert kernels["state_chunk"]["reason"] \
        == "the family states no block of its rule"
    assert eng.check_leaks() == []
    assert eng.tail_cut and eng.cache.ring.shape[:2] == (3, 3)


def test_a_family_without_a_tail_counts_every_row(params):
    """The counters in a family that cuts nothing: every real prompt row
    paid every layer."""
    from deepspeed_tpu.models import granite_hybrid as gh

    cfg = gh.GraniteHybridConfig.tiny()
    eng = _engine(gh.init_params(jax.random.PRNGKey(0), cfg), cfg)
    eng.submit(0, list(range(1, 22)), max_new_tokens=2)
    eng.run()
    counters = eng.registry.snapshot()["counters"]
    assert not eng.tail_cut
    assert counters["serving_chunk_rows_total"] \
        == counters["serving_tail_rows_total"] == 21


# ------------------------------------------------------- (v) the refusals
@pytest.mark.parametrize("kw,named", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(kv_tier={"host_pool_bytes": 1 << 20}), "kv_tier"),
    (dict(speculative={"enabled": True, "k": 2}), "speculative"),
    (dict(zero_inference={"enabled": True}), "zero_inference"),
])
def test_what_is_not_built_is_refused_by_name(params, kw, named):
    with pytest.raises(NotImplementedError, match=named):
        _engine(params, **kw)


def test_a_mesh_axis_the_contiguous_cache_and_a_long_max_seq_are_refused(
        params):
    with pytest.raises(NotImplementedError, match="contiguous_cache"):
        generator(params, CFG)
    mesh = MeshSpec.build({"model": 2}, devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="model or expert axis"):
        _engine(params, mesh=mesh)
    with pytest.raises(ValueError, match="max_seq_len"):
        _engine(params, max_seq=CFG.max_seq_len + 8, num_pages=200)
    fam = decoder_family(CFG)
    assert {m for m, _ in fam.refuses} == {
        "prefix_cache", "kv_tier", "quantized_resident", "speculative",
        "zero_inference", "contiguous_cache"}
    assert dataclasses.is_dataclass(CFG)


# --------------------------------------------------- (vi) names in a program
def test_programs_carry_the_new_scope_words(params):
    """The words a capture shows: ``mamba_*`` inside the attention words,
    ``gmu``, ``yoco_q`` / ``yoco_read``, ``diff_combine`` and the window
    family's ``win_*``, in the decode program and in a chunk program
    (whose Mamba-1 layers scan where the decode program's step)."""
    cache = _cache(CFG, 2, 2, PAD)
    texts = {}
    for name, (T, cont, c) in {
            "decode": (1, False, cache._replace(
                real=jnp.ones((2,), jnp.int32))),
            "chunk": (16, True, cache._replace(
                table=cache.table[:1], seq_lens=jnp.zeros((1,), jnp.int32),
                slot=jnp.zeros((1,), jnp.int32),
                real=jnp.full((1,), 16, jnp.int32)))}.items():
        lowered = jax.jit(lambda toks, c, cont=cont: forward_paged(
            params, toks, CFG, c, continuation=cont, tp=False,
            interpret=True)).lower(
                jnp.zeros((c.table.shape[0], T), jnp.int32), c)
        texts[name] = lowered.as_text(debug_info=True)
    for word in ("attn_qkv/mamba_proj", "attn_qkv/mamba_conv",
                 "attn_out/mamba_gate", "kv_write/mamba_write", "gmu",
                 "attn_qkv/yoco_q", "kv_attend/yoco_read",
                 "attn_out/diff_combine", "win_write", "win_attend"):
        for name, text in texts.items():
            assert word in text, (word, name)
    assert "kv_attend/mamba_step" in texts["decode"]
    assert "kv_attend/mamba_scan" not in texts["decode"]
    assert "kv_attend/mamba_scan" in texts["chunk"]
    assert "kv_attend/mamba_step" not in texts["chunk"]

"""The laguna family on the serving path, against the benchmark's plain
float32 reference (``benchmark/reference/laguna.py``, which imports
nothing from ``deepspeed_tpu``): sliding layers over a ring of K/V rows a
slot beside a page pool that holds the full layers alone, two head counts
and two RoPE tables over the same K/V heads, a per-head gate, a dense
lead, a share of sigmoid-routed experts, the seam's refusals (each stated
piece left out, and the band's kernel alone: ``test_laguna_pieces.py``).
A tiny preset of the published shape (two periods of S S S F behind the
lead, window 8, 6 and 9 query heads over 3 K/V heads, 8 experts top-3),
seeded weights, CPU."""

import dataclasses
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.families import laguna as bench_family  # noqa: E402
from benchmark.reference import laguna as reference  # noqa: E402
from deepspeed_tpu.inference import kernels as K  # noqa: E402
from deepspeed_tpu.inference.generation import generator  # noqa: E402
from deepspeed_tpu.inference.paged_forward import forward_paged  # noqa: E402
from deepspeed_tpu.inference.serving import serving_engine  # noqa: E402
from deepspeed_tpu.models import laguna as lg  # noqa: E402
from deepspeed_tpu.models.family import (CarriedRows,  # noqa: E402
                                         decoder_families, decoder_family)
from deepspeed_tpu.topology import MeshSpec  # noqa: E402

CFG = lg.LagunaConfig.tiny()
W = CFG.sliding_window
PAGE = 8
PAD = 64
# float32 end to end, the two sides summing in different orders (a band
# in blocks and a ring against whole masked rows, gathered pages against
# whole rows): 2e-6 on logits of about unit variance, read.  With
# bfloat16 weights, activations, pages and rings in place of float32 the
# same comparison reads 0.05 and more: the tolerance sits a hundred
# times above the one and a hundred times under the other
TOL = dict(atol=2e-4, rtol=2e-4)


@pytest.fixture(scope="module")
def params():
    return lg.init_params(jax.random.PRNGKey(0), CFG)


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


def _argmax_served(params, out, prompts):
    for i, p in prompts.items():
        want = _reference_logits(params, out[i]).argmax(-1)
        assert out[i][len(p):] == want[len(p) - 1:-1].tolist(), i


def _cache(cfg, slots, rows, max_seq, slot=None, dtype=jnp.float32,
           stale=7.0):
    """A pool of the full layers alone, the rings beside it (holding
    rubbish: a slot's last owner's); ``rows`` rows of table."""
    fam = decoder_family(cfg)
    sr, row = fam.recurrent.state_row(cfg), fam.cache_row(cfg)
    mp = -(-max_seq // PAGE)
    shape = (cfg.n_full_layers, row.n_kv, slots * mp + 1, PAGE,
             row.pool_width)
    table = np.arange(slots * mp).reshape(slots, mp)[:rows]
    return K.PagedKVCache(
        k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
        table=jnp.asarray(table, jnp.int32),
        seq_lens=jnp.zeros((rows,), jnp.int32), page_size=PAGE,
        conv=jnp.full((sr.layers, slots) + sr.conv, stale, dtype),
        slot=slot)


def _forwards(params, cfg):
    """(chunk, step): ``forward_paged`` jitted, each compiled once a
    shape (called eagerly its loops compile anew at every call)."""
    chunk = jax.jit(lambda toks, c: forward_paged(
        params, toks, cfg, c, continuation=True, tp=False, interpret=True))
    step = jax.jit(lambda toks, c: forward_paged(
        params, toks, cfg, c, tp=False, interpret=True))
    return (lambda toks, c: chunk(jnp.asarray(toks), c),
            lambda toks, c: step(jnp.asarray(toks), c))


def _chunks_then_steps(params, cfg, seq, n_prompt, C=16, dtype=jnp.float32):
    """Logits of every position of ``seq``: its first ``n_prompt``
    tokens through chunks of ``C`` (a padded last chunk), the rest a
    decode step each, over one slot of a paged cache."""
    cache = _cache(cfg, 1, 1, PAD, dtype=dtype)
    chunk, step = _forwards(params, cfg)
    got = []
    for done in range(0, n_prompt, C):
        take = min(C, n_prompt - done)
        toks = np.zeros((1, C), np.int32)
        toks[0, :take] = seq[done:done + take]
        logits, cache = chunk(toks, cache._replace(
            slot=jnp.zeros((1,), jnp.int32),
            seq_lens=jnp.full((1,), done, jnp.int32),
            real=jnp.full((1,), take, jnp.int32)))
        assert cache.real is None
        got.append(np.asarray(logits[0, :take], np.float32))
    for at in range(n_prompt, len(seq)):
        logits, cache = step([[seq[at]]], cache._replace(
            slot=None, seq_lens=jnp.full((1,), at, jnp.int32),
            real=jnp.ones((1,), jnp.int32)))
        got.append(np.asarray(logits[0], np.float32))
    return np.concatenate(got)


# -------------------------------- (i) the paged forward vs the reference
def test_the_family_is_registered_and_its_shape_is_as_stated():
    assert "LagunaConfig" in [f.name for f in decoder_families()]
    fam = decoder_family(CFG)
    assert fam.recurrent.period(CFG) == (True, True, True, False)
    assert fam.recurrent.write_scope == "win_write"
    assert fam.recurrent.rows_in_place and fam.lead[0] == "lead_blocks"
    assert fam.expert_rows(CFG) == (8, 3 * 8) and fam.router(CFG) == (8, 3)
    # the cell's share: layer 0 and three periods, 16 of 256 experts, an
    # eighth of the vocabulary
    cell = lg.LagunaConfig(n_layers=13, experts_held=(0, 16),
                           vocab_size=12544)
    assert fam.recurrent.state_row(cell) == (9, (512, 2048), None)
    assert fam.cache_row(cell)[:3] == (8, 128, 128)
    assert (cell.n_full_layers, cell.n_sliding_layers) == (4, 9)
    assert fam.expert_rows(cell) == (16, 120) and fam.router(cell) == (256, 10)
    assert lg.param_count(cell) == 2_869_994_496
    assert bench_family.param_count(cell) == 2_869_994_496
    with pytest.raises(AssertionError, match="whole periods"):
        lg.LagunaConfig()               # 48 = 1 + 11 periods + 3 layers


@pytest.mark.parametrize("n_prompt,C", [
    (37, 16),           # the carry over chunks; a padded last chunk
    (1, 16), (W - 1, 16), (W, 16), (W + 1, 16), (3 * W + 5, 16),
    (37, 5),            # chunks under the window, none a multiple of it
    (37, 8),            # chunks of exactly the window
    (40, 40),           # one chunk, several blocks of the band
], ids=lambda v: str(v))
def test_chunks_then_decode_steps_match_the_reference_logits(
        params, n_prompt, C):
    """A prompt through chunks of ``C`` (which need not divide it, nor
    the window them) into a slot whose rings held rubbish (a first chunk
    sees nothing of it), then decode steps to 48 tokens: every
    position's logits are the reference's full forward's, so any chunk
    size gives the unchunked numbers; contexts of 1, W - 1, W, W + 1 and
    3 W + 5 put the ring's wrap and its validity at every edge."""
    seq = np.random.default_rng(1).integers(0, CFG.vocab_size, 48)
    want = _reference_logits(params, seq)
    got = _chunks_then_steps(params, CFG, seq, n_prompt, C)
    np.testing.assert_allclose(got, want, **TOL)


def test_bfloat16_parts_from_float32_by_a_hundred_tolerances(params):
    seq = np.random.default_rng(1).integers(0, CFG.vocab_size, 48)
    want = _reference_logits(params, seq)
    half = lg.init_params(jax.random.PRNGKey(0), CFG, jnp.bfloat16)
    low = _chunks_then_steps(half, CFG, seq, 37, dtype=jnp.bfloat16)
    assert np.abs(low - want).max() > 100 * TOL["atol"]


def test_two_slots_at_ragged_lengths_beside_an_idle_one(params):
    """Three slots in one decode step: one at 21 tokens, one at 5, one
    idle (length 0, ``real`` 0).  The live rows' logits are the
    reference's; the idle slot's rings stay bit for bit, and a chunk's
    rows past its last real token move no ring row."""
    rng = np.random.default_rng(5)
    seqs = [rng.integers(0, CFG.vocab_size, n) for n in (22, 6)]
    cache = _cache(CFG, 3, 3, PAD)
    chunk, step = _forwards(params, CFG)
    for b, seq in enumerate(seqs):
        toks = np.zeros((1, 32), np.int32)
        toks[0, :len(seq) - 1] = seq[:-1]
        _, view = chunk(toks, cache._replace(
            table=cache.table[b:b + 1], slot=jnp.full((1,), b, jnp.int32),
            seq_lens=jnp.zeros((1,), jnp.int32),
            real=jnp.full((1,), len(seq) - 1, jnp.int32)))
        cache = cache._replace(k=view.k, v=view.v, conv=view.conv)
    before = np.asarray(cache.conv)
    logits, after = step([[seqs[0][-1]], [seqs[1][-1]], [3]], cache._replace(
        seq_lens=jnp.asarray([21, 5, 0], jnp.int32),
        real=jnp.asarray([1, 1, 0], jnp.int32)))
    for b, seq in enumerate(seqs):
        np.testing.assert_allclose(np.asarray(logits[b, 0]),
                                   _reference_logits(params, seq)[-1], **TOL)
    np.testing.assert_array_equal(np.asarray(after.conv[:, 2]), before[:, 2])
    np.testing.assert_array_equal(np.asarray(after.seq_lens), [22, 6, 0])
    # one ring row a live slot moved, no other
    moved = (np.asarray(after.conv) != before).any(-1)      # [L, B, W]
    assert moved[:, 0].sum(-1).tolist() == [1] * CFG.n_sliding_layers
    assert moved[:, 0, 21 % W].all() and moved[:, 1, 5 % W].all()
    # padding: other tokens past the last real one, the same rings
    toks = rng.integers(0, CFG.vocab_size, (1, 16))
    other = toks.copy()
    other[0, 11:] = 5
    run = lambda t, real: chunk(t, _cache(CFG, 1, 1, PAD, slot=jnp.zeros(
        (1,), jnp.int32))._replace(real=jnp.full((1,), real, jnp.int32)))[1]
    a, b = run(toks, 11), run(other, 11)
    np.testing.assert_array_equal(np.asarray(a.conv), np.asarray(b.conv))
    # and a row with no real token at all moves nothing of its own (the
    # seam hands a row that starts at 0 zeros, not what the slot held)
    assert float(jnp.abs(run(toks, 0).conv).max()) == 0.0


def test_a_decode_step_updates_the_carried_rings_where_they_lie(
        params, monkeypatch):
    """A decode step over every slot hands ``win_mix`` the carried
    buffer and the layer, not a layer's slice; a chunk's one-slot view
    hands it the slot's rings."""
    seen = []
    mix = lg.win_mix

    def spy(cfg, x, lp, state, valid, start, ctx):
        seen.append(type(state[0]))
        return mix(cfg, x, lp, state, valid, start, ctx)

    fam = dataclasses.replace(lg.FAMILY, recurrent=dataclasses.replace(
        lg.FAMILY.recurrent, mix=spy))
    monkeypatch.setattr(lg, "FAMILY", fam)
    cache = _cache(CFG, 2, 2, 32)._replace(
        seq_lens=jnp.asarray([9, 3], jnp.int32),
        real=jnp.asarray([1, 1], jnp.int32))
    forward_paged(params, jnp.zeros((2, 1), jnp.int32), CFG, cache,
                  tp=False, interpret=True)
    assert seen and all(t is CarriedRows for t in seen)
    del seen[:]
    forward_paged(params, jnp.zeros((1, 16), jnp.int32), CFG,
                  _cache(CFG, 1, 1, 32, slot=jnp.zeros((1,), jnp.int32)),
                  continuation=True, tp=False, interpret=True)
    assert seen and CarriedRows not in seen


# ------------------------------------------ (iii) through serving_engine
def test_the_engine_serves_the_reference_argmax(params):
    """Scheduler, allocator, rings, boundary sampling: five requests
    through three slots in split-fuse chunks of 16 (the later ones reuse
    slots whose rings longer requests left full), greedy tokens the
    reference's argmax given the served prefix."""
    eng = _engine(params)
    assert eng.cache.k.shape[0] == CFG.n_full_layers == 3
    assert eng.cache.conv.shape == (6, 3, W, 2 * 3 * 16)
    assert eng.cache.state is None
    assert eng.cache.expert_rows.shape == (8,)
    assert eng.statusz()["kernels"]["state_step"] == "xla"
    assert eng.statusz()["kernels"]["fallbacks"] == []
    assert eng.statusz()["kernels"]["window"] == {
        "reader": "xla", "reason": "interpret: no TPU backend"}
    assert eng.statusz()["kernels"]["chunk"] == {
        "reader": "xla", "reason": "interpret: no TPU backend"}
    rng = np.random.default_rng(0)
    prompts = {i: rng.integers(0, CFG.vocab_size, n).tolist()
               for i, n in enumerate((37, 21, 5, 9, 33))}
    for i, p in prompts.items():
        eng.submit(i, p, max_new_tokens=7)
    out = eng.run()
    _argmax_served(params, out, prompts)
    assert eng.check_leaks() == []
    counters = eng.registry.snapshot()["counters"]
    assert counters["serving_state_fresh_starts"] == 5
    assert counters["serving_state_rows_masked"] > 0
    assert counters["serving_routed_rows"] > 0
    status = eng.statusz()
    assert status["cache.state"]["layers"] == 6
    assert status["cache.state"]["bytes"] == eng.cache.conv.nbytes
    assert status["cache.state"]["bytes_per_slot"] == 6 * W * 96 * 4
    assert status["kv"]["layers"] == 3
    assert status["kv"]["bytes_per_token"] == 3 * 2 * 3 * 16 * 4


def test_a_long_prompt_costs_a_slot_what_a_short_one_does(params):
    """A sliding layer keeps ``window`` rows a slot whatever the length:
    a 100-token prompt leaves ``serving_state_cache_bytes`` and the
    pool's pages a token as a 9-token one does, and the pages it takes
    are the full layers' alone."""
    eng = _engine(params, max_batch=2)
    rng = np.random.default_rng(4)
    gauge = lambda: eng.registry.snapshot()["gauges"][
        "serving_state_cache_bytes"]
    seen = []
    for rid, n in enumerate((9, 100)):
        eng.submit(rid, rng.integers(0, CFG.vocab_size, n).tolist(),
                   max_new_tokens=4)
        peak = 0
        while eng.queue or any(s is not None for s in eng.slots):
            eng.step()
            kv = eng.statusz()["kv"]
            peak = max(peak, kv["pages_live"])
        seen.append((gauge(), eng.statusz()["kv"]["bytes_per_token"], peak))
    (b0, t0, p0), (b1, t1, p1) = seen
    assert b0 == b1 == eng.cache.conv.nbytes and t0 == t1
    # whole chunks of 16: a padded last chunk's pages are taken too
    assert p0 == 16 // PAGE and p1 == 112 // PAGE
    assert eng.cache.k.shape[0] * 2 * 3 * 16 * 4 == t0


def test_a_preempted_request_resumes_with_nothing_in_view(params):
    """More requests than slots and too few pages for two at once: the
    younger request is preempted while it decodes; it is prefilled again
    (prompt and what it had generated) into rings it sees nothing of and
    ends where an undisturbed run ends."""
    rng = np.random.default_rng(2)
    prompts = {i: rng.integers(0, CFG.vocab_size, n).tolist()
               for i, n in enumerate((30, 26, 11))}
    eng = _engine(params, max_batch=2, num_pages=10, max_seq=64)
    for i, p in prompts.items():
        eng.submit(i, p, max_new_tokens=14)
    out = eng.run()
    counters = eng.registry.snapshot()["counters"]
    assert counters["serving_preempted_requests"] >= 1
    _argmax_served(params, out, prompts)
    assert eng.check_leaks() == []


# --------------------------------------------------- (iv) what is refused
@pytest.mark.parametrize("mechanism,kw", [
    ("prefix_cache", dict(prefix_cache=True)),
    ("kv_tier", dict(kv_tier={"host_pool_bytes": 1 << 20})),
    ("speculative", dict(speculative={"enabled": True, "draft_tokens": 2})),
    ("zero_inference", dict(zero_inference={"enabled": True})),
])
def test_the_family_refuses_by_name(params, mechanism, kw):
    with pytest.raises(NotImplementedError, match=mechanism):
        _engine(params, **kw)


def test_quantized_resident_contiguous_cache_and_a_mesh_are_refused(params):
    fam = decoder_family(CFG)
    assert {m for m, _ in fam.refuses} == {
        "prefix_cache", "kv_tier", "quantized_resident", "speculative",
        "zero_inference", "contiguous_cache"}
    with pytest.raises(NotImplementedError, match="quantized_resident"):
        fam.refuse(quantized_resident=True)
    with pytest.raises(NotImplementedError, match="contiguous_cache"):
        generator(params, CFG)
    mesh = MeshSpec.build({"model": 2}, devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="model or expert axis"):
        _engine(params, mesh=mesh)
    with pytest.raises(ValueError, match="max_seq_len"):
        _engine(params, max_seq=CFG.max_seq_len + PAGE)

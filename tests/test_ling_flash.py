"""The ling_flash family on the serving path, against the benchmark's
plain float32 reference (``benchmark/reference/ling_flash.py``, which
imports nothing from ``deepspeed_tpu``): KDA layers (a delta rule gated a
key channel) over a per-slot state beside a pool of latent rows, one
latent-attention layer a period, a leading KDA layer with a dense FFN,
the seam's refusals (the rule by itself, its kernels and the
group-limited share of the experts: ``test_ling_flash_rule.py``).  Toy
widths, seeded weights, CPU."""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.families import ling_flash as bench_family  # noqa: E402
from benchmark.reference import ling_flash as reference  # noqa: E402
from deepspeed_tpu.inference import kernels as K  # noqa: E402
from deepspeed_tpu.inference.generation import generator  # noqa: E402
from deepspeed_tpu.inference.paged_forward import forward_paged  # noqa: E402
from deepspeed_tpu.inference.serving import serving_engine  # noqa: E402
from deepspeed_tpu.models import ling_flash as lf  # noqa: E402
from deepspeed_tpu.models.family import decoder_family  # noqa: E402
from deepspeed_tpu.topology import MeshSpec  # noqa: E402

CFG = lf.LingFlashConfig.tiny()     # two periods of two KDA and one MLA layer
PAGE = 8


@pytest.fixture(scope="module")
def params():
    return lf.init_params(jax.random.PRNGKey(0), CFG)


_REFERENCE = jax.jit(lambda params, tokens: reference.forward(
    params, tokens, **bench_family._ref_kw(CFG)))


def _reference_logits(params, tokens):
    """The reference's logits of ``tokens``; run at one padded length
    (causal: what follows a position does not reach it), so that it
    compiles once."""
    padded = np.zeros(64, np.int32)
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


def _cache(cfg, slots, rows, max_seq, slot=None):
    """A pool of the latent layers alone (one row a token, no V pool),
    the per-slot state beside it, and the counter; ``rows`` rows of
    table."""
    sr = decoder_family(cfg).recurrent.state_row(cfg)
    mp = -(-max_seq // PAGE)
    pages = slots * mp + 1
    table = np.arange(slots * mp).reshape(slots, mp)[:rows]
    return K.PagedKVCache(
        k=jnp.zeros((cfg.n_mla_layers, 1, pages, PAGE, cfg.head_dim)),
        v=None, table=jnp.asarray(table, jnp.int32),
        seq_lens=jnp.zeros((rows,), jnp.int32), page_size=PAGE,
        expert_rows=jnp.zeros((cfg.experts_held[1],), jnp.int32),
        conv=jnp.zeros((sr.layers, slots) + sr.conv),
        state=jnp.full((sr.layers, slots) + sr.state, 7.0),  # stale
        slot=slot)


# ------------------------------------------------- what the family states
def test_the_layers_are_a_dense_lead_a_part_period_and_whole_periods():
    """Layer 0 (and 1, as published) is the first KDA layer of the first
    period with a dense FFN: the lead's stack; the rest of that period
    and the whole periods behind it are sections over one pool of the
    latent layers and one state buffer of every KDA layer."""
    fam = decoder_family(CFG)
    assert fam.latent is not None and fam.recurrent is not None
    assert fam.lead is None and fam.recurrent.lead[0] == "lead_blocks"
    assert lf._sections(CFG) == (((True, False), 1),
                                 ((True, True, False), 1))
    full = lf.LingFlashConfig()
    assert lf._sections(full) == (
        ((True,) * 3 + (False,), 1), ((True,) * 5 + (False,), 6))
    assert (full.n_kda_layers, full.n_mla_layers, fam.pool_layers(full)) \
        == (35, 7, 7)
    assert fam.recurrent.state_row(full) == (35, (3, 12288), (32, 128, 128))
    row = fam.cache_row(full)
    assert (row.key_width, row.pool_width, row.values_in_keys) \
        == (576, 640, True)
    shapes = jax.eval_shape(lambda: lf.init_params(jax.random.PRNGKey(0),
                                                   CFG))
    assert shapes["lead_blocks"]["w1"].shape == (1, CFG.dim, CFG.ffn_dim)
    assert shapes["kda_blocks"]["w_qkv"].shape[0] == 3
    assert shapes["blocks"]["wq"].shape[0] == 2
    none = lf.LingFlashConfig.tiny(n_dense_layers=0)
    assert lf._sections(none) == (((True, True, False), 2),)


# -------------------------------- (i) the paged forward vs the reference
def test_chunks_then_masked_decode_steps_match_the_reference_logits(params):
    """Prompts of 5, 21 and 37 tokens go through chunks of 16 that do
    not divide them (the carry, and a padded last chunk whose padding
    must move nothing), each into its own slot over a state that held
    rubbish (a first chunk starts from zero); between one slot's chunks
    the others' decode steps run over all three rows with the
    unfinished row masked.  Every real position's logits match the
    reference's full forward."""
    rng = np.random.default_rng(1)
    lens, new, C = (5, 21, 37), 5, 16
    seqs = [rng.integers(0, CFG.vocab_size, n + new) for n in lens]
    want = [_reference_logits(params, s) for s in seqs]
    got = [np.zeros_like(w) for w in want]
    cache = _cache(CFG, 3, 3, 64)
    tables, trash = cache.table, cache.k.shape[2] - 1
    fwd = jax.jit(lambda toks, c, continuation=False: forward_paged(
        params, toks, CFG, c, tp=False, interpret=True,
        continuation=continuation), static_argnames="continuation")
    done, at = [0, 0, 0], list(lens)            # prefilled; decoded up to

    def chunk(b):
        take = min(C, lens[b] - done[b])
        toks = np.zeros((1, C), np.int32)
        toks[0, :take] = seqs[b][done[b]:done[b] + take]
        view = cache._replace(
            table=cache.table[b:b + 1], slot=jnp.full((1,), b, jnp.int32),
            seq_lens=jnp.full((1,), done[b], jnp.int32),
            real=jnp.full((1,), take, jnp.int32))
        logits, view = fwd(jnp.asarray(toks), view, continuation=True)
        assert view.real is None
        got[b][done[b]:done[b] + take] = np.asarray(logits[0, :take])
        done[b] += take
        return cache._replace(k=view.k, conv=view.conv, state=view.state,
                              expert_rows=view.expert_rows)

    def decode():
        ready = [b for b in range(3) if done[b] == lens[b]
                 and at[b] < len(seqs[b])]
        lens_now = np.array([at[b] if b in ready else 0 for b in range(3)])
        toks = [[seqs[b][at[b]] if b in ready else 0] for b in range(3)]
        # as the engine uploads them: a row that is not ready has length
        # 0 and the trash page for a table
        table = np.where((lens_now > 0)[:, None], np.asarray(tables),
                         trash)
        c = cache._replace(seq_lens=jnp.asarray(lens_now, jnp.int32),
                           table=jnp.asarray(table, jnp.int32),
                           real=jnp.asarray(lens_now > 0, jnp.int32))
        logits, c = fwd(jnp.asarray(toks, jnp.int32), c)
        c = c._replace(table=tables)
        np.testing.assert_array_equal(      # a masked row does not advance
            np.asarray(c.seq_lens), lens_now + (lens_now > 0))
        for b in ready:
            got[b][at[b]] = np.asarray(logits[b, 0])
            at[b] += 1
        return c

    cache = chunk(0)                            # slot 0 ready
    for _ in range(3):                          # slot 2's three chunks,
        cache = chunk(2)                        # slot 0 decoding between
        cache = decode()
    cache = chunk(1)
    cache = chunk(1)
    while any(at[b] < len(seqs[b]) for b in range(3)):
        cache = decode()
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=3e-4, rtol=3e-4)
    assert int(cache.expert_rows.sum()) > 0


def test_padding_and_masked_rows_leave_the_state_bit_for_bit(params):
    """A decode step over a masked row, and a chunk's rows past its
    last real token, leave (conv, S) exactly as they were, in the
    leading layer's state as in the others'."""
    cache = _cache(CFG, 2, 2, 32)._replace(
        seq_lens=jnp.asarray([9, 0], jnp.int32),
        real=jnp.asarray([1, 0], jnp.int32))
    _, after = forward_paged(params, jnp.zeros((2, 1), jnp.int32), CFG,
                             cache, tp=False, interpret=True)
    for was, now in ((cache.conv, after.conv), (cache.state, after.state)):
        np.testing.assert_array_equal(np.asarray(was[:, 1]),
                                      np.asarray(now[:, 1]))
        for layer in range(was.shape[0]):       # every KDA layer stepped
            assert not np.array_equal(np.asarray(was[layer, 0]),
                                      np.asarray(now[layer, 0]))
    toks = np.random.default_rng(3).integers(0, CFG.vocab_size, (1, 16))
    view = lambda real: _cache(CFG, 1, 1, 32, slot=jnp.zeros(
        (1,), jnp.int32))._replace(real=jnp.full((1,), real, jnp.int32))
    run = lambda t, real: forward_paged(
        params, jnp.asarray(t), CFG, view(real), continuation=True,
        tp=False, interpret=True)[1]
    padded, other = toks.copy(), toks.copy()
    other[0, 11:] = 5                           # other padding, same state
    a, b = run(padded, 11), run(other, 11)
    np.testing.assert_array_equal(np.asarray(a.state), np.asarray(b.state))
    np.testing.assert_array_equal(np.asarray(a.conv), np.asarray(b.conv))


@pytest.mark.parametrize("start", [0, 16, 23, 47, 64])
def test_the_reference_in_blocks_is_the_reference_whole(params, monkeypatch,
                                                        start):
    """A KDA layer of the reference 16 rows at a time (the chip's 4,096
    at the rehearsal's size: the check pads its longest request to 32,768
    rows) leaves what the layer at once leaves: the logits of a stretch
    that begins at a block's edge, inside a block, at position 0 and
    past the last token, run from what ``carry`` kept."""
    kw = bench_family._ref_kw(CFG)
    tokens = jnp.asarray(np.random.default_rng(5).integers(
        0, CFG.vocab_size, 64))
    count = min(8, 64 - start) or 1
    none = jnp.zeros((CFG.n_layers, count), jnp.int32)

    def run():
        held = reference.carry(params, tokens, start, **kw)
        states = [held["kda"][1]]
        if start == 64:
            return states
        return states + [reference.logits(params, tokens, held, start,
                                          count, none, **kw)[0]]

    whole = run()
    monkeypatch.setattr(reference, "M_BLOCK", 16)
    for got, want in zip(run(), whole):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)


# ------------------------------------------- (ii) through serving_engine
@pytest.mark.parametrize("engine_kw", [
    dict(prefill_chunk=16), dict(prefill_chunk=0, prefill_bucket=16)],
    ids=["chunks", "whole_prompt"])
def test_the_engine_serves_the_reference_argmax(params, engine_kw):
    """Scheduler, allocator, latent pool, state cache, boundary sampling
    and the decode program's packed fetch: four requests through three
    slots (the fourth reuses a slot whose state a longer request left),
    greedy tokens the reference's argmax given the served prefix."""
    eng = _engine(params, **engine_kw)
    sr = decoder_family(CFG).recurrent.state_row(CFG)
    assert eng.cache.k.shape[:2] == (CFG.n_mla_layers, 1) == (2, 1)
    assert eng.cache.k.shape[-1] == 128 and eng.cache.v is None
    assert eng.cache.state.shape == (4, 3) + sr.state
    assert eng.cache.state.dtype == jnp.float32
    rng = np.random.default_rng(0)
    prompts = {i: rng.integers(0, CFG.vocab_size, n).tolist()
               for i, n in enumerate((37, 21, 5, 9))}
    for i, p in prompts.items():
        eng.submit(i, p, max_new_tokens=7)
    out = eng.run()
    _argmax_served(params, out, prompts)
    assert eng.check_leaks() == []
    counters = eng.registry.snapshot()["counters"]
    assert counters["serving_state_fresh_starts"] == 4
    assert counters["serving_state_rows_masked"] > 0
    routed = counters["serving_routed_rows"]
    held = sum(counters[f"serving_expert_rows_{e}"]
               for e in range(CFG.experts_held[1]))
    # the dense layer routes nothing: five expert layers of six
    assert CFG.n_expert_layers == 5
    assert routed % (CFG.top_k * CFG.n_expert_layers) == 0
    assert 0 < held < routed
    status = eng.statusz()["cache.state"]
    assert status["bytes"] == eng.cache.conv.nbytes + eng.cache.state.nbytes
    assert status["live_slots"] == 0 and status["fresh_starts"] == 4


def test_a_preempted_request_resumes_from_a_fresh_state(params):
    """Too few pages for both: the younger request is preempted while
    it decodes, its slot's state dropped; it is prefilled again from
    zero state and ends where an undisturbed run ends."""
    rng = np.random.default_rng(2)
    prompts = {i: rng.integers(0, CFG.vocab_size, n).tolist()
               for i, n in enumerate((30, 26))}
    eng = _engine(params, max_batch=2, num_pages=10, max_seq=64)
    for i, p in prompts.items():
        eng.submit(i, p, max_new_tokens=14)
    out = eng.run()
    counters = eng.registry.snapshot()["counters"]
    assert counters["serving_preempted_requests"] >= 1
    assert counters["serving_state_fresh_starts"] >= 3
    _argmax_served(params, out, prompts)
    assert eng.check_leaks() == []


def test_the_engine_serves_the_reference_argmax_through_the_chunk_kernel(
        params, monkeypatch):
    """The rule of the build says ``xla`` off the TPU; forced, every
    prompt chunk carries its state through ``dstpu_state_chunk`` in
    interpret mode under the family's block rule, its operands the
    rule's own four (q, k, v and the running decay a key channel), and
    the greedy tokens are still the reference's argmax."""
    from deepspeed_tpu.inference import paged_forward

    calls = []
    monkeypatch.setattr(paged_forward, "state_chunker",
                        lambda *a, **kw: ("pallas", "forced by the test"))

    def counted(rule, S, mats, cols, lanes, **kw):
        assert kw["interpret"] and kw["block"] == CFG.kda_block
        assert rule is lf.kda_block_rule and len(mats) == 4
        assert cols.shape[-1] == 1
        calls.append(S.shape)
        return K.state_chunk(rule, S, mats, cols, lanes, **kw)

    monkeypatch.setattr(paged_forward, "state_chunk", counted)
    eng = _engine(params)
    rng = np.random.default_rng(1)
    prompts = {i: rng.integers(0, CFG.vocab_size, n).tolist()
               for i, n in enumerate((37, 21, 5, 9))}
    for i, p in prompts.items():
        eng.submit(i, p, max_new_tokens=7)
    _argmax_served(params, eng.run(), prompts)
    sr = decoder_family(CFG).recurrent.state_row(CFG)
    assert calls and set(calls) == {(1,) + sr.state}
    assert eng.check_leaks() == []


def test_the_policy_names_the_readers_and_why(params):
    """``/statusz`` ``kernels``: the state's step ``pallas`` on one
    device, the latent decode reader and the chunk's rule by name with
    their reasons; at the published head (128 x 128) on a TPU the chunk
    runs ``dstpu_state_chunk``."""
    kernels = _engine(params).statusz()["kernels"]
    assert kernels["state_step"] == "pallas" and kernels["fallbacks"] == []
    assert kernels["state_chunk"] == {
        "reader": "xla", "reason": "interpret: no TPU backend"}
    assert "latent rows" in kernels["decode"]["reason"]
    stated = (decoder_family(CFG).recurrent, lf.LingFlashConfig())
    on_chip = K.resolve_serving_kernels(recurrent=True, state_block=stated)
    assert on_chip.state_chunk[0] == "pallas" and on_chip.fallbacks == ()
    assert K.latent_reader(on_chip.decode)[0] == "dstpu_mla_decode"
    assert K.state_chunker((stated[0], CFG), tp=False, interpret=False) == (
        "xla", "a head's state is not whole 128-lane tiles")


# --------------------------------------------------- (v) what is refused
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
    with pytest.raises(AssertionError, match="first period"):
        lf.LingFlashConfig.tiny(n_dense_layers=3)

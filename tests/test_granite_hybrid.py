"""The granite_hybrid family on the serving path, against the benchmark's
plain float32 reference (``benchmark/reference/granite_hybrid.py``, which
imports nothing from ``deepspeed_tpu``): Mamba-2 layers over a per-slot
state beside the page pool, attention layers without positions and with
a stated softmax scale standing INSIDE a period, a dense SwiGLU, four
multipliers, a tied head, the seam's refusals (the recurrence by itself
and its kernel: ``test_granite_hybrid_rule.py``).  Toy widths, seeded
weights, CPU."""

import dataclasses
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.families import granite_hybrid as bench_family  # noqa: E402
from benchmark.reference import granite_hybrid as reference  # noqa: E402
from deepspeed_tpu.inference import kernels as K  # noqa: E402
from deepspeed_tpu.inference.generation import generator  # noqa: E402
from deepspeed_tpu.inference.paged_forward import forward_paged  # noqa: E402
from deepspeed_tpu.inference.serving import serving_engine  # noqa: E402
from deepspeed_tpu.models import granite_hybrid as gh  # noqa: E402
from deepspeed_tpu.models.family import (decoder_families,  # noqa: E402
                                         decoder_family)
from deepspeed_tpu.topology import MeshSpec  # noqa: E402

# two periods of m m A m: runs of two and of one recurrent layer about
# an attention layer that does not end its period
CFG = gh.GraniteHybridConfig.tiny()
PAGE = 8
# float32 end to end, the two sides summing in different orders (blocks
# against a token at a time, gathered pages against whole rows): 3e-6 on
# logits of about unit variance, read.  With bfloat16 weights and
# activations in place of the float32 the tests run in, the same
# comparison reads 0.1 and more: the tolerance sits a hundred times
# above the one and a hundred times under the other
TOL = dict(atol=2e-4, rtol=2e-4)


@pytest.fixture(scope="module")
def params():
    return gh.init_params(jax.random.PRNGKey(0), CFG)


def _ref_kw(cfg):
    return dict(bench_family._ref_kw(cfg), logits_scale=cfg.logits_scaling)


_REFERENCE = jax.jit(lambda params, tokens: reference.forward(
    params, tokens, **_ref_kw(CFG)))


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


def _cache(cfg, slots, rows, max_seq, slot=None, dtype=jnp.float32):
    """A pool of the attention layers alone, the per-slot state beside
    it; ``rows`` rows of table."""
    fam = decoder_family(cfg)
    sr, row = fam.recurrent.state_row(cfg), fam.cache_row(cfg)
    mp = -(-max_seq // PAGE)
    shape = (cfg.n_attn_layers, row.n_kv, slots * mp + 1, PAGE,
             row.pool_width)
    table = np.arange(slots * mp).reshape(slots, mp)[:rows]
    return K.PagedKVCache(
        k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
        table=jnp.asarray(table, jnp.int32),
        seq_lens=jnp.zeros((rows,), jnp.int32), page_size=PAGE,
        conv=jnp.zeros((sr.layers, slots) + sr.conv, dtype),
        state=jnp.full((sr.layers, slots) + sr.state, 7.0),   # stale
        slot=slot)


def _chunks_then_steps(params, cfg, seq, n_prompt, C=16, dtype=jnp.float32):
    """Logits of every position of ``seq``: its first ``n_prompt``
    tokens through chunks of ``C`` (a padded last chunk), the rest a
    decode step each, over one slot of a paged cache."""
    cache = _cache(cfg, 1, 1, 64, dtype=dtype)
    # jitted: compiled once a shape (called eagerly the forward's loops
    # compile anew at every call)
    jitted = jax.jit(lambda toks, c, continuation=False: forward_paged(
        params, toks, cfg, c, tp=False, interpret=True,
        continuation=continuation), static_argnames="continuation")
    fwd = lambda toks, c, **kw: jitted(jnp.asarray(toks), c, **kw)
    got = []
    for done in range(0, n_prompt, C):
        take = min(C, n_prompt - done)
        toks = np.zeros((1, C), np.int32)
        toks[0, :take] = seq[done:done + take]
        logits, cache = fwd(toks, cache._replace(
            slot=jnp.zeros((1,), jnp.int32),
            seq_lens=jnp.full((1,), done, jnp.int32),
            real=jnp.full((1,), take, jnp.int32)), continuation=True)
        assert cache.real is None
        got.append(np.asarray(logits[0, :take], np.float32))
    for at in range(n_prompt, len(seq)):
        logits, cache = fwd([[seq[at]]], cache._replace(
            slot=None, seq_lens=jnp.full((1,), at, jnp.int32),
            real=jnp.ones((1,), jnp.int32)))
        got.append(np.asarray(logits[0], np.float32))
    return np.concatenate(got)


# -------------------------------- (i) the paged forward vs the reference
def test_the_family_is_registered_and_its_period_is_as_stated():
    assert "GraniteHybridConfig" in [f.name for f in decoder_families()]
    assert "GraniteHybridConfig" in [
        f.name for f in decoder_families() if f.recurrent is not None]
    fam = decoder_family(CFG)
    assert fam.recurrent.period(CFG) == (True, True, False, True)
    assert fam.recurrent.write_scope == "ssm_write"
    assert fam.expert_rows(CFG) == fam.router(CFG) == (0, 0)
    whole = gh.GraniteHybridConfig()
    assert fam.recurrent.period(whole) == (True,) * 5 + (False,) \
        + (True,) * 4
    sr = fam.recurrent.state_row(whole)
    assert sr == (36, (3, 4352), (64, 64, 128))
    assert fam.cache_row(whole)[:3] == (8, 128, 128)  # 64 numbers, a tile
    assert (whole.n_ssm_layers, whole.n_attn_layers) == (36, 4)
    assert gh.param_count(whole) == 3_191_396_096
    cut = gh.GraniteHybridConfig.from_layer_types(
        ["mamba", "attention"] * 3, vocab_size=8)
    assert (cut.n_layers, cut.period) == (6, ("mamba", "attention"))


def test_chunks_then_decode_steps_match_the_reference_logits(params):
    """A prompt of 37 tokens through chunks of 16 that do not divide it
    (the carry of state and convolution rows, and a padded last chunk
    whose padding must move nothing) into a slot whose state held
    rubbish (a first chunk starts from zero), then 11 decode steps: every
    position's logits are the reference's full forward's.  The same with
    bfloat16 weights, activations and pages parts from it by a hundred
    times the tolerance: the tolerance holds the float32 path to
    float32."""
    seq = np.random.default_rng(1).integers(0, CFG.vocab_size, 48)
    want = _reference_logits(params, seq)
    got = _chunks_then_steps(params, CFG, seq, 37)
    np.testing.assert_allclose(got, want, **TOL)
    half = gh.init_params(jax.random.PRNGKey(0), CFG, jnp.bfloat16)
    low = _chunks_then_steps(half, CFG, seq, 37, dtype=jnp.bfloat16)
    assert np.abs(low - want).max() > 100 * TOL["atol"]


def test_padding_and_masked_rows_leave_the_state_bit_for_bit(params):
    """A decode step over a masked row (``valid`` 0), and a chunk's rows
    past its last real token, leave (conv, S) exactly as they were."""
    cache = _cache(CFG, 2, 2, 32)._replace(
        seq_lens=jnp.asarray([9, 0], jnp.int32),
        real=jnp.asarray([1, 0], jnp.int32))
    _, after = forward_paged(params, jnp.zeros((2, 1), jnp.int32), CFG,
                             cache, tp=False, interpret=True)
    for was, now in ((cache.conv, after.conv), (cache.state, after.state)):
        np.testing.assert_array_equal(np.asarray(was[:, 1]),
                                      np.asarray(now[:, 1]))
        assert not np.array_equal(np.asarray(was[:, 0]),
                                  np.asarray(now[:, 0]))
    np.testing.assert_array_equal(np.asarray(after.seq_lens), [10, 0])
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
    # and a row with no real token at all keeps what its slot held
    c = run(padded, 0)
    assert float(jnp.abs(c.state).max()) == 0.0     # zeroed at position 0,
    assert float(jnp.abs(c.conv).max()) == 0.0      # then not moved


# ------------------------------ (ii) each stated piece is load-bearing
def _norm_before_gate(o, z, w, eps):
    n = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)
    return n * jax.nn.silu(z.astype(jnp.float32))


def _untied_head(params, x, cfg):
    other = jax.random.normal(jax.random.PRNGKey(9), params["embed"].shape)
    return gh._head(dict(params, embed=other), x, cfg)


@pytest.mark.parametrize("piece,cfg_kw,patch", [
    ("embedding_multiplier", dict(embedding_multiplier=1.0), None),
    ("residual_multiplier", dict(residual_multiplier=1.0), None),
    ("logits_scaling", dict(logits_scaling=1.0), None),
    ("attention_multiplier", dict(attention_multiplier=16 ** -0.5), None),
    ("gate_before_norm", {}, ("_gated_norm", _norm_before_gate)),
    ("tied_head", {}, ("_head", _untied_head)),
], ids=lambda v: v if isinstance(v, str) else "")
def test_each_stated_piece_fails_the_comparison_when_left_out(
        params, monkeypatch, piece, cfg_kw, patch):
    """The program with one piece of the model's statement left out (a
    multiplier at its neutral value, the softmax at ``head_dim^-1/2``
    in place of the stated scale, the norm before the gate, a head that
    is not the embedding) no longer agrees with the reference, which
    has them all."""
    seq = np.random.default_rng(2).integers(0, CFG.vocab_size, 24)
    want = _reference_logits(params, seq)
    cfg = dataclasses.replace(CFG, **cfg_kw)
    if patch is not None:
        if patch[0] == "_head":
            fam = dataclasses.replace(gh.FAMILY, head=patch[1])
            monkeypatch.setattr(gh, "FAMILY", fam)
        else:
            monkeypatch.setattr(gh, *patch)
    got = _chunks_then_steps(params, cfg, seq, 19)
    assert np.abs(got - want).max() > 20 * TOL["atol"], piece


# ------------------------------------------ (iii) through serving_engine
def test_the_engine_serves_the_reference_argmax(params):
    """Scheduler, allocator, state cache, boundary sampling: five
    requests through three slots in split-fuse chunks of 16 (the later
    ones reuse slots whose state longer requests left), greedy tokens
    the reference's argmax given the served prefix."""
    eng = _engine(params)
    sr = decoder_family(CFG).recurrent.state_row(CFG)
    assert eng.cache.k.shape[0] == CFG.n_attn_layers == 2
    assert eng.cache.k.shape[-1] == CFG.kv_width == 128
    assert eng.cache.state.shape == (6, 3) + sr.state
    assert eng.cache.state.dtype == jnp.float32
    assert eng.cache.expert_rows is None
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
    status = eng.statusz()["cache.state"]
    assert status["bytes"] == eng.cache.conv.nbytes + eng.cache.state.nbytes
    assert status["bytes_per_slot"] * 3 == status["bytes"]
    assert status["live_slots"] == 0 and status["fresh_starts"] == 5


def test_a_preempted_request_resumes_from_a_fresh_state(params):
    """More requests than slots and too few pages for two at once: the
    younger request is preempted while it decodes, its slot's state
    dropped; it is prefilled again (prompt and what it had generated)
    from zero state and ends where an undisturbed run ends."""
    rng = np.random.default_rng(2)
    prompts = {i: rng.integers(0, CFG.vocab_size, n).tolist()
               for i, n in enumerate((30, 26, 11))}
    eng = _engine(params, max_batch=2, num_pages=10, max_seq=64)
    for i, p in prompts.items():
        eng.submit(i, p, max_new_tokens=14)
    out = eng.run()
    counters = eng.registry.snapshot()["counters"]
    assert counters["serving_preempted_requests"] >= 1
    assert counters["serving_state_fresh_starts"] >= 4
    _argmax_served(params, out, prompts)
    assert eng.check_leaks() == []


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
    with pytest.raises(NotImplementedError, match="model axis"):
        _engine(params, mesh=mesh)
    with pytest.raises(ValueError, match="max_seq_len"):
        _engine(params, max_seq=CFG.max_seq_len + PAGE)

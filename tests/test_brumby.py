"""The retention family (``deepspeed_tpu/models/brumby.py``): power
retention of degree 2 in every layer, so a family with no pool layer at
all.  The served recurrence against the DEFINITION (``benchmark/reference/
brumby.py``, the attention form, which never uses ``phi``), the chunked
rule against the recurrence, the kernels in interpret mode, the paged
forward through a cache that holds no pool, and the engine."""

import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import brumby as reference
from deepspeed_tpu.inference import kernels as K
from deepspeed_tpu.inference.paged_forward import forward_paged
from deepspeed_tpu.inference.serving import serving_engine
from deepspeed_tpu.models import brumby as M
from deepspeed_tpu.models.family import (CarriedState, SlotState,
                                         decoder_family, sections_of)

CFG = M.BrumbyConfig.tiny()
KW = dict(head_dim=CFG.head_dim, rope_theta=CFG.rope_theta, eps=CFG.norm_eps,
          ret_eps=CFG.ret_eps)


@pytest.fixture(scope="module")
def params():
    return M.init_params(jax.random.PRNGKey(0), CFG)


def _qkvg(T, seed=0, B=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    H, KV, Dh = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
    return (jax.random.normal(ks[0], (B, T, H, Dh)),
            jax.random.normal(ks[1], (B, T, KV, Dh)),
            jax.random.normal(ks[2], (B, T, KV, Dh)),
            jax.nn.log_sigmoid(jax.random.normal(ks[3], (B, T, KV)) + 3.0))


def _zero(B=1):
    return jnp.zeros((B,) + CFG.state_shape)


def _some_state(seed, *lead):
    """A random state as the rule could have left it: the seven rows
    behind the normaliser's in each rotation's tile are zeros."""
    S = jax.random.normal(jax.random.PRNGKey(seed), lead + CFG.state_shape)
    rows = np.arange(CFG.state_shape[1]) % (CFG.head_dim + 8)
    return S * (rows <= CFG.head_dim)[:, None]


def _steps(q, k, v, logg, S):
    """The recurrence a token at a time -> (o [B, T, H, Dh], S)."""
    outs = []
    for t in range(q.shape[1]):
        o, S = M.ret_step(CFG, q[:, t], k[:, t], v[:, t],
                          jnp.exp(logg[:, t]), S)
        outs.append(o)
    return jnp.stack(outs, 1), S


@pytest.mark.parametrize("head_dim", [16, 128])
def test_phi_keeps_the_square_of_the_product(head_dim):
    a, b = jax.random.normal(jax.random.PRNGKey(1), (2, 7, head_dim))
    got = (M.phi(a) * M.phi(b)).sum((-2, -1))
    want = (a * b).sum(-1) ** 2
    assert M.phi(a).shape == (7, head_dim // 2 + 1, head_dim)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-4)
    # the off-diagonal weight matters: without it the square is lost
    w = M.phi_weights(head_dim)
    assert w[0] == w[-1] == 1.0 and np.allclose(w[1:-1], np.sqrt(2.0))


def test_the_recurrence_is_the_definition_token_by_token():
    q, k, v, logg = _qkvg(24)
    got, _ = _steps(q, k, v, logg, _zero())
    want = reference.retention(q[0], k[0], v[0], jnp.cumsum(logg[0], 0),
                               CFG.ret_eps)
    np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("block", [4, 8, 16])
def test_any_block_gives_the_recurrences_numbers(block):
    q, k, v, logg = _qkvg(27, seed=2)       # a last block of padding
    cfg = dataclasses.replace(CFG, ret_block=block)
    want, S_want = _steps(q, k, v, logg, _zero())
    got, S = M.ret_chunk_rule(cfg, q, k, v, logg, _zero())
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(S, S_want, rtol=1e-4, atol=1e-4)


def test_five_queries_read_one_state_as_five_passes_would():
    q, k, v, logg = _qkvg(6, seed=3)
    S0 = _some_state(4, 1)
    o, S = M.ret_step(CFG, q[:, 0], k[:, 0], v[:, 0], jnp.exp(logg[:, 0]), S0)
    n = CFG.queries_per_state
    for j in range(n):
        # one query a state head at a time: the other rows zero
        alone = q[:, 0].reshape(1, CFG.n_kv_heads, n, -1)
        alone = (alone * (jnp.arange(n) == j)[:, None]).reshape(q[:, 0].shape)
        o_j, S_j = M.ret_step(CFG, alone, k[:, 0], v[:, 0],
                              jnp.exp(logg[:, 0]), S0)
        pick = lambda a: a.reshape(1, CFG.n_kv_heads, n, -1)[:, :, j]
        np.testing.assert_allclose(pick(o_j), pick(o), rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(S_j, S)      # the state is one pass's


def test_the_step_kernel_moves_a_layer_in_place_under_interpret():
    B = 3
    q, k, v, logg = _qkvg(1, seed=5, B=B)
    g = jnp.exp(logg[:, 0]).at[1].set(1.0)
    k0 = k[:, 0].at[1].set(0.0)                     # slot 1 is idle
    S = _some_state(6, 2, B)
    o_x, S_x = M.ret_step(CFG, q[:, 0], k0, v[:, 0], g, S[1])
    step = functools.partial(K.state_step, interpret=True)
    o_k, held = M.ret_step(CFG, q[:, 0], k0, v[:, 0], g,
                           CarriedState(S, jnp.int32(1), step))
    np.testing.assert_allclose(o_k, o_x, rtol=1e-4, atol=2e-4)
    np.testing.assert_allclose(held.buffer[1], S_x, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(held.buffer[0], S[0])
    np.testing.assert_array_equal(held.buffer[1, 1], S[1, 1])   # bit for bit


@pytest.mark.parametrize("heads", [1, 2])
def test_the_chunk_kernel_carries_a_heads_state_under_interpret(heads):
    q, k, v, logg = _qkvg(32, seed=7)
    k = k.at[:, 20:].set(0.0)                       # rows past the last real
    logg = logg.at[:, 20:].set(0.0)
    S0 = _some_state(8, 1)
    want, S_want = M.ret_chunk_rule(CFG, q, k, v, logg, S0)
    chunk = functools.partial(K.state_chunk, interpret=True, heads=heads)
    got, S = M.ret_chunk_kernel(CFG, q, k, v, logg, SlotState(S0, chunk))
    np.testing.assert_allclose(got[:, :20], want[:, :20], rtol=1e-3,
                               atol=1e-3)
    np.testing.assert_allclose(S, S_want, rtol=1e-3, atol=2e-3)


# ----------------------------------------------- through the paged forward
def _cache(slots):
    sr = M.FAMILY.recurrent.state_row(CFG)
    return K.PagedKVCache(
        k=None, v=None, table=jnp.zeros((slots, 1), jnp.int32),
        seq_lens=jnp.zeros((slots,), jnp.int32), page_size=8,
        state=jnp.zeros((sr.layers, slots) + sr.state, K.STATE_DTYPE))


def _chunk(params, cache, slot, tokens, done, width):
    """One prompt chunk of ``tokens`` (padded to ``width``) into ``slot``
    from position ``done`` -> (logits [1, 1, V], cache)."""
    toks = np.zeros((1, width), np.int32)
    toks[0, :len(tokens)] = tokens
    view = cache._replace(
        table=cache.table[:1], slot=jnp.full((1,), slot, jnp.int32),
        seq_lens=jnp.full((1,), done, jnp.int32),
        real=jnp.full((1,), len(tokens), jnp.int32))
    logits, view = forward_paged(params, jnp.asarray(toks), CFG, view,
                                 continuation=True, tp=False)
    return logits, cache._replace(state=view.state)


def _decode(params, cache, lens, toks):
    lens = jnp.asarray(lens, jnp.int32)
    logits, out = forward_paged(
        params, jnp.asarray(toks, jnp.int32)[:, None], CFG,
        cache._replace(seq_lens=lens, real=(lens > 0).astype(jnp.int32)),
        tp=False)
    return logits[:, 0], cache._replace(state=out.state)


def _reference_logits(params, seq, start, count):
    with jax.default_matmul_precision("highest"):
        return reference.logits(params, jnp.asarray(seq), start, count, **KW)


def test_chunks_then_decode_through_the_cache_are_the_references_logits(
        params):
    """A prompt of two whole chunks and a padded third into slot 1 of a
    cache whose slot 1 held another request's state (a row that starts at
    0 starts from zero), another slot's decode steps between its chunks
    (they leave a slot between chunks as it was), then decode steps:
    every logit the definition's."""
    rng = np.random.default_rng(0)
    seq = rng.integers(0, CFG.vocab_size, 16 + 16 + 5 + 6)
    n = 16 + 16 + 5
    cache = _cache(2)
    cache = cache._replace(state=cache.state + 3.0)     # a slot reused
    rows = []
    for done in range(0, n, 16):
        logits, cache = _chunk(params, cache, 1, seq[done:min(done + 16, n)],
                               done, 16)
        assert logits.shape == (1, 1, CFG.vocab_size)   # the tail's row
        before = cache.state[:, 1]
        _, cache = _decode(params, cache, [9 + done, 0], [7, 7])
        np.testing.assert_array_equal(cache.state[:, 1], before)
    rows.append(logits[0, 0])
    for j in range(6):
        logits, cache = _decode(params, cache, [0, n + j], [7, seq[n + j]])
        rows.append(logits[1])
    want = _reference_logits(params, seq, n - 1, 7)
    np.testing.assert_allclose(jnp.stack(rows), want, rtol=2e-3, atol=2e-3)


def test_the_family_has_no_pool_layer():
    fam = decoder_family(CFG)
    assert fam.pool_layers(CFG) == 0 and fam.ffn_alone_layers(CFG) == 0
    assert fam.qkv is None and fam.out is None
    sr = fam.recurrent.state_row(CFG)
    assert sr.conv is None and sr.layers == CFG.n_layers
    assert sections_of(fam.recurrent, CFG) == (
        ((True,), CFG.n_layers), ((), 0))
    big = M.BrumbyConfig()
    assert big.state_shape == (8, 65 * 136, 128)
    assert M.param_count(dataclasses.replace(big, n_layers=10)) \
        == 4_859_358_800


def test_the_engine_serves_two_requests_at_once_with_no_pool(params):
    eng = serving_engine(params, CFG, max_batch=2, max_seq=96,
                         prefill_chunk=16, prefill_bucket=0, page_size=8,
                         num_pages=64, telemetry=True,
                         devprof={"sample_rate": 0.0, "cost_analysis": False})
    assert eng.cache.k is None and eng.cache.v is None
    assert eng.cache.conv is None and eng._pool_bytes() == 0
    rng = np.random.default_rng(1)
    prompts = {i: list(rng.integers(0, CFG.vocab_size, n))
               for i, n in enumerate((37, 20, 9))}
    for i, p in prompts.items():
        eng.submit(i, p, max_new_tokens=5)
    both = 0
    while eng.has_work:
        eng.step()
        both = max(both, sum(s is not None for s in eng.slots))
    assert both == 2
    for i, p in prompts.items():
        seq = np.asarray(eng.finished[i])
        lg = _reference_logits(params, seq, len(p) - 1, 5)
        served = lg[np.arange(5), seq[len(p):]]
        np.testing.assert_allclose(served, lg.max(-1), atol=1e-3)
    status = eng.statusz()
    assert (status["kv"]["layers"], status["kv"]["pages_usable"],
            status["kv"]["pages_live"]) == (0, 0, 0)
    assert status["cache.state"]["layers"] == CFG.n_layers
    assert status["cache.state"]["bytes"] == eng.cache.state.nbytes
    # four programs: the chunk (one: no table width to come in), the
    # boundary sampler, what writes its token into the decode's operand
    # (ISSUE 60), the decode step; none compiled later
    assert status["devprof"]["compiles_warmup"] == 4
    assert status["devprof"]["compiles_steady"] == 0
    assert eng.registry.gauge("serving_kv_page_utilization").value == 0
    assert eng.check_leaks() == []


@pytest.mark.parametrize("mechanism, build", [
    ("prefix_cache", {"prefix_cache": True}),
    ("kv_tier", None),
    ("speculative", {"speculative": {"enabled": True, "draft_tokens": 2}}),
    ("zero_inference", {"zero_inference": {"enabled": True}}),
    ("quantized_resident", None), ("tensor_parallel", None),
    ("contiguous_cache", None)])
def test_what_the_family_cannot_serve_with_is_refused_by_name(
        params, mechanism, build):
    fam = decoder_family(CFG)
    assert mechanism in dict(fam.refuses)
    with pytest.raises(NotImplementedError, match=mechanism):
        if build is None:
            fam.refuse(**{mechanism: True})
        else:
            serving_engine(params, CFG, max_batch=2, max_seq=64,
                           prefill_chunk=16, **build)

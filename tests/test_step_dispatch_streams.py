"""The streams a step's programs serve (ISSUE 31; the log, the ledger and
the ring are ``test_step_dispatch.py``'s, whose models and reference
these cases share): the same greedy tokens as the contiguous reference
whichever program sampled the boundary token, sampled streams a function
of the seed, and no token for a slot that left before the flush."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.serving import (RequestFailed, _last_row,
                                             _sample_rows,
                                             boundary_program,
                                             serving_engine)
from deepspeed_tpu.models import llama, mixtral

from test_step_dispatch import (KW, PROMPTS, counters, gpt2_model, model,
                                offline)


# ------------------------------------------ (b) the same greedy streams
def _serve_against_the_reference(cfg, params, kw):
    """Token for token, whichever program sampled the boundary token;
    every admission's token came from its prefill program's own result,
    at most one fetch a step."""
    eng = serving_engine(params, cfg, telemetry=True, **KW, **kw)
    reqs = {i: (p, 5) for i, p in enumerate(PROMPTS)}
    if "prefix_cache" in kw:
        # a second turn over the first's pages: admitted as a
        # continuation chunk behind cached history
        for i, (p, n) in reqs.items():
            eng.submit(("first", i), p, max_new_tokens=n)
        eng.run()
        reqs = {i: (p + [9, 9, 4], n) for i, (p, n) in reqs.items()}
    for i, (p, n) in reqs.items():
        eng.submit(i, p, max_new_tokens=n)
    out = eng.run()
    for i, (p, n) in reqs.items():
        assert out[i] == offline(cfg, params, p, n), i
    c = counters(eng)
    # joined to its decode on the device, or fetched before it
    assert c["serving_boundary_tokens"] + c["serving_boundary_joined"] \
        == c["serving_admitted_requests"]
    assert c["serving_boundary_syncs"] <= c["serving_boundary_tokens"]
    if "speculative" in kw or "zero_inference" in kw:
        assert c["serving_boundary_joined"] == 0
    else:
        assert c["serving_boundary_tokens"] == 0
    return c


@pytest.mark.parametrize("kw", [
    {}, {"prefill_chunk": 8}, {"prefix_cache": True},
    {"decode_chunk": 3}, {"speculative": {"draft_tokens": 3}}],
    ids=["whole_prompt", "split_fuse", "prefix_cache_continuation",
         "decode_chunk", "speculative"])
def test_greedy_streams_equal_the_reference(model, kw):
    c = _serve_against_the_reference(*model, kw)
    if "prefix_cache" in kw:
        assert c["prefix_cache_hits"] >= 2      # the prompts that fill a page


@pytest.mark.parametrize("kw", [
    {}, {"prefill_chunk": 8}, {"speculative": {"draft_tokens": 3}}],
    ids=["whole_prompt", "split_fuse", "speculative"])
@pytest.mark.parametrize("family", [llama, mixtral], ids=["llama", "mixtral"])
def test_streamed_engine_serves_the_reference(family, kw):
    """``ZeroInferenceServingEngine`` installs host-driven sweeps under
    the same contract: a prefill returns its token, a decode chunk
    derives its keys."""
    cfg = (family.LlamaConfig.tiny(dim=64, n_layers=2, n_heads=4,
                                   n_kv_heads=2)
           if family is llama else family.MixtralConfig.tiny())
    params = family.init_params(jax.random.PRNGKey(0), cfg)
    _serve_against_the_reference(cfg, params, {"zero_inference": {}, **kw})


@pytest.mark.parametrize("joins", [True, False],
                         ids=["the_decodes", "their_own"])
def test_admissions_of_one_step_share_a_fetch(gpt2_model, joins):
    """The decode's, where their tokens join it on the device; one of
    their own where the rule says they are fetched first."""
    cfg, params = gpt2_model
    eng = serving_engine(params, cfg, telemetry=True, **KW)
    if not joins:
        eng._joins = lambda: False
    for i, p in enumerate(PROMPTS):
        eng.submit(i, p, max_new_tokens=4)
    eng.step()
    c = counters(eng)
    assert c["serving_boundary_joined"] == (4 if joins else 0)
    assert c["serving_boundary_tokens"] == (0 if joins else 4)
    assert c["serving_boundary_syncs"] == (0 if joins else 1)
    assert [len(s.generated) for s in eng.slots] == [2] * 4


# -------------------------------------------------- (c) sampled streams
def _sampled(model, seed, **kw):
    cfg, params = model
    eng = serving_engine(params, cfg, seed=seed, **KW, **kw)
    for i, p in enumerate(PROMPTS):
        eng.submit(i, p, max_new_tokens=8, temperature=0.9)
    return eng.run()


@pytest.mark.parametrize("kw", [{}, {"prefill_chunk": 8},
                                {"speculative": {"draft_tokens": 3}}],
                         ids=["whole_prompt", "split_fuse", "speculative"])
def test_sampled_streams_are_a_function_of_the_seed(gpt2_model, kw):
    a, b, c = (_sampled(gpt2_model, s, **kw) for s in (3, 3, 4))
    assert a == b
    assert a != c
    # the boundary token alone differs between seeds too, somewhere
    n = [len(p) for p in PROMPTS]
    assert [a[i][n[i]] for i in a] != [c[i][n[i]] for i in c] or \
        [a[i][n[i] + 1] for i in a] != [c[i][n[i] + 1] for i in c]


@pytest.mark.parametrize("temp", [0.9, 0.0])
def test_boundary_token_marginal(temp):
    """What the engine adds to a prefill's forward: the row of the last
    real position (the padded tail's logits would give another token and
    are never read), and over 2,000 admission ordinals the token the
    boundary program draws from it follows softmax(row / T); at
    temperature 0 it is the argmax."""
    N, V, last = 2000, 6, 2
    row = np.array([1.5, 0.2, -0.5, 0.8, -1.0, 0.0], np.float32)
    logits = np.zeros((1, 5, V), np.float32)
    logits[0, :, 4] = 9.0                   # the padding's favourite
    logits[0, last] = row
    key = jax.random.PRNGKey(5)
    boundary = boundary_program(_sample_rows)
    row_d = _last_row(jnp.asarray(logits), jnp.asarray([last], jnp.int32))
    draw = jax.jit(jax.vmap(lambda n: boundary(
        row_d, key, n, jnp.full((1,), temp, jnp.float32))[0]))
    toks = np.asarray(draw(jnp.arange(N, dtype=jnp.int32)))
    if temp == 0.0:
        assert (toks == 0).all()
        return
    p = np.asarray(jax.nn.softmax(jnp.asarray(row) / temp))
    freq = np.bincount(toks, minlength=V) / N
    tol = np.maximum(5 * np.sqrt(p * (1 - p) / N), 0.01)
    assert np.all(np.abs(freq - p) < tol), (freq, p)


# ------------------------------- (d) a slot that leaves before the flush
def _prefilled(gpt2_model, n=2):
    """An engine holding ``n`` admissions whose prefills ran and whose
    boundary tokens wait for the flush."""
    cfg, params = gpt2_model
    eng = serving_engine(params, cfg, telemetry=True, **KW)
    for i in range(n):
        eng.submit(i, PROMPTS[i], max_new_tokens=4)
    while eng._admit_one():
        pass
    assert len(eng._pending_boundary) == n
    return eng


def _fail(eng):
    eng._fail_slot(0, RuntimeError("injected"))
    return 1


def _abandon(eng):
    assert len(eng.abandon_inflight()) == 2
    return 0


def _preempt(eng):
    eng._preempt_youngest()     # both have generated nothing: slot 0 goes
    assert len(eng.queue) == 1
    return 1


@pytest.mark.parametrize("leave", [_fail, _abandon, _preempt],
                         ids=["failed", "abandoned", "preempted"])
def test_a_slot_that_left_appends_no_token(gpt2_model, leave):
    cfg, params = gpt2_model
    eng = _prefilled(gpt2_model)
    kept = leave(eng)
    eng._flush_boundary()
    c = counters(eng)
    assert c["serving_boundary_tokens"] == kept
    assert eng.slots[0] is None
    if kept:
        assert len(eng.slots[1].generated) == 1
    out = eng.run()
    # what stayed, and what was requeued, still serve the reference's
    # tokens; what failed is typed
    for i in range(2):
        if leave is _abandon:
            assert i not in out
        elif leave is _fail and i == 0:
            assert isinstance(out[i], RequestFailed)
        else:
            assert out[i] == offline(cfg, params, PROMPTS[i], 4)
    assert eng.check_leaks() == []

"""A step dispatches its programs and nothing else (ISSUE 31): a
prefill program returns its last row, one compiled program samples the
boundary token from it, sampling keys are derived on the device from
integers the host has, and between the start of ``step()`` and the token
fetch the host runs no eager ``jnp`` / ``jax.random`` / device-indexing
operation."""

import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.generation import paged_generator
from deepspeed_tpu.inference.serving import (RequestFailed, _last_row,
                                             _sample_rows,
                                             boundary_program,
                                             serving_engine)
from deepspeed_tpu.models import gpt2, llama, mixtral

KW = dict(max_batch=4, page_size=8, num_pages=48, max_seq=64,
          prefill_bucket=8)
PROMPTS = [[5, 9, 2], [17, 3, 3, 8, 1], [40, 2, 11, 7, 7, 3, 9, 1, 4, 6],
           list(range(3, 24))]


def _gpt2():
    cfg = gpt2.GPT2Config.tiny(dim=64, n_layers=2, n_heads=4,
                               max_seq_len=64)
    return cfg, gpt2.init_params(jax.random.PRNGKey(0), cfg)


def _mixtral():
    cfg = mixtral.MixtralConfig.tiny()
    return cfg, mixtral.init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module", params=["gpt2", "mixtral"])
def model(request):
    return {"gpt2": _gpt2, "mixtral": _mixtral}[request.param]()


@pytest.fixture(scope="module")
def gpt2_model():
    return _gpt2()


def offline(cfg, params, prompt, n_new):
    """The contiguous-table reference: one request, alone."""
    out = paged_generator(params, cfg, page_size=8).generate(
        jnp.asarray([prompt], jnp.int32), max_new_tokens=n_new)
    return [int(t) for t in np.asarray(out[0])]


def counters(eng):
    return eng.registry.snapshot()["counters"]


# ------------------------------------------------ (a) no eager dispatch
@contextlib.contextmanager
def dispatch_log():
    """Every device program the host starts while this is open, by name:
    a primitive applied eagerly (``EvalTrace.process_primitive``: what
    ``jnp.stack``, ``jax.random.split`` or indexing a device array come
    down to) under its own name, a jitted function under its function's.
    The C++ fast path would hide repeat calls of a jitted function, so
    it is emptied and kept empty: every call takes the Python path."""
    from jax._src import core, pjit
    from jax._src.lib import xla_client

    log = []
    eager, fastpath, call = (core.EvalTrace.process_primitive,
                             pjit._get_fastpath_data,
                             pjit._pjit_call_impl_python)

    def spy_eager(self, primitive, args, params):
        log.append(primitive.name)
        return eager(self, primitive, args, params)

    def spy_call(*args, **params):
        log.append(params["name"])
        return call(*args, **params)

    core.EvalTrace.process_primitive = spy_eager
    pjit._pjit_call_impl_python = spy_call
    pjit._get_fastpath_data = lambda *a, **k: None
    xla_client._xla.PjitFunctionCache.clear_all()
    try:
        yield log
    finally:
        core.EvalTrace.process_primitive = eager
        pjit._pjit_call_impl_python = call
        pjit._get_fastpath_data = fastpath


def test_the_log_sees_what_the_parent_dispatched():
    """The hook itself: the operations ``_flush_boundary`` and
    ``serving_inputs`` used to run eagerly all show, and an upload of a
    NumPy array does not."""
    with dispatch_log() as log:
        jnp.asarray(np.zeros((2, 3), np.int32))
        jnp.asarray(np.array(7, np.int32))
        assert log == []
        rows = jnp.ones((2, 5))
        keys = jax.random.split(jax.random.PRNGKey(0))
        del log[:]
        jnp.stack([rows[0], rows[1]])
        assert "concatenate" in log and "squeeze" in log
        del log[:]
        jax.random.split(keys[0])
        assert "random_split" in log
        del log[:]
        _sample_rows(rows, keys, jnp.zeros(2))
        _sample_rows(rows, keys, jnp.zeros(2))
        assert log.count("_sample_rows") == 2


def _admit_two(eng, log):
    eng.submit("a", PROMPTS[0], max_new_tokens=4)
    eng.submit("b", PROMPTS[1], max_new_tokens=4)
    del log[:]
    eng.step()
    assert counters(eng)["serving_boundary_tokens"] == len(PROMPTS) + 2
    return {"dstpu_prefill": 2, "dstpu_boundary": 2, "dstpu_decode": 1}


def _chunk_end(eng, log):
    eng.submit("long", PROMPTS[3], max_new_tokens=4)   # 21 tokens: 3 chunks
    eng.step()
    eng.step()
    slot = next(s for s in eng.slots if s is not None)
    assert slot.prefilling
    del log[:]
    eng.step()
    assert len(slot.generated) == 2         # the boundary token, one decode
    return {"dstpu_chunk": 1, "dstpu_boundary": 1, "dstpu_decode": 1}


def _plain(eng, log):
    eng.submit("a", PROMPTS[0], max_new_tokens=6)
    eng.step()
    del log[:]
    eng.step()
    return ({"dstpu_sweep": 1, "dstpu_verify": 1} if eng._spec_on
            else {"dstpu_decode": 1})


@pytest.mark.parametrize("drive,kw", [
    (_admit_two, {}), (_chunk_end, {"prefill_chunk": 8}), (_plain, {}),
    (_plain, {"speculative": {"draft_tokens": 3}})],
    ids=["admits_two", "finishes_a_chunked_prefill", "plain_decode",
         "speculative_sweep"])
def test_a_step_dispatches_its_programs_and_nothing_else(model, drive, kw):
    """Every shape runs once before (the warm requests), so nothing
    traces inside the step that is read."""
    cfg, params = model
    with dispatch_log() as log:
        eng = serving_engine(params, cfg, telemetry=True, **KW, **kw)
        for p in PROMPTS:                   # warm every bucket and width
            eng.submit(("warm", len(p)), p, max_new_tokens=3)
        eng.run()
        want = drive(eng, log)
        got = list(log)
    assert {n: got.count(n) for n in set(got)} == want, got


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
    assert c["serving_boundary_tokens"] == c["serving_admitted_requests"]
    assert c["serving_boundary_syncs"] <= c["serving_boundary_tokens"]
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


def test_admissions_of_one_step_share_a_fetch(gpt2_model):
    cfg, params = gpt2_model
    eng = serving_engine(params, cfg, telemetry=True, **KW)
    for i, p in enumerate(PROMPTS):
        eng.submit(i, p, max_new_tokens=4)
    eng.step()
    c = counters(eng)
    assert c["serving_boundary_tokens"] == 4
    assert c["serving_boundary_syncs"] == 1


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

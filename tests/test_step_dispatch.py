"""A step dispatches its programs and nothing else (ISSUE 31): a
prefill program returns its last row, one compiled program samples the
boundary token from it, sampling keys are derived on the device from
integers the host has, and between the start of ``step()`` and the token
fetch the host runs no eager ``jnp`` / ``jax.random`` / device-indexing
operation."""

import contextlib
import gc
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.devprof import (STEP_LEDGER, STEP_PHASES, StepLedger,
                                   StepRow)
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
    # every boundary token joined its decode on the device (ISSUE 60):
    # none was fetched before that decode was built
    c = counters(eng)
    assert c["serving_boundary_joined"] == len(PROMPTS) + 2
    assert c["serving_boundary_tokens"] == c["serving_boundary_syncs"] == 0
    # the step's own decode and, its rows being known to stay, the next
    # one, dispatched ahead of the fetch
    return {"dstpu_prefill": 2, "dstpu_boundary": 2, "dstpu_join": 2,
            "dstpu_decode": 2}


def _chunk_end(eng, log):
    eng.submit("long", PROMPTS[3], max_new_tokens=4)   # 21 tokens: 3 chunks
    eng.step()
    eng.step()
    slot = next(s for s in eng.slots if s is not None)
    assert slot.prefilling
    del log[:]
    eng.step()
    assert len(slot.generated) == 2         # the boundary token, one decode
    return {"dstpu_chunk": 1, "dstpu_boundary": 1, "dstpu_join": 1,
            "dstpu_decode": 2}


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


# ---------------------------------------- (a') the step ledger's rows
def ledger_rows(ordinals):
    """The ledger's rows of the steps whose ordinals are given (the
    ledger is the process's: other tests' engines wrote to it too)."""
    rows = {r["n"]: r for r in STEP_LEDGER.snapshot()["rows"]}
    return [rows[n] for n in ordinals]


def stepped(eng, steps=None, sleep=0.0):
    """Step ``eng`` (until it has no work), and the ordinals its steps
    were given."""
    ordinals = []
    while eng.has_work if steps is None else len(ordinals) < steps:
        if sleep and ordinals:
            time.sleep(sleep)
        eng.step()
        ordinals.append(eng._row.n)
    return ordinals


SITE_OF = {"dstpu_prefill": "prefill", "dstpu_chunk": "chunk",
           "dstpu_decode": "decode", "dstpu_verify": "sweep"}


def decodes(programs):
    """A row's decode programs, whichever way their tokens came: up
    from the host (``decode``) or from the step before them, on the
    device (``decode_ahead``)."""
    return [a + b for a, b in zip(programs["decode"],
                                  programs["decode_ahead"])]


def _rows_admit_two(eng):
    return {"prefill": [2, 16, 8], "decode": [1, 4, 2],
            "decode_ahead": [1, 4, 2]}


def _rows_chunk_end(eng):
    return {"chunk": [1, 8, 5], "decode": [1, 4, 1],    # 21 = 8 + 8 + 5
            "decode_ahead": [1, 4, 1]}


def _rows_plain(eng):
    # a sweep scores draft_tokens + 1 positions a slot: the token fed
    # back and what the drafter proposed
    return ({"sweep": [1, 4 * 4, 1 + eng._c_spec_drafted.value
                       - eng._drafted_before]} if eng._spec_on
            else {"decode_ahead": [1, 4, 1]})    # the step before flew


@pytest.mark.parametrize("drive,kw,want", [
    (_admit_two, {}, _rows_admit_two),
    (_chunk_end, {"prefill_chunk": 8}, _rows_chunk_end),
    (_plain, {}, _rows_plain),
    (_plain, {"speculative": {"draft_tokens": 3}}, _rows_plain)],
    ids=["admits_two", "finishes_a_chunked_prefill", "plain_decode",
         "speculative_sweep"])
def test_a_row_counts_what_the_step_dispatched(model, drive, kw, want):
    """The row of the step that is read says what the dispatch log saw:
    programs by site, and with them the rows each ran and the real
    tokens among them."""
    cfg, params = model
    with dispatch_log() as log:
        eng = serving_engine(params, cfg, telemetry=True, **KW, **kw)
        for p in PROMPTS:
            eng.submit(("warm", len(p)), p, max_new_tokens=3)
        eng.run()
        step = eng.step

        def noting_the_drafts():
            eng._drafted_before = eng._c_spec_drafted.value
            return step()

        eng.step = noting_the_drafts
        drive(eng, log)
        got = list(log)
    (row,) = ledger_rows([eng._row.n])
    seen = {}
    for name in got:
        if name in SITE_OF:
            seen[SITE_OF[name]] = seen.get(SITE_OF[name], 0) + 1
    programs = {site: p for site, p in row["programs"].items() if p[0]}
    by_site = {}
    for site, p in programs.items():
        site = site.replace("_ahead", "")   # one program, either way
        by_site[site] = by_site.get(site, 0) + p[0]
    assert by_site == seen
    assert programs == want(eng)
    # a prefill that completed left its boundary token to this step's
    # decode, one ``dstpu_join`` a token
    assert row["boundary_tokens"] == got.count("dstpu_boundary") \
        == got.count("dstpu_join")
    assert row["admitted"] == got.count("dstpu_prefill")
    assert row["k"] == eng.decode_chunk and row["preempted"] == 0


def test_a_step_writes_one_row_and_none_with_telemetry_off(model):
    cfg, params = model
    eng = serving_engine(params, cfg, telemetry=True, prefill_chunk=8,
                         **KW)
    for i, p in enumerate(PROMPTS):
        eng.submit(i, p, max_new_tokens=4)
    before = STEP_LEDGER.snapshot(last=1)["steps"]
    ordinals = stepped(eng)
    assert STEP_LEDGER.snapshot(last=1)["steps"] - before == len(ordinals)
    assert ordinals == sorted(set(ordinals))
    rows = ledger_rows(ordinals)
    assert [r["queue"] for r in rows][:2] == [len(PROMPTS), 0]
    assert sum(r["admitted"] for r in rows) == len(PROMPTS)
    assert sum(r["boundary_tokens"] for r in rows) == len(PROMPTS)
    c = counters(eng)
    # what the two counters that went said, the rows say
    assert sum(decodes(r["programs"])[0] for r in rows) \
        == c["serving_decode_syncs"]
    assert sum(r["programs"]["chunk"][0] for r in rows) \
        == c["serving_prefill_chunks"]
    # each joined its decode on the device: no fetch of their own
    assert c["serving_boundary_joined"] == len(PROMPTS)
    assert c["serving_boundary_syncs"] == c["serving_boundary_tokens"] == 0
    # the phases tile the step: theirs are the spans' own readings
    for r in rows:
        assert r["t0"] < r["t1"]
        assert set(r["phases"]) <= set(STEP_PHASES)
        assert sum(r["phases"].values()) <= r["t1"] - r["t0"]
    whole = sum(r["t1"] - r["t0"] for r in rows)
    assert sum(sum(r["phases"].values()) for r in rows) >= 0.99 * whole
    hist = eng.registry.snapshot()["histograms"]
    assert hist["serving_step_seconds"]["sum"] == pytest.approx(whole)
    # /statusz: the running totals and the newest row
    block = eng.statusz()["steps"]
    assert [r["n"] for r in block["rows"]] == ordinals[-1:]
    assert block["steps"] == STEP_LEDGER.steps and block["unseen"] == 0
    assert block["programs"]["chunk"][2] >= sum(len(p) for p in PROMPTS)
    # with telemetry off: no row, no pen, nothing written
    off = serving_engine(params, cfg, telemetry=False, prefill_chunk=8,
                         **KW)
    for i, p in enumerate(PROMPTS):
        off.submit(i, p, max_new_tokens=4)
    off.run()
    assert off._row is None
    assert STEP_LEDGER.snapshot(last=1)["steps"] == block["steps"]
    assert "serving_step_seconds" not in \
        off.registry.snapshot()["histograms"]


def test_no_fetch_stands_between_a_prompt_and_its_decode(gpt2_model):
    """Where the boundary token joins its decode on the device (ISSUE
    60) no call of a request's life fetches before it dispatches: no
    instant is known to be idle, and an arrival under a step in flight
    lands nothing first."""
    cfg, params = gpt2_model
    eng = serving_engine(params, cfg, telemetry=True, prefill_chunk=8,
                         **KW)
    eng.submit("long", PROMPTS[3], max_new_tokens=12)   # three chunks
    rows = ledger_rows(stepped(eng, 4))
    third, fourth = rows[2:]
    for row in rows:
        assert (row["exposed_s"], row["exposed"], row["drained"]) \
            == (0.0, {}, False)
    # the third ends the prompt: its token joins the decode, which is
    # read (the token with it) behind the next one
    assert third["boundary_tokens"] == 1
    assert third["programs"]["chunk"][0] == 1
    assert third["programs"]["decode"][0] == 1
    assert third["programs"]["decode_ahead"][0] == 1
    assert {"token_sync", "append"} <= set(third["phases"])
    assert fourth["programs"]["decode_ahead"][0] == 1
    assert fourth["programs"]["decode"][0] == 0
    # an arrival beside a free slot finds a step in flight, which stays
    # there: the first chunk goes out behind it, the next decode behind
    # that, and then the step is read
    eng.submit("next", PROMPTS[3], max_new_tokens=3)
    (row,) = ledger_rows(stepped(eng, 1))
    assert row["programs"]["chunk"][0] == 1
    assert row["programs"]["decode_ahead"][0] == 1
    assert row["programs"]["decode"][0] == 0 and not row["drained"]
    assert row["exposed"] == {}
    # its last chunk too: the token joins the step behind the one in
    # flight, which goes out from the device
    rows = ledger_rows(stepped(eng, 2))
    assert rows[1]["boundary_tokens"] == 1
    assert rows[1]["programs"]["decode_ahead"][0] == 1
    assert rows[1]["programs"]["decode"][0] == 0
    assert rows[1]["exposed"] == {}
    d = eng.statusz()["decode"]
    assert d["joined"] == 2 and not any(
        d["behind"][why] for why in ("admission", "boundary", "prefill"))


def test_exposed_seconds_follow_a_fetch_and_end_at_a_dispatch(gpt2_model):
    """The device provably has nothing queued from the return of a
    fetch to the next dispatch call, and only then.  (The sequence the
    rule falls back to: here every boundary token is fetched.)"""
    cfg, params = gpt2_model
    eng = serving_engine(params, cfg, telemetry=True, prefill_chunk=8,
                         **KW)
    eng._joins = lambda: False
    eng.submit("long", PROMPTS[3], max_new_tokens=6)    # three chunks
    first, second, third, fourth = ledger_rows(stepped(eng, 4))
    # nothing was fetched before the first chunk went out, nor before
    # the second: no instant of these steps is known to be idle
    for row in (first, second):
        assert row["programs"]["chunk"][0] == 1
        assert row["programs"]["decode"][0] == 0
        assert (row["exposed_s"], row["exposed"], row["drained"]) \
            == (0.0, {}, False)
    # the third ends the prompt: its boundary token is fetched, and
    # from there to the decode dispatch the device waits for the host;
    # the next decode goes out behind that one, ahead of its fetch, so
    # the step ends with a program queued: nothing after is exposed
    assert third["boundary_tokens"] == 1 and not third["drained"]
    assert third["programs"]["decode"][0] == 1
    assert third["programs"]["decode_ahead"][0] == 1
    assert set(third["exposed"]) == {"boundary", "grow_pages", "upload",
                                     "inputs", "dispatch"}
    assert third["exposed_s"] == pytest.approx(
        sum(b - a for a, b in third["idle"]))
    assert 0 < sum(third["exposed"].values()) <= third["exposed_s"] \
        < third["t1"] - third["t0"]
    for name, seconds in third["exposed"].items():
        assert seconds <= third["phases"][name] + 1e-9
    # the fourth begins with that step in flight and puts the next one
    # behind it before it reads it: no instant of it is known to be idle
    assert fourth["programs"]["decode_ahead"][0] == 1
    assert fourth["programs"]["decode"][0] == 0
    assert (fourth["exposed_s"], fourth["exposed"], fourth["drained"]) \
        == (0.0, {}, False)
    assert {"token_sync", "append"} <= set(fourth["phases"])
    # an arrival beside a free slot finds a step in flight: that step
    # lands first (its fetch returns with nothing queued), and the chunk
    # that goes out in the prefill phase ends the idle stretch; the
    # decode behind it is this call's last program and is left to fly
    eng.submit("next", PROMPTS[3], max_new_tokens=3)
    (row,) = ledger_rows(stepped(eng, 1))
    assert row["programs"]["chunk"][0] == row["programs"]["decode"][0] == 1
    assert row["programs"]["decode_ahead"][0] == 0 and not row["drained"]
    assert set(row["exposed"]) == {"admit", "prefill", "append"}
    assert eng.statusz()["decode"]["behind"]["admission"] == 1


def test_what_is_dispatched_between_steps_falls_to_the_next_row(gpt2_model):
    """A router or a test may admit between steps: the last row is
    written and keeps what it had; the next one counts the program,
    whose work its decode waits behind."""
    cfg, params = gpt2_model
    eng = serving_engine(params, cfg, telemetry=True, **KW)
    eng.submit("a", PROMPTS[0], max_new_tokens=6)
    (n,) = stepped(eng, 1)
    (before,) = ledger_rows([n])
    # the next decode is in flight behind the one this step read
    assert not before["drained"] and before["admitted"] == 1
    assert eng._flying is not None
    eng.submit("b", PROMPTS[1], max_new_tokens=6)
    assert eng._admit_one()                 # a prefill goes out
    eng._flush_boundary()
    (after,) = ledger_rows([n])
    assert after == before
    (row,) = ledger_rows(stepped(eng, 1))
    assert row["admitted"] == 1 and row["boundary_tokens"] == 1
    assert row["programs"]["prefill"] == [1, 8, 5]
    # the rows are not the rows the step in flight left with: it lands,
    # and this step's decode takes its tokens from the host
    assert row["programs"]["decode"] == [1, 4, 2]
    # the stretch that prefill ended began in the row before: this one
    # is idle from the boundary fetch (before it began) to its decode
    assert "admit" in row["exposed"] and row["idle"][0][0] == row["t0"]


def test_between_is_the_callers_time(gpt2_model):
    cfg, params = gpt2_model
    eng = serving_engine(params, cfg, telemetry=True, **KW)
    eng.submit("a", PROMPTS[0], max_new_tokens=6)
    rows = ledger_rows(stepped(eng, 3, sleep=0.05))
    assert rows[0]["between_s"] == 0.0          # nothing came before it
    for prev, row in zip(rows, rows[1:]):
        assert 0.05 <= row["between_s"] < 0.5
        # from the end of the last call's tick to this step's start
        assert row["between_s"] == pytest.approx(
            row["t0"] - prev["t1"] - prev["tick_s"], abs=1e-3)
        assert row["between_s"] < row["t0"] - prev["t1"]


def test_the_ring_drops_the_oldest_and_outlives_the_engine(gpt2_model):
    cfg, params = gpt2_model
    small = StepLedger(capacity=4)
    eng = serving_engine(params, cfg, telemetry=True, **KW)
    polled = StepLedger(capacity=4)     # read every step: /statusz's way
    other = serving_engine(params, cfg, telemetry=True, **KW)
    row = other._row
    other._row = StepRow("dstpu", row._step, row._tick, row._phases,
                         ledger=polled)
    other.submit("a", PROMPTS[0], max_new_tokens=12)
    while other.has_work:
        other.step()
        polled.snapshot(last=1)
    seen = polled.snapshot(last=1)
    assert (seen["steps"], seen["unseen"]) == (11, 0)
    assert decodes(seen["programs"]) == [11, 44, 11]
    assert seen["programs"]["prefill"] == [1, 8, 3]
    row = eng._row
    eng._row = StepRow("dstpu", row._step, row._tick, row._phases,
                       ledger=small)
    eng.submit("a", PROMPTS[0], max_new_tokens=12)
    ordinals = stepped(eng)
    # the first step fetches the boundary token and a decoded one
    assert len(ordinals) == 11 and ordinals[0] == 0
    eng.shutdown()
    del eng
    gc.collect()
    snap = small.snapshot()
    assert [r["n"] for r in snap["rows"]] == ordinals[-4:]
    # a reader folds what it sees into the sums (the hot path keeps
    # none): this one came after the ring had turned
    assert (snap["steps"], snap["unseen"]) == (4, 7)
    # the first step dispatched two decodes (its own and the next,
    # ahead); the last read its tokens and, the row ending by count,
    # dispatched none
    assert decodes(snap["programs"]) == [3, 12, 3]
    assert snap["step_s"] > snap["exposed_s"] > 0
    again = small.snapshot(last=2)
    assert [r["n"] for r in again["rows"]] == ordinals[-2:]
    assert (again["steps"], again["step_s"]) == (4, snap["step_s"])
    # the process's own ledger is sized for a whole window at the
    # shortest step: ~110 steps a second for over a minute
    assert len(STEP_LEDGER._ring) >= 110 * (8 + 51)


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

"""A step dispatches its programs and nothing else (ISSUE 31): a
prefill program returns its last row, one compiled program samples the
boundary token from it, sampling keys are derived on the device from
integers the host has, and between the start of ``step()`` and the token
fetch the host runs no eager ``jnp`` / ``jax.random`` / device-indexing
operation.  What a step dispatches and what a row counts are here; the
streams those programs serve are ``test_step_dispatch_streams.py``'s."""

import contextlib
import gc
import hashlib
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.devprof import (STEP_LEDGER, STEP_PHASES, StepLedger,
                                   StepRow)
from deepspeed_tpu.inference.generation import paged_generator
from deepspeed_tpu.inference.serving import _sample_rows, serving_engine
from deepspeed_tpu.models import gpt2, mixtral

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


_OFFLINE = {}


def offline(cfg, params, prompt, n_new):
    """The contiguous-table reference: one request, alone.  An answer is
    computed once a process, for the model (its configuration and the
    bytes of its weights), the prompt and the count that define it."""
    weights = hashlib.sha1()
    for leaf in jax.tree.leaves(params):
        weights.update(np.asarray(leaf).tobytes())
    key = repr(cfg), weights.hexdigest(), tuple(prompt), n_new
    if key not in _OFFLINE:
        out = paged_generator(params, cfg, page_size=8).generate(
            jnp.asarray([prompt], jnp.int32), max_new_tokens=n_new)
        _OFFLINE[key] = [int(t) for t in np.asarray(out[0])]
    return list(_OFFLINE[key])


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

"""A decode step is dispatched before the last one's tokens are read
(ISSUE 56): where nothing but the tokens changes between two decode
steps, the engine hands the device step n+1 with step n's output as its
token operand and only then fetches step n.

The oracle is the engine itself with the rule switched off (every step
lands before the next is built: the sequence the engine had before):
every request's tokens must be equal, whatever happened while a step
was in flight.

An admission no longer stops the chip (ISSUE 60): a boundary token stays
on the device until the decode that consumes it has been dispatched
(``dstpu_join``), so an arrival beside a free slot and a prompt's last
chunk go out behind the step in flight, and the token is read with the
decode it joined.  The ``join_*`` scenarios hold that to the same
oracle (``test_decode_ahead_join.py`` runs them).
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.devprof import BUILD_LEDGER, STEP_LEDGER
from deepspeed_tpu.inference.serving import DECODE_BEHIND, serving_engine
from deepspeed_tpu.models import gpt2, granite_hybrid, mixtral

# ------------------------------------------------------------- families
_MODELS = {}


def _model(family):
    """(config, params, engine keywords) of a tiny model: a plain
    family, one whose decode program returns its tokens flat behind the
    experts' rows, one with a per-slot recurrent state."""
    if family not in _MODELS:
        key = jax.random.PRNGKey(0)
        if family == "plain":
            cfg = gpt2.GPT2Config.tiny()
            made = cfg, gpt2.init_params(key, cfg), {}
        elif family == "expert_rows":
            cfg = mixtral.MixtralConfig.tiny(
                dim=64, n_layers=2, n_heads=4, n_kv_heads=2, num_experts=4)
            made = cfg, mixtral.init_params(key, cfg), {}
        else:
            cfg = granite_hybrid.GraniteHybridConfig.tiny()
            made = cfg, granite_hybrid.init_params(key, cfg), {
                "cache_dtype": jnp.float32, "prefill_bucket": 0,
                "prefill_chunk": 16}
        _MODELS[family] = made
    return _MODELS[family]


def _engine(family, synchronous=False, **kw):
    cfg, params, base = _model(family)
    base = dict(dict(max_batch=3, page_size=8, num_pages=64, max_seq=96,
                     prefill_bucket=8, telemetry=True), **base)
    base.update(kw)
    eng = serving_engine(params, cfg, **base)
    if synchronous:
        # the replay: nothing is known ahead, so every step lands in
        # the call that dispatched it
        eng._rows_change = lambda: "other"
        # and every boundary token is fetched before its decode is built
        eng._joins = lambda: False
    return eng


def _prompt(rng, cfg, n):
    return [int(t) for t in rng.integers(1, cfg.vocab_size, size=n)]


# ------------------------------------------------------------ scenarios
def _mix(cfg, lens, news, at):
    """A seeded mix: (step it is submitted before, id, prompt, tokens
    asked for, temperature)."""
    rng = np.random.default_rng(56)
    return [(a, i, _prompt(rng, cfg, n), m, 0.0)
            for i, (a, n, m) in enumerate(zip(at, lens, news))]


SCENARIOS = {
    # a row is admitted while a step is in flight: two rows decode, the
    # third request arrives at the fourth call
    "arrival": dict(lens=(5, 9, 7, 11), news=(14, 12, 9, 6),
                    at=(0, 0, 4, 6)),
    # rows end by count at different steps beside a waiting queue
    "count": dict(lens=(4, 6, 5, 7, 3, 8), news=(3, 9, 5, 12, 7, 2),
                  at=(0,) * 6),
    # a row ends on eos with a step in flight (the token is chosen from
    # what the model says: see _serve_pair)
    "eos": dict(lens=(6, 5, 9, 4, 7), news=(16, 14, 12, 15, 10),
                at=(0,) * 5, eos=True),
    # the pool runs dry under growing rows: the youngest is preempted
    "preempt": dict(lens=(14, 15, 13, 12), news=(30, 28, 26, 20),
                    at=(0, 0, 0, 3), engine=dict(num_pages=13)),
    # a prompt goes through in chunks between decode steps
    "chunked": dict(lens=(5, 41, 6, 37), news=(20, 6, 12, 5),
                    at=(0, 3, 0, 9),
                    engine=dict(prefill_chunk=8, prefill_bucket=0)),
    # four tokens a dispatch: rows end inside a chunk
    "chunk4": dict(lens=(5, 8, 6, 9, 4), news=(13, 6, 10, 3, 9),
                   at=(0, 0, 0, 2, 5), engine=dict(decode_chunk=4)),
    # ---- ISSUE 60: the boundary token joins its decode on the device
    # an arrival beside a free slot finds a step in flight, twice
    "join_arrival": dict(lens=(6, 9, 7), news=(16, 9, 7), at=(0, 3, 7)),
    # two arrivals in one call, beside two free slots
    "join_two": dict(lens=(5, 11, 8), news=(15, 8, 11), at=(0, 4, 4)),
    # a prompt's last chunk goes through under a step in flight
    "join_chunk": dict(lens=(5, 27, 19), news=(22, 7, 6), at=(0, 2, 6),
                       engine=dict(prefill_chunk=8, prefill_bucket=0)),
    # a request that wants ONE token: its boundary token is its last,
    # the host knows, and the synchronous sequence runs
    "join_one": dict(lens=(6, 8, 5), news=(14, 1, 8), at=(0, 4, 4)),
    # the boundary token IS eos (learned by serving once without one:
    # the late arrival's first token)
    "join_eos": dict(lens=(6, 7, 9), news=(15, 9, 8), at=(0, 4, 6),
                     eos="first"),
    # a row fails between the join and the landing of its decode
    "join_fail": dict(lens=(6, 8, 7), news=(15, 9, 8), at=(0, 4, 6),
                      fail=(1, 5)),
    # the arrival's pages are only in the warm pool (a finished
    # request's, published): an eviction, so the step lands first
    "join_evict": dict(lens=(30, 28, 26, 33), news=(4, 14, 3, 6),
                       at=(0, 0, 0, 9),
                       engine=dict(num_pages=15, prefix_cache=True)),
    # the pool is dry when the arrival's decode has to grow: a
    # preemption, so the boundary token is fetched
    "join_preempt": dict(lens=(14, 15, 13), news=(30, 28, 20),
                         at=(0, 0, 8), engine=dict(num_pages=9)),
}


def _serve(eng, mix, fail=None):
    """Submit as the schedule says and step until nothing is left.
    Returns (outputs, whether a row ever ended with a step in flight
    behind it).  ``fail``: (request, the step before which its slot
    fails)."""
    ended_under_a_step = False
    step = 0
    pending = sorted(mix, key=lambda r: r[0])
    while pending or eng.has_work:
        while pending and pending[0][0] <= step:
            _, rid, prompt, n_new, temp = pending.pop(0)
            eng.submit(rid, prompt, max_new_tokens=n_new, temperature=temp)
        if fail is not None and fail[1] == step:
            (b,) = [b for b, s in enumerate(eng.slots)
                    if s is not None and s.req.req_id == fail[0]]
            eng._fail_slot(b, RuntimeError("the test's"))
        done = eng.step()
        step += 1
        if done and eng._flying is not None:
            ended_under_a_step = True
        assert step < 2_000
    return dict(eng.finished), ended_under_a_step


def _serve_pair(family, scenario):
    spec = dict(SCENARIOS[scenario])
    kw = dict(spec.pop("engine", {}), **spec.pop("more", {}))
    want_eos = spec.pop("eos", False)
    fail = spec.pop("fail", None)
    cfg = _model(family)[0]
    mix = _mix(cfg, **spec)
    if want_eos:
        probe = _engine(family, synchronous=True, **kw)
        said, _ = _serve(probe, mix)
        probe.shutdown()
        new = {rid: said[rid][len(prompt):] for _, rid, prompt, _, _ in mix}
        if want_eos == "first":
            # the last arrival's first token, which nobody said earlier
            # than it does
            eos = new[mix[-1][1]][0]
            assert all(eos not in new[rid][:2] for rid in new
                       if rid != mix[-1][1])
        else:
            # the token that some request says LATEST for the first
            # time (a tiny model repeats itself): that row then ends in
            # the middle of the others' decode
            firsts = {}
            for toks in new.values():
                for tok in set(toks):
                    firsts[tok] = min(firsts.get(tok, len(toks)),
                                      toks.index(tok))
            eos = max(firsts, key=firsts.get)
            assert firsts[eos] >= 2
        kw = dict(kw, eos_token_id=eos)
    eng = _engine(family, **kw)
    out, ended = _serve(eng, mix, fail)
    replay = _engine(family, synchronous=True, **kw)
    want, _ = _serve(replay, mix, fail)
    return eng, replay, out, want, ended


# the same with four tokens a dispatch (and answers three times as
# long): the joined token is the last of its row's four in the operand
for _name in ("join_arrival", "join_two", "join_chunk", "join_one",
              "join_eos"):
    SCENARIOS[_name + ".k4"] = dict(
        SCENARIOS[_name], more=dict(decode_chunk=4),
        news=tuple(n if n == 1 else 3 * n
                   for n in SCENARIOS[_name]["news"]))

CASES = [("plain", s) for s in SCENARIOS] + [
    (f, s) for f in ("expert_rows", "state")
    for s in ("eos", "chunked", "chunk4")] + [
    # flat behind the experts' rows: position b * K + K - 1
    ("expert_rows", "join_arrival"), ("expert_rows", "join_chunk"),
    ("expert_rows", "join_eos"), ("expert_rows", "join_two.k4"),
    # a per-slot state (every admission is chunked: the slot is idle,
    # length 0, until the lengths go up with the joined step)
    ("state", "join_arrival"), ("state", "join_eos"),
    ("state", "join_fail"), ("state", "join_two.k4")]


def _served_in_turn(family, scenario):
    """Every request's tokens are what the synchronous engine gives,
    and no page is leaked, whatever found a step in flight."""
    eng, replay, out, want, ended = _serve_pair(family, scenario)
    spec = SCENARIOS[scenario]
    scenario = scenario.split(".")[0]
    failed = spec.get("fail", (None,))[0]
    assert set(out) == set(want) and all(
        isinstance(v, list) for rid, v in out.items() if rid != failed)
    if failed is not None:
        # it had read less of what it had been given when it failed
        assert out.pop(failed).generated <= want.pop(failed).generated
    assert out == want
    assert eng.check_leaks() == [] and replay.check_leaks() == []
    assert eng._flying is None and not eng.has_work
    c = eng.registry.snapshot()["counters"]
    r = replay.registry.snapshot()["counters"]
    assert c["serving_decode_ahead"] > 0 == r["serving_decode_ahead"]
    if scenario == "eos":
        # a row ended on a token the host had not seen when the next
        # step left: that step's token for it is void, never appended
        assert ended
        short = [rid for rid, toks in out.items()
                 if toks[-1] == eng.eos]
        assert short
        # the void step was dispatched all the same
        assert c["serving_decode_syncs"] >= r["serving_decode_syncs"]
    else:
        # by count the host knows: no step is dispatched for nothing.
        # (A row that joins behind a step in flight decodes one step
        # later than where that step lands first: one more at most.)
        assert 0 <= c["serving_decode_syncs"] - r["serving_decode_syncs"] \
            <= c["serving_boundary_joined"]
    if scenario == "preempt":
        assert c["serving_preempted_requests"] > 0
    d = eng.statusz()["decode"]
    assert d["joined"] == c["serving_boundary_joined"]
    assert r["serving_boundary_joined"] == 0
    if failed is None and not c["serving_preempted_requests"]:
        # an admission's first token joined its decode or was fetched
        assert d["joined"] + c["serving_boundary_tokens"] \
            == c["serving_admitted_requests"] == len(want)
    if scenario == "chunked":
        assert c["serving_prefill_chunks"] >= 8
        # chunks went through while a step was in flight, the last of
        # a prompt too: its token joined the step behind that one
        assert d["behind"]["prefill"] == 0 < d["joined"]
    prompts = {rid: p for _, rid, p, _, _ in
               _mix(_model(family)[0], **{k: spec[k] for k in
                                          ("lens", "news", "at")})}
    if scenario in ("join_arrival", "join_two", "join_chunk"):
        # no step landed for an admission, a boundary token or a chunk
        assert d["joined"] >= 2
        assert not any(d["behind"][why]
                       for why in ("admission", "boundary", "prefill"))
        assert c["serving_boundary_syncs"] <= 1      # the first call's
    if scenario == "join_one":
        assert len(out[1]) == len(prompts[1]) + 1
        assert c["serving_boundary_tokens"] >= 1 <= d["joined"]
        assert d["behind"]["admission"] + d["behind"]["boundary"] >= 1
    if scenario == "join_eos":
        # its decode flew with a void token for it, dropped unread
        assert out[2] == prompts[2] + [eng.eos] and d["joined"] >= 1
        assert c["serving_decode_syncs"] >= r["serving_decode_syncs"]
    if scenario == "join_fail":
        # its first token had joined a decode and was never read
        assert d["joined"] + c["serving_boundary_tokens"] \
            == c["serving_admitted_requests"]
        assert eng.finished[failed].generated == 0
    if scenario == "join_evict":
        assert c["prefix_cache_evicted_pages"] > 0
        assert d["behind"]["admission"] >= 1
    if scenario == "join_preempt":
        assert c["serving_preempted_requests"] > 0
        assert c["serving_boundary_tokens"] >= 1
    eng.shutdown()
    replay.shutdown()


# the ``join_*`` scenarios are ``test_decode_ahead_join.py``'s cases
@pytest.mark.parametrize("family,scenario", [
    c for c in CASES if not c[1].startswith("join_")])
def test_served_ahead_is_served_in_turn(family, scenario, devices):
    _served_in_turn(family, scenario)


@pytest.mark.parametrize("at", [(0, 0, 0), (0, 3, 3, 8)],
                         ids=["together", "interleaved"])
def test_sampled_rows_draw_what_they_drew(at, devices):
    """The dispatch ordinal advances once a dispatch, in dispatch
    order, and an admission's once an admission: where the rows are the
    same rows (nothing arrives, rows end by count), sampled tokens are
    the synchronous engine's draws.  With admissions interleaved a row
    that arrives under a step in flight decodes from the step behind
    it, in the parent's sequence (that step lands, then the admission)
    as where its token joins on the device: the oracle there is the
    engine with the join alone switched off, and every draw is equal."""
    cfg = _model("plain")[0]
    rng = np.random.default_rng(7)
    mix = [(a, i, _prompt(rng, cfg, 5 + i), 6 + 3 * i, 0.9)
           for i, a in enumerate(at)]
    eng = _engine("plain", max_batch=4)
    replay = _engine("plain", synchronous=not any(at), max_batch=4)
    replay._joins = lambda: False
    out, _ = _serve(eng, mix)
    want, _ = _serve(replay, mix)
    assert out == want
    d, was = eng.statusz()["decode"], replay.statusz()["decode"]
    assert d["ahead"] > 0 and d["dispatches"] == was["dispatches"]
    assert d["joined"] == len(at) and was["joined"] == 0
    if any(at):
        assert was["behind"]["admission"] > 0 == d["behind"]["admission"]
    eng.shutdown()
    replay.shutdown()


# ----------------------------------------------------------- the build
@pytest.mark.parametrize("family,kw", [
    ("plain", {}), ("plain", {"decode_chunk": 4}), ("expert_rows", {})])
def test_a_build_makes_the_programs_it_made(family, kw, devices):
    """No program a build did not make before: the build ledger names
    the same programs, one ``dstpu_decode`` among them, and the jitted
    decode has ONE cache entry after steps fed from the host and steps
    fed from the device have both run."""
    t_build = time.perf_counter()
    eng = _engine(family, devprof={"enabled": True}, **kw)
    cfg = _model(family)[0]
    # the ledger is the process's: this build's entries are the ones
    # that ended after it began, and its warm-up ends on its decode
    after_build = BUILD_LEDGER.snapshot()
    names = [e["program"] for e in after_build["entries"]
             if e["t_end"] > t_build]
    assert names[-1] == "dstpu_decode"
    made = len(names)
    assert names.count("dstpu_decode") == 1
    assert names.count("dstpu_join") == 1
    assert set(names) == {"dstpu_prefill", "dstpu_chunk", "dstpu_boundary",
                          "dstpu_join", "dstpu_decode"}
    # what PR 55's build made for these shapes: a prefill a bucket
    # multiple up to the row, a chunk a table width, one boundary
    # sampler, one decode; and since ISSUE 60 the one program that
    # writes a boundary token into a decode's operand
    row = eng.max_pages_per_seq * eng.page_size
    widths, w = 0, 1
    while w < eng.max_pages_per_seq:
        widths, w = widths + 1, w * 2
    assert made == -(-row // eng.prefill_bucket) + widths + 1 + 3
    assert eng._decode_jit._cache_size() == 1
    rng = np.random.default_rng(3)
    for i in range(4):
        eng.submit(i, _prompt(rng, cfg, 4 + i), max_new_tokens=5 + 2 * i)
    for _ in range(3):
        eng.step()
    # an arrival beside a free slot under a step in flight: its token
    # joins the operand the decode takes from the device
    eng.submit(4, _prompt(rng, cfg, 6), max_new_tokens=7)
    eng.run()
    d = eng.statusz()["decode"]
    assert d["ahead"] > 0 and sum(d["behind"].values()) > 0
    # tokens up from the host with a token joined, the output of the
    # step before with one joined, and either alone: ONE entry, and ONE
    # of the join's whatever it wrote into
    assert d["joined"] == 5 and d["behind"]["admission"] == 0
    assert eng._decode_jit._cache_size() == 1
    assert eng._join.jfn._cache_size() == 1
    # nothing was made ready after the build, named or not
    now = BUILD_LEDGER.snapshot()
    assert now["programs"] == after_build["programs"]
    assert eng.statusz()["devprof"]["compiles_steady"] == 0
    eng.shutdown()


# --------------------------------------------------------- the counters
def test_the_dispatches_are_counted_where_they_went(devices):
    """``serving_decode_ahead`` + the dispatches that stayed behind, by
    reason, are the decode dispatches; the step ledger's rows name the
    site; a step that went ahead left nothing exposed."""
    eng = _engine("plain", prefill_chunk=8, prefill_bucket=0)
    cfg = _model("plain")[0]
    mix = _mix(cfg, lens=(5, 30, 6, 7), news=(14, 4, 9, 5),
               at=(0, 2, 0, 7))
    n_before = STEP_LEDGER.snapshot()["rows"]
    n_before = n_before[-1]["n"] if n_before else -1
    _serve(eng, mix)
    status = eng.statusz()
    d = status["decode"]
    c = status["metrics"]["counters"]
    assert set(d["behind"]) == set(DECODE_BEHIND)
    assert d["ahead"] == c["serving_decode_ahead"] > 0
    assert d["dispatches"] == c["serving_decode_syncs"]
    assert d["ahead"] + sum(d["behind"].values()) == d["dispatches"]
    # an admission no longer stays behind: its token joins on the device
    assert d["behind"]["finish"] > 0 and d["behind"]["admission"] == 0
    assert d["joined"] == c["serving_boundary_joined"] > 0
    assert not d["in_flight"]
    rows = [r for r in STEP_LEDGER.snapshot()["rows"] if r["n"] > n_before]
    by_site = {site: sum(r["programs"][site][0] for r in rows)
               for site in ("decode", "decode_ahead")}
    assert by_site["decode_ahead"] == d["ahead"]
    assert by_site["decode"] + by_site["decode_ahead"] == d["dispatches"]
    ahead = [r for r in rows if r["programs"]["decode_ahead"][0]]
    # it ended with a program queued: not drained, nothing exposed after
    # its dispatch (a step's idle stretches end at a dispatch call)
    assert ahead and not any(r["drained"] for r in ahead)
    behind = [r for r in rows if r["programs"]["decode"][0]
              and not r["programs"]["decode_ahead"][0]]
    assert behind
    eng.shutdown()


def test_a_step_in_flight_is_work_and_is_dropped_unread(devices):
    """``has_work`` counts a step in flight; ``abandon_inflight`` and
    ``shutdown`` drop it without touching the device."""
    eng = _engine("plain")
    cfg = _model("plain")[0]
    rng = np.random.default_rng(11)
    for i in range(2):
        eng.submit(i, _prompt(rng, cfg, 6), max_new_tokens=12)
    for _ in range(3):
        eng.step()
    assert eng._flying is not None and eng.statusz()["decode"]["in_flight"]
    got = eng.abandon_inflight()
    assert len(got) == 2 and eng._flying is None
    assert not eng.has_work and eng.check_leaks() == []
    eng.submit(9, _prompt(rng, cfg, 5), max_new_tokens=8)
    for _ in range(3):
        eng.step()
    assert eng._flying is not None
    eng.shutdown()
    assert eng._flying is None

"""A decode step is dispatched before the last one's tokens are read
(ISSUE 56): where nothing but the tokens changes between two decode
steps, the engine hands the device step n+1 with step n's output as its
token operand and only then fetches step n.

The oracle is the engine itself with the rule switched off (every step
lands before the next is built: the sequence the engine had before):
every request's tokens must be equal, whatever happened while a step
was in flight.
"""

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
}


def _serve(eng, mix):
    """Submit as the schedule says and step until nothing is left.
    Returns (outputs, whether a row ever ended with a step in flight
    behind it)."""
    ended_under_a_step = False
    step = 0
    pending = sorted(mix, key=lambda r: r[0])
    while pending or eng.has_work:
        while pending and pending[0][0] <= step:
            _, rid, prompt, n_new, temp = pending.pop(0)
            eng.submit(rid, prompt, max_new_tokens=n_new, temperature=temp)
        done = eng.step()
        step += 1
        if done and eng._flying is not None:
            ended_under_a_step = True
        assert step < 2_000
    return dict(eng.finished), ended_under_a_step


def _serve_pair(family, scenario):
    spec = dict(SCENARIOS[scenario])
    kw = spec.pop("engine", {})
    want_eos = spec.pop("eos", False)
    cfg = _model(family)[0]
    mix = _mix(cfg, **spec)
    if want_eos:
        # the token that some request says LATEST for the first time
        # (a tiny model repeats itself), learned by serving once without
        # an eos: that row then ends in the middle of the others' decode
        probe = _engine(family, synchronous=True, **kw)
        said, _ = _serve(probe, mix)
        probe.shutdown()
        firsts = {}
        for _, rid, prompt, _, _ in mix:
            new = said[rid][len(prompt):]
            for tok in set(new):
                firsts[tok] = min(firsts.get(tok, len(new)), new.index(tok))
        eos = max(firsts, key=firsts.get)
        assert firsts[eos] >= 2
        kw = dict(kw, eos_token_id=eos)
    eng = _engine(family, **kw)
    out, ended = _serve(eng, mix)
    replay = _engine(family, synchronous=True, **kw)
    want, _ = _serve(replay, mix)
    return eng, replay, out, want, ended


CASES = [("plain", s) for s in SCENARIOS] + [
    (f, s) for f in ("expert_rows", "state")
    for s in ("eos", "chunked", "chunk4")]


@pytest.mark.parametrize("family,scenario", CASES)
def test_served_ahead_is_served_in_turn(family, scenario, devices):
    """Every request's tokens are what the synchronous engine gives,
    and no page is leaked, whatever found a step in flight."""
    eng, replay, out, want, ended = _serve_pair(family, scenario)
    assert set(out) == set(want) and all(
        isinstance(v, list) for v in out.values())
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
        # by count the host knows: no step is dispatched for nothing
        assert c["serving_decode_syncs"] == r["serving_decode_syncs"]
    if scenario == "preempt":
        assert c["serving_preempted_requests"] > 0
    if scenario == "chunked":
        assert c["serving_prefill_chunks"] >= 8
        # chunks went through while a step was in flight
        assert eng.statusz()["decode"]["behind"]["prefill"] > 0
    eng.shutdown()
    replay.shutdown()


def test_sampled_rows_draw_what_they_drew(devices):
    """The dispatch ordinal advances once a dispatch, in dispatch
    order: where the rows are the same rows (nothing arrives, rows end
    by count), sampled tokens are the same draws."""
    cfg = _model("plain")[0]
    rng = np.random.default_rng(7)
    mix = [(0, i, _prompt(rng, cfg, 5 + i), 6 + 3 * i, 0.9)
           for i in range(3)]
    eng = _engine("plain")
    replay = _engine("plain", synchronous=True)
    out, _ = _serve(eng, mix)
    want, _ = _serve(replay, mix)
    assert out == want
    assert eng.registry.snapshot()["counters"]["serving_decode_ahead"] > 0
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
    eng = _engine(family, devprof={"enabled": True}, **kw)
    cfg = _model(family)[0]
    # the ledger is the process's and keeps its newest entries: a
    # build's warm-up ends on its decode program, so this engine's are
    # the ones behind the decode of whichever engine was built before
    after_build = BUILD_LEDGER.snapshot()
    names = [e["program"] for e in after_build["entries"]]
    assert names[-1] == "dstpu_decode"
    names = names[len(names) - 1 - names[-2::-1].index("dstpu_decode"):] \
        if "dstpu_decode" in names[:-1] else names
    made = len(names)
    assert names.count("dstpu_decode") == 1
    assert set(names) == {"dstpu_prefill", "dstpu_chunk",
                          "dstpu_boundary", "dstpu_decode"}
    # what PR 55's build made for these shapes: a prefill a bucket
    # multiple up to the row, a chunk a table width, one boundary
    # sampler, one decode
    row = eng.max_pages_per_seq * eng.page_size
    widths, w = 0, 1
    while w < eng.max_pages_per_seq:
        widths, w = widths + 1, w * 2
    assert made == -(-row // eng.prefill_bucket) + widths + 1 + 2
    assert eng._decode_jit._cache_size() == 1
    rng = np.random.default_rng(3)
    for i in range(4):
        eng.submit(i, _prompt(rng, cfg, 4 + i), max_new_tokens=5 + 2 * i)
    eng.run()
    d = eng.statusz()["decode"]
    assert d["ahead"] > 0 and sum(d["behind"].values()) > 0
    assert eng._decode_jit._cache_size() == 1
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
    assert d["behind"]["finish"] > 0 and d["behind"]["admission"] > 0
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

"""The boundary token joins its decode on the device (ISSUE 60): the
``join_*`` scenarios of ``test_decode_ahead.py``, held to the same oracle
by the same check, and a joined token that nobody has read yet."""

import numpy as np
import pytest

from test_decode_ahead import (CASES, _engine, _model, _prompt,
                               _served_in_turn)


@pytest.mark.parametrize("family,scenario", [
    c for c in CASES if c[1].startswith("join_")])
def test_served_ahead_is_served_in_turn(family, scenario, devices):
    _served_in_turn(family, scenario)


@pytest.mark.parametrize("family", ["plain", "expert_rows", "state"])
def test_a_joined_token_unread_is_work_and_is_dropped(family, devices):
    """A boundary token that joined a decode on the device is read when
    that decode lands: until then its request has nothing, the engine
    has work, and ``abandon_inflight`` drops token and step unread."""
    eng = _engine(family)
    cfg = _model(family)[0]
    rng = np.random.default_rng(13)
    eng.submit(0, _prompt(rng, cfg, 6), max_new_tokens=30)
    while not (eng._flying is not None and eng.slots[0].generated):
        eng.step()
    eng.submit(1, _prompt(rng, cfg, 7), max_new_tokens=9)
    while eng.slots[1] is None or eng.slots[1].prefilling:
        eng.step()                  # the call that finishes its prompt
    new = eng.slots[1]
    c = eng.registry.snapshot()["counters"]
    assert new.boundary is not None and new.generated == []
    assert c["serving_boundary_joined"] == 2
    assert c["serving_boundary_syncs"] == c["serving_boundary_tokens"] == 0
    assert (1, new) in eng._flying.rows and eng.has_work
    d = eng.statusz()["decode"]
    assert d["joined"] == 2 and d["in_flight"]
    got = eng.abandon_inflight()
    # nothing of it was read: it may be served again elsewhere
    assert sorted((r.req_id, n > 0) for r, n in got) \
        == [(0, True), (1, False)]
    assert eng._flying is None and not eng.has_work
    assert eng.check_leaks() == []
    # and the engine goes on: the next request's tokens are its own
    prompt = _prompt(rng, cfg, 5)
    eng.submit(2, prompt, max_new_tokens=6)
    replay = _engine(family, synchronous=True)
    replay.submit(2, prompt, max_new_tokens=6)
    assert eng.run() == replay.run()
    eng.shutdown()
    replay.shutdown()

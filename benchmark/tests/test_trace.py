"""The reduction from a trace to numbers, on lists written by hand (the
answers worked out on paper) and on a piece of a trace recorded on the
chip and kept beside this file."""

import os

import pytest

from benchmark.harness import trace as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1e-3


def hand_made():
    """13 ms on two chips.  Chip 0: a 10 ms ``while`` holding 2 ms of a
    fusion, 3 ms of a Mosaic kernel, a 2 ms all-gather of which the last
    1 ms runs beside a 1 ms copy; then 2 ms idle, then 1 ms of a copy.
    Chip 1: busy for 6.5 ms in one fusion."""
    chip0 = [("while.1", 0, 10), ("fusion.1", 1, 2),
             ("closed_call.2_pallas", 4, 3), ("all-gather.3", 7.5, 2),
             ("copy.9", 8.5, 1), ("copy.4", 12, 1)]
    chip1 = [("fusion.7", 0, 6.5), ("copy.8", 12.9, 0.1)]
    ms = lambda ev: [(n, s * MS, d * MS) for n, s, d in ev]
    return T.Trace(
        ops={0: ms(chip0), 1: ms(chip1)},
        programs={0: ms([("jit_step", 0, 10), ("jit_step", 12, 1)])},
        spans=ms([("bench/submit", 9.9, 0.4), ("bench/step", 10.3, 2.7)]))


@pytest.fixture(params=["lists", "text_proto"])
def trace(request):
    t = hand_made()
    if request.param == "text_proto":      # through the profiler's format
        t = T.from_text_proto(T.to_text_proto(t))
    return t


def test_busy_and_idle(trace):
    assert T.window(trace) == pytest.approx((0.0, 13 * MS))
    busy = T.busy(trace)
    assert T.length(busy[0]) == pytest.approx(11 * MS)
    assert T.length(busy[1]) == pytest.approx(6.6 * MS)
    assert T.busy_seconds(trace) == pytest.approx(8.8 * MS)
    assert T.idle_share(trace) == pytest.approx(1 - 8.8 / 13)


def test_self_time_goes_to_the_innermost_operation(trace):
    st = {n: t for n, _, _, t in T.self_times(trace.ops[0])}
    assert st["while.1"] == pytest.approx(3 * MS)     # 10 - 2 - 3 - 2
    assert st["all-gather.3"] == pytest.approx(1 * MS)  # the copy inside it
    assert st["closed_call.2_pallas"] == pytest.approx(3 * MS)


def test_pallas_share_of_busy(trace):
    share = T.share_of_busy(trace, lambda n: n.endswith("_pallas"))
    assert share == pytest.approx((3 / 11 + 0) / 2)


def test_exposed_collective_share(trace):
    # chip 0: the all-gather runs 7.5-9.5, a copy 8.5-9.5 beside it:
    # 1 ms exposed of 13; chip 1 has no collective
    assert T.exposed_collective_share(trace) == pytest.approx(
        (1 / 13 + 0) / 2)


def test_top_ops_and_program_runs(trace):
    top = dict(T.top_ops(trace, n=3))
    assert top["fusion.7"] == pytest.approx(6.5 * MS / 2)
    assert len(top) == 3
    assert T.program_seconds(trace) == {
        "jit_step": pytest.approx([1 * MS, 10 * MS])}


def test_idle_gaps_go_to_the_span_that_covers_them(trace):
    gaps = dict(T.idle_gaps(trace))
    # chip 0 idles 10-12: 0.3 ms under submit, the rest under step;
    # one gap goes whole to the span that covers most of it
    assert gaps == {"bench/step": pytest.approx(2 * MS)}
    no_spans = T.Trace(trace.ops, trace.programs, [])
    assert dict(T.idle_gaps(no_spans)) == {T.OUTSIDE: pytest.approx(2 * MS)}


def test_interval_arithmetic():
    assert T.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert T.minus([(0, 10)], [(1, 2), (4, 5), (9, 12)]) == [
        (0, 1), (2, 4), (5, 9)]
    assert T.minus([(0, 1), (2, 3)], []) == [(0, 1), (2, 3)]


def test_an_empty_trace_reads_as_nothing():
    empty = T.Trace({}, {}, [])
    assert T.window(empty) is None and T.idle_share(empty) is None
    assert T.idle_gaps(empty) == [] and T.top_ops(empty) == []


RECORDED = os.path.join(DATA, "v5e_recorded.xplane.txt")


@pytest.mark.skipif(not os.path.isfile(RECORDED),
                    reason="no recorded trace beside the tests")
def test_recorded_piece_of_a_chip_trace():
    import json

    with open(RECORDED) as f:
        trace = T.from_text_proto(f.read())
    with open(os.path.join(DATA, "v5e_recorded.expected.json")) as f:
        want = json.load(f)
    assert T.idle_share(trace) == pytest.approx(want["idle_share"])
    assert T.busy_seconds(trace) == pytest.approx(want["busy_s"])
    assert T.share_of_busy(trace, lambda n: "pallas" in n) == \
        pytest.approx(want["pallas_share_of_busy"])
    assert T.exposed_collective_share(trace) == pytest.approx(
        want["exposed_collective_share"])
    assert [n for n, _ in T.top_ops(trace, 3)] == want["top_ops"]
    assert [n for n, _ in T.idle_gaps(trace, 3)] == want["idle_gaps"]

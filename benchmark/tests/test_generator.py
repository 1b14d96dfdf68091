"""The seed changes order, spacing and contents, never the work."""

import collections

import pytest

from benchmark import manifest
from benchmark.harness import cell, generator

SEEDS = [0, 1, 2, 3, 7, 11, 2 ** 31 + 5, 2 ** 31 + 6, 123456789, 3000000000]
OPEN = [n for n in ("serve.chat-0.8knee",)]
BACKLOG = ["serve.chat-sat", "serve.docs-sat"]


def work(requests):
    return (len(requests),
            collections.Counter((r.prompt_len, r.new_tokens)
                                for r in requests),
            sum(r.prompt_len + r.new_tokens for r in requests))


@pytest.mark.parametrize("mix_name", OPEN)
@pytest.mark.parametrize("seconds", [10, 51])
def test_open_loop_offers_the_same_work_whatever_the_seed(mix_name, seconds):
    mix = cell.load_json("traffic", mix_name)
    runs = [generator.open_loop(mix, s, seconds) for s in SEEDS]
    in_window = [[r for r in run if r.due >= 0] for run in runs]
    assert len({len(run) for run in runs}) == 1
    assert len(in_window[0]) == round(mix["rate_rps"] * seconds)
    first = work(in_window[0])
    for reqs in in_window[1:]:
        assert work(reqs) == first
    # the whole run too: the warm start is the same work as well
    assert len({work(run)[2] for run in runs}) == 1
    orders = {tuple(r.prompt_len for r in reqs) for reqs in in_window}
    dues = {tuple(round(r.due, 6) for r in reqs) for reqs in in_window}
    assert len(orders) == len(SEEDS) and len(dues) == len(SEEDS)
    for reqs in in_window:
        assert all(0 <= r.due < seconds for r in reqs)
        assert [r.due for r in reqs] == sorted(r.due for r in reqs)
        assert all(r.prompt_len + r.new_tokens <= 1024 for r in reqs)


# cell -> its mix, for the cells whose arrivals keep a schedule
OPEN_CELLS = {
    w["name"]: cell.load_json("traffic", w["traffic"])
    for w in manifest.build()["workloads"]
    if cell.load_json("traffic", w["traffic"])["kind"] == "serve_open"}


def test_the_open_cells_are_found():
    assert "gpt2-1.3b.serve.chat-0.8knee" in OPEN_CELLS


@pytest.mark.parametrize("cell_name", sorted(OPEN_CELLS))
def test_an_open_cell_offers_four_fifths_of_its_knee(cell_name):
    """A mix that says 0.8 x the knee offers it: a benchmark PR that
    finds the knee again writes both numbers, to two decimals."""
    mix = OPEN_CELLS[cell_name]
    assert mix["rate_rps"] == round(0.8 * mix["knee_rps"], 2)


@pytest.mark.parametrize("cell_name", sorted(OPEN_CELLS))
def test_a_warm_start_holds_no_more_requests_than_the_cell_has_slots(
        cell_name):
    """The warm start submits round(rate x lifetime) requests at their
    residual life: more than the slots and the window opens on a queue
    no steady state below the knee holds."""
    mix = OPEN_CELLS[cell_name]
    slots = cell.load_json("workloads", cell_name)["engine"]["max_batch"]
    n_held = round(mix["rate_rps"] * mix["mean_lifetime_s"])
    assert 0 < n_held <= slots
    # the held requests come first, all due as the warm-up starts
    reqs = generator.open_loop(mix, SEEDS[0], 51)
    warm = -mix["warm_seconds"]
    assert [r.due for r in reqs[:n_held]] == [warm] * n_held
    assert all(r.due > warm for r in reqs[n_held:])


@pytest.mark.parametrize("mix_name", BACKLOG)
def test_backlog_cycles_the_same_grid_whatever_the_seed(mix_name):
    mix = cell.load_json("traffic", mix_name)
    slots, grid = 8, mix["grid"]
    seen = []
    for s in SEEDS:
        stream = generator.backlog(mix, s, slots)
        reqs = [next(stream) for _ in range(slots + 2 * grid)]
        assert [r.index for r in reqs] == list(range(len(reqs)))
        one, two = reqs[slots:slots + grid], reqs[slots + grid:]
        assert work(one)[1:] == work(two)[1:]        # it cycles
        seen.append((work(one), tuple(r.prompt_len for r in one)))
    assert all(w == seen[0][0] for w, _ in seen)
    assert len({order for _, order in seen}) == len(SEEDS)


def test_every_stretch_of_a_backlog_sees_the_whole_distribution():
    mix = cell.load_json("traffic", "serve.docs-sat")
    block = mix["stratum_block"]
    stream = generator.backlog(mix, 5, 0)
    reqs = [next(stream) for _ in range(mix["grid"])]
    ranked = sorted(r.prompt_len for r in reqs)
    edges = [ranked[i * len(ranked) // block] for i in range(block)]
    for g in range(0, len(reqs), block):
        strata = {max(i for i, e in enumerate(edges) if r.prompt_len >= e)
                  for r in reqs[g:g + block]}
        assert len(strata) >= block - 2      # ties at the clips may merge


def test_lengths_are_quantiles_inside_the_clips():
    dist = {"median": 192, "sigma": 0.7, "lo": 32, "hi": 768}
    got = generator.quantile_lengths(dist, 255)
    assert got == sorted(got) and got[0] >= 32 and got[-1] <= 768
    assert got[127] == 192


def test_prompts_are_distinct_and_seeded():
    a = generator.prompt_tokens(2 ** 31 + 9, 3, 64, 50257)
    assert a == generator.prompt_tokens(2 ** 31 + 9, 3, 64, 50257)
    assert a != generator.prompt_tokens(2 ** 31 + 9, 4, 64, 50257)
    assert a != generator.prompt_tokens(9, 3, 64, 50257)
    assert all(0 <= t < 50257 for t in a)

"""A standing queue whose answers are longer than the token check reads.

``harness/serve.py::check_tokens`` judges up to ``CHECK_TAIL`` (256)
served tokens of a sampled request and reports a longer answer as a
problem; a reasoning mix's answers run to thousands.  This runner is
``serve_backlog`` with that check handed a *window* of each long answer:
requests with an even id their first ``CHECK_TAIL`` served tokens (the
prefill boundary, then decode over the prompt's pages), requests with an
odd id their last (decode deep into the answer), with the served tokens
before the window counted to the prefix the reference is given.  Every
token of a window is judged as the harness judges any.

It also keeps what a family's programs counted: the engine's registry is
gone by the time the readers run, so the rows routed to each held expert
inside the window (``serving_expert_rows_<i>``, ``serving_routed_rows``)
go into the window's facts as ``expert_rows`` and ``routed_rows``; a
program that counts none leaves both out.

And where the family has a ``router_probe`` (a sparse model on one
rank's share, whose token check cannot see the router's precision), it
is run beside the token check, outside the window, at the rows a decode
step and a chunk of this cell's engine hand the expert layer, and a
router that parts from the reference's by more than the probe's limit is
a problem.
"""

import contextlib
import types

from benchmark.harness import serve

EXPERT_ROWS = "serving_expert_rows_"


def _window_of(plen, seq, rid):
    """(prompt length, tokens) the check is to see of ``seq``."""
    if len(seq) - plen <= serve.CHECK_TAIL:
        return plen, seq
    if rid % 2 == 0:
        return plen, seq[:plen + serve.CHECK_TAIL]
    return len(seq) - serve.CHECK_TAIL, seq


def _counted(registry):
    """({held expert's index: its rows}, the routed pairs)."""
    counters = registry.snapshot()["counters"]
    return ({int(n[len(EXPERT_ROWS):]): v for n, v in counters.items()
             if n.startswith(EXPERT_ROWS)},
            counters.get("serving_routed_rows", 0.0))


@contextlib.contextmanager
def _hooks(seen):
    build, drive, check = serve.build_engine, serve.drive, serve.check_tokens

    def build_engine(run):
        out = build(run)
        seen["registry"] = out[0].registry
        return out

    def drive_window(engine, feeder, ledger, run, vocab, until, tracer=None,
                     **kw):
        if tracer is not None:                  # the window, not the warm-up
            seen["at_open"] = _counted(seen["registry"])
        return drive(engine, feeder, ledger, run, vocab, until,
                     tracer=tracer, **kw)

    def check_windows(run, params, cfg, ledger, outputs, completed):
        seen["at_close"] = _counted(seen["registry"])
        probe = getattr(run.family, "router_probe", None)
        if probe is not None:
            engine = dict(run.config["serving"]["engine"],
                          **run.cell["engine"])
            seen["router_probe"] = probe(
                cfg, params, run.seed, engine["max_batch"],
                engine.get("prefill_chunk") or engine["prefill_bucket"])
        cut = {rid: _window_of(ledger.requests[rid].prompt_len,
                               outputs[rid], rid) for rid in completed}
        shim = types.SimpleNamespace(requests={
            rid: types.SimpleNamespace(prompt_len=plen)
            for rid, (plen, _) in cut.items()})
        return check(run, params, cfg, shim,
                     {rid: seq for rid, (_, seq) in cut.items()}, completed)

    serve.build_engine, serve.drive, serve.check_tokens = (
        build_engine, drive_window, check_windows)
    try:
        yield
    finally:
        serve.build_engine, serve.drive, serve.check_tokens = (
            build, drive, check)


def run(run):
    seen = {}
    with _hooks(seen):
        outcome = serve.run_serving(run, backlog=True)
    (held0, routed0), (held1, routed1) = seen["at_open"], seen["at_close"]
    if held1:
        outcome["window"]["expert_rows"] = [
            held1[e] - held0.get(e, 0.0) for e in sorted(held1)]
        outcome["window"]["routed_rows"] = routed1 - routed0
    probe = seen.get("router_probe")
    if probe is not None:
        outcome["window"]["token_check"]["router_probe"] = probe
        if probe["differ"] > probe["limit"]:
            outcome["problems"].append(
                f"the program's router sends {probe['differ']} of "
                f"{probe['routed_here']} rows to other held experts than "
                f"the float32 reference's, over the {probe['limit']} that "
                "float32 rounding explains")
    return outcome

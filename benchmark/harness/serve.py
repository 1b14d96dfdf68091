"""The serving runners: ``serve_open`` (arrivals on a schedule) and
``serve_backlog`` (a standing queue).  One loop, two feeders.

The generator runs on the engine's own thread, between ``step()`` calls:
one process, one thread, nothing printed inside the window.  A token is
stamped when the ``step()`` that produced it returns.
"""

import gc
import json
import os
import time

import numpy as np
from jax.profiler import TraceAnnotation

from benchmark.harness import clock, device, generator
from benchmark.harness.profiler import TailTrace


class OpenFeeder:
    """Requests due at fixed instants, whatever the engine does."""

    def __init__(self, requests, t_open):
        self.pending = sorted(requests, key=lambda r: r.due)
        self.t_open = t_open
        self.at = 0

    def due(self, now, engine):
        out = []
        while self.at < len(self.pending) and \
                self.t_open + self.pending[self.at].due <= now:
            out.append(self.pending[self.at])
            self.at += 1
        return out

    def next_due(self):
        if self.at >= len(self.pending):
            return None
        return self.t_open + self.pending[self.at].due


class BacklogFeeder:
    """Keeps ``slots`` requests queued: what any offered rate above the
    knee becomes."""

    def __init__(self, stream, slots):
        self.stream, self.slots = stream, slots

    def due(self, now, engine):
        return [next(self.stream)
                for _ in range(self.slots - len(engine.queue))]

    def next_due(self):
        return None


class Ledger:
    """What the window saw: token stamps by request, one row a step."""

    def __init__(self):
        self.requests = {}      # id -> Request
        self.due_at = {}        # id -> clock time it was due
        self.lag = {}           # id -> submit call time - due time
        self.stamps = {}        # id -> [clock time of each output token]
        self.ended = {}         # id -> (clock time, "completed"|"failed")
        self.absorbed = {}      # id -> [(clock time, prompt tokens through
        #                          prefill by then)], each step that took
        #                          it further than it had ever been
        self.steps = []         # (t0, t1, admitted, chunks, occupancy,
        #                          kv share, queue depth)

    def tokens_through(self, t_open, t_end):
        """Tokens the engine took through in (t_open, t_end]: prompt
        tokens as they first passed prefill, output tokens as they were
        first produced.  A prompt prefilled again after a preemption is
        not credited again, and a request that failed, whenever it did,
        is credited nothing: work the engine wastes is not throughput."""
        total = 0
        for rid, stamps in self.stamps.items():
            if self.ended.get(rid, (None, ""))[1] == "failed":
                continue
            total += sum(1 for t in stamps if t_open < t <= t_end)
            marks = [(t_open, 0)] + self.absorbed.get(rid, [])
            total += sum(b - a for (_, a), (t, b) in zip(marks, marks[1:])
                         if t_open < t <= t_end)
        return total


def build_engine(run):
    """(engine, params, program config, set-up facts)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.serving import serving_engine

    family, cfg = run.family, run.program_config()
    # a rehearsal walks the control flow: at a toy width bf16 noise says
    # nothing about the chip, so it serves float32 and expects exactness
    dtype = jnp.dtype("float32" if run.rehearse
                      else run.config["serving"]["weight_dtype"])
    run.lap("imports")
    params = jax.block_until_ready(
        jax.jit(lambda key: family.init_params(cfg, key, dtype))(
            jax.random.PRNGKey(run.seed32)))
    run.lap("weights")
    kw = dict(run.config["serving"]["engine"])
    kw.update(run.cell["engine"])
    # devprof's build-time warm-up runs every program the engine can
    # dispatch once, so that what compiles later is counted as a
    # steady-state compile; its sampled syncs and its cost pass would
    # perturb the queue and are off
    engine = serving_engine(
        params, cfg, telemetry=True,
        devprof={"sample_rate": 0.0, "cost_analysis": False}, **kw)
    run.lap("engine")
    return engine, params, cfg


def _submit(engine, ledger, run, req, due_at, vocab):
    tokens = generator.prompt_tokens(run.seed, req.index, req.prompt_len,
                                     vocab)
    with TraceAnnotation("bench/submit"):
        t = clock.now()
        engine.submit(req.index, tokens, max_new_tokens=req.new_tokens,
                      arrival=due_at)
    ledger.requests[req.index] = req
    ledger.due_at[req.index] = due_at
    ledger.lag[req.index] = t - due_at
    ledger.stamps[req.index] = []


def _stamp(engine, ledger, finished, t):
    """Stamp the prompt tokens this step took through prefill and the
    output tokens it produced, each once: a preempted request falls
    back and is prefilled again, and that repeat is not stamped."""

    def absorb(rid, upto):
        marks = ledger.absorbed.setdefault(rid, [])
        if upto > (marks[-1][1] if marks else 0):
            marks.append((t, upto))

    def stamp(rid, n):
        st = ledger.stamps[rid]
        st.extend([t] * (n - len(st)))

    for s in engine.slots:
        if s is not None:
            rid = s.req.req_id
            absorb(rid, s.prefill_done if s.prefilling
                   else ledger.requests[rid].prompt_len)
            stamp(rid, len(s.generated))
    for rid in finished:
        out = engine.finished[rid]
        if isinstance(out, list):
            absorb(rid, ledger.requests[rid].prompt_len)
            stamp(rid, len(out) - ledger.requests[rid].prompt_len)
            ledger.ended[rid] = (t, "completed")
        else:
            ledger.ended[rid] = (t, "failed")


def drive(engine, feeder, ledger, run, vocab, until, tracer=None,
          backlog=False):
    """Feed and step until the clock passes ``until``; returns the time
    the last step ended."""
    reg = engine.registry
    admitted = reg.counter("serving_admitted_requests")
    chunks = reg.counter("serving_prefill_chunks")
    occupancy = reg.gauge("serving_batch_occupancy")
    kv_share = reg.gauge("serving_kv_page_utilization")
    t = clock.now()
    while t < until:
        if tracer is not None:
            tracer.maybe_start(t)
        for req in feeder.due(t, engine):
            _submit(engine, ledger, run, req,
                    clock.now() if backlog else feeder.t_open + req.due,
                    vocab)
        if not engine.has_work:
            nxt = feeder.next_due()
            with TraceAnnotation("bench/poll"):
                time.sleep(max(0.0, min(until, nxt or until) - clock.now()))
            t = clock.now()
            continue
        a0, c0, t0 = admitted.value, chunks.value, clock.now()
        with TraceAnnotation("bench/step"):
            finished = engine.step()
        t = clock.now()
        _stamp(engine, ledger, finished, t)
        ledger.steps.append((t0, t, admitted.value - a0, chunks.value - c0,
                             occupancy.value, kv_share.value,
                             len(engine.queue)))
    return t


def run_serving(run, backlog):
    """Warm the engine, hold the window, check, and return the facts the
    readers work on."""
    mix = run.traffic
    engine, params, cfg = build_engine(run)
    vocab = cfg.vocab_size
    ledger = Ledger()
    preempted = engine.registry.counter("serving_preempted_requests")
    warm_s = mix["warm_seconds"]
    gc.collect()
    gc.freeze()            # a quiet host: no collection inside the window
    gc.disable()
    t_begin = clock.now()
    if backlog:
        slots = engine.max_batch
        feeder = BacklogFeeder(generator.backlog(mix, run.seed, slots), slots)
        t_open = drive(engine, feeder, ledger, run, vocab,
                       t_begin + warm_s, backlog=True)
    else:
        t_open = t_begin + warm_s
        feeder = OpenFeeder(generator.open_loop(mix, run.seed, run.seconds),
                            t_open)
        drive(engine, feeder, ledger, run, vocab, t_open)
    setup_s = t_open - run.t_process_start
    run.lap("warm_start", t_open)
    cache_hits = run.compiles.hits
    p0, step0 = preempted.value, len(ledger.steps)
    tracer = TailTrace(run.trace, run.trace_dir,
                       t_open + run.seconds - mix["trace_seconds"])
    t_close = drive(engine, feeder, ledger, run, vocab,
                    t_open + run.seconds, tracer=tracer, backlog=backlog)
    # an open window closes on the clock; a backlog's at its last step
    t_end = t_close if backlog else max(t_open + run.seconds, t_close)
    preemptions = preempted.value - p0
    traced = tracer.stop()
    gc.unfreeze()          # or the engine, frozen, is never collected
    gc.enable()

    memory = device.memory(run.chips)
    leaks = engine.check_leaks()
    devprof = engine.statusz()["devprof"]
    outputs = {rid: engine.finished[rid] for rid, (t, how)
               in ledger.ended.items() if how == "completed"}
    engine.shutdown()
    pool_pages = engine.trash_page
    page_size = engine.page_size
    del engine
    gc.collect()

    ended = {rid: v for rid, v in ledger.ended.items()
             if t_open <= v[0] <= t_end}
    completed = sorted(r for r, (_, how) in ended.items()
                       if how == "completed")
    failed = len(ended) - len(completed)
    problems = list(leaks)
    if devprof["compiles_steady"]:
        problems.append(f"compiled inside the window: {devprof}")
    if failed:
        problems.append(f"{failed} requests failed")
    if not completed:
        problems.append("no request completed inside the window")
    tokens_completed = sum(len(outputs[r]) for r in completed)
    tokens_through = ledger.tokens_through(t_open, t_end)
    # the two readings of throughput differ by the window's ends: up to
    # a request a slot, at either end.  More, and work is being credited
    # that completes nothing
    if backlog and completed and abs(tokens_through - tokens_completed) \
            > 2 * slots / len(completed) * tokens_completed:
        problems.append(f"{tokens_through} tokens went through and "
                        f"{tokens_completed} belong to completed requests: "
                        f"more apart than {slots} slots' ends explain")
    check = check_tokens(run, params, cfg, ledger, outputs, completed)
    problems += check.pop("problems")
    window = {
        "kind": "serve", "t_open": t_open, "t_end": t_end,
        "setup_s": setup_s, "setup_laps": run.laps,
        "compile_cache_hits": cache_hits, "ledger": ledger,
        "first_step": step0,
        "ended": ended, "completed": completed,
        "tokens_completed": tokens_completed,
        "tokens_through": tokens_through,
        "preemptions": preemptions, "compiles_steady":
        devprof["compiles_steady"], "pool_pages": pool_pages,
        "page_size": page_size, "memory": memory, "program_config": cfg,
        "token_check": check,
    }
    return {"window": window, "trace": traced, "attempted": len(ended),
            "failed": failed, "problems": problems}


CHECK_REQUESTS = 4      # completed requests sampled a run
CHECK_TAIL = 256        # positions read a request: the longest answer a mix has
NEAR = 2.0 ** -5        # of the top logit's magnitude


def check_tokens(run, params, cfg, ledger, outputs, completed):
    """A seeded sample of completed requests against the plain forward.

    Why tokens, and why with a margin.  The engine hands out tokens, not
    logits, and on random weights the top two of tens of thousands of
    logits are often closer than bf16 rounding, so bit-equal greedy
    streams cannot be asked of two programs that round in different
    places (flash blocks against gathered pages, bf16 against float32).
    Each served token is judged against the reference's logits given the
    served prefix.  It is *near* when its logit is within 2^-5 of the top
    logit's magnitude (bf16 keeps 8 bits: about 16 roundings of 2^-9;
    PR 21 read 0.025 against 0.117).  A wrong page, position or cache
    row yields an unrelated token, several units lower; computing in a
    lower precision than the configuration states moves the bulk.

    Every sampled token is judged.  A family whose model is
    discontinuous (a sparse router on a tie) hands back, beside its
    logits, the *alternatives* that are just as right and the positions
    that may claim each; a token is near if it is near under the plain
    logits or under an alternative its position may claim.  The share
    of sampled tokens that must be near is the configuration's
    ``check_near_share``: 1.0 for a dense model; what a sparse one is
    held to, and why not to all, is in its file.
    """
    import jax.numpy as jnp

    t0 = clock.now()
    rng = generator.rng_for(run.seed, 4)
    sample = [completed[int(i)] for i in
              rng.permutation(len(completed))[:CHECK_REQUESTS]]
    forward = run.family.reference_logits(cfg)
    near_share = run.config["serving"]["check_near_share"]
    rows, problems = [], []
    for rid in sample:
        seq = np.asarray(outputs[rid], np.int32)
        plen = ledger.requests[rid].prompt_len
        served = seq[plen:]
        if len(served) > CHECK_TAIL:
            problems.append(f"request {rid}: an answer of {len(served)} "
                            f"tokens, and the check reads {CHECK_TAIL}")
            continue
        padded = 512
        while padded < len(seq):
            padded *= 2
        padded = min(padded, cfg.max_seq_len)   # a learned table ends
        toks = np.zeros(padded, np.int32)
        toks[:len(seq)] = seq
        # logits only where tokens were served: [plen - 1, len - 1)
        start = max(0, min(plen - 1, padded - CHECK_TAIL))
        span = slice(plen - 1 - start, len(seq) - 1 - start)
        at = np.arange(len(served))

        def judge(logits):
            lg = np.asarray(logits[span], np.float32)
            top, mine = lg.max(-1), lg[at, served]
            return (top - mine, top, (lg > mine[:, None]).sum(-1),
                    top - mine <= NEAR * np.maximum(np.abs(top), 1.0))

        plain, alternatives = forward(params, jnp.asarray(toks), start,
                                      CHECK_TAIL)
        gap, top, rank, near = judge(plain)
        if not np.isfinite(gap).all():
            problems.append(f"request {rid}: reference logits not finite")
            continue
        claims = np.zeros(len(served), int)
        other = np.zeros(len(served), bool)
        for logits, where in alternatives:
            may = np.asarray(where[span], bool)
            claims += may
            other |= may & judge(logits)[3]
        rows += [{"request": rid, "token": int(i), "gap": float(gap[i]),
                  "top": float(top[i]), "rank": int(rank[i]),
                  "near": bool(near[i]), "alternatives": int(claims[i]),
                  "near_under_one": bool(other[i])} for i in at]
    ok = [r for r in rows if r["near"] or r["near_under_one"]]
    far = [r for r in rows if not (r["near"] or r["near_under_one"])]
    if len(rows) - len(ok) > int((1 - near_share) * len(rows) + 1e-9):
        problems.append(f"only {len(ok)} of {len(rows)} sampled tokens are "
                        f"within {NEAR} of the reference's top logit, under "
                        f"{near_share:.0%} of them")
    fine = max((r for r in rows if r["near"]), key=lambda r: r["gap"],
               default=None)
    os.makedirs(run.out_dir, exist_ok=True)
    with open(os.path.join(run.out_dir, f"check.{run.name}.{run.seed}.json"),
              "w") as f:
        json.dump(rows, f)
    return {"requests": sample, "tokens": len(rows), "near": len(ok),
            "near_share_asked": near_share,
            "near_plain": sum(r["near"] for r in rows),
            "exact_argmax": sum(r["gap"] == 0 for r in rows),
            "may_claim_an_alternative": sum(r["alternatives"] > 0
                                            for r in rows),
            "worst_near_gap": fine and fine["gap"],
            "top_logit_there": fine and fine["top"], "tolerance": NEAR,
            "far": far[:24],
            "seconds": clock.now() - t0, "problems": problems}

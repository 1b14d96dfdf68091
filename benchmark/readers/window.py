"""What a run says of the sliding-window layers: the scope words that
start ``win_`` (``deepspeed_tpu/models/laguna.py`` nests them in the
words the harness's vocabulary knows: ``kv_attend/win_attend``, a sliding
layer's scores and sum, and ``kv_write/win_write``, its ring's rows), and
the decode step's floor for a family whose slots keep rings beside their
pages.  A program that has no such scope, as every program before PR 44,
reads nothing."""

from benchmark.harness import scopes
from benchmark.harness.clock import percentile
from benchmark.readers import _window
from benchmark.readers.gdn import _runs
from benchmark.roofline import window

PREFIX = "win_"
CHUNK, DECODE = "dstpu_chunk", "dstpu_decode"


def _seconds(scoped, program=None, word=None):
    """Self seconds under a ``win_`` word (``word`` alone, where given;
    inside the runs of ``program``, where given), the chips' mean; None
    if no operation."""
    total, found = 0.0, False
    for ops in scoped.ops.values():
        for op, t in scopes.self_seconds(ops):
            if program is not None and program not in op.path:
                continue
            if any(w == word if word else w.startswith(PREFIX)
                   for w in scopes.WORD.findall(op.path)):
                total += t
                found = True
    return total / max(1, len(scoped.ops)) if found else None


def _decode_step(run, cfg, engine):
    """The least time a decode step could take: every weight and the
    live K/V of the full layers read once, and every live slot's rings
    read ONCE (the rows they hold: ``min(length, window)``, the mean
    length standing for each slot's, which counts no fewer rows than the
    slots hold) with a row written, at the published bandwidth, over the
    median host time of the steps that only decoded.  ``readers/
    decode_step_roofline_state.py`` counts a state read AND written
    whole: right for a recurrence's matrix, twice a ring's traffic."""
    w = run.window
    rows = _window.decode_only(run)
    if not rows:
        return None
    live = percentile([s[5] * w["pool_pages"] * w["page_size"]
                       for s in rows], 50)
    slots = percentile([s[4] for s in rows], 50) * engine["max_batch"]
    held = min(live / max(slots, 1), cfg.sliding_window)
    least = (run.family.weight_bytes(cfg)
             + live * run.family.kv_bytes_per_token(cfg)
             + cfg.n_sliding_layers * window.step_bytes(cfg, slots, held)
             ) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / percentile([s[1] - s[0] for s in rows], 50)


def read(run, what):
    """``decode_step_roofline``: see :func:`_decode_step` (the host's
    clock).  From the trace: ``share_of_busy``, self time under the
    ``win_`` words over busy time; ``step_roofline``, every live slot's
    whole ring read and a row written, a sliding layer a traced decode
    program, at the memory's bandwidth, over the self time under those
    words inside decode programs (the live slots are the median
    occupancy of the window's steps); ``chunk_roofline``, the band's
    operations a sliding layer a traced chunk program (``roofline/
    window.py``: every query of the chunk against a whole window) at
    the bf16 peak, over the self time under ``win_attend`` inside chunk
    programs."""
    cfg = run.window["program_config"]
    if run.peaks is None or not hasattr(cfg, "n_sliding_layers"):
        return None
    engine = dict(run.config["serving"]["engine"], **run.cell["engine"])
    if what == "decode_step_roofline":
        return _decode_step(run, cfg, engine)
    scoped = scopes.of_run(run)
    if scoped is None or not scoped.ops:
        return None
    if what == "share_of_busy":
        took = _seconds(scoped)
        busy = sum(r["self_s"] for r in scopes.by_scope(scoped).values())
        return 100.0 * took / busy if took and busy else None
    if what == "chunk_roofline":
        took, runs = _seconds(scoped, CHUNK, "win_attend"), _runs(scoped,
                                                                  CHUNK)
        tokens = engine.get("prefill_chunk") or engine.get("prefill_bucket")
        least = runs * cfg.n_sliding_layers * window.chunk_floor_seconds(
            cfg, tokens, run.peaks)
    else:
        took, runs = _seconds(scoped, DECODE), _runs(scoped, DECODE)
        steps = took and [s[4] for s in _window.steps(run) if s[4] > 0]
        if not steps:
            return None
        live = percentile(steps, 50) * engine["max_batch"]
        least = runs * cfg.n_sliding_layers * window.step_floor_seconds(
            cfg, live, run.peaks)
    return 100.0 * least / took if took and least else None

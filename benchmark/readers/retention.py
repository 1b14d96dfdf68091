"""What the trace says of the retention layers: the scope words that
start ``ret_`` (``deepspeed_tpu/models/brumby.py`` nests them in the words
the harness's vocabulary knows: ``attn_qkv/ret_proj``, ``attn_qkv/
ret_gate``, ``kv_attend/ret_step``, ``kv_attend/ret_chunk``, ``kv_write/
ret_write``).  A program that has no such scope, as every program before
PR 59, reads nothing."""

from benchmark.harness import scopes
from benchmark.harness.clock import percentile
from benchmark.readers import _window
from benchmark.roofline import retention

PREFIX = "ret_"
CHUNK, DECODE = "dstpu_chunk", "dstpu_decode"


def _seconds(scoped, word=PREFIX, program=None):
    """Self seconds under a scope word that starts with ``word`` (inside
    the runs of ``program``, where given), the chips' mean; None if no
    operation."""
    total, found = 0.0, False
    for ops in scoped.ops.values():
        for op, t in scopes.self_seconds(ops):
            if program is not None and program not in op.path:
                continue
            if any(w.startswith(word)
                   for w in scopes.WORD.findall(op.path)):
                total += t
                found = True
    return total / max(1, len(scoped.ops)) if found else None


def _runs(scoped, program):
    """Runs of ``program`` in the traced stretch, on one chip."""
    return sum(1 for name, _, _ in next(iter(scoped.programs.values()))
               if program in name)


def read(run, what):
    """``share_of_busy``: self time under the ``ret_`` words over busy
    time.  ``step_roofline``: the live slots' state (``roofline/
    retention.py``: S and z at the exact size of phi) once out of the
    memory and once in, a layer a traced decode program, at the memory's
    bandwidth, over the self time under ``ret_step`` inside decode
    programs; the live slots are the median occupancy of the window's
    steps.  ``chunk_roofline``: a layer a traced chunk program, the
    larger of the recurrence's products over a chunk's rows at the bf16
    peak and one slot's state out and in, over the self time under
    ``ret_chunk`` inside chunk programs."""
    scoped = scopes.of_run(run)
    if scoped is None or not scoped.ops:
        return None
    if what == "share_of_busy":
        took = _seconds(scoped)
        busy = sum(r["self_s"] for r in scopes.by_scope(scoped).values())
        return 100.0 * took / busy if took and busy else None
    cfg = run.window["program_config"]
    if run.peaks is None or not hasattr(cfg, "queries_per_state"):
        return None
    engine = dict(run.config["serving"]["engine"], **run.cell["engine"])
    if what == "chunk_roofline":
        took = _seconds(scoped, "ret_chunk", CHUNK)
        tokens = engine.get("prefill_chunk") or engine.get("prefill_bucket")
        least = _runs(scoped, CHUNK) * cfg.n_layers \
            * retention.chunk_floor_seconds(cfg, tokens, run.peaks)
    else:
        took = _seconds(scoped, "ret_step", DECODE)
        steps = [s[4] for s in _window.steps(run) if s[4] > 0]
        if not steps:
            return None
        live = percentile(steps, 50) * engine["max_batch"]
        least = _runs(scoped, DECODE) * cfg.n_layers \
            * retention.step_floor_seconds(cfg, live, run.peaks)
    return 100.0 * least / took if took and least else None

"""What the trace says of the KDA layers: the scope words that start
``kda_`` (``deepspeed_tpu/models/ling_flash.py`` nests them in the words
the harness's vocabulary knows, ``attn_qkv/kda_proj``,
``attn_qkv/kda_conv``, ``kv_attend/kda_scan``, ``kv_attend/kda_step``,
``attn_out/kda_gate_norm``, ``kv_write/kda_write``).  A program that has
no such scope, as every program before PR 51, reads nothing."""

from benchmark.harness import scopes
from benchmark.harness.clock import percentile
from benchmark.readers import _window
from benchmark.readers.gdn import _runs
from benchmark.roofline import kda

PREFIX = "kda_"
CHUNK, DECODE = "dstpu_chunk", "dstpu_decode"


def _seconds(scoped, program=None):
    """Self seconds under a ``kda_`` word (inside the runs of
    ``program``, where given), the chips' mean; None if no operation."""
    total, found = 0.0, False
    for ops in scoped.ops.values():
        for op, t in scopes.self_seconds(ops):
            if program is not None and program not in op.path:
                continue
            if any(w.startswith(PREFIX)
                   for w in scopes.WORD.findall(op.path)):
                total += t
                found = True
    return total / max(1, len(scoped.ops)) if found else None


def read(run, what):
    """``share_of_busy``: self time under the ``kda_`` words over busy
    time.  ``prefill_roofline``: what the KDA layers of the traced chunk
    programs need (``roofline/kda.py``: projections, convolution and the
    recurrence over a chunk's tokens a run, at the bf16 peak, or the
    weights, the state and the tokens' rows at the memory's bandwidth,
    if that is longer) over the self time under those words inside chunk
    programs.  ``step_roofline``: every live slot's state and rows read
    and written once and the layers' weights once a traced decode
    program, at the memory's bandwidth (or the operations at the peak,
    if longer), over the self time under those words inside decode
    programs; the live slots are the median occupancy of the window's
    steps."""
    scoped = scopes.of_run(run)
    if scoped is None or not scoped.ops:
        return None
    if what == "share_of_busy":
        took = _seconds(scoped)
        busy = sum(r["self_s"] for r in scopes.by_scope(scoped).values())
        return 100.0 * took / busy if took and busy else None
    cfg = run.window["program_config"]
    if run.peaks is None or not hasattr(cfg, "n_kda_layers"):
        return None
    engine = dict(run.config["serving"]["engine"], **run.cell["engine"])
    if what == "prefill_roofline":
        took, runs = _seconds(scoped, CHUNK), _runs(scoped, CHUNK)
        tokens = engine.get("prefill_chunk") or engine.get("prefill_bucket")
        least = runs * cfg.n_kda_layers * kda.prefill_floor_seconds(
            cfg, tokens, run.peaks)
    else:
        took, runs = _seconds(scoped, DECODE), _runs(scoped, DECODE)
        steps = [s[4] for s in _window.steps(run) if s[4] > 0]
        if not steps:
            return None
        live = percentile(steps, 50) * engine["max_batch"]
        least = runs * cfg.n_kda_layers * kda.step_floor_seconds(
            cfg, live, run.peaks)
    return 100.0 * least / took if took and least else None

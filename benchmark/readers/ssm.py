"""What the trace says of the Mamba-2 layers: the scope words that start
``ssm_`` (``deepspeed_tpu/models/granite_hybrid.py`` nests them in the
words the harness's vocabulary knows, ``attn_qkv/ssm_proj``,
``attn_qkv/ssm_conv``, ``kv_attend/ssm_scan``, ``kv_attend/ssm_step``,
``attn_out/ssm_gate_norm``, ``kv_write/ssm_write``).  A program that has
no such scope, as every program before PR 42, reads nothing."""

from benchmark.harness import scopes
from benchmark.harness.clock import percentile
from benchmark.readers import _window
from benchmark.readers.gdn import _runs
from benchmark.roofline import ssm

PREFIX = "ssm_"
CHUNK, DECODE = "dstpu_chunk", "dstpu_decode"


def _seconds(scoped, program=None):
    """Self seconds under an ``ssm_`` word (inside the runs of
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
    """``share_of_busy``: self time under the ``ssm_`` words over busy
    time.  ``prefill_roofline``: what the Mamba-2 layers of the traced
    chunk programs need (``roofline/ssm.py``: the operations of a
    chunk's tokens at the bf16 peak, or the layers' weights at the
    memory's bandwidth if that is more) over the self time under those
    words inside chunk programs.  ``step_roofline``: every live slot's
    state read and written once and the layers' weights once a traced
    decode program, at the memory's bandwidth (or the operations at the
    peak, if larger), over the self time under those words inside decode
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
    if run.peaks is None or not hasattr(cfg, "n_ssm_layers"):
        return None
    engine = dict(run.config["serving"]["engine"], **run.cell["engine"])
    program = CHUNK if what == "prefill_roofline" else DECODE
    took, runs = _seconds(scoped, program), _runs(scoped, program)
    if not took:
        return None
    if what == "prefill_roofline":
        tokens = engine.get("prefill_chunk") or engine.get("prefill_bucket")
        least = runs * cfg.n_ssm_layers * ssm.prefill_floor_seconds(
            cfg, tokens, run.peaks)
    else:
        steps = [s[4] for s in _window.steps(run) if s[4] > 0]
        if not steps:
            return None
        live = percentile(steps, 50) * engine["max_batch"]
        least = runs * cfg.n_ssm_layers * ssm.step_floor_seconds(
            cfg, live, run.peaks)
    return 100.0 * least / took if least else None

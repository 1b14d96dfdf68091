"""What a run says of the decoder-hybrid-decoder family
(``deepspeed_tpu/models/phi4_flash.py``): the scope words that start
``mamba_`` (nested in the words the harness's vocabulary knows:
``attn_qkv/mamba_proj``, ``attn_qkv/mamba_conv``, ``kv_attend/mamba_scan``,
``kv_attend/mamba_step``, ``attn_out/mamba_gate``, ``kv_write/
mamba_write``) and ``kv_attend/yoco_read`` (a cross layer's read of the
full layer's pages), the decode step's floor for a model whose one pool
layer eight layers read, and the share of the peak with every row charged
what it paid.  A program that has no such scope, as every program before
PR 55, reads nothing."""

from benchmark.harness import scopes
from benchmark.harness.clock import percentile
from benchmark.readers import _window
from benchmark.readers.gdn import _runs
from benchmark.roofline import mamba1

MAMBA, YOCO = "mamba_", "yoco_read"
CHUNK, DECODE = "dstpu_chunk", "dstpu_decode"


def _seconds(scoped, word, program=None):
    """Self seconds under ``word`` (a whole word, or a prefix that ends
    in ``_``), inside the runs of ``program`` where given, the chips'
    mean; None if no operation."""
    match = (lambda w: w.startswith(word)) if word.endswith("_") \
        else (lambda w: w == word)
    total, found = 0.0, False
    for ops in scoped.ops.values():
        for op, t in scopes.self_seconds(ops):
            if program is not None and program not in op.path:
                continue
            if any(match(w) for w in scopes.WORD.findall(op.path)):
                total += t
                found = True
    return total / max(1, len(scoped.ops)) if found else None


def _live(run, engine, traced=False):
    """(live slots, live pool rows) of the window's decode-only steps,
    their medians; ``traced``: their means over the steps of the traced
    stretch alone (the window's last ``trace_seconds``: what a traced
    program read is held against what was live then, not against the
    window's median).  None where there is no such step."""
    w = run.window
    rows = _window.decode_only(run)
    if traced:
        opened = w["t_end"] - run.traffic["trace_seconds"]
        rows = [s for s in rows if s[0] >= opened]
    if not rows:
        return None
    of = (lambda v: sum(v) / len(v)) if traced \
        else (lambda v: percentile(v, 50))
    return (of([s[4] for s in rows]) * engine["max_batch"],
            of([s[5] * w["pool_pages"] * w["page_size"] for s in rows]))


def _decode_step(run, cfg, engine):
    """The least time a decode step could take: every weight once (the
    head among them, once: it is the embedding), every live slot's
    Mamba-1 state and rows read and written once, its rings read once
    (the rows they hold) with a row written, and the live rows of the one
    pool layer read once by EACH layer that reads it (the full layer and
    the cross layers), at the published bandwidth, over the median host
    time of the steps that only decoded."""
    live = _live(run, engine)
    if live is None:
        return None
    slots, rows = live
    state, rings = run.family.slot_bytes(cfg)
    held = min(rows / max(slots, 1), cfg.sliding_window) / cfg.sliding_window
    least = (run.family.weight_bytes(cfg)
             + run.family.pool_reads(cfg) * rows
             * run.family.kv_bytes_per_token(cfg)
             + slots * (2 * state + held * rings)
             ) / run.peaks["hbm_bytes_per_s"]
    took = percentile([s[1] - s[0] for s in _window.decode_only(run)], 50)
    return 100.0 * least / took


def _mfu_rows(run, cfg):
    """FLOPs of the requests completed with every row charged what it
    paid: a prompt's rows the self-decoder (over half the prompt, on
    average), its last row and every generated token the cross-decoder
    and the head too, a second, over the peak."""
    w, fam = run.window, run.family
    led = w["ledger"]
    flops = 0.0
    for rid in w["completed"]:
        p, g = led.requests[rid].prompt_len, len(led.stamps[rid])
        flops += p * fam.self_flops_per_token(cfg, p / 2) \
            + g * fam.self_flops_per_token(cfg, p + g / 2) \
            + fam.tail_flops_per_token(cfg, p) \
            + g * fam.tail_flops_per_token(cfg, p + g / 2)
    return 100.0 * flops / _window.seconds(run) / (
        run.peaks["bf16_flops_per_s"] * run.chips)


def read(run, what):
    """``decode_step_roofline``: see :func:`_decode_step` (the host's
    clock).  ``serve_mfu_rows``: see :func:`_mfu_rows`.  From the trace:
    ``mamba_share`` / ``yoco_share``, self time under the ``mamba_``
    words / under ``yoco_read`` over busy time; ``mamba_step_roofline``,
    every live slot's state and rows read and written once and the
    layer's mixer weights once, a Mamba-1 layer a traced decode program,
    at the memory's bandwidth (``roofline/mamba1.py``), over the self time
    under the ``mamba_`` words inside decode programs;
    ``mamba_scan_roofline``, what a chunk's tokens of a Mamba-1 layer
    need (their operations at the bf16 peak, or the layer's bytes if
    more) a traced chunk program, over that self time inside chunk
    programs; ``yoco_read_roofline``, the live rows of the pool's one
    layer read once by each cross layer a traced decode program, at the
    memory's bandwidth, over the self time under ``yoco_read`` inside
    decode programs."""
    w = run.window
    cfg = w.get("program_config")
    if w["kind"] != "serve" or run.peaks is None \
            or not hasattr(cfg, "n_mamba_layers"):
        return None
    engine = dict(run.config["serving"]["engine"], **run.cell["engine"])
    if what == "decode_step_roofline":
        return _decode_step(run, cfg, engine)
    if what == "serve_mfu_rows":
        return _mfu_rows(run, cfg)
    scoped = scopes.of_run(run)
    if scoped is None or not scoped.ops:
        return None
    if what in ("mamba_share", "yoco_share"):
        took = _seconds(scoped, MAMBA if what == "mamba_share" else YOCO)
        busy = sum(r["self_s"] for r in scopes.by_scope(scoped).values())
        return 100.0 * took / busy if took and busy else None
    bw = run.peaks["hbm_bytes_per_s"]
    if what == "mamba_scan_roofline":
        took, runs = _seconds(scoped, MAMBA, CHUNK), _runs(scoped, CHUNK)
        tokens = engine.get("prefill_chunk") or engine.get("prefill_bucket")
        least = runs * cfg.n_mamba_layers * mamba1.scan_floor_seconds(
            cfg, tokens, run.peaks)
        return 100.0 * least / took if took and least else None
    live = _live(run, engine, traced=True)
    runs = _runs(scoped, DECODE)
    if live is None or not runs:
        return None
    slots, rows = live
    if what == "mamba_step_roofline":
        took = _seconds(scoped, MAMBA, DECODE)
        least = runs * cfg.n_mamba_layers * mamba1.step_floor_seconds(
            cfg, slots, run.peaks)
    else:                                       # yoco_read_roofline
        took = _seconds(scoped, YOCO, DECODE)
        least = runs * cfg.n_cross_layers * rows \
            * run.family.kv_bytes_per_token(cfg) / bw
    return 100.0 * least / took if took and least else None

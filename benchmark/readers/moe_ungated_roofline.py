from benchmark.harness import scopes
from benchmark.readers import _scope_words
from benchmark.roofline import moe_ungated

SCOPE = "moe_routed"


def read(run):
    """``readers/moe_grouped_roofline.py`` for experts of two matrices
    (``roofline/moe_ungated.py``: ``4 d f`` operations a routed row, ``2
    d f`` numbers a held expert that got a row, at the published width):
    the least time the routed parts of the traced programs' expert
    layers could take on this device, the held experts' shares as the
    programs counted them over the window, over the self time traced
    under the scope ``moe_routed``, whichever implementation ran there.
    A program that counts no rows, or whose experts are not of this
    body (no ``moe_ffn_stored``: every family before PR 48), reads
    nothing."""
    scoped = scopes.of_run(run)
    held, routed = (run.window.get("expert_rows"),
                    run.window.get("routed_rows"))
    cfg = run.window["program_config"]
    if scoped is None or run.peaks is None or not scoped.ops \
            or not held or not routed or not hasattr(cfg, "moe_ffn_stored"):
        return None
    took = _scope_words.self_seconds(scoped, SCOPE)
    if not took:
        return None
    shares = [rows / routed for rows in held]
    engine = dict(run.config["serving"]["engine"], **run.cell["engine"])
    rows_of = {"dstpu_decode": engine["max_batch"],
               "dstpu_chunk": engine.get("prefill_chunk")
               or engine.get("prefill_bucket")}
    least = 0.0
    for name, _, _ in next(iter(scoped.programs.values())):
        rows = next((r for word, r in rows_of.items() if word in name), None)
        if rows:
            least += cfg.n_expert_layers * moe_ungated.floor_seconds(
                cfg.dim, cfg.moe_ffn_dim, rows * cfg.top_k, shares,
                run.peaks)
    return 100.0 * least / took if least else None

from benchmark.harness import scopes
from benchmark.readers import _scope_words
from benchmark.roofline import moe

SCOPE = "moe_routed"


def read(run):
    """The least time the routed parts of the traced programs' expert
    layers could take on this device (``roofline/moe.py``: a decode
    program routes its slots' rows, a chunk program its chunk's, each
    ``top_k`` times, and the held experts get the shares of those pairs
    that the programs counted over the window) over the self time traced
    under the scope ``moe_routed``.  By scope, not by kernel name: a
    decode step's product is XLA's.  A program that counts no rows reads
    nothing."""
    scoped = scopes.of_run(run)
    held, routed = (run.window.get("expert_rows"),
                    run.window.get("routed_rows"))
    if scoped is None or run.peaks is None or not scoped.ops \
            or not held or not routed:
        return None
    took = _scope_words.self_seconds(scoped, SCOPE)
    if not took:
        return None
    cfg = run.window["program_config"]
    shares = [rows / routed for rows in held]
    engine = dict(run.config["serving"]["engine"], **run.cell["engine"])
    rows_of = {"dstpu_decode": engine["max_batch"],
               "dstpu_chunk": engine.get("prefill_chunk")
               or engine.get("prefill_bucket")}
    least = 0.0
    for name, _, _ in next(iter(scoped.programs.values())):
        rows = next((r for word, r in rows_of.items() if word in name), None)
        if rows:
            least += cfg.n_expert_layers * moe.floor_seconds(
                cfg.dim, cfg.moe_ffn_dim, rows * cfg.top_k, shares,
                run.peaks)
    return 100.0 * least / took if least else None

"""A sliding-window attention layer over a per-slot ring
(``deepspeed_tpu/models/laguna.py::win_mix``, scopes ``kv_attend/
win_attend`` and ``kv_write/win_write``; the projections stand under
``attn_qkv`` and the gate under ``attn_out/attn_gate``, which the full
layers share, and are not counted here).

Bytes of a decode step, a layer: every live slot's ring read once (the
rows it has: ``min(length, window)`` of them, K and V) and one row
written.  Operations of a chunk, a layer: a (query, visible key) pair
costs 2 x heads x head_dim for the score and as much for the value; a
query well into its sequence sees ``window`` keys.  The program computes
the band as ``[W, 2 W]`` blocks, twice the visible pairs: what it spends
above the count stands against its share.
"""


def ring_bytes(cfg, itemsize=2):
    """What one slot keeps, one layer: ``window`` rows of K and V."""
    return cfg.sliding_window * 2 * cfg.n_kv_heads * cfg.head_dim * itemsize


def row_bytes(cfg, itemsize=2):
    return 2 * cfg.n_kv_heads * cfg.head_dim * itemsize


def step_bytes(cfg, live_slots, rows_held=None, itemsize=2):
    """One layer of one decode step: the live slots' rings read (each
    ``rows_held`` rows, the whole window if not said) and a row a slot
    written."""
    rows = cfg.sliding_window if rows_held is None else rows_held
    return live_slots * (rows + 1) * row_bytes(cfg, itemsize)


def step_floor_seconds(cfg, live_slots, peaks, itemsize=2):
    return step_bytes(cfg, live_slots, None, itemsize) \
        / peaks["hbm_bytes_per_s"]


def pair_flops(cfg):
    """One (query, visible key) pair, every head: score and value."""
    return 4 * cfg.n_heads_sliding * cfg.head_dim


def chunk_flops(cfg, tokens):
    """One layer over a chunk of ``tokens`` queries, each counted as
    seeing a whole window (a first chunk's early queries see fewer and a
    padded last chunk's rows nothing that counts: an upper count of the
    need, under half of what the blocked band computes)."""
    return tokens * cfg.sliding_window * pair_flops(cfg)


def chunk_floor_seconds(cfg, tokens, peaks):
    return chunk_flops(cfg, tokens) / peaks["bf16_flops_per_s"]

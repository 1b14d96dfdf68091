"""A power-retention layer of degree 2 (``deepspeed_tpu/models/brumby.py
::ret_mix``, scopes ``ret_proj``, ``ret_gate``, ``ret_step`` /
``ret_chunk`` and the state's write-back ``ret_write``), stated as the
SAME WORK whatever implements it.

A K/V head keeps ``S [D, Dh]`` and the normaliser ``z [D]`` in float32,
``D = Dh (Dh + 1) / 2`` (the exact size of ``phi``: 8,256 at 128 lanes; a
program that pads its layout moves more and reads under 100%).

Operations, a token a layer: the recurrence's own products, ``phi(k)
v^T`` into the state of each K/V head and ``phi(q)^T S`` out of it for
each query head: ``2 D Dh (H + KV)``.  A chunked form's blocks (its ``[5b,
b]`` products) are the kernel's own way and are not counted.

Bytes: a slot's state a layer, ``KV (D Dh + D) 4``, once out of the memory
and once in.
"""


def phi_size(cfg):
    return cfg.head_dim * (cfg.head_dim + 1) // 2


def rule_flops(cfg, tokens):
    """The recurrence's products alone, one layer."""
    return 2 * phi_size(cfg) * cfg.head_dim * (
        cfg.n_heads + cfg.n_kv_heads) * tokens


def state_bytes(cfg):
    """What one slot keeps, one layer: S and z of every K/V head."""
    return cfg.n_kv_heads * phi_size(cfg) * (cfg.head_dim + 1) * 4


def step_floor_seconds(cfg, live_slots, peaks):
    """One layer of one decode step: the live slots' state out and in."""
    return 2 * live_slots * state_bytes(cfg) / peaks["hbm_bytes_per_s"]


def chunk_floor_seconds(cfg, tokens, peaks):
    """One layer of one chunk of ``tokens`` rows of one slot: the larger
    of the products at the matrix unit's peak and the slot's state out
    and in at the memory's."""
    return max(rule_flops(cfg, tokens) / peaks["bf16_flops_per_s"],
               2 * state_bytes(cfg) / peaks["hbm_bytes_per_s"])

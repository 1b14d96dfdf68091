"""A Kimi Delta Attention layer (``deepspeed_tpu/models/ling_flash.py::
kda_mix``, scopes ``kda_proj``, ``kda_conv``, ``kda_scan`` / ``kda_step``,
``kda_gate_norm`` and the state's write-back ``kda_write``).

Operations, a token: 2 per weight of the projections (``W_qkv``, ``W_f``,
``w_b``, ``w_g``, ``W_out``), 2 a tap a channel of the convolution, and
the recurrence over a head's ``Dk x Dv`` state: the decay of its rows (1
a number) and the three products (``S'^T k``, ``k u^T``, ``S^T q``: 2
each).  That is what the mathematics needs whichever way it is computed:
the chunked form of a prompt chunk spends more (its strips of decayed
products and its block-triangular solve), and what it spends above this
counts against its share.

Bytes, a layer: a decode step reads and writes every live slot's state
and convolution rows once and reads the layer's weights once; a prompt
chunk reads the weights and the slot's state once, writes the state
once, and reads and writes its tokens' hidden rows.
"""


def projection_params(cfg):
    HD = cfg.n_heads * cfg.kda_head_dim
    return cfg.dim * (4 * HD + 2 * cfg.n_heads) + HD * cfg.dim


def rule_flops(cfg, tokens):
    """The recurrence alone, one layer."""
    return 7 * cfg.n_heads * cfg.kda_head_dim ** 2 * tokens


def flops(cfg, tokens):
    """One layer over ``tokens`` tokens."""
    return tokens * (2 * projection_params(cfg)
                     + 2 * cfg.conv_kernel * cfg.conv_channels) \
        + rule_flops(cfg, tokens)


def state_bytes(cfg, itemsize=2):
    """What one slot keeps, one layer."""
    return (cfg.n_heads * cfg.kda_head_dim ** 2 * 4
            + (cfg.conv_kernel - 1) * cfg.conv_channels * itemsize)


def weight_bytes(cfg, itemsize=2):
    return (projection_params(cfg)
            + cfg.conv_kernel * cfg.conv_channels) * itemsize


def step_bytes(cfg, live_slots, itemsize=2):
    """One layer of one decode step."""
    return 2 * live_slots * state_bytes(cfg, itemsize) \
        + weight_bytes(cfg, itemsize)


def chunk_bytes(cfg, tokens, itemsize=2):
    """One layer of one prompt chunk of one slot."""
    return (weight_bytes(cfg, itemsize) + 2 * state_bytes(cfg, itemsize)
            + 2 * tokens * cfg.dim * itemsize)


def prefill_floor_seconds(cfg, tokens, peaks, itemsize=2):
    return max(flops(cfg, tokens) / peaks["bf16_flops_per_s"],
               chunk_bytes(cfg, tokens, itemsize) / peaks["hbm_bytes_per_s"])


def step_floor_seconds(cfg, live_slots, peaks, itemsize=2):
    return max(flops(cfg, live_slots) / peaks["bf16_flops_per_s"],
               step_bytes(cfg, live_slots, itemsize)
               / peaks["hbm_bytes_per_s"])

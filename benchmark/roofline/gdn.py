"""A Gated DeltaNet layer (``deepspeed_tpu/models/qwen3_next.py::gdn_mix``,
scopes ``gdn_proj``, ``gdn_conv``, ``gdn_scan`` / ``gdn_step``,
``gdn_gate_norm`` and the state's write-back ``gdn_write``).

Operations, a token: 2 per weight of the projections (``W_qkvz``,
``W_ba``, ``W_out``), 2 a tap a channel of the convolution, and the
recurrence's three products over a value head's ``Dk x Dv`` state
(``S'^T k``, ``k u^T``, ``S^T q``: 2 each).  That is what the rule needs
whichever way it is computed: the chunked form of a prompt chunk spends
more (its block-triangular solve), and what it spends above this counts
against its share.

Bytes of a decode step, a layer: every live slot's state and
convolution rows read and written once, and the layer's weights once.
"""


def projection_params(cfg):
    Kd = cfg.lin_k_heads * cfg.lin_k_dim
    Vd = cfg.lin_v_heads * cfg.lin_v_dim
    return cfg.dim * (2 * Kd + 2 * Vd + 2 * cfg.lin_v_heads) + Vd * cfg.dim


def rule_flops(cfg, tokens):
    """The recurrence alone, one layer."""
    return 6 * cfg.lin_v_heads * cfg.lin_k_dim * cfg.lin_v_dim * tokens


def flops(cfg, tokens):
    """One layer over ``tokens`` tokens."""
    return tokens * (2 * projection_params(cfg)
                     + 2 * cfg.conv_kernel * cfg.conv_channels) \
        + rule_flops(cfg, tokens)


def state_bytes(cfg, itemsize=2):
    """What one slot keeps, one layer."""
    return (cfg.lin_v_heads * cfg.lin_k_dim * cfg.lin_v_dim * 4
            + (cfg.conv_kernel - 1) * cfg.conv_channels * itemsize)


def weight_bytes(cfg, itemsize=2):
    return (projection_params(cfg)
            + cfg.conv_kernel * cfg.conv_channels) * itemsize


def step_bytes(cfg, live_slots, itemsize=2):
    """One layer of one decode step."""
    return 2 * live_slots * state_bytes(cfg, itemsize) \
        + weight_bytes(cfg, itemsize)


def prefill_floor_seconds(cfg, tokens, peaks):
    return flops(cfg, tokens) / peaks["bf16_flops_per_s"]


def step_floor_seconds(cfg, live_slots, peaks, itemsize=2):
    return max(flops(cfg, live_slots) / peaks["bf16_flops_per_s"],
               step_bytes(cfg, live_slots, itemsize)
               / peaks["hbm_bytes_per_s"])

"""A Mamba-2 layer (``deepspeed_tpu/models/granite_hybrid.py::ssm_mix``,
scopes ``ssm_proj``, ``ssm_conv``, ``ssm_scan`` / ``ssm_step``,
``ssm_gate_norm`` and the state's write-back ``ssm_write``).

Operations, a token: 2 per weight of the projections (``W_in``,
``W_dt``, ``W_out``), 2 a tap a channel of the convolution, and the recurrence's
decay, write and read over a head's ``P x N`` state (``e^(dt A) S``, ``+
dt x B^T``, ``S C``: 2 each).  That is what the recurrence needs
whichever way it is computed: the chunked form of a prompt chunk spends
more (its block's ``C x C`` products a head), and what it spends above
this counts against its share.

Bytes of a decode step, a layer: every live slot's state and
convolution rows read and written once, and the layer's weights once.
"""


def projection_params(cfg):
    return cfg.dim * (cfg.ssm_inner + cfg.conv_channels + cfg.ssm_heads) \
        + cfg.ssm_inner * cfg.dim


def mixer_params(cfg):
    """Everything a Mamba-2 mixer holds: the projections, the
    convolution's taps and bias, ``dt_bias``, ``A_log`` and ``D`` a head,
    the gated norm's gains."""
    return (projection_params(cfg)
            + (cfg.conv_kernel + 1) * cfg.conv_channels
            + 3 * cfg.ssm_heads + cfg.ssm_inner)


def rule_flops(cfg, tokens):
    """The recurrence alone, one layer."""
    return 6 * cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state * tokens


def flops(cfg, tokens):
    """One layer over ``tokens`` tokens."""
    return tokens * (2 * projection_params(cfg)
                     + 2 * cfg.conv_kernel * cfg.conv_channels) \
        + rule_flops(cfg, tokens)


def state_bytes(cfg, itemsize=2):
    """What one slot keeps, one layer."""
    return (cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state * 4
            + (cfg.conv_kernel - 1) * cfg.conv_channels * itemsize)


def weight_bytes(cfg, itemsize=2):
    return mixer_params(cfg) * itemsize


def step_bytes(cfg, live_slots, itemsize=2):
    """One layer of one decode step."""
    return 2 * live_slots * state_bytes(cfg, itemsize) \
        + weight_bytes(cfg, itemsize)


def prefill_floor_seconds(cfg, tokens, peaks, itemsize=2):
    """A chunk of one row: its operations at the peak, or the layer's
    weights at the bandwidth if that is more."""
    return max(flops(cfg, tokens) / peaks["bf16_flops_per_s"],
               (weight_bytes(cfg, itemsize) + 2 * state_bytes(cfg, itemsize))
               / peaks["hbm_bytes_per_s"])


def step_floor_seconds(cfg, live_slots, peaks, itemsize=2):
    return max(flops(cfg, live_slots) / peaks["bf16_flops_per_s"],
               step_bytes(cfg, live_slots, itemsize)
               / peaks["hbm_bytes_per_s"])

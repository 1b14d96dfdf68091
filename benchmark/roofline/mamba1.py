"""A Mamba-1 layer (``deepspeed_tpu/models/phi4_flash.py::mamba_mix``,
scopes ``mamba_proj``, ``mamba_conv``, ``mamba_scan`` / ``mamba_step``,
``mamba_gate`` and the state's write-back ``mamba_write``), counted as
``roofline/ssm.py`` counts Mamba-2: for the mathematics, not for an
implementation.

Operations, a token: 2 per weight of the projections (``W_in``, ``W_x``,
``W_dt``, ``W_out``), 2 a tap a channel of the convolution, and the
recurrence's decay, write and read over a (channel, state) pair (``e^(dt
A) S``, ``+ dt c B^T``, ``S C``: 2 each; the decay's exponential, one a
pair a token where Mamba-2 has one a head, is not counted: ``peaks.json``
has no row for the unit that computes it).

Bytes of a decode step, a layer: every live slot's state and convolution
rows read and written once, and the layer's weights once.
"""


def projection_params(cfg):
    return (cfg.dim * 2 * cfg.d_inner
            + cfg.d_inner * (cfg.dt_rank + 2 * cfg.d_state)
            + cfg.dt_rank * cfg.d_inner + cfg.d_inner * cfg.dim)


def mixer_params(cfg):
    """Everything a Mamba-1 mixer holds: the projections, the
    convolution's taps and bias, ``dt_bias`` and ``D`` a channel,
    ``A_log`` a (channel, state) pair."""
    return (projection_params(cfg) + (cfg.d_conv + 1) * cfg.d_inner
            + 2 * cfg.d_inner + cfg.d_inner * cfg.d_state)


def rule_flops(cfg, tokens):
    """The convolution and the recurrence, one layer."""
    return tokens * (2 * cfg.d_conv * cfg.d_inner
                     + 6 * cfg.d_inner * cfg.d_state)


def flops(cfg, tokens):
    """One layer over ``tokens`` tokens."""
    return tokens * 2 * projection_params(cfg) + rule_flops(cfg, tokens)


def state_bytes(cfg, itemsize=2):
    """What one slot keeps, one layer: the float32 state and the
    convolution's rows."""
    return (cfg.d_inner * cfg.d_state * 4
            + (cfg.d_conv - 1) * cfg.d_inner * itemsize)


def weight_bytes(cfg, itemsize=2):
    return mixer_params(cfg) * itemsize


def step_bytes(cfg, live_slots, itemsize=2):
    """One layer of one decode step."""
    return 2 * live_slots * state_bytes(cfg, itemsize) \
        + weight_bytes(cfg, itemsize)


def scan_floor_seconds(cfg, tokens, peaks, itemsize=2):
    """A chunk of one row: its operations at the bf16 peak, or the
    layer's weights and the slot's state at the bandwidth if that is
    more."""
    return max(flops(cfg, tokens) / peaks["bf16_flops_per_s"],
               (weight_bytes(cfg, itemsize) + 2 * state_bytes(cfg, itemsize))
               / peaks["hbm_bytes_per_s"])


def step_floor_seconds(cfg, live_slots, peaks, itemsize=2):
    return max(flops(cfg, live_slots) / peaks["bf16_flops_per_s"],
               step_bytes(cfg, live_slots, itemsize)
               / peaks["hbm_bytes_per_s"])

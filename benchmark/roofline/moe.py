"""The routed part of an expert layer that holds a share of its experts
(``deepspeed_tpu/parallel/moe.py::held_experts_ffn``, scope
``moe_routed``).

A row routed to a held expert meets that expert's three matrices:
``6 * d * f`` operations.  The bytes are the weights of the held experts
that received a row, once each (``3 * d * f`` numbers an expert); the
rows themselves are small beside them.  ``shares[e]`` is the share of
all routed (row, expert) pairs that held expert ``e`` received, as the
programs counted it over the window (``serving_expert_rows_<e>`` over
``serving_routed_rows``): of a program's ``pairs`` the held experts get
``pairs * sum(shares)``, and expert ``e`` is left without a row with
probability ``(1 - shares[e]) ** pairs``.
"""


def routed_rows(pairs, shares):
    return pairs * sum(shares)


def experts_touched(pairs, shares):
    return sum(1.0 - (1.0 - s) ** pairs for s in shares)


def flops(d, f, rows):
    return 6 * d * f * rows


def bytes_moved(d, f, experts, itemsize=2):
    return 3 * d * f * experts * itemsize


def floor_seconds(d, f, pairs, shares, peaks, itemsize=2):
    """The least time the routed part of ONE expert layer can take for a
    program that routes ``pairs`` (row, expert) pairs."""
    return max(
        flops(d, f, routed_rows(pairs, shares)) / peaks["bf16_flops_per_s"],
        bytes_moved(d, f, experts_touched(pairs, shares), itemsize)
        / peaks["hbm_bytes_per_s"])

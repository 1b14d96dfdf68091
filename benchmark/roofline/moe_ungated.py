"""The routed part of an expert layer whose experts have two matrices
and no gate, ``W_down act(W_up a)`` (``deepspeed_tpu/parallel/moe.py::
held_experts_ffn`` with ``w3`` None, scope ``moe_routed``).

A row routed to a held expert meets that expert's two matrices: ``4 * d
* f`` operations.  The bytes are the weights of the held experts that
received a row, once each (``2 * d * f`` numbers an expert), at the
PUBLISHED width ``f``: zeros the program stores behind it to fill a
tile are its own cost, and count against its share.  Which rows the
held experts get, and which of them is left without one, is
``roofline/moe.py``'s arithmetic over the shares the programs counted.
"""

from benchmark.roofline.moe import experts_touched, routed_rows


def flops(d, f, rows):
    return 4 * d * f * rows


def bytes_moved(d, f, experts, itemsize=2):
    return 2 * d * f * experts * itemsize


def floor_seconds(d, f, pairs, shares, peaks, itemsize=2):
    """The least time the routed part of ONE expert layer can take for a
    program that routes ``pairs`` (row, expert) pairs."""
    return max(
        flops(d, f, routed_rows(pairs, shares)) / peaks["bf16_flops_per_s"],
        bytes_moved(d, f, experts_touched(pairs, shares), itemsize)
        / peaks["hbm_bytes_per_s"])

"""The latent decode kernel ``dstpu_mla_decode``
(``deepspeed_tpu/inference/kernels.py``).

One call serves every row of a decode step in one layer.  A row of
``live`` cached tokens needs two matrix products with its H heads as
their M dimension: absorbed queries against the rows (``width`` numbers
a row: the compressed KV and the rotated key part) and the softmax
against the rows' first ``value_width`` numbers: ``2 * H * (width +
value_width) * live`` operations.  The bytes are each live row once
(``width`` numbers, what a token's row IS; the pool stores it in whole
128-lane tiles, and a kernel that reads the padding too is charged for
it by the clock, not excused by the count), the absorbed queries and
the result.  Live tokens are counted, not pages: a page's unused tail
is not needed.  Softmax's exponentials are left out.
"""


def flops(heads, width, value_width, live_tokens):
    """``live_tokens``: the rows' live lengths, summed."""
    return 2 * heads * (width + value_width) * live_tokens


def bytes_moved(heads, width, value_width, live_tokens, rows, itemsize=2):
    return itemsize * (width * live_tokens
                       + rows * heads * (width + value_width))


def floor_seconds(heads, width, value_width, live_tokens, rows, peaks,
                  itemsize=2):
    """The least time one call over ``rows`` rows holding
    ``live_tokens`` between them can take on a device with ``peaks``."""
    return max(flops(heads, width, value_width, live_tokens)
               / peaks["bf16_flops_per_s"],
               bytes_moved(heads, width, value_width, live_tokens, rows,
                           itemsize) / peaks["hbm_bytes_per_s"])

"""The flash-attention kernels ``dstpu_flash_fwd``, ``dstpu_flash_bwd_dq``
and ``dstpu_flash_bwd_dkv`` (``deepspeed_tpu/ops/attention_pallas.py``).

One call works on q ``[B*H, T, D]`` and k, v ``[B*KV, S, D]``.  The
operations counted are the matrix products the kernel has to do to turn
its inputs into its outputs, each ``2*T*S*D`` a head, and under a causal
mask only the scores at or below the diagonal: what is needed, not what
a block grid that overshoots the diagonal executes.  Softmax's
exponentials and the rescaling are left out (they run on another unit).
The bytes are every operand read once and every result written once.
"""

# matrix products of T x S x D a head: forward S = QK^T, O = PV; the dq
# kernel recomputes S, then dP = dO V^T and dQ = dS K; the dk/dv kernel
# recomputes S, then dV = P^T dO, dP = dO V^T and dK = dS^T Q
PRODUCTS = {"dstpu_flash_fwd": 2, "dstpu_flash_bwd_dq": 3,
            "dstpu_flash_bwd_dkv": 4}


def scores(t, s, causal):
    """Score entries a head needs: all of ``t x s``, or under a causal
    mask (the last query sees every key) those at or below the diagonal."""
    if not causal:
        return t * s
    t = min(t, s)
    return t * (2 * s - t + 1) // 2


def flops(kernel, q_shape, k_shape, causal=True):
    """Floating-point operations one call of ``kernel`` needs."""
    rows, t, d = q_shape
    s = k_shape[1]
    return PRODUCTS[kernel] * 2 * rows * scores(t, s, causal) * d


def bytes_moved(shapes):
    """Bytes of the operands and results: ``[(dtype, dims), ...]``."""
    size = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1,
            "u8": 1, "pred": 1}
    total = 0
    for dtype, dims in shapes:
        n = 1
        for x in dims:
            n *= x
        total += n * size[dtype]
    return total


def floor_seconds(kernel, shapes, peaks, causal=True):
    """The least time one call can take on a device with ``peaks``:
    the larger of its operations over the peak rate and its bytes over
    the memory's.  ``shapes``: operands first (q, k, v, ...), then
    results, as the trace's HLO line gives them."""
    return max(flops(kernel, shapes[0][1], shapes[1][1], causal)
               / peaks["bf16_flops_per_s"],
               bytes_moved(shapes) / peaks["hbm_bytes_per_s"])

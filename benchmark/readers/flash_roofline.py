from benchmark.harness import scopes
from benchmark.roofline import flash


def read(run, causal=True):
    """The least time the runs of the flash kernels could take on this
    device (``roofline/flash.py``: operations over the peak rate or
    bytes over the memory's, whichever is larger, call by call) over
    the time they took, summed over ``dstpu_flash_*`` operations; the
    chips' mean."""
    scoped = scopes.of_run(run)
    if scoped is None or run.peaks is None:
        return None
    shares = []
    for ops in scoped.ops.values():
        calls = [o for o in ops if o.kernel in flash.PRODUCTS]
        took = sum(o.dur for o in calls)
        if took:
            shares.append(sum(flash.floor_seconds(
                o.kernel, o.operands + o.results, run.peaks, causal)
                for o in calls) / took)
    return 100.0 * sum(shares) / len(shares) if shares else None

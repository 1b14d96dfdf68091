import re

from benchmark.harness import trace


def read(run, pattern):
    """Self time of the operations the trace names as Mosaic kernels
    (``pattern``), over busy time.  Not a roofline share: no kernel has
    a name of its own yet."""
    if run.traced is None or not run.traced.ops:
        return None
    rx = re.compile(pattern)
    share = trace.share_of_busy(run.traced, lambda n: bool(rx.search(n)))
    return None if share is None else 100.0 * share

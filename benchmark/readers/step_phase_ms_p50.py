from benchmark.harness import scopes
from benchmark.harness.clock import percentile


def read(run, spans, beside=()):
    """Per ``dstpu/serving_step`` span of the traced stretch, the time
    its children named in ``spans`` take together (plus the ``beside``
    spans that follow it before the next step); the median, in ms."""
    scoped = scopes.of_run(run)
    if scoped is None:
        return None
    p = percentile(scopes.step_phases(scoped, spans, beside=beside), 50)
    return None if p is None else 1e3 * p

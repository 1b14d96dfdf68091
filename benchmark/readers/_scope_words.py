"""Self time under a scope word the harness's vocabulary does not hold
(``harness/scopes.py::VOCABULARY`` labels an operation by the innermost
word it knows; a later PR's words nest inside those)."""

from benchmark.harness import scopes


def self_seconds(scoped, word):
    """Seconds of self time of the operations whose scope path holds
    ``word``, the chips' mean; None when no operation does."""
    total, found = 0.0, False
    for ops in scoped.ops.values():
        for op, t in scopes.self_seconds(ops):
            if word in scopes.WORD.findall(op.path):
                total += t
                found = True
    return total / max(1, len(scoped.ops)) if found else None

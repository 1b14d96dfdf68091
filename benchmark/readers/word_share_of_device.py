from benchmark.harness import scopes
from benchmark.readers import _scope_words


def read(run, word):
    """Self time of the device operations traced under the program's
    named scope ``word`` over the traced window, the chips' mean; a
    program that has no such scope reads nothing."""
    scoped = scopes.of_run(run)
    if scoped is None or not scoped.ops:
        return None
    w = scopes.window_of(scoped)
    took = _scope_words.self_seconds(scoped, word)
    if took is None or w is None or w[1] <= w[0]:
        return None
    return 100.0 * took / (w[1] - w[0])

"""The one traffic generator.  A mix is a data file; this reads it.

Every draw comes from ``--seed``, and the seed changes contents, order
and spacing only: the number of requests, the multiset of their lengths
and the token total are fixed by the mix and the window.  Lengths are
the quantiles ``(k + 0.5) / n`` of a clipped log-normal, not samples of
it, so that no run happens to draw more long prompts than another.
"""

import dataclasses
import math
from statistics import NormalDist

import numpy as np


# Which prompt meets which output, and which warm request is caught at
# which point of its life, decide how long pages are held.  That is
# work, so it is the same for every ``--seed`` and every mix.
PAIRING_SEED = 0


@dataclasses.dataclass(frozen=True)
class Request:
    index: int          # position in the run's order; the request id
    prompt_len: int
    new_tokens: int     # what the request asks for in this run
    due: float          # seconds from the window's opening; <0 = warm-up


def quantile_lengths(dist, n):
    """n lengths: quantiles (k + 0.5)/n of log-normal(median, sigma of the
    logarithm), clipped to [lo, hi], ascending."""
    norm = NormalDist()
    mu = math.log(dist["median"])
    out = [math.exp(mu + dist["sigma"] * norm.inv_cdf((k + 0.5) / n))
           for k in range(n)]
    return [int(min(max(round(x), dist["lo"]), dist["hi"])) for x in out]


def length_pairs(mix, n):
    """n (prompt, output) pairs, paired the same way for every
    ``--seed`` (``PAIRING_SEED``)."""
    prompts = quantile_lengths(mix["prompt_tokens"], n)
    outputs = quantile_lengths(mix["output_tokens"], n)
    order = np.random.default_rng(PAIRING_SEED).permutation(n)
    return [(prompts[i], outputs[int(order[i])]) for i in range(n)]


def stratified_order(keys, block, rng):
    """A permutation of range(len(keys)) in which every run of ``block``
    consecutive items holds one item of each of ``block`` strata of the
    sorted keys: any stretch of a run then sees nearly the whole
    distribution.  Which item of a stratum, and the order inside a
    group, come from ``rng``."""
    n = len(keys)
    block = max(1, min(block, n))
    ranked = sorted(range(n), key=lambda i: (keys[i], i))
    strata = [[] for _ in range(block)]
    for rank, i in enumerate(ranked):
        strata[rank * block // n].append(i)
    for s in strata:
        rng.shuffle(s)
    out = []
    for g in range(max(len(s) for s in strata)):
        group = [s[g] for s in strata if g < len(s)]
        rng.shuffle(group)
        out.extend(group)
    return out


def _ordered(mix, n, rng, residual=False):
    pairs = length_pairs(mix, n)
    if residual:
        pairs = _residual(pairs)
    order = stratified_order([p for p, _ in pairs], mix["stratum_block"],
                             rng)
    return [pairs[i] for i in order]


def _residual(pairs):
    """Cut each output to a fraction (k + 0.5)/n of itself: requests
    caught mid-life, as a steady state holds them.  Which request gets
    which fraction is fixed like the pairing: it is work."""
    n = len(pairs)
    frac = (np.random.default_rng([PAIRING_SEED, 1]).permutation(n) + 0.5) / n
    return [(p, max(1, int(round(o * f))))
            for (p, o), f in zip(pairs, frac)]


def rng_for(seed, stream):
    """Independent generators from one ``--seed`` of any size."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFF,
                                  int(seed) >> 32, stream])


def open_loop(mix, seed, seconds):
    """Open loop at ``rate_rps``: (warm requests due at the start of the
    warm-up, arrivals).  Arrivals are a Poisson process conditioned on
    its count: round(rate x span) sorted uniform draws, made apart for
    the warm-up span and for the window, so that every window is
    offered the same requests in another order at other instants."""
    rate, warm_s = mix["rate_rps"], mix["warm_seconds"]
    rng = rng_for(seed, 1)
    n_held = int(round(rate * mix["mean_lifetime_s"]))
    held = _ordered(mix, n_held, rng, residual=True)
    reqs = [Request(i, p, o, -warm_s) for i, (p, o) in enumerate(held)]
    for n, lo, hi in ((int(round(rate * warm_s)), -warm_s, 0.0),
                      (int(round(rate * seconds)), 0.0, float(seconds))):
        due = np.sort(rng.uniform(lo, hi, n))
        for (p, o), t in zip(_ordered(mix, n, rng), due):
            reqs.append(Request(len(reqs), p, o, float(t)))
    return reqs


def backlog(mix, seed, slots):
    """A standing backlog: an endless stream of requests, the runner
    keeping ``slots`` of them queued.  The first ``slots`` are cut to
    their residual life so that the slots do not all turn over together;
    after them the mix's ``grid`` lengths cycle in a stratified order."""
    rng = rng_for(seed, 2)
    first = _ordered(mix, slots, rng, residual=True)
    cycle = _ordered(mix, mix["grid"], rng)

    def stream():
        i = 0
        for p, o in first:
            yield Request(i, p, o, 0.0)
            i += 1
        while True:
            for p, o in cycle:
                yield Request(i, p, o, 0.0)
                i += 1

    return stream()


def prompt_tokens(seed, index, length, vocab):
    """The prompt of request ``index``: distinct from every other, so
    that nothing is shared unless a mix says so."""
    return rng_for(seed, 1000 + index).integers(
        0, vocab, length, dtype=np.int64).tolist()


def corpus(seed, sequences, length, vocab):
    """A small seeded corpus of [sequences, length] tokens, cycled by the
    training runner so that the loss truly falls."""
    return rng_for(seed, 3).integers(0, vocab, (sequences, length),
                                     dtype=np.int32)

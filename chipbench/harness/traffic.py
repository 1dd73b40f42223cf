"""The one traffic generator. A cell's ``traffic`` block is data:

``arrivals``       ``{"law": "exponential_gaps", "rate_per_s": r}``: an
                   open loop whose gaps between requests are the
                   exponential law's quantiles, in the seed's order
``prompt_tokens``  ``{"law": "lognormal", "median": m, "sigma": s,
                   "min": a, "max": b}``: the clipped law's quantiles,
                   in the seed's order
``output_tokens``  the same
``ramp_s``         arrivals start this long before the window opens

This is not a Poisson process drawn afresh for every seed, and is named
for what it is. The ramp and the window each get a set of their own:
``round(rate * seconds)`` gaps at the exponential's quantiles
``(i + 0.5) / n``, scaled to sum to the part's length, and as many
prompt and answer lengths at their laws' quantiles. Every seed gets the
same sets; it permutes each of them on its own (and draws the token
ids). So the window holds the same requests under every seed, and what
differs is which gaps and lengths fall beside which: short gaps in a
row, long answers in a row and a long prompt in a burst all occur, as
in a drawn process; only the window's sum of work is pinned, so that a
rate or a tail read from two seeds differs by what the system did and
not by how much work a seed drew.
"""

from __future__ import annotations

import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def _quantiles(law, n):
    kind = law["law"]
    if kind != "lognormal":
        raise ValueError(f"unknown length law {kind!r}")
    z = np.array([_NORMAL.inv_cdf((i + 0.5) / n) for i in range(n)])
    vals = float(law["median"]) * np.exp(float(law["sigma"]) * z)
    return np.clip(vals, law.get("min", -math.inf), law.get("max", math.inf))


def lengths(law, n, rng):
    """``n`` whole lengths: the law's quantile set, in the seed's order."""
    vals = np.maximum(1, np.rint(_quantiles(law, n))).astype(np.int64)
    return vals[rng.permutation(n)]


def arrival_times(law, seconds, rng):
    """Times at which the requests of a part of ``seconds`` are due,
    from its start; the last is due as it ends."""
    kind = law["law"]
    if kind != "exponential_gaps":
        raise ValueError(f"unknown arrival law {kind!r}")
    rate = float(law["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps *= seconds / gaps.sum()
    return np.cumsum(gaps[rng.permutation(n)])


def make_requests(traffic, vocab, seconds, seed):
    """[(due_s, prompt ids, output tokens, in_window)] for a ramp and a
    window of ``seconds``; ``due_s`` counts from the start of the ramp."""
    rng = np.random.RandomState(seed % (2 ** 32))
    ramp = float(traffic.get("ramp_s", 0.0))
    out = []
    for start, length, in_window in ((0.0, ramp, False),
                                     (ramp, float(seconds), True)):
        if length <= 0:
            continue
        due = start + arrival_times(traffic["arrivals"], length, rng)
        p_len = lengths(traffic["prompt_tokens"], len(due), rng)
        o_len = lengths(traffic["output_tokens"], len(due), rng)
        out += [(float(due[i]),
                 rng.randint(0, vocab, int(p_len[i])).astype(np.int32),
                 int(o_len[i]), in_window) for i in range(len(due))]
    return out


def percentile(values, pct):
    """The ``pct``-th percentile by linear interpolation (numpy's
    default); all of ``values`` count."""
    return float(np.percentile(np.asarray(values, np.float64), pct))

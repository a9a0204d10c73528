"""Arithmetic of the end-to-end metrics, kept apart from the drivers so
that it can be checked on its own."""
from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """q-th percentile (0..100) with linear interpolation between the two
    nearest ranks, numpy's default rule.  Values may hold math.inf for a
    request that never answered: it ranks above every served one, and a
    percentile that interpolates onto it is inf."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    frac = pos - lo
    if frac == 0.0 or xs[lo] == xs[hi]:
        return xs[lo]
    if math.isinf(xs[hi]):
        return math.inf
    return xs[lo] + (xs[hi] - xs[lo]) * frac


def ttft_values_ms(requests) -> list:
    """Time to first token of each request due in the window, from its
    due time; inf where no first token ever came."""
    out = []
    for r in requests:
        out.append(math.inf if r.t_first is None
                   else (r.t_first - r.t_due) * 1e3)
    return out


def tpot_ms(r) -> float | None:
    """Mean time per output token after the first:
    (last token - first token) / (tokens - 1).  None below two tokens or
    for an unfinished request.  Tokens land a decode chunk at a time, so
    per-token gaps would read 0 or a whole chunk; the mean over the
    request is what its reader sees."""
    if r.t_done is None or r.n_tokens < 2:
        return None
    return (r.t_last - r.t_first) * 1e3 / (r.n_tokens - 1)


def rate(count: float, seconds: float) -> float:
    if not seconds > 0:
        raise ValueError("rate over an empty window")
    return count / seconds


def spread(values) -> float:
    """Interquartile distance over the median, with Python's
    statistics.quantiles(n=4) quartiles."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med

"""One general traffic generator, driven by the mix's data file
(`traffic/<mix>.json`).

Every seed gets the same requests, due at the same instants: each
distribution is sampled at the fixed quantile points (i + 1/2) / n, and
sizes and gaps are paired and ordered by fixed permutations.  The seed
draws only the token ids (and the weights).  So every seed does the same
work on the same schedule, and the spread between runs is the system's,
not the generator's.  Near the knee the order in which long prompts
arrive moves the time-to-first-token tail by itself: with the order
drawn from the seed, four seeds read a 75th percentile of 1.07 to 2.23 s
at 0.85 req/s on a TPU v5e.

A mix names its driver; there is one:
  open_loop    requests due at Poisson instants at `rate_per_s`; the
               first block of `rate_per_s * seconds` requests is the
               measured one, a tail block of `rate_per_s * tail_s` keeps
               the load on while they finish.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


def seed32(seed: int, stream: int = 0) -> int:
    """A 32-bit seed derived from any whole number, 2**31 and above too."""
    return int(np.random.SeedSequence([seed, stream]).generate_state(1)[0])


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, stream]))


def quantile_sizes(dist: dict, n: int) -> np.ndarray:
    """n integer sizes at the quantile points (i + 1/2)/n of `dist`:
    {"dist": "lognormal", "median", "sigma", "min", "max"} or
    {"dist": "uniform", "min", "max"} (inclusive bounds)."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.asarray([NormalDist().inv_cdf(x) for x in u])
        v = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    elif kind == "uniform":
        v = dist["min"] + u * (dist["max"] - dist["min"] + 1)
    else:
        raise ValueError(f"unknown size distribution {kind!r}")
    return np.clip(np.floor(v), dist["min"], dist["max"]).astype(np.int64)


def exp_gaps(rate_per_s: float, n: int) -> np.ndarray:
    """n inter-arrival gaps of a Poisson process at the quantile points."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate_per_s


@dataclasses.dataclass
class Req:
    """One generated request.  `t_due` is seconds after the window
    opens."""
    idx: int
    prompt: np.ndarray
    max_new: int
    t_due: float = 0.0
    measured: bool = True


def _tokens(rng, vocab: int, n: int) -> np.ndarray:
    return rng.integers(0, vocab, n, dtype=np.int64).astype(np.int32)


def _fixed(n: int, stream: int) -> np.ndarray:
    """A permutation of n that is the same for every seed."""
    return rng_for(0, stream).permutation(n)


def _independent(mix: dict, n: int, rng, vocab: int, idx0: int) -> list:
    plens = quantile_sizes(mix["prompt"], n)
    outs = quantile_sizes(mix["output"], n)[_fixed(n, 99)]
    order = _fixed(n, 98)
    return [Req(idx0 + i, _tokens(rng, vocab, int(plens[j])), int(outs[j]))
            for i, j in enumerate(order)]


def lm_schedule(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """The request list of a language-model mix for one run."""
    if mix["driver"] != "open_loop":
        raise ValueError(f"driver {mix['driver']!r} makes no request list")
    rng = rng_for(seed, 1)
    rate = mix["rate_per_s"]
    n_win = max(1, round(rate * seconds))
    n_tail = max(1, round(rate * mix["tail_s"]))
    reqs = (_independent(mix, n_win, rng, vocab, 0)
            + _independent(mix, n_tail, rng, vocab, n_win))
    gaps = np.concatenate([exp_gaps(rate, n_win)[_fixed(n_win, 97)],
                           exp_gaps(rate, n_tail)[_fixed(n_tail, 96)]])
    t = np.cumsum(gaps)
    for i, r in enumerate(reqs):
        r.t_due = float(t[i])
        r.measured = i < n_win
    return reqs

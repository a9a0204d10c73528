"""Arithmetic of the end-to-end metrics."""
import math
import types

import numpy as np
import pytest

import stats


@pytest.mark.parametrize("q", [0, 25, 50, 90, 95, 99, 100])
def test_percentile_matches_numpy_linear(q):
    v = list(np.random.default_rng(3).exponential(size=37))
    assert stats.percentile(v, q) == pytest.approx(np.percentile(v, q))


def test_censored_requests_rank_above_served():
    served = [100.0 + i for i in range(19)]          # 19 served
    assert stats.percentile(served + [math.inf], 95) == math.inf
    # 40 requests, one never answered: p95 interpolates below it
    vals = [float(i) for i in range(39)] + [math.inf]
    assert stats.percentile(vals, 95) == pytest.approx(
        np.percentile(np.arange(40.0), 95))
    assert stats.percentile(vals, 100) == math.inf


def _rec(t_due, t_first, t_last, t_done, n):
    return types.SimpleNamespace(t_due=t_due, t_first=t_first,
                                 t_last=t_last, t_done=t_done, n_tokens=n)


def test_ttft_and_tpot():
    r = _rec(10.0, 10.25, 11.25, 11.25, 101)
    assert stats.ttft_values_ms([r]) == [pytest.approx(250.0)]
    assert stats.tpot_ms(r) == pytest.approx(10.0)   # 1 s over 100 gaps
    assert stats.tpot_ms(_rec(0, 1, 1, 1, 1)) is None      # one token
    assert stats.tpot_ms(_rec(0, 1, None, None, 0)) is None  # unfinished
    assert stats.ttft_values_ms([_rec(0, None, None, None, 0)]) == [math.inf]


def test_rate_and_spread():
    assert stats.rate(300, 30.0) == 10.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)
    v = [10.0, 11.0, 12.0, 13.0, 14.0, 15.0]
    import statistics
    q1, med, q3 = statistics.quantiles(v, n=4)
    assert stats.spread(v) == pytest.approx((q3 - q1) / med)

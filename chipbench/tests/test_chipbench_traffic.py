"""The traffic generator: seeded, the same sizes for every seed."""
import json
import os

import numpy as np
import pytest

import schedule

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traffic")
LM_MIXES = ["prefill-mix"]
BIG_SEED = 2**33 + 12345


def _mix(name):
    with open(os.path.join(TRAFFIC, name + ".json")) as f:
        return json.load(f)


def _sig(reqs):
    return [(r.idx, r.prompt.tobytes(), r.max_new, round(r.t_due, 12),
             r.measured) for r in reqs]


@pytest.mark.parametrize("mix", LM_MIXES)
def test_same_seed_same_requests(mix):
    a = schedule.lm_schedule(_mix(mix), BIG_SEED, 20, 151936)
    b = schedule.lm_schedule(_mix(mix), BIG_SEED, 20, 151936)
    assert _sig(a) == _sig(b)
    c = schedule.lm_schedule(_mix(mix), BIG_SEED + 1, 20, 151936)
    assert _sig(a) != _sig(c)


@pytest.mark.parametrize("mix", LM_MIXES)
def test_every_seed_gets_the_same_sizes(mix):
    m = _mix(mix)
    runs = [schedule.lm_schedule(m, s, 20, 151936) for s in (1, 2, BIG_SEED)]
    for part in (lambda r: r.measured, lambda r: not r.measured):
        sets = [sorted((len(r.prompt), r.max_new) for r in rs if part(r))
                for rs in runs]
        assert sets[0] == sets[1] == sets[2]
    spans = [rs[-1].t_due for rs in runs]
    assert max(spans) - min(spans) < 1e-6
    n_win = round(m["rate_per_s"] * 20)
    assert all(sum(r.measured for r in rs) == n_win for rs in runs)


@pytest.mark.parametrize("mix", LM_MIXES)
def test_sizes_within_the_mix(mix):
    m = _mix(mix)
    reqs = schedule.lm_schedule(m, 7, 30, 151936)
    out = [r.max_new for r in reqs]
    assert m["output"]["min"] <= min(out) and max(out) <= m["output"]["max"]
    for r in reqs:
        assert r.prompt.dtype == np.int32
        assert 0 <= r.prompt.min() and r.prompt.max() < 151936
    lens = [len(r.prompt) for r in reqs]
    assert m["prompt"]["min"] <= min(lens)
    assert max(lens) <= m["prompt"]["max"]


def test_quantile_sizes_and_gaps():
    d = {"dist": "lognormal", "median": 512, "sigma": 0.8, "min": 64,
         "max": 2048}
    v = schedule.quantile_sizes(d, 1001)
    assert v[500] in (511, 512)
    assert v.min() >= 64 and v.max() <= 2048
    u = schedule.quantile_sizes({"dist": "uniform", "min": 64, "max": 256}, 193)
    assert u.min() == 64 and u.max() == 256
    g = schedule.exp_gaps(4.0, 4000)
    assert g.mean() == pytest.approx(0.25, rel=0.01)


def test_seed32_takes_large_seeds():
    a, b = schedule.seed32(2**40 + 1), schedule.seed32(2**40 + 2)
    assert 0 <= a < 2**32 and a != b

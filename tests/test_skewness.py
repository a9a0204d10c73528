"""Skewness losses (Eq. 1/2) — unit + hypothesis property tests."""
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st
from hypothesis.extra import numpy as hnp

from repro.core.skewness import (
    achieved_skewness,
    combined_loss,
    descent_loss,
    disorder_loss,
    disorder_rate,
    natural_skewness,
    skewness_loss,
    topk_mass,
)


def _norm(a):
    a = np.abs(a) + 1e-6
    return a / a.sum(-1, keepdims=True)


def test_disorder_loss_zero_when_ordered():
    imp = jnp.asarray([[0.5, 0.3, 0.1, 0.06, 0.04]])
    assert float(disorder_loss(imp, k=2)) == 0.0


def test_disorder_loss_positive_when_violated():
    imp = jnp.asarray([[0.1, 0.3, 0.5, 0.06, 0.04]])
    assert float(disorder_loss(imp, k=2)) > 0.0


def test_skewness_loss_zero_when_met():
    imp = jnp.asarray([[0.6, 0.3, 0.05, 0.05]])
    assert float(skewness_loss(imp, k=2, rho=0.8)) == 0.0


def test_skewness_loss_measures_deficit():
    imp = jnp.asarray([[0.3, 0.3, 0.2, 0.2]])
    np.testing.assert_allclose(float(skewness_loss(imp, k=2, rho=0.8)), 0.2,
                               atol=1e-6)


@given(hnp.arrays(np.float64, (4, 8), elements=st.floats(0.01, 10)))
@settings(max_examples=50, deadline=None)
def test_disorder_loss_nonnegative_and_bounded(raw):
    imp = jnp.asarray(_norm(raw))
    v = float(disorder_loss(imp, k=3))
    assert 0.0 <= v <= 1.0


@given(hnp.arrays(np.float64, (4, 8), elements=st.floats(0.01, 10)),
       st.integers(1, 7), st.floats(0.1, 1.0))
@settings(max_examples=50, deadline=None)
def test_skewness_loss_bounds(raw, k, rho):
    imp = jnp.asarray(_norm(raw))
    v = float(skewness_loss(imp, k=k, rho=rho))
    assert 0.0 <= v <= rho + 1e-9
    # loss + achieved mass >= rho (per-sample identity averaged)
    mass = float(jnp.mean(topk_mass(imp, k)))
    assert v >= rho - mass - 1e-6


@given(hnp.arrays(np.float64, (4, 6), elements=st.floats(0.01, 10)))
@settings(max_examples=30, deadline=None)
def test_descent_loss_zero_iff_sorted(raw):
    imp = jnp.asarray(np.sort(_norm(raw))[:, ::-1].copy())
    assert float(descent_loss(imp)) < 1e-12


def test_combined_loss_lambda_mixing():
    imp = jnp.asarray([[0.3, 0.3, 0.2, 0.2]])
    pred = jnp.asarray(2.0)
    total, m = combined_loss(pred, imp, k=2, rho=0.8, lam=0.3)
    expected = 0.3 * 2.0 + 0.7 * (m["loss_skewness"] + m["loss_disorder"])
    np.testing.assert_allclose(float(total), float(expected), rtol=1e-6)


def test_metrics():
    imp = jnp.asarray([[0.5, 0.3, 0.1, 0.1], [0.1, 0.2, 0.4, 0.3]])
    assert float(achieved_skewness(imp, 2)) == np.float32(0.8 + 0.3) / 2
    assert float(disorder_rate(imp, 2)) == 0.5
    ns = natural_skewness(imp, frac=0.5)
    np.testing.assert_allclose(np.asarray(ns), [0.8, 0.7], rtol=1e-6)

"""Grouped MoE dispatch vs the per-expert-loop oracle + properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.nn.moe import _pick_group_size, moe_apply, moe_init, moe_reference

KEY = jax.random.PRNGKey(1)


@pytest.mark.parametrize("B,T,gs,topk", [(2, 8, 4096, 2), (2, 8, 4, 2),
                                         (3, 7, 4096, 1), (1, 16, 8, 3)])
def test_moe_matches_reference_dropless(B, T, gs, topk):
    p = moe_init(KEY, 16, 32, 4)
    x = jax.random.normal(KEY, (B, T, 16))
    y, aux = moe_apply(p, x, top_k=topk, capacity_factor=8.0, group_size=gs)
    yr = moe_reference(p, x, top_k=topk)
    np.testing.assert_allclose(y, yr, atol=1e-4, rtol=1e-4)
    assert float(aux["dropped_fraction"]) == 0.0


def test_moe_capacity_drops_tokens():
    p = moe_init(KEY, 16, 32, 4)
    x = jax.random.normal(KEY, (4, 16, 16))
    _, aux = moe_apply(p, x, top_k=2, capacity_factor=0.5)
    assert float(aux["dropped_fraction"]) > 0.0


def test_moe_load_balance_loss_bounds():
    """E * sum(f * p) >= 1 with equality at perfect balance."""
    p = moe_init(KEY, 16, 32, 4)
    x = jax.random.normal(KEY, (4, 16, 16))
    _, aux = moe_apply(p, x, top_k=2, capacity_factor=8.0)
    assert float(aux["load_balance_loss"]) >= 0.99


@given(n=st.integers(1, 4096), target=st.sampled_from([256, 1024, 4096]))
@settings(max_examples=50, deadline=None)
def test_pick_group_size_divides(n, target):
    s = _pick_group_size(n, target)
    assert n % s == 0
    assert s <= max(target, n)


def test_moe_grad_flows_to_all_parts():
    p = moe_init(KEY, 8, 16, 4)
    x = jax.random.normal(KEY, (2, 8, 8))

    def loss(pp):
        y, aux = moe_apply(pp, x, top_k=2, capacity_factor=8.0)
        return jnp.sum(y ** 2) + aux["load_balance_loss"]

    g = jax.grad(loss)(p)
    for name in ("router", "gate", "up", "down"):
        leaf = g[name]["w"] if isinstance(g[name], dict) else g[name]
        assert float(jnp.abs(leaf).max()) > 0.0, name

"""Hypothesis property tests over system invariants."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.nn.attention import flash_attention, reference_attention
from repro.core.xai import channel_importance
from repro.compress.lzw import (
    lzw_decode,
    lzw_encode,
    pack_indices,
    pack_indices_batch,
)
from repro.compress.quantize import dequantize, hard_indices, quantizer_init

KEY = jax.random.PRNGKey(11)


@given(T=st.integers(4, 24), Hkv=st.sampled_from([1, 2]),
       G=st.sampled_from([1, 2, 3]), D=st.sampled_from([4, 8]),
       qb=st.sampled_from([4, 8]), kb=st.sampled_from([4, 8]))
@settings(max_examples=20, deadline=None)
def test_flash_attention_blocking_invariance(T, Hkv, G, D, qb, kb):
    """Output must not depend on the block decomposition."""
    Hq = Hkv * G
    ks = jax.random.split(jax.random.PRNGKey(T * 131 + Hq), 3)
    q = jax.random.normal(ks[0], (1, T, Hq, D))
    k = jax.random.normal(ks[1], (1, T, Hkv, D))
    v = jax.random.normal(ks[2], (1, T, Hkv, D))
    a = flash_attention(q, k, v, q_block=qb, kv_block=kb)
    b = reference_attention(q, k, v)
    np.testing.assert_allclose(a, b, atol=3e-5, rtol=3e-5)


@given(T=st.integers(2, 16))
@settings(max_examples=15, deadline=None)
def test_attention_rows_are_convex_combinations(T):
    """Causal attention output at position t lies in the convex hull of
    v[:t+1] — per-dim bounds check."""
    ks = jax.random.split(jax.random.PRNGKey(T), 3)
    q = jax.random.normal(ks[0], (1, T, 2, 4))
    k = jax.random.normal(ks[1], (1, T, 2, 4))
    v = jax.random.normal(ks[2], (1, T, 2, 4))
    out = reference_attention(q, k, v, causal=True)   # Hq == Hkv (G=1)
    for t in range(T):
        lo = jnp.min(v[:, :t + 1], axis=1)    # (1, H, D)
        hi = jnp.max(v[:, :t + 1], axis=1)
        o = out[:, t]                          # (1, H, D)
        assert bool(jnp.all(o >= lo - 1e-4))
        assert bool(jnp.all(o <= hi + 1e-4))


@given(st.integers(2, 64))
@settings(max_examples=20, deadline=None)
def test_channel_importance_is_distribution(C):
    x = jax.random.uniform(jax.random.PRNGKey(C), (3, 5, 5, C)) + 1e-3
    imp = channel_importance(x)
    np.testing.assert_allclose(np.asarray(jnp.sum(imp, -1)), 1.0, rtol=1e-5)
    assert bool(jnp.all(imp >= 0))


@given(st.one_of(
    st.binary(max_size=1024),
    # low-entropy payloads (the quantized-index regime LZW targets)
    st.lists(st.integers(0, 3), max_size=2048).map(bytes)))
@settings(max_examples=40, deadline=None)
def test_lzw_round_trip(data):
    """decode(encode(x)) == x for arbitrary and low-entropy byte strings."""
    assert lzw_decode(lzw_encode(data)) == data


@given(B=st.integers(1, 9), n=st.integers(1, 80),
       bits=st.sampled_from([1, 2, 3, 4, 5, 8]),
       seed=st.integers(0, 2 ** 16))
@settings(max_examples=40, deadline=None)
def test_pack_indices_batch_matches_per_sample(B, n, bits, seed):
    """The vectorized batch packer is byte-identical to packing each
    sample alone, across ragged batch/row sizes and every bit width."""
    idx = np.random.RandomState(seed).randint(0, 2 ** bits, size=(B, n))
    got = pack_indices_batch(idx, bits)
    assert len(got) == B
    for b in range(B):
        assert got[b] == pack_indices(idx[b], bits)


@given(st.lists(st.floats(-10, 10), min_size=1, max_size=64),
       st.sampled_from([2, 4, 8, 16]))
@settings(max_examples=30, deadline=None)
def test_quantizer_idempotent(vals, L):
    """Quantizing a dequantized value is a fixed point."""
    q = quantizer_init(L, -4, 4)
    x = jnp.asarray(vals, jnp.float32)
    once = dequantize(q, hard_indices(q, x))
    twice = dequantize(q, hard_indices(q, once))
    np.testing.assert_allclose(once, twice)

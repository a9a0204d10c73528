"""LZW codec, bit packing, learned quantizer: unit + hypothesis."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.compress.lzw import (
    compress_payload,
    lzw_decode,
    lzw_encode,
    lzw_encoded_bytes,
    pack_indices,
)
from repro.compress.quantize import (
    dequantize,
    hard_indices,
    quantization_bits,
    quantize_ste,
    quantizer_init,
    soft_quantize,
)


@given(st.binary(min_size=0, max_size=2000))
@settings(max_examples=60, deadline=None)
def test_lzw_roundtrip(data):
    assert lzw_decode(lzw_encode(data)) == data


def test_lzw_compresses_repetitive_data():
    data = b"abab" * 500
    nbytes, _ = compress_payload(data)
    assert nbytes < len(data) / 4


def test_lzw_encoded_bytes_nonzero():
    assert lzw_encoded_bytes(lzw_encode(b"hello world")) > 0
    assert lzw_encoded_bytes([]) == 0


@pytest.mark.parametrize("bits", [1, 2, 3, 4, 8])
def test_pack_indices_size(bits):
    idx = np.random.RandomState(0).randint(0, 2 ** bits, size=257)
    packed = pack_indices(idx, bits)
    assert len(packed) == -(-257 * bits // 8)


def test_quantizer_roundtrip_on_centers():
    q = quantizer_init(8, -4, 4)
    x = q["centers"]
    idx = hard_indices(q, x)
    np.testing.assert_array_equal(np.asarray(idx), np.arange(8))
    np.testing.assert_allclose(dequantize(q, idx), x)


def test_soft_quantize_approaches_hard_at_low_temp():
    q = quantizer_init(8, -4, 4)
    x = jnp.asarray([0.3, -1.2, 2.7])
    soft = soft_quantize(q, x, temperature=1e-4)
    hard = dequantize(q, hard_indices(q, x))
    np.testing.assert_allclose(soft, hard, atol=1e-3)


def test_quantize_ste_gradient_passthrough():
    import jax
    q = quantizer_init(8, -4, 4)
    x = jnp.asarray([0.3, -1.2, 2.7])
    g = jax.grad(lambda xx: jnp.sum(quantize_ste(q, xx)))(x)
    # straight-through: gradient flows (soft path), not zero
    assert float(jnp.abs(g).min()) > 0.0


def test_quantization_bits():
    assert quantization_bits(8) == 3
    assert quantization_bits(16) == 4
    assert quantization_bits(2) == 1

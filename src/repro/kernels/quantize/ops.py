"""Jit'd wrapper: quantize an arbitrary-shape feature tensor."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.quantize.kernel import quantize_tpu


@partial(jax.jit, static_argnames=("interpret",))
def quantize_op(x, centers, *, interpret: bool):
    """x: any shape; centers: (L,).  Returns (indices, dequantized)."""
    shape = x.shape
    n = x.size
    # pack into (rows, 128) lanes with padding; row padding to the tile
    # grid is handled inside the kernel
    w = 128
    rows = -(-n // w)
    flat = jnp.zeros((rows * w,), x.dtype).at[:n].set(x.reshape(-1))
    x2 = flat.reshape(rows, w)
    idx2, deq2 = quantize_tpu(x2, centers, interpret=interpret)
    idx = idx2.reshape(-1)[:n].reshape(shape)
    deq = deq2.reshape(-1)[:n].reshape(shape)
    return idx, deq

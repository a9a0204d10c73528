"""Jit'd wrapper for the fused RMSNorm kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.rmsnorm.kernel import rmsnorm_tpu


@partial(jax.jit, static_argnames=("eps", "interpret"))
def rmsnorm_op(x, scale, *, eps: float = 1e-6, interpret: bool):
    """x: (..., d)."""
    shape = x.shape
    d = shape[-1]
    n = x.size // d
    n_p = -(-n // 8) * 8
    x2 = jnp.zeros((n_p, d), x.dtype).at[:n].set(x.reshape(n, d))
    y = rmsnorm_tpu(x2, scale, eps=eps, block_rows=n_p, interpret=interpret)
    return y[:n].reshape(shape)

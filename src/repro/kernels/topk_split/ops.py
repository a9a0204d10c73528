"""Jit'd wrapper: permute channels and split local/remote."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.topk_split.kernel import channel_permute_tpu


@partial(jax.jit, static_argnames=("perm", "k", "interpret"))
def split_op(x, *, perm: tuple, k: int, interpret: bool):
    """x: (..., C) -> (local (..., k), remote (..., C-k))."""
    shape = x.shape
    C = shape[-1]
    n = x.size // C
    y = channel_permute_tpu(x.reshape(n, C), perm, interpret=interpret)
    y = y.reshape(shape)
    return y[..., :k], y[..., k:]

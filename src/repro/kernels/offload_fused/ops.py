"""Jit'd wrappers for the fused offload pass.

``fused_offload_op`` drives the Pallas kernel; callers say whether it runs
compiled (TPU) or in interpret mode (the CPU correctness harness).
``fused_offload_jnp`` is the jnp fallback: the same one-pass unrolled
nearest-center scan, fused by XLA — unlike the seed two-pass path it
never materializes the (..., C-k, L) distance tensor, so it is the
substrate hot path on non-TPU backends.
``fused_offload`` picks per backend.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.common import nearest_center_scan
from repro.kernels.offload_fused.kernel import offload_fused_tpu


@partial(jax.jit, static_argnames=("perm", "k", "interpret"))
def fused_offload_op(x, centers, *, perm: tuple, k: int, interpret: bool):
    """x: (..., C) -> (local (..., k), remote, indices, dequantized)."""
    shape = x.shape
    C = shape[-1]
    n = x.size // C
    outs = offload_fused_tpu(x.reshape(n, C), centers, perm=perm, k=k,
                             interpret=interpret)
    local, remote, idx, deq = outs
    lead = shape[:-1]
    return (local.reshape(lead + (k,)), remote.reshape(lead + (C - k,)),
            idx.reshape(lead + (C - k,)), deq.reshape(lead + (C - k,)))


@partial(jax.jit, static_argnames=("perm", "k"))
def fused_offload_jnp(x, centers, *, perm: tuple, k: int):
    """jnp fallback: identical outputs, single pass over the features."""
    y = jnp.take(x, jnp.asarray(perm), axis=-1)
    local, remote = y[..., :k], y[..., k:]
    best_i, best_v = nearest_center_scan(remote.astype(jnp.float32),
                                         centers.astype(jnp.float32))
    return local, remote, best_i, best_v.astype(x.dtype)


def fused_offload(x, centers, *, perm: tuple, k: int):
    """Backend dispatch: compiled Pallas on TPU, fused jnp elsewhere."""
    if jax.default_backend() == "tpu":
        return fused_offload_op(x, centers, perm=perm, k=k, interpret=False)
    return fused_offload_jnp(x, centers, perm=perm, k=k)

"""Jit'd public wrapper around the flash-attention Pallas kernel.

Handles layout (B, T, H, D) <-> (B, H, T, D) and padding to block
multiples.  Callers say whether the kernel runs compiled (TPU) or in
interpret mode (CPU validation).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.attention.kernel import flash_attention_tpu


@partial(jax.jit, static_argnames=("causal", "window", "q_block", "kv_block",
                                   "interpret"))
def flash_attention_op(q, k, v, *, causal: bool = True, window: int = 0,
                       q_block: int = 128, kv_block: int = 128,
                       interpret: bool):
    """q: (B, T, Hq, D); k/v: (B, S, Hkv, D) — framework layout."""
    B, T, Hq, D = q.shape
    S = k.shape[1]
    qb = min(q_block, _round_up(T, 8))
    kb = min(kv_block, _round_up(S, 8))
    Tp, Sp = _round_up(T, qb), _round_up(S, kb)
    qt = jnp.pad(q, ((0, 0), (0, Tp - T), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    kt = jnp.pad(k, ((0, 0), (0, Sp - S), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    vt = jnp.pad(v, ((0, 0), (0, Sp - S), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    # padded keys must never win the softmax: rely on causal mask for the
    # padded q rows; mask padded keys via window-independent causal bound
    # (padded k positions > any valid q position when causal). For
    # non-causal use, caller must pass exact multiples.
    out = flash_attention_tpu(qt, kt, vt, causal=causal, window=window,
                              q_block=qb, kv_block=kb, interpret=interpret)
    return out.transpose(0, 2, 1, 3)[:, :T]


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m

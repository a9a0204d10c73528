"""Grouped SwiGLU oracle over expert-sorted, tile-padded rows: one
`jax.lax.ragged_dot` per projection, each group's rows against its own
expert's weights; rows past the groups' total come back zero."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def expert_ffn_ref(x, gate, up, down, group_rows):
    """x: (M, d) rows sorted by expert; gate/up (E, d, ff), down (E, ff, d);
    group_rows (E,) int32 rows of each expert, consecutive from row 0.
    Returns (M, d) float32."""
    f32 = jnp.float32
    g = jax.lax.ragged_dot(x, gate, group_rows, preferred_element_type=f32)
    u = jax.lax.ragged_dot(x, up, group_rows, preferred_element_type=f32)
    h = (g * jax.nn.sigmoid(g) * u).astype(down.dtype)
    return jax.lax.ragged_dot(h, down, group_rows, preferred_element_type=f32)

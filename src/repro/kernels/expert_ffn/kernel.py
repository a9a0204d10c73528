"""Pallas TPU grouped SwiGLU over the experts a device holds.

The serving expert layer (`repro.nn.moe.moe_serve`) lays the rows routed
to this device's experts out sorted by expert, each expert's rows padded
to whole row tiles of `tm`, so a tile belongs to one expert.  The grid
runs one step per *live* tile and d_ff block: the tile's expert comes
from a scalar-prefetch schedule and the grid's length is the traced
count of live tiles, so an expert that no row routed to is never read
from HBM and idle tiles cost nothing.  Each step loads the expert's
gate/up columns and down rows of one d_ff block, so a tile's weights
stream through once while its (tm, d) accumulator stays in VMEM.

The weights are a layer-stacked pool, and the layer is a second
scalar-prefetch operand that leads the weights' index maps: a decode
step that scans its layers hands the kernel every layer's experts and
only the hit experts of that layer leave HBM (a per-layer slice would
copy all of the layer's experts first).

Layout: x (M, d); gate/up (L, E, d, ff); down (L, E, ff, d); out (M, d)
f32.  Rows of tiles past the live count are left unwritten.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ffn_kernel(group_ref, layer_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_ref, *,
                n_blocks: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    g = jax.lax.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
    u = jax.lax.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
    h = (g * jax.nn.sigmoid(g) * u).astype(wd_ref.dtype)
    acc_ref[...] += jax.lax.dot(h, wd_ref[...],
                                preferred_element_type=jnp.float32)

    @pl.when(c == n_blocks - 1)
    def _finish():
        o_ref[...] = acc_ref[...]


def ff_block(d_ff: int) -> int:
    """d_ff columns per grid step: 128 lanes where d_ff splits into them,
    else the whole width (a block must tile by 128 or span its axis)."""
    return 128 if d_ff % 128 == 0 else d_ff


def expert_ffn_tpu(x, gate, up, down, tile_group, n_tiles, layer, *,
                   tm: int, interpret: bool = False):
    """x: (M, d) rows sorted by expert, M a multiple of tm; gate/up
    (L, E, d, ff), down (L, E, ff, d); tile_group (M // tm,) int32 the
    expert of each row tile; n_tiles () int32 live tiles (the leading
    ones); layer () int32 the layer of the weights to use.
    Returns (M, d) float32, rows of the live tiles only."""
    M, d = x.shape
    ff = gate.shape[-1]
    tf = ff_block(ff)
    n_blocks = ff // tf
    kernel = functools.partial(_ffn_kernel, n_blocks=n_blocks)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_tiles, n_blocks),
        in_specs=[
            pl.BlockSpec((tm, d), lambda i, c, grp, lay: (i, 0)),
            pl.BlockSpec((None, None, d, tf),
                         lambda i, c, grp, lay: (lay[0], grp[i], 0, c)),
            pl.BlockSpec((None, None, d, tf),
                         lambda i, c, grp, lay: (lay[0], grp[i], 0, c)),
            pl.BlockSpec((None, None, tf, d),
                         lambda i, c, grp, lay: (lay[0], grp[i], c, 0)),
        ],
        out_specs=pl.BlockSpec((tm, d), lambda i, c, grp, lay: (i, 0)),
        scratch_shapes=[pltpu.VMEM((tm, d), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(tile_group, jnp.reshape(layer, (1,)).astype(jnp.int32), x, gate, up,
      down)

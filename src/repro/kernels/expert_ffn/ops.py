"""Backend dispatch for the held experts' grouped SwiGLU.

``held_experts_ffn`` (prefill) and ``held_experts_ffn_decode`` (the
decode step) run the same computation under two names, so a device
trace tells the decode step's expert time apart from prefill's: the
compiled Pallas kernel on the TPU (`kernel.py`), the `ragged_dot`
oracle elsewhere (`ref.py`).  Both take rows laid out by
`repro.nn.moe.held_layout`.
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.expert_ffn.kernel import expert_ffn_tpu
from repro.kernels.expert_ffn.ref import expert_ffn_ref


def _ffn(x, gate, up, down, tile_group, n_tiles, group_rows, layer, *,
         tm: int, interpret: bool):
    if interpret or jax.default_backend() == "tpu":
        return expert_ffn_tpu(x, gate, up, down, tile_group, n_tiles, layer,
                              tm=tm, interpret=interpret)
    return expert_ffn_ref(x, gate[layer], up[layer], down[layer], group_rows)


@partial(jax.jit, static_argnames=("tm", "interpret"))
def held_experts_ffn(x, gate, up, down, tile_group, n_tiles, group_rows,
                     layer, *, tm: int, interpret: bool = False):
    """Prefill's grouped SwiGLU: (M, d) -> (M, d) float32, with the
    weights of layer `layer` of (L, E, ...) stacks."""
    return _ffn(x, gate, up, down, tile_group, n_tiles, group_rows, layer,
                tm=tm, interpret=interpret)


@partial(jax.jit, static_argnames=("tm", "interpret"))
def held_experts_ffn_decode(x, gate, up, down, tile_group, n_tiles,
                            group_rows, layer, *, tm: int,
                            interpret: bool = False):
    """The decode step's grouped SwiGLU: (M, d) -> (M, d) float32, with
    the weights of layer `layer` of (L, E, ...) stacks."""
    return _ffn(x, gate, up, down, tile_group, n_tiles, group_rows, layer,
                tm=tm, interpret=interpret)

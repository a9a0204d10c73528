"""Production mesh definitions (TPU v5e pod slices).

Single pod: (data=16, model=16) = 256 chips.
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; `pod` is an outer
data-parallel axis (batch shards over ("pod", "data")).

Defined as functions, never module-level constants, so importing this
module never touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_serving_mesh(*, data: int | None = None, model: int = 1):
    """Serving mesh: the decode slot pool shards over ``data``, params go
    tensor-parallel over ``model``.  Defaults to every visible device on
    the data axis — on a single-device host this is the degenerate (1, 1)
    mesh, so the same code path serves laptops and pods."""
    if data is None:
        data = max(1, jax.device_count() // model)
    # Auto axes: the serving programs leave sharding propagation to the
    # compiler from the placed params and pool (Explicit axes, the
    # make_mesh default, would demand an out_sharding on every scatter
    # into the sharded pool)
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def data_axes(mesh) -> tuple[str, ...]:
    """The batch-sharding axes of a mesh from make_production_mesh."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh) -> str:
    return "model"


def axis_size(mesh, names) -> int:
    if isinstance(names, str):
        names = (names,)
    size = 1
    for n in names:
        size *= mesh.shape[n]
    return size

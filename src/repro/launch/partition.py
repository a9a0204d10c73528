"""Name-rule-based parameter partitioning (GSPMD PartitionSpecs).

Rules map parameter-path suffixes to *candidate* tensor axes (counted from
the end, so stacked superblock axes never shift a rule) to shard over the
"model" mesh axis — the first candidate that divides wins (e.g. mixtral's
8 experts don't divide a 16-way model axis, so its expert FFNs fall back
to tensor-parallel over d_ff).

FSDP mode (training): after the model axis is placed, the largest
remaining divisible axis is sharded over the data axes — ZeRO-3-style
weight/grad/optimizer sharding; GSPMD inserts the per-layer all-gather /
reduce-scatter.  Serving paths keep params tensor-parallel only (weights
stay resident; no per-token gathers).
"""
from __future__ import annotations

import re

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import axis_size, data_axes
from repro.nn.module import map_with_path

# (path regex, candidate axes-from-end for the "model" axis)
# order matters: first matching rule wins; first dividing candidate wins.
_RULES: list[tuple[str, tuple[int, ...]]] = [
    (r"embed/table$", (2, 1)),         # (V, d): vocab, else d
    (r"lm_head/w$", (1, 2)),           # (d, V)
    (r"attn/w[qkv]/w$", (1, 2)),       # (d, H*hd): column parallel
    (r"attn/w[qkv]/b$", (1,)),
    (r"attn/wo/w$", (2, 1)),           # (H*hd, d): row parallel
    (r"cross/w[qkv]/w$", (1, 2)),
    (r"cross/wo/w$", (2, 1)),
    (r"ffn/(gate|up)/w$", (1, 2)),
    (r"ffn/down/w$", (2, 1)),
    (r"ffn/fc1/w$", (1, 2)),
    (r"ffn/fc1/b$", (1,)),
    (r"ffn/fc2/w$", (2, 1)),
    (r"(dense_res|shared)/(gate|up)/w$", (1, 2)),
    (r"(dense_res|shared)/down/w$", (2, 1)),
    (r"moe/(gate|up)$", (3, 1, 2)),    # (E, d, ff): experts, else ff, else d
    (r"moe/down$", (3, 2, 1)),         # (E, ff, d): experts, else ff
    (r"mamba/in_proj/w$", (1, 2)),     # (d, 2*di)
    (r"mamba/out_proj/w$", (2, 1)),    # (di, d)
    (r"mamba/conv_w$", (1,)),          # (k, 1, di)
    (r"mamba/conv_b$", (1,)),
    (r"mamba/x_proj/w$", (2,)),        # (di, r+2s): row parallel
    (r"mamba/dt_proj/w$", (1,)),       # (r, di)
    (r"mamba/dt_bias$", (1,)),
    (r"mamba/A_log$", (2,)),           # (di, s)
    (r"mamba/D$", (1,)),
    (r"cell/w[qkv]/w$", (1, 2)),       # mLSTM projections
    (r"cell/out/w$", (2, 1)),
    (r"cell/wx/w$", (1, 2)),           # sLSTM gates (d, 4d)
    (r"cell/wx/b$", (1,)),
    (r"cell/wr/w$", (1, 2)),
    (r"vision_proj/w$", (1, 2)),
]

_FSDP_MIN_ELEMENTS = 1 << 18            # don't bother sharding small tensors


def _spec_for(path: str, shape, model_size: int, *, fsdp_axes=None,
              fsdp_size: int = 1, model_axes=("model",),
              expert_axes=None, expert_size: int = 1) -> P:
    ndim = len(shape)
    spec: list = [None] * ndim
    is_expert = bool(re.search(r"moe/(gate|up|down)$", path))
    if is_expert and expert_axes:
        # 2D resident expert sharding (§Perf H2): expert axis over
        # `expert_axes`, matmul axis over the model axes — no FSDP gathers.
        e_axis = ndim - 3
        if shape[e_axis] % expert_size == 0 and shape[e_axis] >= expert_size:
            spec[e_axis] = expert_axes
        ff_from_end = 1 if re.search(r"moe/(gate|up)$", path) else 2
        ff_axis = ndim - ff_from_end
        if model_size > 1 and shape[ff_axis] % model_size == 0:
            spec[ff_axis] = model_axes if len(model_axes) > 1 else model_axes[0]
        return P(*spec)
    if model_size > 1:
        for pattern, candidates in _RULES:
            if re.search(pattern, path):
                for axis_from_end in candidates:
                    axis = ndim - axis_from_end
                    if 0 <= axis < ndim and shape[axis] % model_size == 0 \
                            and shape[axis] >= model_size:
                        spec[axis] = (model_axes if len(model_axes) > 1
                                      else model_axes[0])
                        break
                break
    if fsdp_axes and _numel(shape) >= _FSDP_MIN_ELEMENTS:
        # largest remaining divisible axis over the data axes
        order = sorted(range(ndim), key=lambda i: -shape[i])
        for i in order:
            if spec[i] is None and shape[i] % fsdp_size == 0 \
                    and shape[i] >= fsdp_size:
                spec[i] = fsdp_axes
                break
    return P(*spec)


def _numel(shape) -> int:
    n = 1
    for s in shape:
        n *= s
    return n


def param_shardings(params_shape, mesh, *, fsdp: bool = False,
                    strategy=None):
    """Tree of NamedShardings aligned with a params pytree (arrays or
    ShapeDtypeStructs).  fsdp=True additionally shards params over the
    data axes (training).  `strategy` (launch.steps.Strategy) overrides
    the model-parallel axes / expert placement (§Perf hillclimbs)."""
    model_axes = ("model",)
    expert_axes = None
    if strategy is not None:
        model_axes = strategy.model_axes
        if strategy.expert_data_sharding:
            expert_axes = data_axes(mesh)
        if strategy.fsdp is not None:
            fsdp = strategy.fsdp
    model_size = 1
    for a in model_axes:
        model_size *= mesh.shape[a]
    daxes = tuple(a for a in mesh.axis_names if a not in model_axes)
    dsize = 1
    for a in daxes:
        dsize *= mesh.shape[a]
    e_size = 1
    if expert_axes:
        for a in expert_axes:
            e_size *= mesh.shape[a]

    def rule(path, leaf):
        return NamedSharding(mesh, _spec_for(
            path, leaf.shape, model_size,
            fsdp_axes=(daxes if (fsdp and daxes) else None), fsdp_size=dsize,
            model_axes=model_axes, expert_axes=expert_axes,
            expert_size=e_size))

    return map_with_path(rule, params_shape)


def pool_shardings(pool, mesh, *, model_axes=("model",)):
    """Shardings for a decode slot pool (`serve.scheduler`): every leaf
    leads with the slot axis, which shards over the data axes, so a slot
    lives wholly on one data shard and host-side evict/inject touches
    exactly that shard's rows.  Cache K/V and latent leaves
    (n_sb, n_layer, S, W, Hkv, hd) carry the slot axis third and
    additionally go model-parallel over kv-heads when divisible, matching
    the tensor-parallel attention params.  Axes that don't divide stay
    replicated, so a 1-device mesh degenerates to the unsharded layout."""
    daxes = data_axes(mesh)
    dsize = axis_size(mesh, daxes)
    msize = axis_size(mesh, model_axes)
    dval = daxes if len(daxes) > 1 else (daxes[0] if daxes else None)
    mval = (model_axes if len(model_axes) > 1
            else (model_axes[0] if model_axes else None))

    def rule(path, leaf):
        shape = leaf.shape
        if re.search(r"(^|/)(k|v|latent)$", path):
            assert len(shape) == 6, (path, shape)
            spec: list = [None] * 6
            if dval is not None and shape[2] % dsize == 0:
                spec[2] = dval
            if mval is not None and msize > 1 and shape[4] % msize == 0:
                spec[4] = mval
            return NamedSharding(mesh, P(*spec))
        spec = [None] * len(shape)
        if dval is not None and shape[0] % dsize == 0:
            spec[0] = dval
        return NamedSharding(mesh, P(*spec))

    return map_with_path(rule, pool)


def batch_spec(mesh, ndim: int, *, batch_axis: int = 0) -> NamedSharding:
    """Shard dim `batch_axis` over the data axes."""
    spec = [None] * ndim
    spec[batch_axis] = data_axes(mesh)
    return NamedSharding(mesh, P(*spec))


def replicated(mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def cache_shardings(cache_shapes, mesh, *, batch: int, strategy=None):
    """Shardings for a decode cache pytree: batch dim over the batch axes
    when divisible; one model-parallel dim chosen by divisibility
    (kv-heads, then sequence/feature)."""
    model_axes = ("model",) if strategy is None else strategy.model_axes
    daxes = tuple(a for a in mesh.axis_names if a not in model_axes)
    dsize = 1
    for a in daxes:
        dsize *= mesh.shape[a]
    msize = 1
    for a in model_axes:
        msize *= mesh.shape[a]
    model_val = (model_axes if len(model_axes) > 1 else
                 (model_axes[0] if model_axes else None))

    def rule(path, leaf):
        shape = leaf.shape
        ndim = len(shape)
        spec = [None] * ndim
        b_idx = None
        for i, s in enumerate(shape):
            if s == batch:
                b_idx = i
                break
        if b_idx is not None and daxes and batch % dsize == 0 and batch >= dsize:
            spec[b_idx] = daxes
        start = (b_idx + 1) if b_idx is not None else 0
        cand = list(range(ndim - 1, start - 1, -1))
        if re.search(r"(^|/)(k|v|ck|cv)$", path) and ndim >= 3:
            cand = [ndim - 2, ndim - 3] + cand  # heads first, then sequence
        if model_val is not None:
            for i in cand:
                if spec[i] is None and shape[i] % msize == 0 and shape[i] >= msize:
                    spec[i] = model_val
                    break
        return NamedSharding(mesh, P(*spec))

    return map_with_path(rule, cache_shapes)

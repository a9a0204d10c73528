"""Decode attention: the one entry the model's layers call.

``decode_attend`` alone decides how a decode step attends its cache.
Where the cache width pages for the compiled kernel (`_tpu_page`: on the
TPU) it runs the Pallas kernel, per shard of the ambient mesh if there is
one (`_kernel`); the kernel writes the step's token into the pool in
place and walks a ragged grid of the live pages only.  Everywhere else it
runs the dense reference (`ref.decode_attention_ref`), after writing the
token with one scatter.  Both paths give the same meaning to a row at
depth 0, which is what `live` makes of the rows that decode nothing: it
is neither attended nor written, and its output is zeros.  The kernel agrees with the
reference within float32 tolerance (its online softmax reorders the
accumulation); the tests hold it to that, not to bit-identity.

``paged_decode_attention_op`` (the GQA pools, K and V) and
``mla_decode_attention_op`` (latent attention: one cached row per token
holds the keys, and its first `dv` entries the values) are the kernel's
jitted ops under the names a device trace shows.  Callers say whether
they run compiled (TPU) or in interpret mode (the CPU correctness
harness), so the interpreter never slips onto the chip by default.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.common import auto_page_size
from repro.kernels.decode_attention.kernel import paged_decode_attention_tpu
from repro.kernels.decode_attention.ref import decode_attention_ref


def decode_attend(q, pool, attend_len, *, new=None, slot=None, layer=None,
                  live=None, scale=None, dv: int = 0):
    """One decode step's attention over a cache or a layer-stacked pool.

    q: (B, 1, Hq, D).  pool: the cache operands, (k, v) each
    (B, S, Hkv, D), or with `dv` (latent attention) (rows,) whose first dv
    entries are the values; with `layer` (one index per leading axis)
    each is a (*L, B, S, Hkv, D) pool of which that layer is attended.
    attend_len: () or (B,) valid slots per row.  new: optional step's
    token of each pool operand, one slot of its rows (B, 1, Hkv, D),
    written at slot (() or (B,)) of each row before it is attended.
    live: optional (B,) bool, the rows that decode a request.  A row
    that is not live, or at attend_len 0, is neither attended nor
    written: its output is zeros.  scale defaults to 1/sqrt(D).

    Returns out (B, 1, Hq, dv or D), and with `new` (out, pool), the
    pool operands updated."""
    if live is not None:
        attend_len = jnp.where(live, attend_len, 0)
    page = _tpu_page(pool[0].shape[-3])
    if page:
        return _kernel(q, pool, attend_len, layer,
                       None if new is None else tuple(n[:, 0] for n in new),
                       slot, page, scale=scale, dv=dv)
    if new is not None:
        pool = tuple(_write(c, n, slot, attend_len, layer)
                     for c, n in zip(pool, new))
    rows = pool if layer is None else tuple(_layer_of(c, layer)
                                            for c in pool)
    out = decode_attention_ref(q, rows[0], None if dv else rows[1],
                               attend_len, scale=scale, dv=dv)
    return out if new is None else (out, pool)


def _tpu_page(S: int) -> int:
    """Page width of the compiled kernel over a cache of width S, 0 where
    it does not run: off the TPU, or where the width does not page.  The
    token axis is the kernel's lane axis, so a page is a multiple of 128
    lanes or the whole width."""
    page = auto_page_size(S)
    if not page or jax.default_backend() != "tpu":
        return 0
    return page if page % 128 == 0 else S


def _layer_of(pool, layer):
    """Layer `layer` (one index per leading axis) of a stacked pool."""
    n = len(layer)
    start = tuple(layer) + (0,) * (pool.ndim - n)
    return jax.lax.dynamic_slice(pool, start, (1,) * n + pool.shape[n:]
                                 ).reshape(pool.shape[n:])


def _write(cache, new, slot, attend_len, layer):
    """The reference step's token write, one scatter: new (B, 1, Hkv, D)
    into slot of each row (of layer `layer`); a row at attend_len 0
    keeps what it held."""
    lead = () if layer is None else tuple(layer)
    B = new.shape[0]
    slot = jnp.where(attend_len > 0, slot, cache.shape[-3])  # -> dropped
    slot = jnp.broadcast_to(slot, (B,))
    return cache.at[(*lead, jnp.arange(B), slot)].set(
        new[:, 0].astype(cache.dtype), mode="drop")


def _pool_op(q, k_cache, v_cache, attend_len, layer, new, slot, *,
             page_size: int, interpret: bool, scale=None, dv: int = 0):
    """The kernel in the framework layout: q (B, 1, Hq, D) -> (B, 1, Hq,
    Dv).  k/v_cache: (B, S, Hkv, D), or with `layer` (an index per leading
    axis) a (*L, B, S, Hkv, D) pool whose layer the kernel reads in
    place; with `dv` v_cache is None and the values are the first dv
    entries of each k row.  new: optional new token of each pool operand,
    (B, Hkv, D) each, written at slot (B,) of each row before it is
    attended; the call then also returns the pool operands, updated in
    place.  A row with attend_len 0 is neither attended nor written: its
    output is zeros."""
    lead = layer is None
    pool = (k_cache,) if dv else (k_cache, v_cache)
    if lead:
        pool, layer = tuple(c[None] for c in pool), (0,)
    B, _, Hq, D = q.shape
    Hkv = pool[0].shape[-2]
    G = Hq // Hkv
    attend = jnp.broadcast_to(jnp.asarray(attend_len, jnp.int32), (B,))
    qg = q[:, 0].reshape(B, Hkv, G, D)
    # token axis minor: the TPU's own HBM order for this cache, so the
    # transpose is a bitcast and the kernel reads pages in place
    tokens_minor = lambda c: jnp.moveaxis(c, -3, -1)
    if new is not None:
        new = tuple(n[..., None] for n in new)          # (B, Hkv, D, 1)
    pool_t = [tokens_minor(c) for c in pool]
    res = paged_decode_attention_tpu(
        qg, pool_t[0], None if dv else pool_t[1], attend,
        jnp.stack(layer).astype(jnp.int32), new, slot, page_size=page_size,
        interpret=interpret, scale=scale, dv=dv)
    out, *pool = (res,) if new is None else res
    # the kernel never visits a row at depth 0: its output is zeros here
    out = jnp.where(attend[:, None, None, None] > 0, out, 0)
    out = out.reshape(B, 1, Hq, dv or D)
    if new is None:
        return out
    back = lambda c: jnp.moveaxis(c, -1, -3)[0] if lead \
        else jnp.moveaxis(c, -1, -3)
    return (out, *map(back, pool))


@partial(jax.jit, static_argnames=("page_size", "interpret"))
def paged_decode_attention_op(q, k_cache, v_cache, attend_len, layer=None,
                              new=None, slot=None, *, page_size: int = 128,
                              interpret: bool):
    """Pallas path in the framework layout: q (B, 1, Hq, D) -> same.

    k/v_cache: (B, S, Hkv, D), or with `layer` (an index per leading
    axis) a (*L, B, S, Hkv, D) pool whose layer the kernel reads in
    place.  new: optional (k_new, v_new), each (B, Hkv, D), written at
    slot (B,) of each row before it is attended; the call then returns
    (out, k_cache, v_cache), the caches updated in place."""
    return _pool_op(q, k_cache, v_cache, attend_len, layer, new, slot,
                    page_size=page_size, interpret=interpret)


@partial(jax.jit, static_argnames=("page_size", "interpret", "scale", "dv"))
def mla_decode_attention_op(q, cache, attend_len, layer=None, new=None,
                            slot=None, *, page_size: int = 128,
                            interpret: bool, scale: float, dv: int):
    """Latent attention's decode step on the same kernel, under its own
    name in a device trace.  q: (B, 1, H, Dl) absorbed queries (the
    latent part folded through W_UK, then the rope part); cache: (B, S,
    1, Dl) latent rows, or a (*L, B, S, 1, Dl) pool with `layer`; the
    values are each row's first dv entries.  new: optional (B, 1, Dl)
    latent row written at slot (B,) first; the call then returns
    (out (B, 1, H, dv), cache)."""
    return _pool_op(q, cache, None, attend_len, layer,
                    None if new is None else (new,), slot,
                    page_size=page_size, interpret=interpret, scale=scale,
                    dv=dv)


def _kernel(q, pool, attend_len, layer, new, slot, page_size, *, scale,
            dv: int):
    """The kernel op of the pool's form, per shard of the ambient mesh if
    there is one (the compiler cannot partition a Mosaic kernel itself).
    Rows split over the data axes and kv heads over "model", the layout
    `launch.partition` gives the slot pool and the attention weights; an
    axis that does not divide stays whole, and so do the pool's layer
    axes.  Query heads split with their kv heads: a contiguous block of
    Hq/m query heads uses exactly the block of Hkv/m kv heads on the same
    device.  Compiled on the TPU; elsewhere, where a test steers the
    choice onto the kernel, interpreted."""
    interpret = jax.default_backend() != "tpu"
    if dv:
        op = lambda q, pool, a, lay, new, slot: mla_decode_attention_op(
            q, *pool, a, lay, None if new is None else new[0], slot,
            page_size=page_size, interpret=interpret, scale=scale, dv=dv)
    else:
        op = lambda q, pool, a, lay, new, slot: paged_decode_attention_op(
            q, *pool, a, lay, new, slot, page_size=page_size,
            interpret=interpret)
    B = q.shape[0]
    if new is not None:
        slot = jnp.broadcast_to(jnp.asarray(slot, jnp.int32), (B,))
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        res = op(q, pool, attend_len, layer, new, slot)
    else:
        res = _per_shard(op, mesh, q, pool, attend_len, layer, new, slot)
    return res if new is None else (res[0], tuple(res[1:]))


def _per_shard(op, mesh, q, pool, attend_len, layer, new, slot):
    """`op` run per shard of `mesh`, in the layout `_kernel` describes."""
    B, Hkv = q.shape[0], pool[0].shape[-2]
    n_lead = pool[0].ndim - 4
    data = tuple(a for a in mesh.axis_names if a != "model")
    dsize = math.prod(mesh.shape[a] for a in data)
    msize = mesh.shape.get("model", 1)
    rows = data if data and B % dsize == 0 else None
    heads = "model" if msize > 1 and Hkv % msize == 0 else None
    spec = P(rows, None, heads, None)
    pool_spec = P(*(None,) * n_lead, rows, None, heads, None)
    attend = jnp.broadcast_to(jnp.asarray(attend_len, jnp.int32), (B,))
    lay = jnp.stack(layer or (0,)).astype(jnp.int32)
    n = len(pool)
    args = [q, *pool, attend, lay]
    in_specs = [spec, *(pool_spec,) * n, P(rows), P()]
    out_specs = spec
    if new is not None:
        args += [*new, slot]
        in_specs += [*(P(rows, heads, None),) * n, P(rows)]
        out_specs = (spec, *(pool_spec,) * n)

    def per_shard(q, *rest):
        pool, (a, lay), append = rest[:n], rest[n:n + 2], rest[n + 2:]
        new, slot = (append[:n], append[n]) if append else (None, None)
        return op(q, pool, a, tuple(lay) if n_lead else None, new, slot)

    return jax.shard_map(per_shard, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=out_specs, check_vma=False)(*args)

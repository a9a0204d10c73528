"""Jit'd public wrappers for paged decode attention.

``paged_decode_attention_op`` drives the Pallas kernel; callers say
whether it runs compiled (TPU) or in interpret mode (the CPU correctness
harness), so the interpreter can never slip onto the chip by default.
``paged_decode_attention_jnp`` is the blocked fallback: a `lax.switch`
over page-aligned prefix widths —
the branch for width W runs the *dense* reference math over k/v[:, :W],
where W is the smallest page multiple covering max(attend_len).  Because
masked tail keys feed exact zeros into every reduction, each branch is
bit-identical to the full-width dense path while doing only W/S of its
work, so swapping it under `nn.attention.decode_attention` cannot change
a single greedy token.  ``paged_decode_attention`` picks per backend and
falls back to the dense reference when the cache width doesn't page.

``mla_decode_attention_op`` runs latent attention's absorbed decode on
the same kernel: one cached row per token holds the keys, and its first
`dv` entries the values.

``paged_decode_append`` is the compiled kernel's write-and-attend step
over a layer-stacked pool (*L, B, S, Hkv, D) with a ``layer`` index
tuple, one index per leading axis: the form a decode step that updates
its KV cache in place hands over.  The kernel writes the step's token
and fetches that layer's pages straight from the pool; off the TPU the
caller writes the token and slices the layer out (`layer_of`), then runs
exactly the math above on a single cache.
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


def layer_of(pool, layer):
    """Layer `layer` (one index per leading axis) of a stacked pool."""
    n = len(layer)
    start = tuple(layer) + (0,) * (pool.ndim - n)
    return jax.lax.dynamic_slice(pool, start, (1,) * n + pool.shape[n:]
                                 ).reshape(pool.shape[n:])


def _width_ladder(S: int, page_size: int) -> tuple[int, ...]:
    """Page-multiple prefix widths that *divide* S.

    Only divisor widths are offered: XLA's CPU dot panelizes the
    contraction axis, and a prefix contraction over W is bit-identical to
    the full-width one (whose tail summands are exact zeros) only when W
    lands on a panel boundary — empirically, when W divides S.  Non-
    divisor widths (e.g. 768 of 1024) reassociate the accumulation and
    drift by ~1 ULP, which the greedy bit-compat contract forbids.
    `test_paged_jnp_bit_identical_to_dense` guards this assumption.
    """
    return tuple(W for W in range(page_size, S + 1, page_size)
                 if S % W == 0)


@partial(jax.jit, static_argnames=("page_size",))
def paged_decode_attention_jnp(q, k_cache, v_cache, attend_len, *,
                               page_size: int = 128):
    """Blocked-jnp paged decode attention, bit-identical to the dense ref.

    q: (B, 1, Hq, D); k/v_cache: (B, S, Hkv, D) with S a page multiple;
    attend_len: () or (B,) valid-slot counts.  Only the pages below the
    smallest ladder width covering max(attend_len) are touched.
    """
    S = k_cache.shape[1]
    assert S % page_size == 0 and S // page_size >= 1, (S, page_size)
    widths = _width_ladder(S, page_size)
    attend_len = jnp.asarray(attend_len)
    branch = jnp.clip(
        jnp.searchsorted(jnp.asarray(widths), jnp.max(attend_len),
                         side="left"),
        0, len(widths) - 1)

    def prefix(W, q, k, v, attend):
        return decode_attention_ref(
            q, jax.lax.slice_in_dim(k, 0, W, axis=1),
            jax.lax.slice_in_dim(v, 0, W, axis=1), attend)

    return jax.lax.switch(branch, [partial(prefix, W) for W in widths],
                          q, k_cache, v_cache, attend_len)


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


def tpu_page(S: int, page_size: int | None = None) -> int:
    """Page width of the compiled kernel over a cache of width S, 0 where
    it does not run: off the TPU, or where the width does not page.  The
    token axis is the kernel's lane axis, so a page is a multiple of 128
    lanes or the whole width."""
    page = page_size or auto_page_size(S)
    if not page or jax.default_backend() != "tpu":
        return 0
    return page if page % 128 == 0 else S


def paged_decode_attention(q, k_cache, v_cache, attend_len, *,
                           page_size: int | None = None):
    """Backend dispatch for the serving decode hot path.

    Caches whose width pages cleanly run the paged path (compiled Pallas
    on TPU, bit-identical blocked jnp elsewhere); anything else takes the
    dense reference, so callers never pay page-padding for tiny caches.
    """
    tpu = tpu_page(k_cache.shape[1], page_size)
    if tpu:
        return _kernel(q, k_cache, v_cache, attend_len, None, None, tpu)
    page = page_size or auto_page_size(k_cache.shape[1])
    if not page or jax.default_backend() != "cpu":
        # the divisor-ladder bit-identity is an XLA *CPU* dot-panelization
        # property; an unverified backend (GPU) gets the dense reference
        # rather than a maybe-ULP-off switch branch
        return decode_attention_ref(q, k_cache, v_cache, attend_len)
    return paged_decode_attention_jnp(q, k_cache, v_cache, attend_len,
                                      page_size=page)


def paged_decode_append(q, k_cache, v_cache, k_new, v_new, slot,
                        attend_len, *, page_size: int, layer=None):
    """The compiled kernel's write-and-attend step, at the page size
    `tpu_page` gives: the step's K/V token k/v_new (B, Hkv, D) goes into
    slot (B,) of each row (of layer `layer` of a pool), in place, and
    that layer is attended over attend_len slots.  Returns
    (out, k_cache, v_cache)."""
    slot = jnp.broadcast_to(jnp.asarray(slot, jnp.int32), q.shape[:1])
    return _kernel(q, k_cache, v_cache, attend_len, layer,
                   ((k_new, v_new), slot), page_size)


def _kernel(q, k_cache, v_cache, attend_len, layer, append, page_size):
    """The compiled kernel, per shard of the ambient mesh if there is one
    (the compiler cannot partition a Mosaic kernel itself).  Rows split
    over the data axes and kv heads over "model", the layout
    `launch.partition` gives the slot pool and the attention weights; an
    axis that does not divide stays whole, and so do the pool's layer
    axes.  Query heads split with their kv heads: a contiguous block of
    Hq/m query heads uses exactly the block of Hkv/m kv heads on the same
    device."""
    op = partial(paged_decode_attention_op, page_size=page_size,
                 interpret=False)
    new, slot = append or (None, None)
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return op(q, k_cache, v_cache, attend_len, layer, new, slot)
    B, Hkv = k_cache.shape[-4], k_cache.shape[-2]
    n_lead = k_cache.ndim - 4
    data = tuple(a for a in mesh.axis_names if a != "model")
    dsize = math.prod(mesh.shape[a] for a in data)
    msize = mesh.shape.get("model", 1)
    rows = data if data and B % dsize == 0 else None
    heads = "model" if msize > 1 and Hkv % msize == 0 else None
    spec = P(rows, None, heads, None)
    pool = P(*(None,) * n_lead, rows, None, heads, None)
    attend = jnp.broadcast_to(jnp.asarray(attend_len, jnp.int32), (B,))
    lay = jnp.stack(layer or (0,)).astype(jnp.int32)
    args = [q, k_cache, v_cache, attend, lay]
    in_specs = [spec, pool, pool, P(rows), P()]
    out_specs = spec
    if new is not None:
        tok = P(rows, heads, None)
        args += [*new, slot]
        in_specs += [tok, tok, P(rows)]
        out_specs = (spec, pool, pool)

    def per_shard(q, k, v, a, lay, *append):
        new = (append[:2], append[2]) if append else ()
        return op(q, k, v, a, tuple(lay) if n_lead else None, *new)

    return jax.shard_map(per_shard, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=out_specs, check_vma=False)(*args)

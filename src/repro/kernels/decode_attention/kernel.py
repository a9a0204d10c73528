"""Pallas TPU paged decode-attention kernel (flash-decoding over KV pages).

One decode step attends a single query token per sequence against a
block-paged KV cache.  The grid is ragged: one step per *live* page of
each row (the pages below its `attend_len`), rows one after another, so
a 2,048-wide cache costs only as many steps and page fetches as its rows'
actual contexts; a row with `attend_len` 0 (a pool slot that decodes
nothing) costs none, and its output is left to the caller.  The schedule
(row and page of each step) and `attend_len` are scalar-prefetch
operands, the grid's length is their traced total (at least one step:
a call whose rows are all idle runs one, which writes back unchanged the
page it read), and the online-softmax accumulators of a row live in VMEM
scratch across its pages.  Lanes past the depth inside a row's last page
are masked.

One grid step loads a whole page for *all* kv heads.  The kernel reads
K/V with the token axis minor, as (Hkv, D, page_size) blocks of a
(..., Hkv, D, S) operand: that is the order in which the TPU keeps a
(..., S, Hkv, D) cache in HBM (its default layout tiles the (D, S) plane,
since Hkv and D are narrower than a tile), so the framework-layout cache
reaches the kernel through a transpose the compiler makes a bitcast, and
a page is one dense (D, page_size) tile run per head, with no lane
padding and no relayout copy of the cache.  GQA is handled in-kernel:
the query tile holds all G = Hq/Hkv group heads of every kv head, so
every K/V page is streamed from HBM exactly once per row.

The K/V operands are a layer-stacked pool, with any number of leading
layer axes, and the layer to attend is a second scalar-prefetch operand
(one index per leading axis): the page index map leads with it, so a
decode step that carries the whole pool through its layer loop hands the
kernel the pool itself and only that layer's live pages leave HBM (no
per-layer slice is ever materialized).  A single cache is a pool of one.

With `append`, the kernel also writes the step's new K/V token of every
row into the pool, in place (the pool operands alias the pool outputs):
the page holding a row's write slot is the one block of that row's pool
outputs, filled from the fetched page with the token's lane replaced
when the grid reaches it, and attended in that form.  A row with
`attend_len` 0 writes nothing.  The TPU's scatter
and per-row dynamic-update-slice would each want the pool in another
layout (a relayout copy of the whole pool per layer) or one op per row;
here the write costs one page per row.

Latent attention (MLA) caches one row per token, shared by every head:
its keys are the whole row and its values the row's first `dv` rows, so
the pool is one operand, Hkv is 1 and the G query heads are all heads.

Layout: q (B, Hkv, G, D); k/v (*L, B, Hkv, D, S); new k/v (B, Hkv, D, 1);
out (B, Hkv, G, Dv).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_decode_kernel(layer_ref, attend_ref, slot_ref, row_ref,
                         page_ref, q_ref, *refs, scale: float,
                         page_size: int, n_pages: int, append: bool,
                         dv: int):
    """Refs (VMEM): q (Hkv, G, D); the pool pages, k and v (Hkv, D,
    page_size) each, or with `dv` one shared page whose first dv rows are
    the values; o (Hkv, G, Dv); with `append` also the new token of each
    pool operand (Hkv, D, 1) in and its pool page (Hkv, D, page_size) out.
    layer_ref (SMEM) is read by the index maps only; slot_ref (SMEM) holds
    each row's write slot, row_ref and page_ref the row and page of each
    grid step.

    Scratch: m/l (Hkv, G, 128) lane-replicated running max / normalizer,
    acc (Hkv, G, Dv) unnormalized output accumulator.
    """
    n_pool = 1 if dv else 2
    pool_refs, refs = refs[:n_pool], refs[n_pool:]
    if append:
        new_refs, refs = refs[:n_pool], refs[n_pool:]
        o_ref, out_refs = refs[0], refs[1:1 + n_pool]
    else:
        o_ref = refs[0]
    m_ref, l_ref, acc_ref = refs[-3:]
    step = pl.program_id(0)
    b = row_ref[step]
    page = page_ref[step]
    attend = attend_ref[b]
    last = _live_pages(attend, page_size, n_pages) - 1
    n_kv = q_ref.shape[0]

    @pl.when(page == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if append:
        slot = slot_ref[b]
        here = page == slot // page_size
        lane = jax.lax.broadcasted_iota(jnp.int32, pool_refs[0].shape[1:], 1)
        fresh = here & (lane == slot % page_size) & (attend > 0)

        @pl.when(here)
        def _append():
            for h in range(n_kv):
                for src, new, out in zip(pool_refs, new_refs, out_refs):
                    out[h] = jnp.where(fresh, new[h], src[h])

    for h in range(n_kv):                               # static unroll
        q = q_ref[h].astype(jnp.float32) * scale        # (G, D)
        pages = [r[h] for r in pool_refs]               # (D, page) each
        if append:
            pages = [jnp.where(fresh, new[h], pg)
                     for new, pg in zip(new_refs, pages)]
        k = pages[0].astype(jnp.float32)
        v = (k[:dv] if dv else pages[1].astype(jnp.float32))
        s = jax.lax.dot_general(q, k, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        col = page * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        mask = col < attend
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[h][:, :1]                        # (G, 1)
        l_prev = l_ref[h][:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.where(mask, jnp.exp(s - m_safe), 0.0)
        corr = jnp.where(m_prev <= NEG_INF / 2, 0.0,
                         jnp.exp(m_prev - m_safe))
        m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
        l_ref[h] = jnp.broadcast_to(
            corr * l_prev + jnp.sum(p, axis=-1, keepdims=True),
            l_ref.shape[1:])
        acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
            p, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(page == last)
    def _finish():
        l = jnp.maximum(l_ref[...][:, :, :1], 1e-20)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def _live_pages(attend, page_size: int, n_pages: int):
    """Pages a row visits: those below its depth (none at depth 0)."""
    return jnp.clip((attend + page_size - 1) // page_size, 0, n_pages)


def paged_decode_attention_tpu(q, k, v, attend_len, layer, new=None,
                               slot=None, *, page_size: int = 128,
                               interpret: bool = False,
                               scale: float | None = None, dv: int = 0):
    """q: (B, Hkv, G, D); k/v: (*L, B, Hkv, D, S) pool, token axis minor,
    or with `dv` (latent attention) v None and the values the first dv
    rows of each k row; attend_len: (B,) int32; layer: (len(L),) int32
    index of the layer to attend.  S must be a multiple of page_size.
    scale defaults to 1/sqrt(D).  Returns (B, Hkv, G, Dv) in q.dtype.

    new: optional new token of each pool operand (k_new, v_new), or
    (k_new,) with `dv`, each (B, Hkv, D, 1), written at slot (B,) int32
    (< attend_len) of each row of that layer before it is attended; the
    call then returns (out, k, v), or (out, k) with `dv`, the pool
    updated in place.

    A row with attend_len 0 is not attended and its token not written:
    its output rows are left unwritten.
    """
    B, Hkv, G, D = q.shape
    n_lead = k.ndim - 4
    S = k.shape[-1]
    assert S % page_size == 0, (S, page_size)
    n_pages = S // page_size
    scale = 1.0 / math.sqrt(D) if scale is None else scale
    Dv = dv or D
    pool = (k,) if dv else (k, v)
    append = new is not None
    attend_len = jnp.asarray(attend_len, jnp.int32)
    if slot is None:
        slot = jnp.zeros((B,), jnp.int32)
    # an idle row's slot is in page 0: where every row is idle, the one
    # step run reads row B-1's page 0 and writes it back as it read it
    slot = jnp.where(attend_len > 0, slot, 0)

    kernel = functools.partial(_paged_decode_kernel, scale=scale,
                               page_size=page_size, n_pages=n_pages,
                               append=append, dv=dv)

    # the ragged schedule: row b's live pages are steps
    # [ends[b] - live[b], ends[b]), none for an idle row; entries past
    # the total are never run (one compare-and-count: a searchsorted is a
    # loop of small ops on the TPU, run for every layer of every step)
    live = _live_pages(attend_len, page_size, n_pages)
    ends = jnp.cumsum(live)
    steps = jnp.arange(B * n_pages, dtype=jnp.int32)
    row_of = jnp.minimum(jnp.sum(ends[None, :] <= steps[:, None], axis=1,
                                 dtype=jnp.int32), B - 1)
    page_of = steps - (ends - live)[row_of]

    def lead(layer):
        return tuple(layer[j] for j in range(n_lead))

    def kv_page(i, layer, attend, slot, row, page):
        return (*lead(layer), row[i], 0, 0, page[i])

    def slot_page(i, layer, attend, slot, row, page):
        # one output block per row, the page of its write slot: written
        # back once, when the grid moves on to the next row
        return (*lead(layer), row[i], 0, 0, slot[row[i]] // page_size)

    row = lambda i, layer, attend, slot, rows, page: (rows[i], 0, 0, 0)
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    page_block = (None,) * (n_lead + 1) + (Hkv, D, page_size)
    in_specs = [vmem((None, Hkv, G, D), row)] + \
        [vmem(page_block, kv_page)] * len(pool)
    out_specs = vmem((None, Hkv, G, Dv), row)
    out_shape = jax.ShapeDtypeStruct((B, Hkv, G, Dv), q.dtype)
    operands = [q, *pool]
    aliases = {}
    if append:
        in_specs += [vmem((None, Hkv, D, 1), row)] * len(pool)
        out_specs = [out_specs] + [vmem(page_block, slot_page)] * len(pool)
        out_shape = [out_shape] + [jax.ShapeDtypeStruct(p.shape, p.dtype)
                                   for p in pool]
        operands += [n.astype(k.dtype) for n in new]
        # each pool operand (after 5 prefetch operands and q) is its output
        aliases = {6 + j: 1 + j for j in range(len(pool))}
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(jnp.maximum(ends[-1], 1),),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, 128), jnp.float32),   # running max
            pltpu.VMEM((Hkv, G, 128), jnp.float32),   # running normalizer
            pltpu.VMEM((Hkv, G, Dv), jnp.float32),    # unnormalized acc
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(n_lead), attend_len,
      jnp.asarray(slot, jnp.int32), row_of, page_of, *operands)

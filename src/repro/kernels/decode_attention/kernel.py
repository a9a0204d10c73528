"""Pallas TPU paged decode-attention kernel (flash-decoding over KV pages).

One decode step attends a single query token per sequence against a
block-paged KV cache.  The grid walks (batch, kv_page); the page axis is
sequential ("arbitrary"), so the online-softmax accumulators for one row
live in VMEM scratch across page visits.  `attend_len` is a
scalar-prefetch operand: pages at or past a row's valid depth are skipped
entirely (the early-exit that makes a 1024-wide cache cost only as much
as the row's actual context), and lanes past the depth inside the last
live page are masked.

One grid step loads a whole page for *all* kv heads: the K/V block is
(page_size, Hkv, D), whose last two dimensions equal the cache's, which
is what the TPU's block-shape rule asks of a dimension under its (8, 128)
tile (a per-head (page_size, 1, D) block over an Hkv > 1 cache is
refused).  GQA is handled in-kernel: the query tile holds all
G = Hq/Hkv group heads of every kv head, so every K/V page is streamed
from HBM exactly once per row.

Layout: q (B, Hkv, G, D); k/v (B, S, Hkv, D); out (B, Hkv, G, D).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_decode_kernel(attend_ref, q_ref, k_ref, v_ref, o_ref,
                         m_ref, l_ref, acc_ref, *,
                         scale: float, page_size: int, n_pages: int):
    """Refs (VMEM): q (Hkv, G, D); k/v (page_size, Hkv, D); o (Hkv, G, D).

    Scratch: m/l (Hkv, G, 128) lane-replicated running max / normalizer,
    acc (Hkv, G, D) unnormalized output accumulator.
    """
    b = pl.program_id(0)
    page = pl.program_id(1)
    attend = attend_ref[b]
    n_kv = q_ref.shape[0]

    @pl.when(page == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(page * page_size < attend)
    def _visit():
        for h in range(n_kv):                               # static unroll
            q = q_ref[h].astype(jnp.float32) * scale        # (G, D)
            k = k_ref[:, h, :].astype(jnp.float32)          # (page, D)
            v = v_ref[:, h, :].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
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
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(page == n_pages - 1)
    def _finish():
        l = jnp.maximum(l_ref[...][:, :, :1], 1e-20)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_decode_attention_tpu(q, k, v, attend_len, *, page_size: int = 128,
                               interpret: bool = False):
    """q: (B, Hkv, G, D); k/v: (B, S, Hkv, D); attend_len: (B,) int32.
    S must be a multiple of page_size.  Returns (B, Hkv, G, D) in q.dtype.
    """
    B, Hkv, G, D = q.shape
    S = k.shape[1]
    assert S % page_size == 0, (S, page_size)
    n_pages = S // page_size
    scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(_paged_decode_kernel, scale=scale,
                               page_size=page_size, n_pages=n_pages)

    def kv_page(b, p, attend):
        # clamp the fetched page to the row's last live one: Pallas skips
        # the HBM->VMEM copy when consecutive grid steps map to the same
        # block, so pages past attend_len cost no bandwidth (the compute
        # guard in the kernel body already skips their math)
        live = jnp.maximum((attend[b] + page_size - 1) // page_size, 1)
        return (b, jnp.minimum(p, live - 1), 0, 0)

    row = lambda b, p, _: (b, 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, n_pages),
        in_specs=[
            pl.BlockSpec((None, Hkv, G, D), row, memory_space=pltpu.VMEM),
            pl.BlockSpec((None, page_size, Hkv, D), kv_page,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((None, page_size, Hkv, D), kv_page,
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((None, Hkv, G, D), row,
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, 128), jnp.float32),   # running max
            pltpu.VMEM((Hkv, G, 128), jnp.float32),   # running normalizer
            pltpu.VMEM((Hkv, G, D), jnp.float32),     # unnormalized acc
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(attend_len, jnp.int32), q, k, v)

"""Attention: GQA / MHA, causal, sliding-window, flash-style blocked softmax.

Layout conventions:
  queries      (B, T, Hq, D)
  keys/values  (B, S, Hkv, D)     Hq % Hkv == 0 (GQA groups)

`flash_attention` is the training/prefill path: a lax.scan over KV blocks
(and an outer scan over query chunks) with an online-softmax accumulator,
so the (T, S) score matrix is never materialized.  `decode_attention` is
the single-token serving path.  Both support causal masking and a
sliding window (window > 0 => only the last `window` positions attend).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.ops import decode_attend
from repro.nn import init as initializers
from repro.nn.linear import dense_apply, dense_init
from repro.nn.module import split_keys
from repro.nn.rope import apply_rope

NEG_INF = -1e30


# ------------------------------------------------------------ projections --
def attention_init(key, d_model: int, n_heads: int, n_kv_heads: int,
                   head_dim: int | None = None, *, qkv_bias: bool = False,
                   dtype=jnp.float32):
    head_dim = head_dim or d_model // n_heads
    kk = split_keys(key, ["wq", "wk", "wv", "wo"])
    return {
        "wq": dense_init(kk["wq"], d_model, n_heads * head_dim, use_bias=qkv_bias, dtype=dtype),
        "wk": dense_init(kk["wk"], d_model, n_kv_heads * head_dim, use_bias=qkv_bias, dtype=dtype),
        "wv": dense_init(kk["wv"], d_model, n_kv_heads * head_dim, use_bias=qkv_bias, dtype=dtype),
        "wo": dense_init(kk["wo"], n_heads * head_dim, d_model, use_bias=False, dtype=dtype),
    }


def project_qkv(params, x, *, n_heads: int, n_kv_heads: int, head_dim: int):
    B, T, _ = x.shape
    q = dense_apply(params["wq"], x).reshape(B, T, n_heads, head_dim)
    k = dense_apply(params["wk"], x).reshape(B, T, n_kv_heads, head_dim)
    v = dense_apply(params["wv"], x).reshape(B, T, n_kv_heads, head_dim)
    return q, k, v


# ------------------------------------------------------------ flash core ---
def _block_attend(q, k, v, q_pos, k_pos, *, causal: bool, window: int,
                  scale: float, m_prev, l_prev, acc_prev, kv_valid_len=None):
    """One online-softmax update for a (q_chunk, kv_block) tile.

    q: (B, Tq, Hkv, G, D);  k/v: (B, Sk, Hkv, D)
    m/l: (B, Hkv, G, Tq);   acc: (B, Tq, Hkv, G, D)
    kv_valid_len: optional (B,) per-row count of valid key positions
    (right-padded prefill batches mask pad keys out of every row).
    """
    s = jnp.einsum("bthgd,bshd->bhgts", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale           # (B,Hkv,G,Tq,Sk)
    mask = jnp.ones(s.shape[-2:], dtype=bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if window > 0:
        mask &= k_pos[None, :] > q_pos[:, None] - window
    if kv_valid_len is not None:
        mask = mask[None] & (k_pos[None, None, :]
                             < kv_valid_len[:, None, None])  # (B,Tq,Sk)
        maskx = mask[:, None, None]                          # vs (B,Hkv,G,Tq,Sk)
    else:
        maskx = mask
    s = jnp.where(maskx, s, NEG_INF)

    m_cur = jnp.max(s, axis=-1)                             # (B,Hkv,G,Tq)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked rows: keep m finite
    m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(maskx, p, 0.0)
    corr = jnp.exp(jnp.where(m_prev <= NEG_INF / 2, NEG_INF, m_prev) - m_safe)
    corr = jnp.where(m_prev <= NEG_INF / 2, 0.0, corr)
    l_new = corr * l_prev + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhgts,bshd->bthgd", p, v.astype(jnp.float32))
    acc_new = acc_prev * corr.transpose(0, 3, 1, 2)[..., None] + pv
    return m_new, l_new, acc_new


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_block: int = 512, kv_block: int = 1024,
                    q_offset: int = 0, kv_valid_len=None) -> jnp.ndarray:
    """Blocked attention; never materializes (T, S).

    q: (B, T, Hq, D), k: (B, S, Hkv, D), v: (B, S, Hkv, Dv) (latent
    attention's values are narrower than its keys).  q_offset: absolute position of
    q[0] relative to k[0] (for chunked prefill continuation).
    kv_valid_len: optional (B,) count of valid keys per row — keys at or
    beyond it never receive probability mass (bucketed prefill padding).
    Returns (B, T, Hq, Dv) in q.dtype.
    """
    B, T, Hq, D = q.shape
    S, Hkv, Dv = k.shape[1], k.shape[2], v.shape[3]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D)

    qb = min(q_block, T)
    kb = min(kv_block, S)
    # pad to multiples
    Tp = -(-T // qb) * qb
    Sp = -(-S // kb) * kb
    qp = jnp.pad(q, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
    q_positions = jnp.arange(Tp) + q_offset
    k_positions = jnp.where(jnp.arange(Sp) < S, jnp.arange(Sp), 2**30)  # pad keys out of window

    qg = qp.reshape(B, Tp // qb, qb, Hkv, G, D)
    kg = kp.reshape(B, Sp // kb, kb, Hkv, D)
    vg = vp.reshape(B, Sp // kb, kb, Hkv, Dv)
    qpos_g = q_positions.reshape(Tp // qb, qb)
    kpos_g = k_positions.reshape(Sp // kb, kb)

    def per_q_chunk(q_chunk, q_pos):
        # q_chunk: (B, qb, Hkv, G, D)
        m0 = jnp.full((B, Hkv, G, qb), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, qb), jnp.float32)
        a0 = jnp.zeros((B, qb, Hkv, G, Dv), jnp.float32)

        def body(carry, kv):
            m, l, a = carry
            k_blk, v_blk, k_pos = kv
            m, l, a = _block_attend(q_chunk, k_blk, v_blk, q_pos, k_pos,
                                    causal=causal, window=window, scale=scale,
                                    m_prev=m, l_prev=l, acc_prev=a,
                                    kv_valid_len=kv_valid_len)
            return (m, l, a), None

        (m, l, a), _ = jax.lax.scan(
            body, (m0, l0, a0),
            (kg.swapaxes(0, 1), vg.swapaxes(0, 1), kpos_g))
        l = jnp.maximum(l, 1e-20)
        out = a / l.transpose(0, 3, 1, 2)[..., None]
        return out  # (B, qb, Hkv, G, Dv)

    def q_body(_, qc):
        q_chunk, q_pos = qc
        return None, per_q_chunk(q_chunk, q_pos)

    _, outs = jax.lax.scan(q_body, None, (qg.swapaxes(0, 1), qpos_g))
    # outs: (nq, B, qb, Hkv, G, Dv)
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Tp, Hq, Dv)
    return out[:, :T].astype(q.dtype)


def decode_attention(q, k_cache, v_cache, attend_len) -> jnp.ndarray:
    """Single-step attention against a cache.

    q: (B, 1, Hq, D); k/v_cache: (B, S, Hkv, D); attend_len: () or (B,)
    number of valid cache slots (per-row counts serve slot pools whose
    rows sit at different depths).  Ring buffers (SWA) pass attend_len ==
    S once full; slot order does not matter because keys carry absolute
    RoPE phases.  Returns (B, 1, Hq, D).

    `kernels.decode_attention.ops.decode_attend` chooses the path: the
    Pallas kernel where the cache pages on the TPU, the dense reference
    elsewhere; they agree within float tolerance.
    """
    return decode_attend(q, (k_cache, v_cache), attend_len)


# ----------------------------------------------------------- full layer ----
def attention_apply(params, x, *, n_heads: int, n_kv_heads: int,
                    head_dim: int, causal: bool = True, window: int = 0,
                    rope_theta: float = 10000.0, positions=None,
                    q_block: int = 512, kv_block: int = 1024,
                    return_kv: bool = False, kv_valid_len=None):
    """Self-attention over x: (B, T, d_model).

    With return_kv, also returns the (roped) K/V tensors (B, T, Hkv, D)
    so prefill can populate a decode cache.  kv_valid_len (B,) masks
    right-padding keys out of every row (bucketed prefill).
    """
    B, T, _ = x.shape
    q, k, v = project_qkv(params, x, n_heads=n_heads, n_kv_heads=n_kv_heads,
                          head_dim=head_dim)
    if positions is None:
        positions = jnp.arange(T)[None, :]
    if rope_theta > 0:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          q_block=q_block, kv_block=kv_block,
                          kv_valid_len=kv_valid_len)
    out = out.reshape(B, T, n_heads * head_dim)
    y = dense_apply(params["wo"], out)
    if return_kv:
        return y, k, v
    return y


def cross_attention_apply(params, x, k, v, *, n_heads: int, head_dim: int):
    """Encoder-decoder cross attention; k/v precomputed (B, F, H, D)."""
    B, T, _ = x.shape
    q = dense_apply(params["wq"], x).reshape(B, T, n_heads, head_dim)
    out = flash_attention(q, k, v, causal=False)
    out = out.reshape(B, T, n_heads * head_dim)
    return dense_apply(params["wo"], out)


def cross_kv(params, enc_out, *, n_kv_heads: int, head_dim: int):
    """Precompute cross-attention K/V from encoder output."""
    B, F, _ = enc_out.shape
    k = dense_apply(params["wk"], enc_out).reshape(B, F, n_kv_heads, head_dim)
    v = dense_apply(params["wv"], enc_out).reshape(B, F, n_kv_heads, head_dim)
    return k, v


def cross_attention_decode(params, x, k, v, *, n_heads: int, head_dim: int):
    """One-token cross attention (cache = precomputed encoder K/V)."""
    B = x.shape[0]
    q = dense_apply(params["wq"], x).reshape(B, 1, n_heads, head_dim)
    out = decode_attention(q, k, v, attend_len=k.shape[1])
    out = out.reshape(B, 1, n_heads * head_dim)
    return dense_apply(params["wo"], out)


def attention_decode_apply(params, x, k_cache, v_cache, cache_len, *,
                           n_heads: int, n_kv_heads: int, head_dim: int,
                           rope_theta: float = 10000.0, layer=None,
                           live=None):
    """One-token decode.  x: (B, 1, d_model); cache_len: () or (B,) tokens
    seen so far (per-row counts let a slot pool decode rows that sit at
    different context depths in one program).

    The cache is a ring buffer of size S (SWA archs size it to the window;
    full-attention archs size it to the max context).  The new token's K/V
    are written at cache_len % S; attention covers min(cache_len + 1, S)
    slots.  Returns (out (B,1,d_model), new_k_cache, new_v_cache).

    With `layer` (one index per leading axis), k/v_cache are
    (*L, B, S, Hkv, D) pools: the new K/V go in with one update at
    [*layer, row, cache_len % S] and that layer is attended, so a caller
    that carries the pool through its layer loop updates it in place
    instead of slicing and restacking it per layer.

    live: optional (B,) bool, the rows that decode a request: the others
    are neither attended nor written (their output is zeros, and their
    slots keep what they held).
    """
    B = x.shape[0]
    S = k_cache.shape[-3]
    q, k, v = project_qkv(params, x, n_heads=n_heads, n_kv_heads=n_kv_heads,
                          head_dim=head_dim)
    pos = jnp.asarray(cache_len)
    pos_b = jnp.broadcast_to(pos, (B,))[:, None]
    if rope_theta > 0:
        q = apply_rope(q, pos_b, rope_theta)
        k = apply_rope(k, pos_b, rope_theta)
    out, (k_cache, v_cache) = decode_attend(
        q, (k_cache, v_cache), jnp.minimum(pos + 1, S),
        new=(k, v), slot=pos % S, layer=layer, live=live)
    out = out.reshape(B, 1, n_heads * head_dim)
    return dense_apply(params["wo"], out), k_cache, v_cache


# ----------------------------------------------------------- references ----
def reference_attention(q, k, v, *, causal: bool = True, window: int = 0,
                        q_offset: int = 0) -> jnp.ndarray:
    """O(T*S)-memory oracle used by tests."""
    B, T, Hq, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, T, Hkv, G, D)
    s = jnp.einsum("bthgd,bshd->bhgts", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    q_pos = jnp.arange(T) + q_offset
    k_pos = jnp.arange(S)
    mask = jnp.ones((T, S), bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if window > 0:
        mask &= k_pos[None, :] > q_pos[:, None] - window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask, p, 0.0)
    out = jnp.einsum("bhgts,bshd->bthgd", p, v.astype(jnp.float32))
    return out.reshape(B, T, Hq, D).astype(q.dtype)

"""Multi-head latent attention (DeepSeek-V2/V3; Moonlight), without a query
low-rank projection.

Per token: q = x W_q split per head into a no-RoPE part (qk_nope) and a
RoPE part (qk_rope); [c, k_pe] = x W_kva, with c the kv_lora_rank latent
(RMS-normed) and k_pe one qk_rope key part shared by every head (RoPE
applied); per head, [k_nope, v] = c W_kvb.  Keys are [k_nope, k_pe],
queries [q_nope, q_pe], the softmax scale 1/sqrt(qk_nope + qk_rope), and
the heads' values go through W_o.

The cache holds one row per token and layer, [c, k_pe] (kv_lora_rank +
qk_rope entries), the same for every head.  Prefill expands keys and
values from it per head (`mla_apply`, `mla_chunk_attend`).  Decode uses
the absorbed form (`mla_decode_apply`): each head's q_nope is folded
through its W_UK slice of W_kvb into the latent space, so a query row is
[q_nope W_UK, q_pe] against the cached rows themselves, the values are
the rows' first kv_lora_rank entries, and each head's output leaves the
latent space through its W_UV slice.  Decode then reads each cached row
once for all heads.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.ops import decode_attend
from repro.nn.attention import flash_attention
from repro.nn.linear import dense_apply, dense_init
from repro.nn.module import split_keys
from repro.nn.norm import rmsnorm_apply, rmsnorm_init
from repro.nn.rope import apply_rope

LATENT_NORM_EPS = 1e-6     # the latent's own RMSNorm (the modeling default)


def mla_init(key, d_model: int, n_heads: int, spec, *, dtype=jnp.float32):
    kk = split_keys(key, ["wq", "wkv_a", "wkv_b", "wo"])
    r = spec.kv_lora_rank
    return {
        "wq": dense_init(kk["wq"], d_model, n_heads * spec.qk_head_dim,
                         use_bias=False, dtype=dtype),
        "wkv_a": dense_init(kk["wkv_a"], d_model, spec.latent_dim,
                            use_bias=False, dtype=dtype),
        "kv_norm": rmsnorm_init(r, dtype),
        "wkv_b": dense_init(kk["wkv_b"], r,
                            n_heads * (spec.qk_nope_head_dim + spec.v_head_dim),
                            use_bias=False, dtype=dtype),
        "wo": dense_init(kk["wo"], n_heads * spec.v_head_dim, d_model,
                         use_bias=False, dtype=dtype),
    }


def mla_latent(params, x, positions, spec, rope_theta: float):
    """Cache rows of x (B, T, d): [normed c, roped k_pe], (B, T, Dl)."""
    kv = dense_apply(params["wkv_a"], x)
    c, k_pe = jnp.split(kv, [spec.kv_lora_rank], axis=-1)
    c = rmsnorm_apply(params["kv_norm"], c, eps=LATENT_NORM_EPS)
    k_pe = apply_rope(k_pe[:, :, None], positions, rope_theta)[:, :, 0]
    return jnp.concatenate([c, k_pe.astype(c.dtype)], axis=-1)


def mla_query(params, x, positions, n_heads: int, spec, rope_theta: float):
    """(q_nope (B, T, H, nope), roped q_pe (B, T, H, rope))."""
    B, T, _ = x.shape
    q = dense_apply(params["wq"], x).reshape(B, T, n_heads, spec.qk_head_dim)
    q_nope, q_pe = jnp.split(q, [spec.qk_nope_head_dim], axis=-1)
    return q_nope, apply_rope(q_pe, positions, rope_theta)


def mla_expand(params, latent, n_heads: int, spec):
    """Per-head keys (B, S, H, qk) and values (B, S, H, v) of cache rows
    (B, S, Dl)."""
    B, S, _ = latent.shape
    c, k_pe = jnp.split(latent, [spec.kv_lora_rank], axis=-1)
    kv = dense_apply(params["wkv_b"], c).reshape(
        B, S, n_heads, spec.qk_nope_head_dim + spec.v_head_dim)
    k_nope, v = jnp.split(kv, [spec.qk_nope_head_dim], axis=-1)
    k_pe = jnp.broadcast_to(k_pe[:, :, None].astype(k_nope.dtype),
                            (B, S, n_heads, spec.qk_rope_head_dim))
    return jnp.concatenate([k_nope, k_pe], axis=-1), v


def _out(params, o, n_heads: int, spec):
    B, T = o.shape[:2]
    return dense_apply(params["wo"], o.reshape(B, T, n_heads * spec.v_head_dim))


def mla_apply(params, x, *, n_heads: int, spec, rope_theta: float,
              window: int = 0, kv_valid_len=None):
    """Causal self-attention over x (B, T, d) in the expanded form.
    Returns (y (B, T, d), cache rows (B, T, Dl))."""
    B, T, _ = x.shape
    positions = jnp.arange(T)[None, :]
    latent = mla_latent(params, x, positions, spec, rope_theta)
    q_nope, q_pe = mla_query(params, x, positions, n_heads, spec, rope_theta)
    k, v = mla_expand(params, latent, n_heads, spec)
    q = jnp.concatenate([q_nope, q_pe.astype(q_nope.dtype)], axis=-1)
    o = flash_attention(q, k, v, causal=True, window=window,
                        kv_valid_len=kv_valid_len)
    return _out(params, o, n_heads, spec), latent


def mla_chunk_attend(params, x, latent_cache, positions, depth, *,
                     n_heads: int, spec, rope_theta: float,
                     attend_width: int):
    """A chunked-prefill segment x (B, C, d) at absolute positions depth +
    [0, C): its queries attend, in the expanded form, to keys and values
    expanded from the first attend_width rows of latent_cache (B, W, Dl),
    which already holds the segment's own rows."""
    B, C, _ = x.shape
    q_nope, q_pe = mla_query(params, x, positions, n_heads, spec, rope_theta)
    q = jnp.concatenate([q_nope, q_pe.astype(q_nope.dtype)], axis=-1)
    k, v = mla_expand(params, jax.lax.slice_in_dim(latent_cache, 0,
                                                   attend_width, axis=1),
                      n_heads, spec)
    o = flash_attention(q, k, v, causal=True, q_offset=depth)
    return _out(params, o, n_heads, spec)


def mla_decode_apply(params, x, cache, cache_len, *, n_heads: int, spec,
                     rope_theta: float, layer=None, live=None):
    """One-token decode in the absorbed form.  x: (B, 1, d); cache: (B, S,
    1, Dl) latent rows, or with `layer` (one index per leading axis) a
    (*L, B, S, 1, Dl) pool updated in place; cache_len () or (B,) tokens
    seen.  The new row goes in at cache_len % S.  live: optional (B,)
    bool, as in `attention_decode_apply`.  Returns (y (B, 1, d),
    cache)."""
    B = x.shape[0]
    S = cache.shape[-3]
    r, nope, dv = spec.kv_lora_rank, spec.qk_nope_head_dim, spec.v_head_dim
    pos = jnp.asarray(cache_len)
    pos_b = jnp.broadcast_to(pos, (B,))[:, None]
    row = mla_latent(params, x, pos_b, spec, rope_theta)        # (B, 1, Dl)
    q_nope, q_pe = mla_query(params, x, pos_b, n_heads, spec, rope_theta)
    w_kvb = params["wkv_b"]["w"].reshape(r, n_heads, nope + dv)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w_kvb[..., :nope])
    q = jnp.concatenate([q_lat, q_pe[:, 0].astype(q_lat.dtype)],
                        axis=-1)[:, None]                       # (B,1,H,Dl)
    scale = 1.0 / math.sqrt(spec.qk_head_dim)
    o, (cache,) = decode_attend(
        q, (cache,), jnp.minimum(pos + 1, S), new=(row[:, :, None],),
        slot=pos % S, layer=layer, live=live, scale=scale, dv=r)
    o = jnp.einsum("bhr,rhv->bhv", o[:, 0].astype(q_lat.dtype),
                   w_kvb[..., nope:])
    return _out(params, o[:, None], n_heads, spec), cache

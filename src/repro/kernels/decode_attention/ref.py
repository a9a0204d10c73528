"""Dense decode-attention reference.

One float32 einsum of the (B, 1) query block against the full cache
width, a masked softmax, and a second einsum against the values.  It is
what `ops.decode_attend` runs off the TPU, and the oracle the Pallas
kernel is held to within float tolerance (its online softmax reorders the
accumulation).  It takes the kernel's arguments: a softmax `scale`, and
the shared-value form of latent attention (`dv`: the values are the
first dv entries of each key row).  A row at attend_len 0 attends
nothing and reads zeros, as the kernel's does.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def decode_attention_ref(q, k_cache, v_cache, attend_len, *, scale=None,
                         dv: int = 0) -> jnp.ndarray:
    """q: (B, 1, Hq, D); k_cache: (B, S, Hkv, D); v_cache: the same, or
    with `dv` None and the values the first dv entries of each k row;
    attend_len: () or (B,) count of valid cache slots per row.  scale
    defaults to 1/sqrt(D).  Returns (B, 1, Hq, dv or D) in q.dtype.
    """
    B, _, Hq, D = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D) if scale is None else scale
    if dv:
        v_cache = k_cache[..., :dv]
    qg = q.reshape(B, 1, Hkv, G, D)
    s = jnp.einsum("bthgd,bshd->bhgts", qg.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * scale      # (B,Hkv,G,1,S)
    attend_len = jnp.asarray(attend_len)
    if attend_len.ndim == 0:
        valid = jnp.arange(S) < attend_len                   # broadcast over S
        nonempty = attend_len > 0
    else:
        valid = (jnp.arange(S)[None, :]
                 < attend_len[:, None])[:, None, None, None, :]
        nonempty = (attend_len > 0)[:, None, None, None]
    s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgts,bshd->bthgd", p, v_cache.astype(jnp.float32))
    out = jnp.where(nonempty, out.reshape(B, 1, Hq, v_cache.shape[-1]), 0)
    return out.astype(q.dtype)

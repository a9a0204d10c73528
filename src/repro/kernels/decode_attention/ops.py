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


@partial(jax.jit, static_argnames=("page_size", "interpret"))
def paged_decode_attention_op(q, k_cache, v_cache, attend_len, *,
                              page_size: int = 128, interpret: bool):
    """Pallas path in the framework layout: q (B, 1, Hq, D) -> same."""
    B, _, Hq, D = q.shape
    Hkv = k_cache.shape[2]
    G = Hq // Hkv
    attend = jnp.broadcast_to(jnp.asarray(attend_len, jnp.int32), (B,))
    qg = q[:, 0].reshape(B, Hkv, G, D)
    out = paged_decode_attention_tpu(qg, k_cache, v_cache, attend,
                                     page_size=page_size,
                                     interpret=interpret)
    return out.reshape(B, 1, Hq, D)


def paged_decode_attention(q, k_cache, v_cache, attend_len, *,
                           page_size: int | None = None):
    """Backend dispatch for the serving decode hot path.

    Caches whose width pages cleanly run the paged path (compiled Pallas
    on TPU, bit-identical blocked jnp elsewhere); anything else takes the
    dense reference, so callers never pay page-padding for tiny caches.
    """
    S = k_cache.shape[1]
    page = page_size or auto_page_size(S)
    backend = jax.default_backend()
    if not page:
        return decode_attention_ref(q, k_cache, v_cache, attend_len)
    if backend == "tpu":
        op = partial(paged_decode_attention_op, page_size=page,
                     interpret=False)
        mesh = jax.sharding.get_abstract_mesh()
        if mesh.empty:
            return op(q, k_cache, v_cache, attend_len)
        return _per_shard(op, mesh, q, k_cache, v_cache, attend_len)
    if backend != "cpu":
        # the divisor-ladder bit-identity is an XLA *CPU* dot-panelization
        # property; an unverified backend (GPU) gets the dense reference
        # rather than a maybe-ULP-off switch branch
        return decode_attention_ref(q, k_cache, v_cache, attend_len)
    return paged_decode_attention_jnp(q, k_cache, v_cache, attend_len,
                                      page_size=page)


def _per_shard(op, mesh, q, k_cache, v_cache, attend_len):
    """Run the kernel on each device's shard of a meshed program (the
    compiler cannot partition a Mosaic kernel itself).  Rows split over
    the data axes and kv heads over "model", the layout
    `launch.partition` gives the slot pool and the attention weights; an
    axis that does not divide stays whole.  Query heads split with their
    kv heads: a contiguous block of Hq/m query heads uses exactly the
    block of Hkv/m kv heads on the same device."""
    B, Hkv = k_cache.shape[0], k_cache.shape[2]
    data = tuple(a for a in mesh.axis_names if a != "model")
    dsize = math.prod(mesh.shape[a] for a in data)
    msize = mesh.shape.get("model", 1)
    rows = data if data and B % dsize == 0 else None
    heads = "model" if msize > 1 and Hkv % msize == 0 else None
    spec = P(rows, None, heads, None)
    attend = jnp.broadcast_to(jnp.asarray(attend_len, jnp.int32), (B,))
    return jax.shard_map(op, mesh=mesh, in_specs=(spec, spec, spec, P(rows)),
                         out_specs=spec, check_vma=False)(
        q, k_cache, v_cache, attend)

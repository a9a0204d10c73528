"""Kernel microbenchmarks: us/call of the jnp substrate paths on CPU
(interpret-mode Pallas is a correctness harness, not a perf path, so the
timed paths are the jit'd jnp implementations the dry-run lowers)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

import numpy as np

from benchmarks.common import timed_us
from repro.nn.attention import flash_attention
from repro.nn.moe import moe_apply, moe_init
from repro.nn.ssm import mamba_apply, mamba_init
from repro.nn.xlstm import mlstm_apply, mlstm_init

KEY = jax.random.PRNGKey(0)


def offload_hot_path_rows() -> list[tuple]:
    """Online offload hot path: fused one-pass split+quantize vs the seed
    two-pass composition, vectorized vs per-sample bit-packing, and one
    serving-engine decode step."""
    from functools import partial

    from repro.compress.lzw import pack_indices, pack_indices_batch
    from repro.compress.quantize import dequantize, hard_indices
    from repro.kernels.offload_fused.ops import fused_offload_jnp

    rows = []
    B, H, W, C, k, L = 64, 8, 8, 64, 8, 8
    x = jax.random.normal(KEY, (B, H, W, C))
    centers = jnp.linspace(-3, 3, L)
    perm = tuple(int(i) for i in np.random.RandomState(0).permutation(C))
    q = {"centers": centers}

    @jax.jit
    def seed_two_pass(x, centers):
        y = jnp.take(x, jnp.asarray(perm), axis=-1)
        f_local, f_remote = y[..., :k], y[..., k:]
        idx = hard_indices({"centers": centers}, f_remote)
        return f_local, idx, dequantize({"centers": centers}, idx)

    fused = jax.jit(partial(fused_offload_jnp, perm=perm, k=k))
    us_seed = timed_us(seed_two_pass, x, centers, iters=20)
    us_fused = timed_us(fused, x, centers, iters=20)
    rows.append(("kernel.offload_split_quant_seed.us", us_seed,
                 f"B{B}x{H}x{W}x{C} 2-pass"))
    rows.append(("kernel.offload_split_quant_fused.us", us_fused,
                 f"B{B}x{H}x{W}x{C} fused 1-pass"))

    # serving-shaped packing: many independent samples, small payload each
    Bp = 256
    idx = np.asarray(hard_indices(q, jax.random.normal(KEY, (Bp, 4, 4, C - k))))
    bits = 3

    def pack_loop(idx):
        return [pack_indices(idx[b], bits) for b in range(idx.shape[0])]

    us_loop = timed_us(pack_loop, idx, iters=20)
    us_vec = timed_us(lambda a: pack_indices_batch(a, bits), idx, iters=20)
    rows.append(("kernel.pack_indices_loop.us", us_loop, f"B={Bp} per-sample"))
    rows.append(("kernel.pack_indices_batch.us", us_vec,
                 f"B={Bp} vectorized"))

    from repro.configs import get_config
    from repro.models import backbone as bb
    cfg = get_config("qwen2-0.5b").reduced()
    params = bb.init_params(cfg, KEY)
    batch = {"tokens": jnp.zeros((2, 8), jnp.int32)}
    logits, cache, total_T = bb.prefill(cfg, params, batch, max_len=64)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    step = jax.jit(lambda p, t, c, n: bb.decode_step(cfg, p, t, c, n))
    us_step = timed_us(lambda p, t, c: step(p, t, c, total_T)[0],
                       params, tok, cache, iters=10)
    rows.append(("engine.decode_step.us", us_step, "qwen2-0.5b reduced B=2"))
    return rows


def kernel_micro_rows() -> list[tuple]:
    rows = []
    B, T, H, D = 1, 512, 4, 64
    q = jax.random.normal(KEY, (B, T, H, D))
    k = jax.random.normal(KEY, (B, T, 2, D))
    v = jax.random.normal(KEY, (B, T, 2, D))
    f = jax.jit(lambda q, k, v: flash_attention(q, k, v, q_block=128,
                                                kv_block=128))
    us = timed_us(f, q, k, v)
    rows.append(("kernel.flash_attention.us", us,
                 f"B{B} T{T} H{H} D{D} causal"))

    p = moe_init(KEY, 128, 256, 8)
    x = jax.random.normal(KEY, (2, 256, 128))
    f = jax.jit(lambda p, x: moe_apply(p, x, top_k=2)[0])
    rows.append(("kernel.moe_dispatch.us", timed_us(f, p, x), "8e top-2"))

    p = mamba_init(KEY, 128)
    x = jax.random.normal(KEY, (1, 512, 128))
    f = jax.jit(lambda p, x: mamba_apply(p, x, chunk=128))
    rows.append(("kernel.mamba_scan.us", timed_us(f, p, x), "chunked"))

    p = mlstm_init(KEY, 128, 4)
    f = jax.jit(lambda p, x: mlstm_apply(p, x, n_heads=4, chunk=64))
    rows.append(("kernel.mlstm_chunked.us", timed_us(f, p, x), ""))
    rows.extend(offload_hot_path_rows())
    return rows

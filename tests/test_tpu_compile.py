"""Compile the main path's Pallas kernels for a described TPU v5e chip.

The TPU compiler checks what interpret mode never does (block shapes
against the (8, 128) tiling, fast-memory limits), so these tests compile
each kernel at the widths the served path runs: the paged decode kernel
at qwen2-0.5b's head shapes and the fused AgileNN offload at the paper's
96x96 size.  Nothing runs; no chip is needed.  The topology is described
inside a fixture, so only the worker given this file loads the TPU
compiler, and every worker collects the same tests.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention.ops import paged_decode_attention_op
from repro.kernels.offload_fused.ops import fused_offload_op


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    """The serving mesh over the described 2x2 chips, Auto axes as
    `launch.mesh.make_serving_mesh` makes it."""
    import numpy as np
    from jax.sharding import AxisType, Mesh
    return Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)


@pytest.fixture
def no_compile_cache():
    """Compiles for a described chip are written to the persistent cache
    but cannot be read back without one; keep the cache out of them."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_kernel_compiles_at_qwen2_0_5b_shapes(one_chip,
                                                     no_compile_cache, dtype):
    """The scheduler's pool at qwen2-0.5b: 8 slots, KV width 1024 paged
    at 128, 14 query / 2 kv heads of 64 (G = 7)."""
    B, S, Hq, Hkv, D = 8, 1024, 14, 2, 64
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    q = sds((B, 1, Hq, D), dtype)
    kv = sds((B, S, Hkv, D), dtype)
    attend = sds((B,), jnp.int32)
    text = _compiled_text(
        lambda q, k, v, a: paged_decode_attention_op(
            q, k, v, a, page_size=128, interpret=False), q, kv, kv, attend)
    assert "tpu_custom_call" in text


def _lower_chunk(sched, idle: bool, *args):
    """The decode chunk lowered at these shapes; with `idle` its active
    rows are a constant vector of none, so every step decodes no row
    and the kernel sees every row at depth 0."""
    if not idle:
        return sched._chunk.lower(*args)
    return jax.jit(lambda p, pool, a, key, n: sched._chunk(
        p, pool, jnp.zeros_like(a), key, n), donate_argnums=1).lower(*args)


def test_decode_chunk_compiles_in_place_for_the_chip(one_chip,
                                                     no_compile_cache,
                                                     monkeypatch):
    """The scheduler's decode chunk compiled for the chip holds the
    Pallas kernel and no copy, broadcast, dynamic-slice or
    dynamic-update-slice of the KV pool's shape: the kernel writes each
    row's token into the pool and reads its pages from it, in the layout
    the TPU keeps the pool in.  The backend is reported as the TPU so
    the chunk traces the kernel path."""
    _check_qwen2_chunk(one_chip, monkeypatch, idle=False)


def _check_qwen2_chunk(one_chip, monkeypatch, idle: bool):
    import re

    from repro.configs import get_config
    from repro.models import backbone as bb
    from repro.serve.scheduler import ContinuousScheduler, SchedulerConfig

    cfg = get_config("qwen2-0.5b").reduced()      # 14/2 heads of 64 kept
    params = bb.init_params(cfg, jax.random.PRNGKey(0))
    sched = ContinuousScheduler(
        cfg, params, max_len=256,
        sched=SchedulerConfig(buckets=(64,), max_slots=4, chunk=4))
    on_chip = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                             sharding=one_chip)
    pool = jax.tree.map(on_chip, sched._pool)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _lower_chunk(
        sched, idle, jax.tree.map(on_chip, params), pool,
        on_chip(jnp.ones((4,), bool)), on_chip(jax.random.PRNGKey(1)),
        on_chip(jnp.int32(4))).compile().as_text()
    assert "tpu_custom_call" in text
    k = pool["cache"]["k"]
    shape = rf"f32\[{','.join(map(str, k.shape))}\]"
    ops = set(re.findall(rf"= {shape}\S* ([a-z-]+)\(", text))
    bad = {"copy", "broadcast", "dynamic-slice", "dynamic-update-slice",
           "fusion"} & ops
    assert not bad, (bad, sorted(ops))


def test_offload_fused_compiles_at_paper_size(one_chip, no_compile_cache):
    """AgileNN at 96x96: the extractor gives 24x24x24 features per image,
    so a batch of 64 is 36,864 rows of C 24, split at k 5 and quantized
    against 8 centers."""
    rows, C, k, L = 64 * 24 * 24, 24, 5, 8
    x = jax.ShapeDtypeStruct((rows, C), jnp.float32, sharding=one_chip)
    centers = jax.ShapeDtypeStruct((L,), jnp.float32, sharding=one_chip)
    perm = tuple(reversed(range(C)))
    text = _compiled_text(
        lambda x, c: fused_offload_op(x, c, perm=perm, k=k, interpret=False),
        x, centers)
    assert "tpu_custom_call" in text


def _moonlight_cell_cfg():
    """Moonlight-16B-A3B as the `moonlight-16b-a3b.chat-long` cell serves
    it: published widths, 8 held experts of the router's 64."""
    import dataclasses

    from repro.configs import get_config
    cfg = get_config("moonshot-v1-16b-a3b")
    return dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe,
                                                            n_held=8))


def test_mla_decode_kernel_compiles_at_moonlight_shapes(one_chip,
                                                        no_compile_cache):
    """The latent pool of the cell: 32 slots, 4,096 tokens paged at 128,
    one 576-wide bfloat16 row per token shared by 16 heads, the values
    its first 512 entries; the kernel writes the step's row in place."""
    from repro.kernels.decode_attention.ops import mla_decode_attention_op
    B, S, H, Dl, L = 32, 4096, 16, 576, 27
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    q = sds((B, 1, H, Dl), jnp.bfloat16)
    pool = sds((L, 1, B, S, 1, Dl), jnp.bfloat16)
    row = sds((B, 1, Dl), jnp.bfloat16)
    ints = sds((B,), jnp.int32)
    lay = (sds((), jnp.int32), sds((), jnp.int32))
    compiled = jax.jit(
        lambda q, c, a, l, r, s: mla_decode_attention_op(
            q, c, a, l, r, s, page_size=128, interpret=False,
            scale=192 ** -0.5, dv=512),
        donate_argnums=1).lower(q, pool, ints, lay, row, ints).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_moonlight_decode_chunk_compiles_for_the_chip(one_chip,
                                                      no_compile_cache,
                                                      monkeypatch, capsys):
    """The cell's decode chunk (27 layers, 32 slots of 4,096 tokens)
    compiled for the chip: the MLA kernel and the held experts' grouped
    SwiGLU are there under their own names, nothing copies the latent
    pool, and its temporaries stay small beside the 4.1 GB pool and
    6.7 GB of weights.  Shapes only: nothing is allocated at this size."""
    temp = _check_moonlight_chunk(one_chip, monkeypatch, idle=False)
    with capsys.disabled():
        print(f"\nmoonlight decode chunk: temporaries {temp / 2**30:.3f} GiB")


def _check_moonlight_chunk(one_chip, monkeypatch, idle: bool) -> int:
    import re

    from repro.models import backbone as bb
    from repro.serve.scheduler import ContinuousScheduler, SchedulerConfig

    cfg = _moonlight_cell_cfg()
    params = jax.eval_shape(lambda: bb.init_params(cfg, jax.random.PRNGKey(0)))
    sched = ContinuousScheduler(
        cfg, jax.tree.map(lambda s: jnp.zeros((), s.dtype), params),
        max_len=32, sched=SchedulerConfig(buckets=(32,), max_slots=32,
                                          chunk=8))
    on_chip = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                             sharding=one_chip)
    pool = dict(jax.tree.map(on_chip, sched._pool))
    pool["cache"] = jax.tree.map(on_chip, jax.eval_shape(
        lambda: bb.init_cache(cfg, 32, 4096)))
    pool["buf"] = on_chip(jax.ShapeDtypeStruct((32, 4096), jnp.int32))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = _lower_chunk(
        sched, idle, jax.tree.map(on_chip, params), pool,
        on_chip(jnp.ones((32,), bool)), on_chip(jax.random.PRNGKey(1)),
        on_chip(jnp.int32(8))).compile()
    text = compiled.as_text()
    assert "mla_decode_attention_op" in text
    assert "held_experts_ffn_decode" in text
    lat = pool["cache"]["latent"]
    shape = rf"bf16\[{','.join(map(str, lat.shape))}\]"
    ops = set(re.findall(rf"= {shape}\S* ([a-z-]+)\(", text))
    bad = {"copy", "broadcast", "dynamic-slice", "dynamic-update-slice",
           "fusion"} & ops
    assert not bad, (bad, sorted(ops))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 2 << 30
    return temp


@pytest.mark.parametrize("cell", ["qwen2-0.5b", "moonlight-16b-a3b"])
def test_decode_chunk_compiles_with_every_row_idle(one_chip,
                                                   no_compile_cache,
                                                   monkeypatch, cell):
    """Both cells' decode chunks still compile for the chip, in place,
    where no row is active: each step hands the kernel every row at
    depth 0, and the grid runs one step that writes back the page it
    read."""
    check = _check_qwen2_chunk if cell == "qwen2-0.5b" \
        else _check_moonlight_chunk
    check(one_chip, monkeypatch, idle=True)


@pytest.mark.parametrize("form", ["gqa", "mla"])
def test_decode_kernel_compiles_with_every_row_idle(one_chip,
                                                    no_compile_cache, form):
    """The kernel, writing the step's token, at the qwen2-0.5b pool
    (8 slots of 1,024, 14/2 heads of 64) and the Moonlight latent pool
    (32 slots of 4,096, rows of 576) with every row at depth 0, a
    constant: the grid's length is then a constant too.  (Without the
    write, an all-idle call is constant zeros and the compiler drops the
    kernel.)"""
    from repro.kernels.decode_attention.ops import mla_decode_attention_op
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    if form == "gqa":
        B, S, Hq, Hkv, D = 8, 1024, 14, 2, 64
        q = sds((B, 1, Hq, D), jnp.float32)
        kv = sds((B, S, Hkv, D), jnp.float32)
        tok = sds((B, Hkv, D), jnp.float32)
        fn = lambda q, k, v, kn, vn, s: paged_decode_attention_op(
            q, k, v, jnp.zeros_like(s), None, (kn, vn), s, page_size=128,
            interpret=False)
        args = (q, kv, kv, tok, tok, sds((B,), jnp.int32))
    else:
        B, S, H, Dl, L = 32, 4096, 16, 576, 27
        fn = lambda q, c, l, r, s: mla_decode_attention_op(
            q, c, jnp.zeros_like(s), l, r, s, page_size=128,
            interpret=False, scale=192 ** -0.5, dv=512)
        args = (sds((B, 1, H, Dl), jnp.bfloat16),
                sds((L, 1, B, S, 1, Dl), jnp.bfloat16),
                (sds((), jnp.int32), sds((), jnp.int32)),
                sds((B, 1, Dl), jnp.bfloat16), sds((B,), jnp.int32))
    assert "tpu_custom_call" in _compiled_text(fn, *args)


def _placed(tree, shardings):
    return jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=s), tree, shardings)


def test_decode_chunk_compiles_under_a_mesh(mesh, no_compile_cache,
                                            monkeypatch):
    """The scheduler's decode chunk (qwen2-0.5b reduced, 14/2 heads of
    64) traced under the 2x2 serving mesh, with params and pool placed
    as `launch.partition` places them: the kernel runs per shard."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.launch.partition import param_shardings, pool_shardings
    from repro.models import backbone as bb
    from repro.serve.scheduler import ContinuousScheduler, SchedulerConfig

    cfg = get_config("qwen2-0.5b").reduced()
    params = bb.init_params(cfg, jax.random.PRNGKey(0))
    sched = ContinuousScheduler(
        cfg, params, max_len=256,
        sched=SchedulerConfig(buckets=(64,), max_slots=4, chunk=4))
    params = jax.eval_shape(lambda: params)
    pool = jax.eval_shape(lambda: sched._pool)
    whole = NamedSharding(mesh, P())
    args = (_placed(params, param_shardings(params, mesh)),
            _placed(pool, pool_shardings(pool, mesh)),
            *(jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=whole)
              for x in (jnp.ones((4,), bool), jax.random.PRNGKey(1),
                        jnp.int32(4))))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with jax.set_mesh(mesh):
        text = sched._chunk.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert "paged_decode_attention_op" in text


def test_mla_decode_compiles_under_a_mesh(mesh, no_compile_cache,
                                          monkeypatch):
    """`decode_attend` on the Moonlight cell's latent pool (27 layers, 32
    slots of 4,096 rows of 576, bfloat16) under the 2x2 serving mesh,
    the pool's slots split over "data" as `launch.partition` places
    them: the latent kernel runs per shard, writing the step's row in
    place, and no collective gathers the pool.  (The whole Moonlight
    decode chunk does not compile under a mesh yet: the held-expert
    kernel has no per-shard wrapper.)"""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.kernels.decode_attention.ops import decode_attend
    from repro.launch.partition import pool_shardings

    B, S, H, Dl, L = 32, 4096, 16, 576, 27
    pool = jax.ShapeDtypeStruct((L, 1, B, S, 1, Dl), jnp.bfloat16)
    pool = _placed(pool, pool_shardings({"latent": pool}, mesh)["latent"])
    rows = lambda shape, dt: jax.ShapeDtypeStruct(
        shape, dt, sharding=NamedSharding(
            mesh, P("data", *(None,) * (len(shape) - 1))))
    whole = jax.ShapeDtypeStruct((), jnp.int32,
                                 sharding=NamedSharding(mesh, P()))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn = lambda q, c, a, r, s, l: decode_attend(
        q, (c,), a, new=(r,), slot=s, layer=l, scale=192 ** -0.5, dv=512)
    with jax.set_mesh(mesh):
        text = jax.jit(fn, donate_argnums=1).lower(
            rows((B, 1, H, Dl), jnp.bfloat16), pool,
            rows((B,), jnp.int32), rows((B, 1, 1, Dl), jnp.bfloat16),
            rows((B,), jnp.int32), (whole, whole)).compile().as_text()
    assert "tpu_custom_call" in text
    assert "mla_decode_attention_op" in text
    assert "all-gather" not in text

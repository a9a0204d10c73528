"""Paged decode attention vs the dense oracle.

The load-bearing claim is *bit*-identity of the blocked-jnp fallback:
`decode_attention` swapped the dense einsum for the paged path in the
serving hot loop, and greedy decode must not move by one ULP.  The Pallas
kernel (online softmax) is held to float tolerance in interpret mode.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.common import auto_page_size
from repro.kernels.decode_attention.ops import (
    mla_decode_attention_op,
    paged_decode_attention,
    paged_decode_attention_jnp,
    paged_decode_attention_op,
)
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.nn.attention import (
    attention_decode_apply,
    attention_init,
    decode_attention,
    reference_attention,
)

KEY = jax.random.PRNGKey(0)


def _qkv(B, S, Hq, Hkv, D, key=KEY):
    kq, kk, kv = jax.random.split(key, 3)
    return (jax.random.normal(kq, (B, 1, Hq, D)),
            jax.random.normal(kk, (B, S, Hkv, D)),
            jax.random.normal(kv, (B, S, Hkv, D)))


# ------------------------------------------------------ fallback bit-exact --


@pytest.mark.parametrize("B,S,Hq,Hkv,D,page", [
    (4, 1024, 8, 2, 64, 128),
    (2, 256, 4, 4, 32, 64),
    (3, 96, 6, 3, 16, 32),
    (1, 512, 2, 1, 128, 128),
])
def test_paged_jnp_bit_identical_to_dense(B, S, Hq, Hkv, D, page):
    """Every page-prefix branch must reproduce the full-width dense path
    bit-for-bit (masked tail keys are exact zeros in every reduction)."""
    q, k, v = _qkv(B, S, Hq, Hkv, D)
    rng = np.random.RandomState(0)
    for _ in range(4):
        attend = jnp.asarray(rng.randint(1, S + 1, size=B), jnp.int32)
        got = paged_decode_attention_jnp(q, k, v, attend, page_size=page)
        want = decode_attention_ref(q, k, v, attend)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_paged_jnp_scalar_attend_bit_identical():
    q, k, v = _qkv(2, 256, 4, 2, 64)
    for attend in (1, 77, 128, 129, 256):
        got = paged_decode_attention_jnp(q, k, v, attend, page_size=128)
        want = decode_attention_ref(q, k, v, attend)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_decode_attention_dispatch_bit_identical():
    """The public decode_attention (auto page size) == dense oracle, both
    for paging widths and for widths that fall back to dense."""
    for S in (64, 56, 1024):
        q, k, v = _qkv(2, S, 4, 2, 16)
        attend = jnp.asarray([S // 2, S], jnp.int32)
        np.testing.assert_array_equal(
            np.asarray(decode_attention(q, k, v, attend)),
            np.asarray(decode_attention_ref(q, k, v, attend)))


def test_decode_loop_tokens_match_dense_path(monkeypatch):
    """End-to-end pre-PR equivalence: a greedy decode loop through
    bb.decode_step produces the same tokens with the paged path as with
    the dense einsum (the verbatim seed math) forced in its place."""
    from repro.configs import get_config
    from repro.models import backbone as bb
    import repro.nn.attention as attn

    cfg = get_config("qwen2-0.5b").reduced()
    params = bb.init_params(cfg, KEY)
    rng = np.random.RandomState(0)
    batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab, (2, 8)),
                                   jnp.int32)}
    logits, cache, T = bb.prefill(cfg, params, batch, max_len=64)

    def run():
        step = jax.jit(lambda p, t, c, n: bb.decode_step(cfg, p, t, c, n))
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        cl = jnp.full((2,), T, jnp.int32)
        c = cache
        toks = []
        for _ in range(12):
            lg, c = step(params, tok, c, cl)
            tok = jnp.argmax(lg, -1)[:, None].astype(jnp.int32)
            cl = cl + 1
            toks.append(np.asarray(tok))
        return np.concatenate(toks, axis=1)

    got = run()                                    # paged (S=64 pages at 32)
    monkeypatch.setattr(attn, "decode_attention", decode_attention_ref)
    want = run()                                   # seed dense path
    np.testing.assert_array_equal(got, want)


def test_auto_page_size():
    assert auto_page_size(1024) == 128
    assert auto_page_size(64) == 32
    assert auto_page_size(56) == 0      # not page-divisible -> dense
    assert auto_page_size(128) == 64    # >= 2 pages, else nothing to skip


# ------------------------------------------------------- pallas interpret --


@pytest.mark.parametrize("B,S,Hq,Hkv,D,page", [
    (2, 256, 4, 2, 64, 128),
    (1, 512, 8, 4, 64, 128),
    (3, 256, 2, 1, 128, 64),
    (2, 128, 4, 4, 32, 32),
])
def test_pallas_paged_decode_sweep(B, S, Hq, Hkv, D, page):
    q, k, v = _qkv(B, S, Hq, Hkv, D)
    rng = np.random.RandomState(1)
    attend = jnp.asarray(rng.randint(1, S + 1, size=B), jnp.int32)
    got = paged_decode_attention_op(q, k, v, attend, page_size=page,
                                    interpret=True)
    want = decode_attention_ref(q, k, v, attend)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_pallas_paged_decode_bf16():
    q, k, v = _qkv(2, 256, 4, 2, 64)
    q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))
    attend = jnp.asarray([100, 256], jnp.int32)
    got = paged_decode_attention_op(q, k, v, attend, page_size=128,
                                    interpret=True)
    assert got.dtype == jnp.bfloat16
    want = decode_attention_ref(q, k, v, attend)
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), atol=3e-2, rtol=3e-2)


# ---------------------------------------- layer-stacked pool, in place --


def _pool(n_sb, n_attn, B, S, Hkv, D, key=KEY):
    kk, kv = jax.random.split(key)
    shape = (n_sb, n_attn, B, S, Hkv, D)
    return jax.random.normal(kk, shape), jax.random.normal(kv, shape)


@pytest.mark.parametrize("layer", [(0, 0), (2, 1)])     # first and last
@pytest.mark.parametrize("B,S,Hq,Hkv,D,page", [
    (4, 512, 14, 2, 64, 128),                           # qwen2-0.5b, G = 7
    (3, 256, 4, 4, 32, 64),
])
def test_pallas_layer_indexed_equals_sliced_layer(layer, B, S, Hq, Hkv, D,
                                                  page):
    """The kernel reading one layer of a stacked pool in place gives what
    the same kernel gives on that layer sliced out, for ragged depths
    from 1 to the full width."""
    k, v = _pool(3, 2, B, S, Hkv, D)
    q = jax.random.normal(jax.random.PRNGKey(5), (B, 1, Hq, D))
    attend = jnp.asarray([1, S, 77, page + 1][:B], jnp.int32)
    got = paged_decode_attention_op(q, k, v, attend, layer, page_size=page,
                                    interpret=True)
    want = paged_decode_attention_op(q, k[layer], v[layer], attend,
                                     page_size=page, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_allclose(
        got, decode_attention_ref(q, k[layer], v[layer], attend),
        atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("layer", [(0, 0), (2, 1), None])
def test_pallas_append_writes_pool_in_place(layer):
    """With the step's token, the kernel writes it into its slot of each
    row of that layer (the rest of the pool untouched) and attends the
    written layer: equal to writing with a scatter, then attending.
    Slots cover a page's first and last lane, a mid page and a full ring
    (attend_len == S with the slot behind it)."""
    B, S, Hq, Hkv, D, page = 4, 512, 14, 2, 64, 128
    k, v = _pool(3, 2, B, S, Hkv, D)
    if layer is None:                   # a single cache
        k, v = k[0, 0], v[0, 0]
    kq, kn, vn = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(kq, (B, 1, Hq, D))
    k_new = jax.random.normal(kn, (B, Hkv, D))
    v_new = jax.random.normal(vn, (B, Hkv, D))
    slot = jnp.asarray([0, 127, 300, 40], jnp.int32)
    attend = jnp.asarray([1, 128, 301, S], jnp.int32)
    out, k2, v2 = paged_decode_attention_op(
        q, k, v, attend, layer, (k_new, v_new), slot, page_size=page,
        interpret=True)
    lead = () if layer is None else layer
    idx = (*lead, jnp.arange(B), slot)
    k_want, v_want = k.at[idx].set(k_new), v.at[idx].set(v_new)
    np.testing.assert_array_equal(np.asarray(k2), np.asarray(k_want))
    np.testing.assert_array_equal(np.asarray(v2), np.asarray(v_want))
    want = paged_decode_attention_op(q, k_want[lead], v_want[lead],
                                     attend, page_size=page, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


# ----------------------------------- idle rows (attend_len 0) visit nothing --

_IDLE = {"first": [0], "middle": [2], "last": [4], "mixed": [0, 2, 4],
         "all": [0, 1, 2, 3, 4]}


def _idle_call(form, append, attend, slot, idx):
    """One kernel call on a 2x2-layer pool of rows `idx` (of 5) at layer
    (1, 0), in the GQA form (14/2 heads of 64) or the latent form (4
    heads, rows of 80 whose first 64 are the values): a call on a subset
    of rows sees the same rows.  Returns (out, pool operands after the
    call, pool operands before)."""
    S, page, layer = 512, 128, (1, 0)
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    rows = lambda key, shape: jax.random.normal(key, (5, *shape))[idx]
    if form == "gqa":
        Hq, Hkv, D = 14, 2, 64
        pool = tuple(jnp.moveaxis(rows(k, (2, 2, S, Hkv, D)), 0, 2)
                     for k in ks[:2])
    else:
        Hq, D = 4, 80
        pool = (jnp.moveaxis(rows(ks[0], (2, 2, S, 1, D)), 0, 2),)
    q = rows(ks[2], (1, Hq, D))
    new = tuple(rows(jax.random.fold_in(ks[3], j), (pool[0].shape[-2], D))
                for j in range(len(pool))) if append else None
    attend, slot = jnp.asarray(attend, jnp.int32), jnp.asarray(slot, jnp.int32)
    if form == "gqa":
        res = paged_decode_attention_op(q, *pool, attend, layer, new, slot,
                                        page_size=page, interpret=True)
    else:
        res = mla_decode_attention_op(q, *pool, attend, layer,
                                      new and new[0], slot, page_size=page,
                                      interpret=True, scale=0.1, dv=64)
    out, *after = res if append else (res,)
    return out, after, pool


@pytest.mark.parametrize("idle", list(_IDLE))
@pytest.mark.parametrize("append", [False, True])
@pytest.mark.parametrize("form", ["gqa", "mla"])
def test_pallas_idle_rows_visit_no_page(form, append, idle):
    """Rows with attend_len 0 among live rows at several depths (one
    page, a page and one lane, a mid page, the full width): the live
    rows' outputs and pool pages are bit-identical to a call without the
    idle rows, the idle rows' outputs are exactly zero, and their pool
    rows keep every value they held, write slot included."""
    depth = np.asarray([1, 129, 300, 512, 128])
    slot = depth - 1
    gone = _IDLE[idle]
    attend = np.where(np.isin(np.arange(5), gone), 0, depth)
    out, after, before = _idle_call(form, append, attend, slot,
                                    np.arange(5))
    out = np.asarray(out)
    np.testing.assert_array_equal(out[gone], 0)
    for a, b in zip(after, before):
        np.testing.assert_array_equal(np.asarray(a)[:, :, gone],
                                      np.asarray(b)[:, :, gone])
    kept = np.setdiff1d(np.arange(5), gone)
    if not len(kept):
        return
    want, want_after, _ = _idle_call(form, append, attend[kept], slot[kept],
                                     kept)
    np.testing.assert_array_equal(out[kept], np.asarray(want))
    for a, w in zip(after, want_after):
        np.testing.assert_array_equal(np.asarray(a)[:, :, kept],
                                      np.asarray(w))


def _kernel_path(monkeypatch):
    """Send the decode step through the Pallas kernel in interpret mode,
    at pages of 128, as the TPU backend would send it compiled."""
    import repro.kernels.decode_attention.ops as ops
    import repro.nn.attention as attn
    import repro.nn.mla as mla

    page = lambda S, page_size=None: 128 if S % 128 == 0 else 0
    monkeypatch.setattr(attn, "tpu_page", page)
    monkeypatch.setattr(mla, "tpu_page", page)
    for mod, name in ((ops, "paged_decode_attention_op"),
                      (mla, "mla_decode_attention_op")):
        op = getattr(mod, name)
        monkeypatch.setattr(mod, name, lambda *a, interpret, _op=op, **k:
                            _op(*a, interpret=True, **k))


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "moonlight"])
def test_decode_step_live_mask_on_the_kernel_path(arch, monkeypatch):
    """`decode_step_counted` with a live mask, on the kernel path: the
    live rows' logits and pool rows are those of the same step with
    live=None, and the free slot (depth 0) and the finished row keep
    every pool value they held, their next write slot included, where
    live=None writes it."""
    from repro.configs import get_config
    from repro.models import backbone as bb

    if arch == "moonlight":
        import moonlight_ref as twin
        cfg, params = twin.program_config(), twin.make_params()
    else:
        cfg = get_config(arch).reduced()
        params = bb.init_params(cfg, KEY)
    _kernel_path(monkeypatch)
    B, S = 4, 256
    cache = bb.init_cache(cfg, B, S)
    cache = {n: jax.random.normal(jax.random.fold_in(KEY, i), c.shape
                                  ).astype(c.dtype)
             for i, (n, c) in enumerate(sorted(cache.items()))}
    depth = jnp.asarray([5, 0, 130, 40], jnp.int32)
    live = jnp.asarray([True, False, True, False])
    tok = jnp.asarray([[3], [1], [4], [1]], jnp.int32)
    step = jax.jit(lambda c, m: bb.decode_step_counted(cfg, params, tok, c,
                                                       depth, m)[:2])
    lg_all, c_all = step(cache, None)
    lg, c_live = step(cache, live)
    on, off = np.asarray([0, 2]), np.asarray([1, 3])
    np.testing.assert_array_equal(np.asarray(lg)[on], np.asarray(lg_all)[on])
    for name in c_live:
        got, was = np.asarray(c_live[name]), np.asarray(cache[name])
        np.testing.assert_array_equal(got[:, :, on],
                                      np.asarray(c_all[name])[:, :, on])
        np.testing.assert_array_equal(got[:, :, off], was[:, :, off])
        for b in off:               # live=None writes the row's next slot
            assert not np.array_equal(
                np.asarray(c_all[name])[:, :, b, depth[b]],
                was[:, :, b, depth[b]])


# ---------------------------------------------- SWA ring / per-row depths --


@settings(max_examples=20, deadline=None)
@given(st.lists(st.integers(min_value=1, max_value=64), min_size=1,
                max_size=6),
       st.integers(min_value=0, max_value=2**31 - 1))
def test_ring_depth_property(depths, seed):
    """Paged and dense decode_attention agree with a per-row oracle built
    from reference_attention across random cache_len vectors, including
    full (ring-wrapped) caches where attend_len == S."""
    S, Hq, Hkv, D = 64, 4, 2, 16
    B = len(depths)
    key = jax.random.PRNGKey(seed % (2**31))
    q, k, v = _qkv(B, S, Hq,Hkv, D, key=key)
    attend = jnp.asarray(depths, jnp.int32)

    paged = paged_decode_attention_jnp(q, k, v, attend, page_size=32)
    dense = decode_attention_ref(q, k, v, attend)
    np.testing.assert_array_equal(np.asarray(paged), np.asarray(dense))

    for b, n in enumerate(depths):     # per-row oracle over the valid prefix
        want = reference_attention(q[b:b + 1], k[b:b + 1, :n],
                                   v[b:b + 1, :n], causal=False)
        np.testing.assert_allclose(paged[b:b + 1], want,
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_ring_depth_oracle_fixed_seeds(seed):
    """Deterministic twin of the hypothesis property (always runs): random
    per-row depths, including attend_len == S (a full ring), against the
    per-row reference_attention oracle."""
    S, Hq, Hkv, D = 64, 4, 2, 16
    rng = np.random.RandomState(seed)
    B = rng.randint(1, 7)
    depths = rng.randint(1, S + 1, size=B)
    depths[rng.randint(B)] = S          # force a wrapped row
    q, k, v = _qkv(B, S, Hq, Hkv, D, key=jax.random.PRNGKey(seed))
    attend = jnp.asarray(depths, jnp.int32)
    paged = paged_decode_attention_jnp(q, k, v, attend, page_size=32)
    np.testing.assert_array_equal(
        np.asarray(paged), np.asarray(decode_attention_ref(q, k, v, attend)))
    for b, n in enumerate(depths):
        want = reference_attention(q[b:b + 1], k[b:b + 1, :n],
                                   v[b:b + 1, :n], causal=False)
        np.testing.assert_allclose(paged[b:b + 1], want,
                                   atol=2e-5, rtol=2e-5)


def test_swa_ring_wrap_decode_loop():
    """Step-by-step SWA decode through a ring-wrapped cache (paged path,
    S=64 pages at 32) matches windowed full attention — per-row depths
    past the wrap keep attending the whole ring."""
    cfgk = dict(n_heads=4, n_kv_heads=2, head_dim=8)
    d_model, W, T = 32, 64, 80
    params = attention_init(KEY, d_model, 4, 2, 8)
    x = 0.3 * jax.random.normal(KEY, (2, T, d_model))
    from repro.nn.attention import attention_apply
    full = attention_apply(params, x, causal=True, window=W,
                           rope_theta=10000.0, **cfgk)
    k_cache = jnp.zeros((2, W, 2, 8))
    v_cache = jnp.zeros((2, W, 2, 8))
    outs = []
    for t in range(T):
        o, k_cache, v_cache = attention_decode_apply(
            params, x[:, t:t + 1], k_cache, v_cache,
            jnp.asarray([t, t], jnp.int32), rope_theta=10000.0, **cfgk)
        outs.append(o)
    stepped = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(stepped, full, atol=2e-4, rtol=2e-4)

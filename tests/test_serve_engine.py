"""Serving engine: batched generation, stop handling, determinism."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import backbone as bb
from repro.serve.engine import Completion, Request, ServeEngine

KEY = jax.random.PRNGKey(0)


def _engine(arch="qwen2-0.5b"):
    cfg = get_config(arch).reduced()
    params = bb.init_params(cfg, KEY)
    return cfg, ServeEngine(cfg, params, max_len=64)


def test_generate_batch_shapes_and_lengths():
    cfg, eng = _engine()
    rng = np.random.RandomState(0)
    reqs = [Request(tokens=rng.randint(0, cfg.vocab, 8), max_new_tokens=5)
            for _ in range(3)]
    outs = eng.generate(reqs)
    assert len(outs) == 3
    for c in outs:
        assert isinstance(c, Completion)
        assert len(c.tokens) == 5
        assert c.tokens.min() >= 0 and c.tokens.max() < cfg.vocab


def test_generate_greedy_deterministic():
    cfg, eng = _engine()
    rng = np.random.RandomState(1)
    prompt = rng.randint(0, cfg.vocab, 8)
    a = eng.generate([Request(tokens=prompt, max_new_tokens=6)])[0]
    b = eng.generate([Request(tokens=prompt, max_new_tokens=6)])[0]
    np.testing.assert_array_equal(a.tokens, b.tokens)


def test_decode_loop_is_single_device_program():
    """The decode phase lowers to one while_loop: no per-token host
    round-trip of logits/tokens inside generation."""
    from functools import partial
    import jax.numpy as jnp
    from repro.serve.engine import _decode_loop

    cfg, eng = _engine()
    batch = {"tokens": jnp.zeros((2, 8), jnp.int32)}
    logits, cache, total_T = bb.prefill(cfg, eng.params, batch, max_len=64)
    jaxpr = jax.make_jaxpr(
        partial(_decode_loop, cfg, buf_len=64, greedy=True))(
        eng.params, logits, cache, total_T, KEY,
        jnp.full((2,), -1, jnp.int32), jnp.full((2,), 6, jnp.int32),
        jnp.int32(6), jnp.float32(1.0))
    prims = {eqn.primitive.name for eqn in jaxpr.eqns}
    assert "while" in prims


def test_generate_respects_per_request_lengths():
    cfg, eng = _engine()
    rng = np.random.RandomState(3)
    prompt = rng.randint(0, cfg.vocab, 8)
    outs = eng.generate([Request(tokens=prompt, max_new_tokens=3),
                         Request(tokens=prompt, max_new_tokens=6)])
    assert len(outs[0].tokens) == 3
    assert len(outs[1].tokens) == 6
    # a 1-token budget holds even inside a larger batch, and an EOS hit
    # on the very first sampled token stops that request immediately
    outs = eng.generate([Request(tokens=prompt, max_new_tokens=1),
                         Request(tokens=prompt, max_new_tokens=6)])
    assert len(outs[0].tokens) == 1
    eos = int(outs[1].tokens[0])
    outs = eng.generate([Request(tokens=prompt, max_new_tokens=6, eos_id=eos),
                         Request(tokens=prompt, max_new_tokens=6)])
    assert outs[0].tokens.tolist() == [eos]
    assert len(outs[1].tokens) == 6


def test_generate_varied_budgets_do_not_recompile():
    """max_new is a traced loop bound: distinct per-call budgets reuse
    one compiled decode program."""
    cfg, eng = _engine()
    rng = np.random.RandomState(4)
    prompt = rng.randint(0, cfg.vocab, 8)
    for n in (3, 5, 7):
        eng.generate([Request(tokens=prompt, max_new_tokens=n)])
    assert eng._loop._cache_size() == 1


def test_per_request_temperatures_in_one_batch():
    """Regression: the engine used requests[0].temperature for the whole
    batch.  A greedy row batched with a sampled row must still produce its
    greedy (argmax) tokens, in one compiled program."""
    cfg, eng = _engine()
    rng = np.random.RandomState(7)
    prompt = rng.randint(0, cfg.vocab, 8)
    ref = eng.generate([Request(tokens=prompt, max_new_tokens=5)])[0]
    outs = eng.generate([
        Request(tokens=prompt, max_new_tokens=5, temperature=0.0),
        Request(tokens=prompt, max_new_tokens=5, temperature=1.4),
    ])
    np.testing.assert_array_equal(outs[0].tokens, ref.tokens)
    assert len(outs[1].tokens) == 5
    assert outs[1].tokens.min() >= 0 and outs[1].tokens.max() < cfg.vocab


def test_extras_key_mismatch_raises():
    """Regression: a batch whose first request carried extras crashed with
    TypeError on the extras-less rows (and extras-less first requests
    silently dropped the others' extras).  Both now raise ValueError."""
    import pytest

    cfg, eng = _engine()
    rng = np.random.RandomState(8)
    prompt = rng.randint(0, cfg.vocab, 8)
    patch = rng.randn(4, 16).astype(np.float32)
    with_ex = Request(tokens=prompt, extras={"patches": patch})
    without = Request(tokens=prompt)
    with pytest.raises(ValueError, match="extras"):
        eng.generate([with_ex, without])
    with pytest.raises(ValueError, match="extras"):
        eng.generate([without, with_ex])


def test_uniform_extras_batch_generates():
    """A batch where every request carries the same extras keys runs the
    vlm prefill path end to end."""
    cfg = get_config("internvl2-1b").reduced()
    params = bb.init_params(cfg, KEY)
    eng = ServeEngine(cfg, params, max_len=64)
    rng = np.random.RandomState(9)
    reqs = [Request(tokens=rng.randint(0, cfg.vocab, 8), max_new_tokens=3,
                    extras={"patches": rng.randn(
                        cfg.vlm.n_patches, cfg.vlm.vision_dim
                    ).astype(np.float32)})
            for _ in range(2)]
    outs = eng.generate(reqs)
    assert [len(c.tokens) for c in outs] == [3, 3]


def test_generate_matches_manual_decode_loop():
    """Engine greedy output == hand-rolled prefill+decode loop."""
    cfg, eng = _engine()
    rng = np.random.RandomState(2)
    prompt = rng.randint(0, cfg.vocab, 8)
    got = eng.generate([Request(tokens=prompt, max_new_tokens=4)])[0].tokens

    import jax.numpy as jnp
    params = eng.params
    batch = {"tokens": jnp.asarray(prompt[None], jnp.int32)}
    logits, cache, total_T = bb.prefill(cfg, params, batch, max_len=64)
    toks = [int(jnp.argmax(logits, -1)[0])]
    cl = total_T
    for _ in range(3):
        t = jnp.asarray([[toks[-1]]], jnp.int32)
        logits, cache = bb.decode_step(cfg, params, t, cache, cl)
        toks.append(int(jnp.argmax(logits, -1)[0]))
        cl += 1
    np.testing.assert_array_equal(got, np.asarray(toks))


# ------------------------------------------- carried-cache decode_step ----


def _xs_ys_decode_step(cfg, params, tokens, cache, cache_len):
    """The decode step as it was before the layer loop carried the cache:
    each superblock's cache slice goes into the scan as xs, is updated
    out of place, and is restacked as ys.  Kept here as the oracle."""
    import jax.numpy as jnp
    from repro.nn.attention import attention_decode_apply
    from repro.nn.linear import embedding_apply
    from repro.nn.ssm import mamba_decode_apply
    from repro.nn.xlstm import mlstm_decode_apply, slstm_decode_apply

    def sublayer(spec, p, x, cache_sb, counters):
        h = bb._norm_apply(cfg, p["norm"], x)
        kind = spec["kind"]
        i = counters[kind]
        counters[kind] += 1
        cache_sb = dict(cache_sb)
        if kind == "attn":
            y, k_new, v_new = attention_decode_apply(
                p["attn"], h, cache_sb["k"][i], cache_sb["v"][i], cache_len,
                n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta)
            cache_sb["k"] = cache_sb["k"].at[i].set(k_new)
            cache_sb["v"] = cache_sb["v"].at[i].set(v_new)
        else:
            leaves = bb._STATE_LEAVES[kind]
            st = {nm: cache_sb[leaf][i] for leaf, nm in leaves}
            if kind == "mamba":
                y, st = mamba_decode_apply(p["mamba"], h, st,
                                           **bb._mamba_kwargs(cfg))
            elif kind == "mlstm":
                y, st = mlstm_decode_apply(p["cell"], h, st,
                                           n_heads=cfg.n_heads)
            else:
                y, st = slstm_decode_apply(p["cell"], h, st,
                                           n_heads=cfg.n_heads)
            for leaf, nm in leaves:
                cache_sb[leaf] = cache_sb[leaf].at[i].set(
                    st[nm].astype(cache_sb[leaf].dtype))
        x, _ = bb._apply_ffn(cfg, spec, p, x + y, dropless=True)
        return x, cache_sb

    specs = bb.sublayer_specs(cfg)

    def body(h, xs):
        sb_params, cache_sb = xs
        counters = {"attn": 0, "mamba": 0, "mlstm": 0, "slstm": 0}
        for spec, p in zip(specs, sb_params):
            h, cache_sb = sublayer(spec, p, h, cache_sb, counters)
        return h, cache_sb

    x = embedding_apply(params["embed"], tokens)
    x, new_cache = jax.lax.scan(body, x, (params["blocks"], cache))
    x = bb._norm_apply(cfg, params["final_norm"], x)
    logits = (x[:, 0].astype(jnp.float32)
              @ bb._readout_weight(cfg, params).astype(jnp.float32))
    return logits, new_cache


@pytest.mark.parametrize("arch,per_row", [
    ("qwen2-0.5b", True), ("qwen2-0.5b", False),
    ("jamba-1.5-large-398b", True), ("jamba-1.5-large-398b", False),
    ("xlstm-350m", False),
])
def test_carried_cache_decode_step_bit_identical_to_xs_ys(arch, per_row):
    """decode_step, which carries the whole cache through its layer loop
    and updates it in place, gives bit for bit the logits and cache of the
    xs/ys form, over several steps from a prefilled cache, with per-row
    (slot pool) and scalar (equal-length engine) cache_len."""
    import dataclasses
    import jax.numpy as jnp

    cfg = get_config(arch).reduced()
    # two superblocks, so the carried superblock index is exercised
    cfg = dataclasses.replace(cfg, n_layers=2 * cfg.superblock)
    params = bb.init_params(cfg, KEY)
    B, T = 3, 6
    tokens = jax.random.randint(KEY, (B, T), 0, cfg.vocab)
    logits, cache, total_T = bb.prefill(cfg, params, {"tokens": tokens},
                                        max_len=64)
    if per_row:
        depth = jnp.asarray([total_T, total_T - 2, total_T - 5], jnp.int32)
    else:
        depth = jnp.int32(total_T)
    new = jax.jit(lambda p, t, c, n: bb.decode_step(cfg, p, t, c, n))
    old = jax.jit(lambda p, t, c, n: _xs_ys_decode_step(cfg, p, t, c, n))
    c_new = c_old = cache
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    for _ in range(4):
        lg_new, c_new = new(params, tok, c_new, depth)
        lg_old, c_old = old(params, tok, c_old, depth)
        np.testing.assert_array_equal(np.asarray(lg_new), np.asarray(lg_old))
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), c_new, c_old)
        tok = jnp.argmax(lg_new, -1)[:, None].astype(jnp.int32)
        depth = depth + 1

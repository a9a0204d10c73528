"""Moonlight-16B-A3B (latent attention, sigmoid-routed experts of which a
device holds a share, a leading dense layer) on the serving path, held to
the plain float32 reference (`tests/moonlight_ref.py`) on seeded random
weights at a reduced size.  Logits are compared, not tokens.

Tolerances.  Program and reference both compute in float32 here; they
differ only in the order of their sums (flash-blocked vs whole softmax,
absorbed vs expanded latent attention in decode, grouped vs per-expert
SwiGLU), so each logit agrees to float32 rounding of sums over a few
hundred terms: `TOL` (5e-5 of the reference logits' standard deviation,
absolute) leaves room over the 3.2e-6 measured and sits far below any
change of mechanism (one expert routed differently moves logits by
~1e-1 of the std).  A routing decision that rounding could flip is a
near-tie of selection scores, which these seeds do not draw.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import moonlight_ref as twin
from repro.models import backbone as bb
from repro.nn.moe import moe_serve
from repro.serve.engine import Request, ServeEngine
from repro.serve.scheduler import (
    ContinuousScheduler,
    SchedulerConfig,
    supports_continuous_batching,
)

TOL = 5e-5          # of the reference logits' std, absolute (docstring)
CFG = twin.program_config()
PARAMS = twin.make_params()


def _err(got, want) -> float:
    """Largest |gap| over the reference logits' standard deviation."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / want.std())


def _prompts(lengths, seed=3):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, CFG.vocab, n).astype(np.int32) for n in lengths]


# ----------------------------------------------------------- model path --


def test_bucketed_prefill_then_decode_match_the_reference():
    """Right-padded group prefill at one bucket, then decode steps with
    per-row depths through the latent cache: the logits of every
    position agree with the reference's full forward."""
    prompts = _prompts((9, 14, 5))
    B, W = len(prompts), 16
    toks = np.zeros((B, W), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    lengths = jnp.asarray([len(p) for p in prompts], jnp.int32)
    logits, cache, _ = bb.prefill(CFG, PARAMS, {"tokens": jnp.asarray(toks)},
                                  max_len=32, lengths=lengths)
    assert set(cache) == {"latent"}
    seqs = [list(p) for p in prompts]
    depth = lengths
    for step in range(4):
        for i, s in enumerate(seqs):
            want = twin.logits(PARAMS, s)[-1]
            assert _err(logits[i], want) < TOL, (step, i)
        nxt = np.asarray(jnp.argmax(logits, -1), np.int32)
        for s, t in zip(seqs, nxt):
            s.append(int(t))
        logits, cache = bb.decode_step(CFG, PARAMS, jnp.asarray(nxt)[:, None],
                                       cache, depth)
        depth = depth + 1


def test_chunked_prefill_matches_the_reference():
    """A prompt prefilled in 8-token segments, pads past its tail: the
    last segment's logits agree with the reference, and so does a decode
    step from the staged cache."""
    (p,) = _prompts((21,), seed=5)
    W, seg = 32, 8
    toks = np.zeros((W,), np.int32)
    toks[:len(p)] = p
    cache = bb.init_cache(CFG, 1, W)
    for d in range(0, W, seg):
        lg, cache = bb.prefill_chunk(
            CFG, PARAMS, jnp.asarray(toks[None, d:d + seg]), cache,
            jnp.int32(d), attend_width=W,
            last_index=jnp.int32(min(max(len(p) - 1 - d, 0), seg - 1)),
            lengths=jnp.asarray([len(p)], jnp.int32))
        if d <= len(p) - 1 < d + seg:
            first = lg
    ref = twin.logits(PARAMS, p)
    assert _err(first[0], ref[-1]) < TOL
    t = int(jnp.argmax(first[0]))
    lg, _ = bb.decode_step(CFG, PARAMS, jnp.asarray([[t]], jnp.int32), cache,
                           jnp.asarray([len(p)], jnp.int32))
    assert _err(lg[0], twin.logits(PARAMS, list(p) + [t])[-1]) < TOL


# ------------------------------------------------------ through serving --


class _FirstLogits:
    """Keeps the logits each admission's first token is sampled from."""

    def __init__(self, sched):
        self.inner, self.sched, self.by_rid = sched._inject, sched, {}
        sched._inject = self

    def __call__(self, pool, slots, rows, logits0, *rest):
        for i, s in enumerate(np.asarray(slots)):
            if s < len(self.sched._slot_rid):
                self.by_rid[self.sched._slot_rid[s]] = np.asarray(logits0[i])
        return self.inner(pool, slots, rows, logits0, *rest)


def _sched(**kw):
    sc = SchedulerConfig(buckets=(16, 32, 64), max_slots=4, page_size=8,
                         prefill_segment=16, chunk=3, prefix_cache=True,
                         **kw)
    s = ContinuousScheduler(CFG, PARAMS, sched=sc, max_len=96)
    return s, _FirstLogits(s)


def _check(prompt, first, tokens):
    """First-token logits and every served token against the reference:
    each served token is the reference's best, up to TOL."""
    seq = list(prompt) + [int(t) for t in tokens]
    ref = twin.logits(PARAMS, seq)[len(prompt) - 1:]
    assert _err(first, ref[0]) < TOL
    for k, t in enumerate(tokens):
        row = ref[k]
        assert (row.max() - row[int(t)]) / row.std() < TOL, k


@pytest.mark.parametrize("lengths", [(9, 12, 14), (40, 7, 60)],
                         ids=["bucketed", "chunked"])
def test_scheduler_serves_what_the_reference_computes(lengths):
    """Group prefill (bucketed, with pads) and chunked prefill (staged
    segments beside decoding rows), then decode through the slot pool."""
    s, firsts = _sched()
    prompts = _prompts(lengths, seed=11)
    rids = [s.submit(Request(tokens=p, max_new_tokens=6)) for p in prompts]
    out = s.run()
    for rid, p in zip(rids, prompts):
        _check(p, firsts.by_rid[rid], out[rid].tokens)


def test_prefix_hit_serves_what_the_reference_computes():
    """A second prompt sharing the first's leading pages is seeded from
    the prefix cache (its latent rows) and prefills only the suffix."""
    s, firsts = _sched()
    (a,) = _prompts((30,), seed=13)
    b = np.concatenate([a[:24], _prompts((6,), seed=14)[0]])
    ra = s.submit(Request(tokens=a, max_new_tokens=4))
    out = s.run()
    rb = s.submit(Request(tokens=b, max_new_tokens=4))
    out.update(s.run())
    assert s.prefix.stats["page_hits"] >= 2     # whole 16-token segments
    for rid, p in ((ra, a), (rb, b)):
        _check(p, firsts.by_rid[rid], out[rid].tokens)


def test_suspend_and_resume_serve_what_the_reference_computes():
    """A row suspended mid-decode and resumed (prompt + its tokens
    prefilled again) ends with the reference's tokens."""
    s, firsts = _sched()
    (p,) = _prompts((13,), seed=17)
    rid = s.submit(Request(tokens=p, max_new_tokens=16))
    for _ in range(2):
        s.step()
    sus = s.suspend(rid)
    assert sus is not None and 0 < len(sus.generated) < 16
    rid2 = s.submit_suspended(sus)
    out = s.run()
    toks = out[rid2].tokens
    assert len(toks) == 16
    np.testing.assert_array_equal(toks[:len(sus.generated)], sus.generated)
    _check(p, firsts.by_rid[rid], toks)


def test_moonshot_is_served_by_the_scheduler(monkeypatch):
    """The corrected registry config passes the scheduler's gate and a
    mixed-length batch never reaches equal-length grouping."""
    assert supports_continuous_batching(CFG)
    assert supports_continuous_batching(
        twin.program_config(dict(twin.MODEL, n_held=0, held_first=0)))
    eng = ServeEngine(CFG, PARAMS, max_len=64)

    def refuse(*a, **k):
        raise AssertionError("equal-length grouping")

    monkeypatch.setattr(eng, "_generate_equal", refuse)
    outs = eng.generate([Request(tokens=p, max_new_tokens=3)
                         for p in _prompts((5, 11, 8), seed=19)])
    assert [len(c.tokens) for c in outs] == [3, 3, 3]


# ------------------------------------------------------------ expert layer --


def _layer_params(held: int, first: int, layer: int = 0):
    """One expert layer of the twin's weights with 8 held experts, cut to
    `held` of them from `first`."""
    full = twin.make_params(model=dict(twin.MODEL, n_held=8, held_first=0))
    p = jax.tree.map(lambda a: a[layer], full["blocks"][0])
    mo = dict(p["moe"])
    for k in ("gate", "up", "down"):
        mo[k] = mo[k][first:first + held]
    return p, mo


def test_expert_shares_add_up_to_the_uncut_layer():
    """The parts of the four 2-expert shares of a layer, plus the shared
    experts counted once, equal the uncut reference's whole layer."""
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 7, CFG.d_model))
    p, _ = _layer_params(8, 0)
    total = jnp.zeros(x.shape, jnp.float32)
    for first in range(0, 8, 2):
        _, mo = _layer_params(2, first)
        spec = dataclasses.replace(CFG.moe, n_held=2, held_first=first)
        part, st = moe_serve(mo, x, spec)
        total = total + part
    sh = p["shared"]
    total = total + jax.nn.silu(x @ sh["gate"]["w"]) * (x @ sh["up"]["w"]) \
        @ sh["down"]["w"]
    uncut = dict(twin.MODEL, n_held=8, held_first=0)
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda r: twin.ref._moe(uncut, p, r, None))(x)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-5 * float(jnp.std(want)))


def test_pads_and_idle_rows_route_nothing():
    """Rows marked invalid route no token: the live rows' outputs equal
    those of a run over the live rows alone, and only their routings are
    counted; junk in the idle rows of a decode step changes no live
    row's logits."""
    _, mo = _layer_params(2, 2)
    x = jax.random.normal(jax.random.PRNGKey(4), (4, 6, CFG.d_model))
    valid = jnp.asarray([[1, 1, 1, 0, 0, 0], [1] * 6, [0] * 6,
                         [1, 1, 0, 0, 0, 0]], bool)
    y, st = moe_serve(mo, x, CFG.moe, valid=valid)
    for b in range(4):
        n = int(valid[b].sum())
        if n:
            alone, _ = moe_serve(mo, x[b:b + 1, :n], CFG.moe)
            np.testing.assert_allclose(np.asarray(y[b, :n]),
                                       np.asarray(alone[0]), atol=1e-6)
    _, st_live = moe_serve(mo, x[1:2], CFG.moe)
    _, st_all = moe_serve(mo, x, CFG.moe)
    assert int(st["routed_local"]) < int(st_all["routed_local"])
    assert int(st_live["routed_local"]) <= int(st["routed_local"])

    prompts = _prompts((7, 7, 7), seed=23)
    _, cache, T = bb.prefill(CFG, PARAMS, {"tokens": jnp.asarray(prompts)},
                             max_len=16)
    live = jnp.asarray([True, False, True])
    depth = jnp.full((3,), T, jnp.int32)
    outs = []
    for junk in (0, 99):
        tok = jnp.asarray([[1], [junk], [2]], jnp.int32)
        lg, _, counts = bb.decode_step_counted(CFG, PARAMS, tok, cache, depth,
                                               live)
        outs.append(np.asarray(lg))
        assert 0 < int(counts[1]) <= 2 * CFG.moe.top_k * 2
    np.testing.assert_array_equal(outs[0][[0, 2]], outs[1][[0, 2]])


# ------------------------------------------------------------------ kernels --


@pytest.mark.parametrize("tm", [16, 128])
def test_held_experts_kernel_matches_the_oracle(tm):
    """The Pallas grouped SwiGLU (interpret mode) against `ragged_dot`,
    on the serving layer's own layout: empty experts skipped, rows of
    several tiles per expert, idle tiles past the live count unread."""
    from repro.kernels.expert_ffn.ops import held_experts_ffn
    from repro.nn.moe import held_layout
    rng = np.random.RandomState(tm)
    E, d, f, R = 4, 128, 256, 40
    ks = jax.random.split(jax.random.PRNGKey(tm), 4)
    w = [jax.random.normal(k, s) / np.sqrt(s[1]) for k, s in zip(
        ks, [(E, d, f), (E, d, f), (E, f, d)])]
    idx = jnp.asarray(rng.choice([0, 2, 3], R), jnp.int32)   # expert 1 idle
    local = jnp.asarray(rng.rand(R) < 0.8)
    dest, tile_group, n_tiles, padded, counts = held_layout(idx, local, E, tm)
    M = tile_group.shape[0] * tm
    x = jax.random.normal(ks[3], (R, d))
    rows = jnp.zeros((M, d)).at[dest].set(x, mode="drop")
    w = [jnp.stack([a * 0, a]) for a in w]      # layer 1 of a 2-layer stack
    one = jnp.int32(1)
    got = held_experts_ffn(rows, *w, tile_group, n_tiles, padded, one, tm=tm,
                           interpret=True)
    want = held_experts_ffn(rows, *w, tile_group, n_tiles, padded, one, tm=tm)
    live = np.asarray(dest)[np.asarray(local)]
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=1e-5, atol=1e-5)
    assert int(counts[1]) == 0 and int(counts.sum()) == int(local.sum())


def test_mla_decode_kernel_matches_the_dense_rows():
    """The paged kernel on a latent pool (interpret mode): it writes each
    row's new latent row in place and attends every head to the rows,
    whose first kv_lora_rank entries are the values, as the dense
    float32 reference does."""
    from repro.kernels.decode_attention.ops import mla_decode_attention_op
    from repro.kernels.decode_attention.ref import decode_attention_ref
    B, S, H, Dl, r, page = 3, 256, 4, 80, 64, 128
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    pool = jax.random.normal(ks[0], (2, 1, B, S, 1, Dl))
    q = jax.random.normal(ks[1], (B, 1, H, Dl))
    new = jax.random.normal(ks[2], (B, 1, Dl))
    depth = jnp.asarray([5, 130, 200], jnp.int32)
    layer = (jnp.int32(1), jnp.int32(0))
    out, pool2 = mla_decode_attention_op(
        q, pool, depth + 1, layer, new, depth, page_size=page,
        interpret=True, scale=0.1, dv=r)
    want_pool = pool.at[1, 0, jnp.arange(B), depth].set(new)
    np.testing.assert_array_equal(np.asarray(pool2), np.asarray(want_pool))
    want = decode_attention_ref(q, want_pool[1, 0], None, depth + 1,
                                scale=0.1, dv=r)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)

"""Shared-prefix page cache: bit-identity, refcounts, and the quantized
host tier.

Acceptance claim first: with prefix sharing ON, a workload of clients
sharing a system prompt produces greedy tokens *bit-identical* to
sharing OFF, while the hit rate is deterministic and > 0 and measurably
less prefill work runs.  The sharing machinery (chain-hashed page keys,
refcounted physical pages, seed-the-prefix/prefill-the-suffix
admissions) must be invisible in the tokens because a page's K/V is a
pure function of the token prefix through it — the hash key — and the
suffix chunks attend at the full bucket width, the same
segment-vs-one-shot identity chunked prefill already guarantees.

The cold tier is *lossy by design* (the transmission codec turned
inward), so its tests bound the reconstruction error by the codebook's
step size and check the demote/promote lifecycle instead of
bit-identity; bit-exact runs keep their working set inside
`prefix_hot_pages` (pinned pages never demote, so live slots are always
exact).
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import backbone as bb
from repro.serve.engine import Request
from repro.serve.prefix_cache import PrefixCache, page_keys
from repro.serve.scheduler import ContinuousScheduler, SchedulerConfig

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def system():
    cfg = get_config("qwen2-0.5b").reduced()
    params = bb.init_params(cfg, KEY)
    return cfg, params


def _sched(cfg, params, *, prefix=True, max_len=64, clock=None, **kw):
    base = dict(buckets=(8, 16, 32), max_slots=4, prefill_group=2, chunk=4,
                page_size=8, prefix_cache=prefix)
    base.update(kw)
    return ContinuousScheduler(cfg, params, max_len=max_len, clock=clock,
                               sched=SchedulerConfig(**base))


def _shared_workload(cfg, *, seed=0, sys_len=20, tails=(4, 9, 12, 4, 7, 12,
                                                        3, 9)):
    rng = np.random.RandomState(seed)
    sys_prompt = rng.randint(0, cfg.vocab, sys_len)
    return [Request(tokens=np.concatenate(
                [sys_prompt, rng.randint(0, cfg.vocab, L)]),
                    max_new_tokens=4)
            for L in tails]


def _run(sched, reqs):
    rids = [sched.submit(r) for r in reqs]
    outs = sched.run()
    return [outs[r].tokens.tolist() for r in rids]


# ------------------------------------------------------------ page keys --


def test_page_keys_chain_over_full_prefix():
    """Two prompts share page p's key only when they agree on *every*
    token before the page's end — causal K/V depends on the whole
    prefix, so a same-content page at a different history must not
    collide."""
    a = np.arange(32)
    ka = page_keys(a, 8)
    assert len(ka) == 3                       # page holding token 31 excluded
    b = a.copy()
    b[0] += 1                                 # divergence inside page 0
    kb = page_keys(b, 8)
    assert all(x != y for x, y in zip(ka, kb))
    c = a.copy()
    c[9] += 1                                 # divergence inside page 1
    kc = page_keys(c, 8)
    assert kc[0] == ka[0] and kc[1] != ka[1] and kc[2] != ka[2]


def test_page_keys_exclude_last_token_page():
    """The page holding the final prompt token is never shareable: the
    admission must compute that position itself for its first-token
    logits."""
    assert page_keys(np.arange(8), 8) == []            # T == page
    assert len(page_keys(np.arange(9), 8)) == 1        # page 0 full + final
    assert len(page_keys(np.arange(17), 8)) == 2


# ----------------------------------------------------- acceptance check --


def test_shared_prefix_tokens_bit_identical(system):
    """Acceptance: N clients sharing a system prompt decode the exact
    greedy tokens sharing-off produces, the hit rate is > 0, and a rerun
    reproduces tokens and stats bit-for-bit."""
    cfg, params = system
    reqs = _shared_workload(cfg)
    off = _run(_sched(cfg, params, prefix=False), reqs)
    on_sched = _sched(cfg, params)
    on = _run(on_sched, reqs)
    assert on == off
    pc = on_sched.prefix
    assert pc.hit_rate > 0 and pc.stats["page_hits"] > 0
    again = _sched(cfg, params)
    assert _run(again, reqs) == on
    assert again.prefix.stats == pc.stats          # deterministic hit rate


@pytest.mark.parametrize("overlap", [False, True])
def test_bit_identity_holds_in_both_overlap_modes(system, overlap):
    cfg, params = system
    reqs = _shared_workload(cfg, seed=3)
    off = _run(_sched(cfg, params, prefix=False, overlap=overlap), reqs)
    on = _run(_sched(cfg, params, overlap=overlap), reqs)
    assert on == off


def test_staged_long_prompts_seed_from_shared_pages(system):
    """Long admissions (chunked prefill) seed resident pages and start
    staging past them — tokens stay bit-identical and whole-segment
    seeding registers hits."""
    cfg, params = system
    rng = np.random.RandomState(5)
    sys_prompt = rng.randint(0, cfg.vocab, 24)
    reqs = [Request(tokens=np.concatenate(
                [sys_prompt, rng.randint(0, cfg.vocab, L)]),
                    max_new_tokens=4)
            for L in (12, 20, 12, 6, 20)]     # bucket 48 > segment 16
    kw = dict(buckets=(8, 16, 24, 48), prefill_segment=16, max_len=96)
    off = _run(_sched(cfg, params, prefix=False, **kw), reqs)
    s = _sched(cfg, params, **kw)
    assert _run(s, reqs) == off
    assert s.prefix.hit_rate > 0


def test_prefix_sharing_saves_prefill_work(system):
    """The point of the tentpole: sharing must run measurably less
    prefill.  Counted as prefilled token-positions across the group path
    (rows x bucket) and the chunk path (chunk widths)."""
    cfg, params = system
    reqs = _shared_workload(cfg, seed=4)

    def counted(sched):
        work = {"tok": 0}
        gp, cp = sched._prefill, sched._prefill_chunk

        def prefill(params, tokens, lengths, *, max_len):
            work["tok"] += int(np.sum(np.asarray(lengths)))
            return gp(params, tokens, lengths, max_len=max_len)

        def chunk(params, toks, cache, depth, **kw):
            work["tok"] += toks.shape[0] * toks.shape[1]
            return cp(params, toks, cache, depth, **kw)

        sched._prefill, sched._prefill_chunk = prefill, chunk
        _run(sched, reqs)
        return work["tok"]

    off = counted(_sched(cfg, params, prefix=False))
    on = counted(_sched(cfg, params))
    assert on < off


# ---------------------------------------------------- ownership / refs --


def test_refcounts_pin_during_occupancy_and_release_after(system):
    """While a slot lives, its pages are pinned (a tiny hot budget
    cannot demote them); after run() every ref is dropped and the
    budget is enforced."""
    cfg, params = system
    reqs = _shared_workload(cfg, seed=6)
    sched = _sched(cfg, params, prefix_hot_pages=1, kv_tier_mb=4.0)
    rids = [sched.submit(r) for r in reqs]
    seen_pinned = False
    while sched._queue or sched._staging or sched._slots.any_occupied():
        sched.step()
        pinned = [e for e in sched.prefix._index.values() if e.refs > 0]
        seen_pinned = seen_pinned or bool(pinned)
        assert all(e.hot is not None for e in pinned), \
            "a referenced page must stay device-resident"
    assert seen_pinned
    assert all(e.refs == 0 for e in sched.prefix._index.values())
    assert not sched.prefix._slot_keys
    assert sched.prefix.n_hot <= 1            # budget enforced once unpinned
    assert sorted(sched._results) == sorted(rids)


class _Clock:
    """Deterministic wall clock: every read advances by one tick."""

    def __init__(self, tick: float):
        self.t = 0.0
        self.tick = tick

    def __call__(self) -> float:
        self.t += self.tick
        return self.t


def test_deadline_eviction_releases_refs(system):
    """A pooled slot deadline-evicted mid-decode drops its page refs —
    nothing stays pinned by a dead request."""
    cfg, params = system
    reqs = _shared_workload(cfg, seed=7, tails=(4, 9))
    sched = _sched(cfg, params, max_slots=2, prefill_group=1, chunk=2,
                   clock=_Clock(0.01))
    ra = sched.submit(Request(tokens=reqs[0].tokens, max_new_tokens=40,
                              deadline_s=0.055))
    sched.submit(reqs[1])
    outs = sched.run()
    assert outs[ra].timed_out and 0 < len(outs[ra].tokens) < 40
    assert all(e.refs == 0 for e in sched.prefix._index.values())
    assert not sched.prefix._slot_keys


# ------------------------------------------------------------ cold tier --


def _unit_cache(**kw):
    base = dict(hot_pages=4, cold_bytes=1 << 20, bits=8)
    base.update(kw)
    return PrefixCache(8, **base)


def _fake_rows(rng, n_pages, page=8):
    shape = (2, 1, n_pages * page, 2, 4)      # (n_sb, n_attn, W, n_kv, hd)
    return (rng.standard_normal(shape).astype(np.float32),
            rng.standard_normal(shape).astype(np.float32))


def test_cold_roundtrip_error_bounded_by_codebook_step():
    """Demote -> promote reconstructs every element within half the
    uniform codebook's step over the page's own range."""
    rng = np.random.default_rng(0)
    pc = _unit_cache(hot_pages=0, bits=8)
    keys = page_keys(np.arange(9), 8)
    k, v = _fake_rows(rng, 1)
    pc.pin(0, keys, {"k": k, "v": v})
    pc.release(0)                             # unpinned -> demoted cold
    assert pc.n_hot == 0 and pc.n_cold == 1
    assert pc.stats["demotions"] == 1
    got = pc.fetch(keys)
    assert pc.stats["promotions"] == 1
    for orig, rec in ((k, got["k"]), (v, got["v"])):
        ref = orig[:, :, :8]
        step = (ref.max() - ref.min()) / (2 ** 8 - 1)
        assert np.max(np.abs(np.asarray(rec) - ref)) <= step / 2 + 1e-6


def test_promoted_page_keeps_cold_blob_and_never_requantizes():
    """Demote -> promote -> demote again must reuse the original blob
    (re-quantizing a reconstruction would compound the loss)."""
    rng = np.random.default_rng(1)
    pc = _unit_cache(hot_pages=0)
    keys = page_keys(np.arange(9), 8)
    k, v = _fake_rows(rng, 1)
    pc.pin(0, keys, {"k": k, "v": v})
    pc.release(0)
    first = pc.fetch(keys)
    pc._enforce_budgets()                     # hot budget 0: demote again
    assert pc.stats["demotions"] == 2
    second = pc.fetch(keys)
    np.testing.assert_array_equal(np.asarray(first["k"]),
                                  np.asarray(second["k"]))


def test_cold_budget_drops_lru_pages():
    """Cold blobs past cold_bytes drop oldest-first; a dropped page is a
    clean miss on the next lookup, never a corrupt hit."""
    rng = np.random.default_rng(2)
    one_page_cold = None
    pc = _unit_cache(hot_pages=0, cold_bytes=1 << 30)
    keys = page_keys(np.arange(9), 8)
    k, v = _fake_rows(rng, 1)
    pc.pin(0, keys, {"k": k, "v": v})
    pc.release(0)
    one_page_cold = pc.cold_used_bytes
    assert one_page_cold > 0

    pc = _unit_cache(hot_pages=0, cold_bytes=2 * one_page_cold)
    toks = [np.arange(9) + 100 * i for i in range(3)]
    for i, t in enumerate(toks):
        kk, vv = _fake_rows(rng, 1)
        pc.pin(i, page_keys(t, 8), {"k": kk, "v": vv})
        pc.release(i)
    assert pc.cold_used_bytes <= 2 * one_page_cold
    assert pc.stats["cold_drops"] == 1
    assert pc.lookup(toks[0])[1] == 0         # the LRU page is gone
    assert pc.lookup(toks[2])[1] == 1


def test_zero_cold_budget_drops_on_demotion():
    rng = np.random.default_rng(3)
    pc = _unit_cache(hot_pages=0, cold_bytes=0)
    keys = page_keys(np.arange(9), 8)
    k, v = _fake_rows(rng, 1)
    pc.pin(0, keys, {"k": k, "v": v})
    pc.release(0)
    assert pc.stats["hot_drops"] == 1
    assert pc.n_hot == pc.n_cold == 0
    assert pc.lookup(np.arange(9))[1] == 0


def test_end_to_end_demote_promote_through_scheduler(system):
    """Two admission waves under a 2-page hot budget: wave B's hits
    promote pages wave A demoted, and every request still completes."""
    cfg, params = system
    rng = np.random.RandomState(8)
    sysp = rng.randint(0, cfg.vocab, 24)
    sched = ContinuousScheduler(
        cfg, params, max_len=64,
        sched=SchedulerConfig(buckets=(8, 16, 32), max_slots=2,
                              prefill_group=2, chunk=4, page_size=8,
                              prefix_cache=True, prefix_hot_pages=2,
                              kv_tier_mb=8.0))

    def wave():
        reqs = [Request(tokens=np.concatenate(
                    [sysp, rng.randint(0, cfg.vocab, 6)]),
                        max_new_tokens=2) for _ in range(3)]
        rids = [sched.submit(r) for r in reqs]
        outs = sched.run()
        assert all(len(outs[r].tokens) == 2 for r in rids)

    wave()
    assert sched.prefix.stats["demotions"] > 0
    wave()
    assert sched.prefix.stats["promotions"] > 0
    assert all(e.refs == 0 for e in sched.prefix._index.values())

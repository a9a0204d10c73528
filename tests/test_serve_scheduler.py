"""Continuous-batching scheduler invariants.

Every correctness claim is checked against the one-request-at-a-time
reference (the engine's equal-length fast path, which PR 1 proved equal
to a hand-rolled prefill+decode loop): bucket padding must not leak into
outputs, evict/inject must preserve the surviving slots' cache contents,
and no request may be starved by other buckets.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import backbone as bb
from repro.serve.engine import Request, ServeEngine
from repro.serve.scheduler import (
    ContinuousScheduler,
    SchedulerConfig,
    supports_continuous_batching,
)

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def system():
    cfg = get_config("qwen2-0.5b").reduced()
    params = bb.init_params(cfg, KEY)
    return cfg, params


def _engine(cfg, params, **sched_kw):
    kw = dict(buckets=(8, 16, 32), max_slots=4, prefill_group=2, chunk=4)
    kw.update(sched_kw)
    return ServeEngine(cfg, params, max_len=64,
                       scheduler=SchedulerConfig(**kw))


def _reference(eng, req) -> np.ndarray:
    """One-request-at-a-time greedy decode via the fast path."""
    return eng.generate([req])[0].tokens


# ------------------------------------------------------- acceptance check --


def test_mixed_queue_matches_per_request_greedy(system):
    """24 mixed-length requests ({8, 16, 32} prompts) through the
    scheduler produce exactly the tokens per-request decoding produces."""
    cfg, params = system
    eng = _engine(cfg, params)
    rng = np.random.RandomState(0)
    lengths = [8, 16, 32] * 8
    rng.shuffle(lengths)
    reqs = [Request(tokens=rng.randint(0, cfg.vocab, L), max_new_tokens=4)
            for L in lengths]
    outs = eng.generate(reqs)
    assert len(outs) == 24
    for req, got in zip(reqs, outs):
        np.testing.assert_array_equal(got.tokens, _reference(eng, req))


def test_bucket_padding_never_leaks(system):
    """Off-bucket prompts (5 -> bucket 8, 11 -> 16, 27 -> 32) decode to
    the same tokens as the unpadded per-request reference."""
    cfg, params = system
    eng = _engine(cfg, params)
    rng = np.random.RandomState(1)
    reqs = [Request(tokens=rng.randint(0, cfg.vocab, L), max_new_tokens=5)
            for L in (5, 11, 27, 5)]
    outs = eng.generate(reqs)
    for req, got in zip(reqs, outs):
        assert len(got.tokens) == 5
        np.testing.assert_array_equal(got.tokens, _reference(eng, req))


def test_evict_inject_preserves_slot_cache(system):
    """A 2-slot pool over 6 staggered-budget requests forces several
    evict/inject cycles mid-decode; surviving slots must keep decoding as
    if alone (their cache rows untouched by neighbours swapping)."""
    cfg, params = system
    eng = _engine(cfg, params, max_slots=2, prefill_group=1, chunk=2)
    rng = np.random.RandomState(2)
    lens = [8, 16, 8, 32, 16, 8]
    buds = [2, 9, 5, 3, 7, 4]          # finish at different segments
    reqs = [Request(tokens=rng.randint(0, cfg.vocab, L), max_new_tokens=n)
            for L, n in zip(lens, buds)]
    outs = eng.generate(reqs)
    for req, got in zip(reqs, outs):
        assert len(got.tokens) == req.max_new_tokens
        np.testing.assert_array_equal(got.tokens, _reference(eng, req))


def test_no_request_starved_across_buckets(system):
    """FIFO head-bucket admission: a lone bucket-32 request buried in a
    stream of bucket-8 arrivals still completes (and every rid is
    returned exactly once)."""
    cfg, params = system
    sched = ContinuousScheduler(
        cfg, params, max_len=64,
        sched=SchedulerConfig(buckets=(8, 16, 32), max_slots=2,
                              prefill_group=2, chunk=2))
    rng = np.random.RandomState(3)
    rids = []
    for i in range(10):
        L = 32 if i == 4 else 8
        rids.append(sched.submit(Request(
            tokens=rng.randint(0, cfg.vocab, L), max_new_tokens=3)))
    outs = sched.run()
    assert sorted(outs) == sorted(rids)
    for rid in rids:
        assert len(outs[rid].tokens) == 3


# -------------------------------------------------- in-graph per-request --


def test_per_request_eos_and_temperature_in_pool(system):
    """EOS ids and sampling temperatures are per-slot, in-graph: a greedy
    row keeps its reference tokens while a sampled row runs at its own
    temperature, and an EOS hit stops only that request."""
    cfg, params = system
    eng = _engine(cfg, params)
    rng = np.random.RandomState(4)
    p8 = rng.randint(0, cfg.vocab, 8)
    p16 = rng.randint(0, cfg.vocab, 16)
    ref8 = _reference(eng, Request(tokens=p8, max_new_tokens=6))
    eos = int(ref8[2])
    stop = int(np.argmax(ref8 == eos)) + 1   # first greedy eos hit

    outs = eng.generate([
        Request(tokens=p8, max_new_tokens=6, eos_id=eos),
        Request(tokens=p16, max_new_tokens=6, temperature=1.3),
        Request(tokens=p8, max_new_tokens=6),
    ])
    np.testing.assert_array_equal(outs[0].tokens, ref8[:stop])  # stops at eos
    assert len(outs[1].tokens) == 6
    assert outs[1].tokens.min() >= 0 and outs[1].tokens.max() < cfg.vocab
    np.testing.assert_array_equal(outs[2].tokens, ref8)       # full budget


def test_overlap_matches_serialized(system):
    """The pipelined scheduler (chunk dispatched before the host blocks,
    drain one round behind, admissions double-buffered) produces exactly
    the serialized scheduler's greedy tokens on a mixed queue with
    chunked-prefill admissions in it."""
    cfg, params = system
    rng = np.random.RandomState(6)
    reqs = [Request(tokens=rng.randint(0, cfg.vocab, L), max_new_tokens=4)
            for L in (8, 16, 100, 5, 27, 16, 8, 120)]

    def tokens_with(overlap):
        sched = ContinuousScheduler(
            cfg, params, max_len=192,
            sched=SchedulerConfig(buckets=(8, 16, 32, 64, 128),
                                  max_slots=4, prefill_group=2, chunk=4,
                                  prefill_segment=32, overlap=overlap))
        rids = [sched.submit(r) for r in reqs]
        outs = sched.run()
        assert sorted(outs) == sorted(rids)
        return [outs[r].tokens for r in rids]

    for a, b in zip(tokens_with(True), tokens_with(False)):
        np.testing.assert_array_equal(a, b)


def test_mesh_engine_routes_equal_lengths_through_scheduler(system):
    """A meshed engine must not silently drop its sharding: equal-length
    batches go through the (sharded) scheduler, not the single-device
    fast path, and still match the per-request reference."""
    from repro.launch.mesh import make_serving_mesh
    cfg, params = system
    eng = ServeEngine(cfg, params, max_len=64,
                      mesh=make_serving_mesh(data=1, model=1),
                      scheduler=SchedulerConfig(buckets=(8, 16, 32),
                                                max_slots=2, prefill_group=2,
                                                chunk=4))
    ref = ServeEngine(cfg, params, max_len=64)
    rng = np.random.RandomState(9)
    reqs = [Request(tokens=rng.randint(0, cfg.vocab, 16), max_new_tokens=4)
            for _ in range(3)]
    outs = eng.generate(reqs)
    assert eng._sched is not None          # scheduler path, not fast path
    for req, got in zip(reqs, outs):
        np.testing.assert_array_equal(got.tokens,
                                      ref.generate([req])[0].tokens)


# ------------------------------------------------------------- gating -----


def test_unsupported_arch_falls_back_to_length_groups():
    """MoE/hybrid/absolute-position archs are gated out of the scheduler;
    mixed-length generate still works via equal-length grouping."""
    cfg = get_config("mixtral-8x7b").reduced()
    assert not supports_continuous_batching(cfg)
    params = bb.init_params(cfg, KEY)
    eng = ServeEngine(cfg, params, max_len=64)
    rng = np.random.RandomState(5)
    reqs = [Request(tokens=rng.randint(0, cfg.vocab, L), max_new_tokens=2)
            for L in (8, 12, 8)]
    outs = eng.generate(reqs)
    assert [len(c.tokens) for c in outs] == [2, 2, 2]
    # grouping preserves request order: re-running one request alone
    # reproduces its grouped tokens
    np.testing.assert_array_equal(outs[1].tokens,
                                  eng.generate([reqs[1]])[0].tokens)


def test_scheduler_rejects_unsupported_arch():
    cfg = get_config("jamba-1.5-large-398b").reduced()
    assert not supports_continuous_batching(cfg)
    with pytest.raises(AssertionError):
        ContinuousScheduler(cfg, bb.init_params(cfg, KEY), max_len=32)


# ------------------------------------------------- deadlines and faults ---


class _Clock:
    """Deterministic wall clock: every read advances by one tick."""

    def __init__(self, tick: float):
        self.t = 0.0
        self.tick = tick

    def __call__(self) -> float:
        self.t += self.tick
        return self.t


def _fault_sched(cfg, params, *, overlap=False, clock=None, faults=None,
                 **kw):
    base = dict(buckets=(8, 16, 32), max_slots=2, prefill_group=1, chunk=2,
                prefill_segment=8, overlap=overlap)
    base.update(kw)
    return ContinuousScheduler(cfg, params, max_len=64,
                               sched=SchedulerConfig(**base),
                               clock=clock, faults=faults)


def test_deadline_evicts_queued_and_pooled(system):
    """An expired queued request resolves empty; a pooled request evicts
    between chunks with the tokens generated so far — a prefix of its
    reference decode — and a deadline-free neighbour is untouched."""
    cfg, params = system
    eng = _engine(cfg, params)
    rng = np.random.RandomState(7)
    pa = rng.randint(0, cfg.vocab, 8)
    pb = rng.randint(0, cfg.vocab, 8)
    pc = rng.randint(0, cfg.vocab, 8)
    ref_a = _reference(eng, Request(tokens=pa, max_new_tokens=40))
    ref_b = _reference(eng, Request(tokens=pb, max_new_tokens=4))

    sched = _fault_sched(cfg, params, clock=_Clock(0.01))
    ra = sched.submit(Request(tokens=pa, max_new_tokens=40, deadline_s=0.055))
    rb = sched.submit(Request(tokens=pb, max_new_tokens=4))
    rc = sched.submit(Request(tokens=pc, max_new_tokens=4, deadline_s=0.001))
    outs = sched.run()
    assert sorted(outs) == sorted([ra, rb, rc])
    assert outs[rc].timed_out and len(outs[rc].tokens) == 0
    assert outs[ra].timed_out
    assert 0 < len(outs[ra].tokens) < 40
    np.testing.assert_array_equal(outs[ra].tokens,
                                  ref_a[:len(outs[ra].tokens)])
    assert not outs[rb].timed_out
    np.testing.assert_array_equal(outs[rb].tokens, ref_b)
    assert not sched._slots.any_occupied() and not sched._deadlines


def test_deadline_aborts_staging_and_slot_is_reused(system):
    """A chunked-prefill admission whose deadline lapses mid-staging
    frees its claimed slot; a later request reuses the slot and decodes
    its reference tokens."""
    cfg, params = system
    eng = _engine(cfg, params)
    rng = np.random.RandomState(8)
    long_p = rng.randint(0, cfg.vocab, 32)
    short_p = rng.randint(0, cfg.vocab, 8)
    ref_short = _reference(eng, Request(tokens=short_p, max_new_tokens=3))

    sched = _fault_sched(cfg, params, clock=_Clock(0.01), max_slots=1)
    rl = sched.submit(Request(tokens=long_p, max_new_tokens=4,
                              deadline_s=0.015))
    rs = sched.submit(Request(tokens=short_p, max_new_tokens=3))
    outs = sched.run()
    assert outs[rl].timed_out and len(outs[rl].tokens) == 0
    np.testing.assert_array_equal(outs[rs].tokens, ref_short)
    assert not sched._slots.any_occupied()


@pytest.mark.parametrize("overlap", [False, True])
def test_stalled_pool_exits_via_deadline_eviction(system, overlap):
    """Acceptance: a permanently stalled decode pool cannot hang run() —
    every deadline-carrying request leaves through deadline eviction."""
    from repro.serve.faults import FaultInjector, SlotPoolStall
    cfg, params = system
    rng = np.random.RandomState(9)
    sched = _fault_sched(cfg, params, overlap=overlap, clock=_Clock(0.01),
                         faults=FaultInjector((SlotPoolStall(),)))
    rids = [sched.submit(Request(tokens=rng.randint(0, cfg.vocab, 8),
                                 max_new_tokens=4, deadline_s=0.04))
            for _ in range(4)]
    outs = sched.run()
    assert sorted(outs) == sorted(rids)
    assert all(outs[r].timed_out for r in rids)
    assert not sched._slots.any_occupied()


def test_bounded_stall_only_delays_decode(system):
    """A stall window without deadlines delays rounds but changes no
    tokens — requests decode their exact reference output after it."""
    from repro.serve.faults import FaultInjector, SlotPoolStall
    cfg, params = system
    eng = _engine(cfg, params)
    rng = np.random.RandomState(10)
    reqs = [Request(tokens=rng.randint(0, cfg.vocab, L), max_new_tokens=4)
            for L in (8, 16, 8)]
    sched = _fault_sched(cfg, params,
                         faults=FaultInjector((SlotPoolStall(0, 3),)))
    rids = [sched.submit(r) for r in reqs]
    outs = sched.run()
    for r, rid in zip(reqs, rids):
        assert not outs[rid].timed_out
        np.testing.assert_array_equal(outs[rid].tokens, _reference(eng, r))


def test_idle_injector_and_generous_deadlines_keep_tokens(system):
    """Acceptance (bit-identity): an empty fault schedule and deadlines
    that never fire leave the scheduler's greedy tokens unchanged, in
    both overlap modes."""
    from repro.serve.faults import FaultInjector
    cfg, params = system
    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, cfg.vocab, L) for L in (8, 16, 32, 8)]

    def tokens(deadline, faults, overlap):
        sched = _fault_sched(cfg, params, overlap=overlap, faults=faults)
        rids = [sched.submit(Request(tokens=p, max_new_tokens=4,
                                     deadline_s=deadline))
                for p in prompts]
        outs = sched.run()
        assert not any(outs[r].timed_out for r in rids)
        return [outs[r].tokens for r in rids]

    for overlap in (False, True):
        plain = tokens(None, None, overlap)
        faulted = tokens(1e6, FaultInjector(()), overlap)
        for a, b in zip(plain, faulted):
            np.testing.assert_array_equal(a, b)


def test_deadline_churn_preserves_slot_invariants(system):
    """Satellite: repeated deadline-evict/readmit cycles on a width-2
    pool never leak or double-assign a slot, and every rid resolves
    exactly once (overlap mode's stale snapshot must not complete a
    readmitted slot's new occupant)."""
    cfg, params = system
    rng = np.random.RandomState(12)
    sched = _fault_sched(cfg, params, overlap=True, clock=_Clock(0.005))
    rids = []
    for i in range(12):
        rids.append(sched.submit(Request(
            tokens=rng.randint(0, cfg.vocab, 8), max_new_tokens=30,
            deadline_s=0.03 + 0.015 * (i % 4))))
    outs = sched.run()
    assert sorted(outs) == sorted(rids)       # exactly once each
    assert all(outs[r].timed_out for r in rids)
    assert not sched._slots.any_occupied() and not sched._deadlines
    assert not sched._staging and sched._pending is None


def test_long_prompts_bucket_at_page_granularity(system):
    """Satellite (compile-cache bound): prompts above every configured
    bucket round up to the next page_size multiple instead of bucketing
    at their raw length — distinct long lengths share one prefill
    compilation (watched through the telemetry compile counter, not a
    private cache poke), and tokens still match the reference."""
    from repro.serve.telemetry import Telemetry
    cfg, params = system
    eng = _engine(cfg, params)
    tel = Telemetry(enabled=True)
    sched = ContinuousScheduler(
        cfg, params, max_len=64,
        sched=SchedulerConfig(buckets=(8, 16), max_slots=4,
                              prefill_group=2, chunk=4, page_size=16,
                              prefill_segment=0),   # group path only
        telemetry=tel)
    assert sched._bucket_of(33) == 48
    assert sched._bucket_of(41) == 48
    assert sched._bucket_of(63) == 64               # capped at max_len
    rng = np.random.RandomState(14)
    reqs = [Request(tokens=rng.randint(0, cfg.vocab, L), max_new_tokens=4)
            for L in (33, 37, 41, 45)]
    rids = [sched.submit(r) for r in reqs]
    outs = sched.run()
    assert tel.compile_count("sched.prefill") == 1, \
        "four long lengths in one page bucket must share one compilation"
    assert tel.counter("jit.sched.prefill.compiles", shape="bucket48").n == 1
    for req, rid in zip(reqs, rids):
        np.testing.assert_array_equal(outs[rid].tokens,
                                      _reference(eng, req))


def test_steady_state_decode_zero_recompiles(system):
    """Satellite (telemetry compile counter): once a first drain has paid
    the per-bucket prefill and fixed-width decode-chunk compiles, an
    identically shaped second workload must record zero new jit
    compilations — the steady-state guarantee the CI gate watches."""
    from repro.serve.telemetry import Telemetry
    cfg, params = system
    tel = Telemetry(enabled=True)
    sched = ContinuousScheduler(
        cfg, params, max_len=64,
        sched=SchedulerConfig(buckets=(8, 16), max_slots=4,
                              prefill_group=2, chunk=4),
        telemetry=tel)
    rng = np.random.RandomState(21)

    def batch():
        for L in (8, 16, 8, 16):
            sched.submit(Request(tokens=rng.randint(0, cfg.vocab, L),
                                 max_new_tokens=3))
        sched.run()

    batch()                                 # pays every compile
    warm = tel.compile_count("sched")
    assert warm >= 3                        # two prefill buckets + chunk
    batch()                                 # same shapes: steady state
    assert tel.compile_count("sched") == warm, \
        "steady-state decode recompiled"


def test_stale_snapshot_skips_readmitted_slot(system):
    """Satellite: under overlap, a slot deadline-evicted between a
    chunk's dispatch and its `_drain_pending`, then re-admitted, must
    not be completed from the stale snapshot (`p["rids"][i] == rid`):
    the new occupant decodes its own full reference and no slot leaks."""
    cfg, params = system
    eng = _engine(cfg, params)
    rng = np.random.RandomState(15)
    pa = rng.randint(0, cfg.vocab, 8)
    pb = rng.randint(0, cfg.vocab, 8)
    ref_a = _reference(eng, Request(tokens=pa, max_new_tokens=40))
    ref_b = _reference(eng, Request(tokens=pb, max_new_tokens=4))

    sched = _fault_sched(cfg, params, overlap=True, max_slots=1,
                         clock=_Clock(0.005))
    ra = sched.submit(Request(tokens=pa, max_new_tokens=40,
                              deadline_s=0.06))
    rb = sched.submit(Request(tokens=pb, max_new_tokens=4))
    outs = sched.run()
    assert sorted(outs) == sorted([ra, rb])   # each resolved exactly once
    # the evictee kept its own partial decode (a prefix of its reference)
    assert outs[ra].timed_out and 0 < len(outs[ra].tokens) < 40
    np.testing.assert_array_equal(outs[ra].tokens,
                                  ref_a[:len(outs[ra].tokens)])
    # the slot's new occupant was admitted while ra's snapshot was still
    # pending; that snapshot must not have completed it early or with the
    # evictee's buffer
    assert not outs[rb].timed_out
    np.testing.assert_array_equal(outs[rb].tokens, ref_b)
    assert not sched._slots.any_occupied() and sched._pending is None
    assert not sched._deadlines and not sched._staging


def test_engine_routes_deadlines_through_scheduler(system):
    """Equal-length requests carrying deadlines leave the fast path (it
    cannot evict) and still produce the fast path's tokens when the
    deadline never fires."""
    cfg, params = system
    eng = _engine(cfg, params)
    rng = np.random.RandomState(13)
    p = rng.randint(0, cfg.vocab, 16)
    ref = _reference(eng, Request(tokens=p, max_new_tokens=4))
    outs = eng.generate([Request(tokens=p, max_new_tokens=4,
                                 deadline_s=1e6)])
    assert eng._sched is not None             # scheduler path was taken
    assert not outs[0].timed_out
    np.testing.assert_array_equal(outs[0].tokens, ref)


def test_decode_chunk_updates_the_pool_in_place(system):
    """The compiled decode chunk never copies, broadcasts or rebuilds the
    KV pool: no copy, broadcast or dynamic-update-slice in its optimized
    HLO has the pool's K/V shape (the layer loop carries the pool and
    writes one token per row and layer into it)."""
    import re

    import jax.numpy as jnp

    cfg, params = system
    sched = ContinuousScheduler(
        cfg, params, max_len=64,
        sched=SchedulerConfig(buckets=(8, 16), max_slots=4,
                              prefill_group=2, chunk=4))
    pool = sched._pool
    k = pool["cache"]["k"]
    text = sched._chunk.lower(params, pool, jnp.ones((4,), bool), KEY,
                              jnp.int32(4)).compile().as_text()
    shape = rf"f32\[{','.join(map(str, k.shape))}\]"
    ops = re.findall(rf"= {shape}\S* ([a-z-]+)\(", text)
    assert ops, "the pool's shape does not appear in the chunk's HLO"
    bad = {"copy", "broadcast", "dynamic-update-slice"} & set(ops)
    assert not bad, (bad, sorted(set(ops)))


@pytest.mark.parametrize("arch", ["arctic-480b", "moonshot-v1-16b-a3b"])
def test_dropless_moe_queue_matches_per_request_greedy(arch):
    """MoE configs serve through the scheduler: dropless expert layers
    with pads routing nothing, so a mixed-length queue (bucketed and
    padded beside other rows) gives the tokens each request gets alone."""
    cfg = get_config(arch).reduced()
    assert supports_continuous_batching(cfg)
    params = bb.init_params(cfg, KEY)
    eng = _engine(cfg, params)
    rng = np.random.RandomState(1)
    reqs = [Request(tokens=rng.randint(0, cfg.vocab, L), max_new_tokens=4)
            for L in (5, 16, 9, 30, 12)]
    outs = eng.generate(reqs)
    for req, got in zip(reqs, outs):
        np.testing.assert_array_equal(got.tokens, _reference(eng, req))

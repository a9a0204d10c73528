"""Fault injection and graceful degradation across the serving stack.

Three layers of claims: the channel's bounded-retry/backoff/deadline
arithmetic (closed forms), the gateway's degradation ladder (fault-free
runs bit-identical with an idle injector attached; a total blackout
resolves EVERY request as a Local-NN fallback whose logits are the
fleet's own local pass bitwise and agree with the standalone per-image
local path within float32 reassociation; corruption degrades to the ERASED floor,
never crashes), and the decode scheduler's deadline eviction (a stalled
slot pool cannot hang `run()`)."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agreement import FAULT_MARGIN, miss
from repro.compress.lzw import (
    PayloadCorruptionError,
    compress_payload,
    lzw_decode,
    pack_indices,
    packed_nbytes,
    unpack_indices,
    unpack_indices_batch,
)
from repro.configs.agilenn_cifar import AgileNNConfig
from repro.configs.base import AgileSpec
from repro.core.agile import (
    agile_forward, init_agile_params, remote_forward_jit,
)
from repro.serve.faults import (
    Blackout,
    BurstLoss,
    DeviceStall,
    FaultInjector,
    GatewayStall,
    LinkDegrade,
    PayloadCorruption,
    SlotPoolStall,
    parse_faults,
)
from repro.serve.gateway import (
    NARROWBAND,
    WIFI_UDP,
    Channel,
    ChannelConfig,
    ClientSpec,
    Fleet,
    GatewayConfig,
    OffloadGateway,
    mixed_fleet,
)
from repro.serve.gateway.channel import RETRY_SAFETY_CAP
from repro.serve.scheduler import SlotError, SlotPool

KEY = jax.random.PRNGKey(9)
CFG = AgileNNConfig(image_size=16, remote_width=16, remote_blocks=2,
                    reference_width=16, reference_blocks=2,
                    agile=AgileSpec(enabled=True, extractor_channels=24, k=5,
                                    rho=0.8, lam=0.3, ig_steps=2))
PARAMS = init_agile_params(CFG, KEY)


# ------------------------------------------------- parameter validation ---

@pytest.mark.parametrize("kw", [
    {"bandwidth_bps": -1.0}, {"bandwidth_bps": 0.0},
    {"propagation_s": -1e-3}, {"jitter_s": -1e-3},
    {"drop_prob": -0.1}, {"drop_prob": 1.5},
    {"retransmit_timeout_s": 0.0}, {"retransmit_timeout_s": -0.1},
    {"max_attempts": -1}, {"backoff_mult": 0.5},
    {"backoff_max_s": 0.0}, {"backoff_jitter": -0.1},
])
def test_channel_config_rejects_bad_params(kw):
    with pytest.raises(ValueError, match=next(iter(kw))):
        ChannelConfig(**kw)


@pytest.mark.parametrize("kw", [
    {"channel": "wifi"}, {"arrival_rate_hz": 0.0},
    {"arrival_rate_hz": -5.0}, {"n_requests": -1},
    {"slo_ms": 0.0}, {"deadline_ms": 0.0}, {"deadline_ms": -10.0},
])
def test_client_spec_rejects_bad_params(kw):
    with pytest.raises(ValueError, match=next(iter(kw))):
        ClientSpec(**kw)


@pytest.mark.parametrize("make", [
    lambda: Blackout(0.2, 0.1),
    lambda: BurstLoss(p_good_bad=1.5),
    lambda: LinkDegrade(bandwidth_scale=0.0),
    lambda: LinkDegrade(extra_loss=2.0),
    lambda: DeviceStall(stall_s=0.0),
    lambda: GatewayStall(stall_s=-1.0),
    lambda: PayloadCorruption(prob=0.0),
    lambda: SlotPoolStall(5, 5),
])
def test_fault_events_reject_bad_params(make):
    with pytest.raises(ValueError):
        make()


def test_gateway_config_rejects_bad_params():
    with pytest.raises(ValueError, match="batch_width"):
        GatewayConfig(batch_width=0)
    with pytest.raises(ValueError, match="batch_window_s"):
        GatewayConfig(batch_window_s=-1e-3)


def test_injector_rejects_unknown_event():
    with pytest.raises(ValueError, match="unknown fault event"):
        FaultInjector(("not-a-fault",))


# -------------------------------------------- channel retry arithmetic ---

def test_backoff_waits_closed_form():
    """mult=2 doubles the retry wait, capped at backoff_max_s; the default
    mult=1.0 reproduces the fixed timeout bit-exactly."""
    cfg = ChannelConfig(bandwidth_bps=1e6, drop_prob=1.0, max_attempts=5,
                        retransmit_timeout_s=0.1, backoff_mult=2.0,
                        backoff_max_s=0.3, propagation_s=0.0)
    d = Channel(cfg, seed=0).transmit(1250, t_send=0.0)   # ser = 10 ms
    assert d.attempts == 5 and d.delivered
    # waits: 0.1, 0.2, min(0.4, 0.3), min(0.8, 0.3)
    assert d.device_free_s == pytest.approx(5 * 0.01 + 0.1 + 0.2 + 0.3 + 0.3)
    fixed = ChannelConfig(bandwidth_bps=1e6, drop_prob=1.0, max_attempts=3,
                          retransmit_timeout_s=0.1)
    df = Channel(fixed, seed=0).transmit(1250, t_send=0.0)
    assert df.device_free_s == pytest.approx(3 * 0.01 + 2 * 0.1)


def test_backoff_jitter_bounded_and_deterministic():
    cfg = ChannelConfig(bandwidth_bps=1e6, drop_prob=1.0, max_attempts=4,
                        retransmit_timeout_s=0.1, backoff_jitter=0.5)
    a = Channel(cfg, seed=7).transmit(1250, 0.0)
    b = Channel(cfg, seed=7).transmit(1250, 0.0)
    assert a == b
    base = 4 * 0.01 + 3 * 0.1
    assert base <= a.device_free_s <= base + 3 * 0.05 + 1e-12


def test_deadline_stops_retries_as_expired():
    """No retry is attempted past deadline_s: the transmit returns a
    failed, expired delivery the moment the next wait cannot land."""
    cfg = ChannelConfig(bandwidth_bps=1e6, drop_prob=1.0, max_attempts=8,
                        retransmit_timeout_s=0.1)
    d = Channel(cfg, seed=0).transmit(1250, t_send=0.0, deadline_s=0.25)
    assert not d.delivered and d.expired
    # attempts at 0.01, 0.12, 0.23; the wait to 0.34 crosses the deadline
    assert d.attempts == 3
    assert d.arrive_s == d.device_free_s == pytest.approx(0.23)


def test_attempt_overrunning_deadline_is_expired_not_delivered():
    """Satellite: a single attempt whose serialization alone overruns
    deadline_s is a deadline miss — the payload would land late, so the
    transmit reports expired, not a clean delivery.  A deadline the
    attempt beats leaves the closed forms bit-exact."""
    cfg = ChannelConfig(bandwidth_bps=1e6, propagation_s=0.0)
    late = Channel(cfg, seed=0).transmit(125000, 0.0, deadline_s=0.5)
    assert not late.delivered and late.expired      # ser = 1.0 s > 0.5 s
    assert late.attempts == 1
    assert late.arrive_s == late.device_free_s == pytest.approx(1.0)
    ok = Channel(cfg, seed=0).transmit(125000, 0.0, deadline_s=1.5)
    assert ok.delivered and not ok.expired
    assert ok.device_free_s == pytest.approx(1.0)


def test_retry_forever_terminates_under_total_loss():
    """Satellite: max_attempts=0 ("app retries forever") + a 100%-loss
    link must terminate as a failed delivery at the safety cap, never
    hang the event loop."""
    cfg = ChannelConfig(bandwidth_bps=1e8, drop_prob=1.0, max_attempts=0,
                        retransmit_timeout_s=1e-4)
    d = Channel(cfg, seed=0).transmit(100, t_send=0.0)
    assert not d.delivered and not d.expired
    assert d.attempts == RETRY_SAFETY_CAP


def test_forced_loss_has_no_final_attempt_rescue():
    """Benign i.i.d. loss delivers on the final attempt (the app keeps
    retrying); a fault-forced loss does not — a dark link delivers
    nothing."""
    cfg = ChannelConfig(bandwidth_bps=1e6, drop_prob=1.0, max_attempts=4,
                        retransmit_timeout_s=0.01)
    assert Channel(cfg, seed=0).transmit(1250, 0.0).delivered
    inj = FaultInjector((Blackout(),), seed=0)
    d = Channel(cfg, seed=0).transmit(1250, 0.0, link=inj.link(0))
    assert not d.delivered and not d.expired and d.attempts == 4


def test_degrade_scales_bandwidth_and_airtime():
    inj = FaultInjector((LinkDegrade(0.0, 10.0, bandwidth_scale=0.5),))
    cfg = ChannelConfig(bandwidth_bps=1e6, propagation_s=0.0)
    d = Channel(cfg, seed=0).transmit(1250, 0.0, link=inj.link(3))
    assert d.delivered and d.attempts == 1
    assert d.airtime_s == pytest.approx(0.02)      # 10 ms doubled
    clean = Channel(cfg, seed=0).transmit(1250, 20.0, link=inj.link(3))
    assert clean.airtime_s == pytest.approx(0.01)  # window over


def test_fault_schedule_replays_deterministically():
    """Same (schedule, seed): identical forced-loss sequences; fault
    randomness is per-client, so interleaving clients doesn't perturb
    either stream."""
    sched = (BurstLoss(0.0, 1.0, p_good_bad=0.3, p_bad_good=0.3),
             LinkDegrade(0.0, 1.0, extra_loss=0.2))
    a = FaultInjector(sched, seed=4)
    b = FaultInjector(sched, seed=4)
    seq_a = [a.link(1).attempt_lost(t) for t in np.linspace(0, 0.9, 50)]
    # interleave a second client's draws into b only
    seq_b = []
    for t in np.linspace(0, 0.9, 50):
        b.link(2).attempt_lost(t)
        seq_b.append(b.link(1).attempt_lost(t))
    assert seq_a == seq_b
    assert any(seq_a) and not all(seq_a)


def test_parse_faults_round_trip():
    sched = parse_faults(
        "blackout:0.05:0.2; burst:0:1:0.2:0.4; degrade:0:1:0.5:0.1;"
        "devstall:0:1:0.03; gwstall:0:1:0.02; corrupt:0:1:0.3")
    kinds = [type(e).__name__ for e in sched]
    assert kinds == ["Blackout", "BurstLoss", "LinkDegrade", "DeviceStall",
                     "GatewayStall", "PayloadCorruption"]
    assert sched[1] == BurstLoss(0.0, 1.0, p_good_bad=0.2, p_bad_good=0.4)
    assert sched[2] == LinkDegrade(0.0, 1.0, bandwidth_scale=0.5,
                                   extra_loss=0.1)
    assert parse_faults("blackout") == (Blackout(),)
    with pytest.raises(ValueError, match="unknown fault kind"):
        parse_faults("meteor:0:1")


# ------------------------------------------------- hardened LZW decode ---

def test_lzw_decode_rejects_corruption_typed():
    """Random truncations and bit flips of valid code streams raise
    `PayloadCorruptionError` (or survive decode into a frame the length
    check catches) — never KeyError/IndexError."""
    rng = np.random.RandomState(0)
    bits, n = 3, 19 * 16
    expect = packed_nbytes(bits, n)
    caught = 0
    for trial in range(60):
        idx = rng.randint(0, 1 << bits, size=n)
        _, codes = compress_payload(pack_indices(idx, bits))
        bad = list(codes)
        if rng.randint(2) and len(bad) > 1:
            bad = bad[:rng.randint(1, len(bad))]
        else:
            i = rng.randint(len(bad))
            bad[i] = int(bad[i]) ^ (1 << rng.randint(14))
        if bad == list(codes):
            continue
        try:
            data = lzw_decode(bad)
        except PayloadCorruptionError:
            caught += 1
            continue
        if len(data) != expect:
            caught += 1                 # framing length check catches it
    assert caught > 10                  # corruption is actually detected


def test_lzw_decode_rejects_bad_head_and_types():
    with pytest.raises(PayloadCorruptionError):
        lzw_decode([300, 0])            # head must be a literal byte
    with pytest.raises(PayloadCorruptionError):
        lzw_decode([-1])
    with pytest.raises(PayloadCorruptionError):
        lzw_decode([5, 99999])          # far past next_code


def test_unpack_indices_rejects_short_frames():
    idx = np.arange(16) % 4
    packed = pack_indices(idx, 2)
    with pytest.raises(PayloadCorruptionError):
        unpack_indices(packed[:-1], 2, 16)
    with pytest.raises(PayloadCorruptionError):
        unpack_indices_batch([packed, packed[:-1]], 2, 16)
    np.testing.assert_array_equal(unpack_indices(packed, 2, 16), idx)


# ------------------------------------------------- gateway degradation ---

def _trace_key(r):
    return [(t.client, t.req, t.t_born, t.t_sent, t.t_arrive, t.t_serve,
             t.t_done, t.e2e_s, t.energy_j, t.attempts, t.status,
             t.deadline_missed) for t in r.traces]


def _run(specs, *, seed=0, width=4, faults=None, gw=None):
    fleet = Fleet(CFG, PARAMS, specs, seed=seed)
    report = OffloadGateway(
        CFG, PARAMS, fleet, gw or GatewayConfig(batch_width=width),
        faults=faults).run()
    return fleet, report


def test_idle_injector_is_bit_identical_to_none():
    """Acceptance: with faults disabled (empty schedule) every trace and
    every logit is bit-identical to a run with no injector at all."""
    specs = mixed_fleet(6, n_requests=3, slo_ms=8.0, deadline_ms=500.0)
    _, plain = _run(specs, seed=5)
    _, idle = _run(specs, seed=5, faults=FaultInjector(()))
    assert _trace_key(plain) == _trace_key(idle)
    assert all(np.array_equal(a.logits, b.logits)
               for a, b in zip(plain.traces, idle.traces))
    assert plain.fallback_rate == idle.fallback_rate == 0.0


# float32 reassociation: the fleet's batched device pass against one
# image at a time (measured: 3.0e-8 on the local logits)
LOCAL_RTOL = 1e-5
LOCAL_ATOL_FRAC = 1e-5        # of max|per-image local logit|


def _blackout_local_miss(params=PARAMS) -> float:
    """Run a 6-request fleet under a run-long blackout, built with
    `params`, and return how far its fallback logits land from the
    per-image local path of PARAMS, in tolerances.  Every request must
    complete as a Local-NN fallback whose logits are the fleet's own
    local pass, bitwise (one program) — including with the retry-forever
    channel config."""
    forever = dataclasses.replace(WIFI_UDP, max_attempts=0,
                                  retransmit_timeout_s=1e-3)
    specs = (ClientSpec(channel=WIFI_UDP, n_requests=3),
             ClientSpec(channel=forever, n_requests=3))
    fleet = Fleet(CFG, params, specs, seed=0)
    report = OffloadGateway(CFG, params, fleet, GatewayConfig(batch_width=4),
                            faults=FaultInjector((Blackout(),))).run()
    assert len(report.traces) == 6          # nothing hangs, nothing lost
    assert report.fallback_rate == 1.0
    assert all(t.status == "fallback" for t in report.traces)
    got, want = [], []
    for t in report.traces:
        row = fleet.clients[t.client].row0 + t.req
        np.testing.assert_array_equal(t.logits, fleet.local_logits[row])
        image = jnp.asarray(fleet.images[row:row + 1])
        ref = np.asarray(agile_forward(
            CFG, PARAMS, image, train=False)[1]["local_logits"])[0]
        if params is PARAMS:
            assert t.pred == int(np.argmax(ref))
        got.append(t.logits)
        want.append(ref)
    return miss(np.stack(got), np.stack(want), rtol=LOCAL_RTOL,
                atol_frac=LOCAL_ATOL_FRAC)


def test_total_blackout_all_fallback_agrees_with_local():
    """Acceptance: under a run-long blackout every request completes as a
    Local-NN fallback whose logits agree with the standalone local path
    within the written tolerance, with the same predictions."""
    assert _blackout_local_miss() <= 1


def test_total_blackout_tolerance_catches_dropped_channel():
    """A device whose extractor drops the first local feature channel
    (always zero) misses the standalone local logits by FAULT_MARGIN
    tolerances or more."""
    c = int(PARAMS["mapping"][0])
    conv = PARAMS["extractor"]["convs"][-1]
    dropped = dict(PARAMS, extractor=dict(PARAMS["extractor"], convs=[
        *PARAMS["extractor"]["convs"][:-1],
        {"w": conv["w"].at[..., c].set(0), "b": conv["b"].at[c].set(0)}]))
    assert _blackout_local_miss(dropped) >= FAULT_MARGIN


def test_fault_run_fixed_seed_determinism():
    """Acceptance: a chaos schedule replays identically run-to-run."""
    sched = (Blackout(0.02, 0.1), BurstLoss(0.0, 2.0, p_good_bad=0.3),
             PayloadCorruption(0.0, 2.0, prob=0.5),
             DeviceStall(0.0, 0.5, stall_s=0.01),
             GatewayStall(0.0, 0.5, stall_s=0.01))
    specs = mixed_fleet(8, n_requests=3, deadline_ms=120.0)
    _, r1 = _run(specs, faults=FaultInjector(sched, seed=11))
    _, r2 = _run(specs, faults=FaultInjector(sched, seed=11))
    assert len(r1.traces) == 24
    assert _trace_key(r1) == _trace_key(r2)
    assert all(np.array_equal(a.logits, b.logits)
               for a, b in zip(r1.traces, r2.traces))
    # and a different fault seed actually changes the run
    _, r3 = _run(specs, faults=FaultInjector(sched, seed=12))
    assert _trace_key(r1) != _trace_key(r3)


def test_corruption_degrades_to_erased_floor():
    """Detected corruption serves with every offloaded channel
    zero-filled: logits equal Remote-NN-on-zeros + combine, and no
    exception leaks.  (A bit flip can land on another valid code and
    slip through as a well-framed payload — without checksums that is
    undetectable, and such requests stay 'served'.)"""
    specs = (ClientSpec(channel=WIFI_UDP, n_requests=8),)
    fleet, report = _run(
        specs, faults=FaultInjector((PayloadCorruption(prob=1.0),), seed=2))
    assert len(report.traces) == 8
    assert report.degraded_rate > 0.5
    fh, Cr = fleet.feat_hw, fleet.n_remote
    for t in report.traces:
        if t.status != "degraded":
            continue
        row = fleet.clients[t.client].row0 + t.req
        ref = np.asarray(remote_forward_jit(
            PARAMS, jnp.zeros((1, fh, fh, Cr), jnp.float32),
            jnp.asarray(fleet.local_logits[row:row + 1]),
            temperature=CFG.agile.alpha_temperature))[0]
        np.testing.assert_array_equal(t.logits, ref)


def test_deadline_sheds_and_marks_misses():
    """A stalled gateway + tight deadlines: requests that cannot be
    served in time resolve as shed/fallback at their deadline instant —
    every request still resolves exactly once."""
    sched = (GatewayStall(0.0, 100.0, stall_s=0.25),)
    specs = mixed_fleet(6, n_requests=3, deadline_ms=60.0)
    fleet, report = _run(specs, faults=FaultInjector(sched), width=2)
    assert len(report.traces) == 18
    seen = {(t.client, t.req) for t in report.traces}
    assert len(seen) == 18
    assert report.deadline_miss_rate > 0
    for t in report.traces:
        deadline = t.t_born + 0.060
        if t.status in ("shed", "fallback") and t.deadline_missed:
            assert t.t_done <= deadline + 1e-12
            row = fleet.clients[t.client].row0 + t.req
            np.testing.assert_array_equal(t.logits, fleet.local_logits[row])
        elif t.status == "served":
            assert t.deadline_missed == (t.t_done > deadline)


def test_edf_admission_serves_tightest_deadline_first():
    """While a stalled width-1 pool is busy, a later-arriving narrowband
    request with the tightest deadline jumps the queued WiFi request
    (EDF); without deadlines the same fleet admits in arrival order."""
    def specs(deadlines):
        d0, d1, d2 = deadlines
        return (ClientSpec(channel=WIFI_UDP, n_requests=1,
                           arrival_rate_hz=1e4, deadline_ms=d0),
                ClientSpec(channel=WIFI_UDP, n_requests=1,
                           arrival_rate_hz=1e4, deadline_ms=d1),
                ClientSpec(channel=NARROWBAND, n_requests=1,
                           arrival_rate_hz=1e4, deadline_ms=d2))
    gw = GatewayConfig(batch_width=1)
    stall = FaultInjector((GatewayStall(0.0, 100.0, stall_s=0.1),))
    _, report = _run(specs((5000.0, 5000.0, 300.0)), gw=gw, faults=stall)
    by_client = {t.client: t for t in report.traces}
    assert len(by_client) == 3
    assert all(t.status == "served" for t in report.traces)
    # the narrowband client arrived last, while the first batch held the
    # only slot; both later requests were queued at its completion ...
    first = min(report.traces, key=lambda t: t.t_serve)
    queued = [t for t in report.traces if t is not first]
    assert by_client[2] in queued
    assert by_client[2].t_arrive == max(t.t_arrive for t in report.traces)
    assert all(t.t_arrive < first.t_serve + 0.1 for t in queued)
    # ... and its tighter deadline won the freed slot over the WiFi
    # request queued ahead of it
    other = next(t for t in queued if t is not by_client[2])
    assert by_client[2].t_serve < other.t_serve
    # without deadlines the same contention resolves FIFO
    stall2 = FaultInjector((GatewayStall(0.0, 100.0, stall_s=0.1),))
    _, fifo = _run(specs((None, None, None)), gw=gw, faults=stall2)
    order = sorted(fifo.traces, key=lambda t: t.t_serve)
    arrivals = sorted(fifo.traces, key=lambda t: t.t_arrive)
    assert [t.client for t in order] == [t.client for t in arrivals]


def test_device_and_gateway_stalls_stretch_latency():
    specs = (ClientSpec(channel=WIFI_UDP, n_requests=2),)
    _, base = _run(specs)
    _, stalled = _run(specs, faults=FaultInjector(
        (DeviceStall(0.0, 100.0, stall_s=0.02),
         GatewayStall(0.0, 100.0, stall_s=0.03),)))
    assert len(stalled.traces) == 2
    assert stalled.latency_percentile_ms(50) >= \
        base.latency_percentile_ms(50) + 20.0


# ------------------------------------------------- slot pool churn -------

def test_slot_pool_churn_never_leaks_or_double_assigns():
    """Satellite: randomized acquire/release/preempt/resume churn
    preserves the pool invariants — free() and occupied() partition the
    slots, double acquire and double/foreign release raise SlotError,
    release returns the occupant exactly once, and a preempted rid can
    resume on any free slot (not necessarily the one it vacated)."""
    rng = np.random.RandomState(0)
    pool = SlotPool(6)
    live = {}
    suspended = []
    next_rid = 0
    for _ in range(500):
        choice = rng.randint(4)
        if live and (len(pool.free()) == 0 or choice == 0):
            # plain drain: release without owner check
            slot = int(rng.choice(sorted(live)))
            assert pool.release(slot) == live.pop(slot)
        elif live and choice == 1:
            # preempt: owner-checked release parks the rid off-pool
            slot = int(rng.choice(sorted(live)))
            rid = live.pop(slot)
            assert pool.release(slot, rid) == rid
            suspended.append(rid)
        elif suspended and pool.free() and choice == 2:
            # resume: the suspended rid re-admits on any free slot
            rid = suspended.pop(int(rng.randint(len(suspended))))
            slot = int(rng.choice(pool.free()))
            pool.acquire(slot, rid)
            live[slot] = rid
        elif pool.free():
            slot = int(rng.choice(pool.free()))
            pool.acquire(slot, next_rid)
            live[slot] = next_rid
            next_rid += 1
        free, occ = set(pool.free()), dict(pool.occupied())
        assert free | set(occ) == set(range(6)) and not free & set(occ)
        assert occ == live
        assert len(pool) == 6
    if not pool.free():
        slot0 = sorted(live)[0]
        pool.release(slot0)
        live.pop(slot0)
    slot = pool.free()[0]
    pool.acquire(slot, next_rid)
    with pytest.raises(SlotError, match="already occupied"):
        pool.acquire(slot, next_rid + 1)
    # foreign-owner release is rejected without freeing the slot ...
    with pytest.raises(SlotError, match="owned by"):
        pool.release(slot, next_rid + 1)
    assert pool.rids[slot] == next_rid
    # ... and releasing a free slot twice is a typed error, not a no-op
    assert pool.release(slot, next_rid) == next_rid
    with pytest.raises(SlotError, match="released twice"):
        pool.release(slot)


def test_gateway_pool_returns_to_empty_after_chaos():
    """Fault-driven shed/fallback churn never leaks a gateway feature
    slot: after any chaos run the pool is fully free."""
    sched = (Blackout(0.01, 0.08), PayloadCorruption(prob=0.4),
             GatewayStall(0.0, 0.2, stall_s=0.05))
    specs = mixed_fleet(6, n_requests=4, deadline_ms=80.0)
    fleet = Fleet(CFG, PARAMS, specs, seed=1)
    gw = OffloadGateway(CFG, PARAMS, fleet, GatewayConfig(batch_width=3),
                        faults=FaultInjector(sched, seed=5))
    report = gw.run()
    assert len(report.traces) == 24
    assert gw._slots.free() == list(range(3))
    assert not gw._slots.any_occupied()

"""Multi-client offload gateway: channel/codec/controller units, fleet
determinism (bitwise: one program run twice), and agreement of the
static path with the per-image offload runtime within float32
reassociation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from agreement import FAULT_MARGIN, miss
from repro.compress.lzw import (
    compress_payload,
    lzw_decode,
    pack_indices,
    pack_indices_batch,
    unpack_indices,
    unpack_indices_batch,
)
from repro.configs.agilenn_cifar import AgileNNConfig
from repro.configs.base import AgileSpec
from repro.core.agile import agile_forward, device_forward, init_agile_params
from repro.serve.gateway import (
    LOSSY_WIFI,
    NARROWBAND,
    WIFI_UDP,
    Channel,
    ChannelConfig,
    ClientSpec,
    Fleet,
    GatewayConfig,
    OffloadGateway,
    RateController,
    default_ladder,
    mixed_fleet,
    requantize,
    subset_centers,
)
from repro.serve.offload import run_offload_inference

KEY = jax.random.PRNGKey(9)
CFG = AgileNNConfig(image_size=16, remote_width=16, remote_blocks=2,
                    reference_width=16, reference_blocks=2,
                    agile=AgileSpec(enabled=True, extractor_channels=24, k=5,
                                    rho=0.8, lam=0.3, ig_steps=2))
PARAMS = init_agile_params(CFG, KEY)
# float32 reassociation: the fleet's batched device pass and the gateway's
# padded batches against one image at a time (measured: 8.9e-8 on logits)
OFFLOAD_RTOL = 1e-5
OFFLOAD_ATOL_FRAC = 1e-5      # of max|per-image logit|


# ------------------------------------------------------------- channel ---

def test_channel_clean_link_closed_form():
    ch = Channel(ChannelConfig(bandwidth_bps=1e6, propagation_s=5e-3), seed=0)
    d = ch.transmit(1250, t_send=1.0)          # 10 kbit at 1 Mbps = 10 ms
    assert d.attempts == 1
    assert d.airtime_s == pytest.approx(0.01)
    assert d.device_free_s == pytest.approx(1.01)
    assert d.arrive_s == pytest.approx(1.015)


def test_channel_full_loss_retransmits_to_cap():
    cfg = ChannelConfig(bandwidth_bps=1e6, drop_prob=1.0,
                        retransmit_timeout_s=0.1, max_attempts=4)
    d = Channel(cfg, seed=0).transmit(1250, t_send=0.0)
    assert d.attempts == 4                     # final attempt delivers
    assert d.airtime_s == pytest.approx(4 * 0.01)
    assert d.device_free_s == pytest.approx(4 * 0.01 + 3 * 0.1)


def test_channel_deterministic_and_lossy_slower():
    a = Channel(LOSSY_WIFI, seed=3)
    b = Channel(LOSSY_WIFI, seed=3)
    da = [a.transmit(200, i * 0.1) for i in range(20)]
    db = [b.transmit(200, i * 0.1) for i in range(20)]
    assert da == db
    clean = Channel(WIFI_UDP, seed=3)
    assert sum(d.airtime_s for d in da) > \
        sum(clean.transmit(200, i * 0.1).airtime_s for i in range(20))


def test_narrowband_slower_than_wifi():
    wifi = Channel(WIFI_UDP, seed=0).transmit(1000, 0.0)
    nb = Channel(NARROWBAND, seed=0).transmit(1000, 0.0)
    assert nb.airtime_s / wifi.airtime_s == pytest.approx(6e6 / 270e3)


# --------------------------------------------------------------- codec ---

@pytest.mark.parametrize("bits", [1, 2, 3, 4, 8])
def test_unpack_indices_roundtrip(bits):
    rng = np.random.RandomState(bits)
    idx = rng.randint(0, 1 << bits, size=(5, 77))
    packed = pack_indices_batch(idx, bits)
    for row, data in zip(idx, packed):
        np.testing.assert_array_equal(
            unpack_indices(data, bits, 77), row)
    np.testing.assert_array_equal(
        unpack_indices_batch(packed, bits, 77), idx)


def test_unpack_survives_lzw_roundtrip():
    rng = np.random.RandomState(0)
    idx = rng.randint(0, 8, size=(4, 4, 19))
    packed = pack_indices(idx, 3)
    nbytes, codes = compress_payload(packed)
    assert 0 < nbytes
    np.testing.assert_array_equal(
        unpack_indices(lzw_decode(codes), 3, idx.size).reshape(idx.shape),
        idx)


# ------------------------------------------------- rate control ladder ---

def test_controller_static_never_moves():
    ctl = RateController(default_ladder(8), slo_s=None)
    for lat in (1.0, 10.0, 0.0):
        ctl.observe(lat)
    assert ctl.level == 0
    assert ctl.profile().bits == 3 and ctl.profile().keep_frac == 1.0


def test_controller_walks_down_and_recovers():
    ladder = default_ladder(8)
    ctl = RateController(ladder, slo_s=0.03)
    for _ in range(10):
        ctl.observe(0.08)                      # sustained SLO violation
    assert ctl.level == len(ladder) - 1
    for _ in range(10):
        ctl.observe(0.001)                     # channel recovered
    assert ctl.level == 0


def test_subset_centers_and_requantize():
    centers = np.asarray(PARAMS["quant"]["centers"], np.float32)
    assert subset_centers(centers, 3) is centers or np.array_equal(
        subset_centers(centers, 3), centers)   # full bits: unchanged
    two = subset_centers(centers, 1)
    assert two.shape == (2,) and two[0] <= two[1]
    # tie resolves to the lowest index, like the fused kernel
    idx = requantize(np.asarray([0.5], np.float32),
                     np.asarray([0.0, 1.0], np.float32))
    assert idx[0] == 0
    # requantize matches the fused full-codebook indices bit-for-bit
    f = np.asarray(jax.random.normal(KEY, (3, 4, 4, 19)), np.float32)
    from repro.compress.quantize import hard_indices
    np.testing.assert_array_equal(
        requantize(f, centers), np.asarray(hard_indices(PARAMS["quant"], f)))


# ------------------------------------------------- device half parity ---

def test_device_forward_matches_agile_forward():
    """The fleet's one batched device pass must reproduce the deployment
    path's local logits exactly (it IS the device half of it)."""
    x = jax.random.normal(KEY, (6, 16, 16, 3))
    local_logits, f_remote, idx = device_forward(CFG, PARAMS, x)
    _, internals = agile_forward(CFG, PARAMS, x, train=False)
    np.testing.assert_array_equal(np.asarray(local_logits),
                                  np.asarray(internals["local_logits"]))
    assert idx.shape == f_remote.shape
    # seed two-pass oracle agrees
    ll2, fr2, idx2 = device_forward(CFG, PARAMS, x, use_fused=False)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx2))
    np.testing.assert_array_equal(np.asarray(local_logits), np.asarray(ll2))


def test_fleet_batched_codec_matches_per_request_reference():
    """The fleet-wide payload cache (one requantize + pack_indices_batch
    + LZW sweep per rate profile) must frame every request byte-identically
    to the per-request reference codec, at the static profile and down
    the rate ladder."""
    specs = mixed_fleet(4, n_requests=3)
    fleet = Fleet(CFG, PARAMS, specs, seed=3)
    ladder = default_ladder(PARAMS["quant"]["centers"].shape[0])
    for prof in ladder:
        if prof.bits >= fleet.full_bits and prof.keep_frac >= 1.0:
            keep = fleet.n_remote
        else:
            keep = max(1, int(round(prof.keep_frac * fleet.n_remote)))
        got = fleet._encoded_rows(prof.bits, keep)
        assert len(got) == fleet.n_requests
        for row in range(fleet.n_requests):
            if prof.bits >= fleet.full_bits and keep >= fleet.n_remote:
                idx = fleet.idx[row]
            else:
                idx = requantize(fleet.f_remote[row][..., :keep],
                                 fleet.centers_for(prof.bits))
            ref_bytes, ref_codes = compress_payload(
                pack_indices(idx, prof.bits))
            assert got[row] == (ref_bytes, ref_codes)
        # second lookup is the cache, not a recompute
        assert fleet._encoded_rows(prof.bits, keep) is got


def test_fleet_payload_cache_hits_across_requests():
    """Repeated sends at one profile reuse the fleet-wide sweep: the
    cache holds exactly the profiles used, and make_payload frames are
    identical across lookups."""
    specs = mixed_fleet(3, n_requests=2)
    fleet = Fleet(CFG, PARAMS, specs, seed=4)
    c = fleet.clients[1]
    p1 = fleet.make_payload(c, 0)
    p2 = fleet.make_payload(c, 0)
    assert (p1.nbytes, p1.codes, p1.count) == (p2.nbytes, p2.codes, p2.count)
    assert set(fleet._payloads) == {(fleet.full_bits, fleet.n_remote)}


# ------------------------------------------------------- gateway runs ---

def _run(specs, *, seed=0, width=4):
    fleet = Fleet(CFG, PARAMS, specs, seed=seed)
    report = OffloadGateway(CFG, PARAMS, fleet,
                            GatewayConfig(batch_width=width)).run()
    return fleet, report


def _static_gateway_miss(fault=None) -> float:
    """How far the static-configuration gateway's logits land from the
    per-image `agile_forward(train=False)` logits, in tolerances, over
    every request of a 6-client fleet — through LZW + bit-pack framing,
    batching and pool padding.  Each request's prediction must also be
    the per-image `run_offload_inference` one.  fault: "misquantized"
    sends every image's first offloaded channel one codebook center
    up."""
    specs = mixed_fleet(6, n_requests=2, channels=(WIFI_UDP, NARROWBAND))
    fleet = Fleet(CFG, PARAMS, specs, seed=0)
    if fault == "misquantized":
        n_centers = PARAMS["quant"]["centers"].shape[0]
        fleet.idx = fleet.idx.copy()
        fleet.idx[..., 0] = (fleet.idx[..., 0] + 1) % n_centers
    report = OffloadGateway(CFG, PARAMS, fleet,
                            GatewayConfig(batch_width=4)).run()
    assert len(report.traces) == 12
    got, want = [], []
    for t in report.traces:
        row = fleet.clients[t.client].row0 + t.req
        image = jnp.asarray(fleet.images[row:row + 1])
        got.append(t.logits)
        want.append(np.asarray(
            agile_forward(CFG, PARAMS, image, train=False)[0])[0])
        if fault is None:
            preds, _ = run_offload_inference(CFG, PARAMS, image)
            assert t.pred == int(preds[0])
        assert t.bits == 3 and t.keep == fleet.n_remote
    return miss(np.stack(got), np.stack(want), rtol=OFFLOAD_RTOL,
                atol_frac=OFFLOAD_ATOL_FRAC)


def test_static_gateway_agrees_with_per_image_offload():
    """Acceptance: static-configuration gateway logits == the per-image
    offload path within the written tolerance, for every request, with
    the same predictions."""
    assert _static_gateway_miss() <= 1


def test_static_gateway_tolerance_catches_misquantized_channel():
    """One offloaded channel quantized one center off misses the
    per-image logits by FAULT_MARGIN tolerances or more."""
    assert _static_gateway_miss("misquantized") >= FAULT_MARGIN


def test_gateway_fixed_seed_determinism():
    """Same-seed fleet runs replay identical latency traces and logits —
    for the static fleet and the adaptive one."""
    for slo in (None, 8.0):
        specs = mixed_fleet(6, n_requests=3, slo_ms=slo)
        _, r1 = _run(specs, seed=5)
        _, r2 = _run(specs, seed=5)
        key1 = [(t.client, t.req, t.t_born, t.t_sent, t.t_arrive, t.t_serve,
                 t.t_done, t.e2e_s, t.energy_j, t.payload_bytes, t.bits,
                 t.keep, t.attempts) for t in r1.traces]
        key2 = [(t.client, t.req, t.t_born, t.t_sent, t.t_arrive, t.t_serve,
                 t.t_done, t.e2e_s, t.energy_j, t.payload_bytes, t.bits,
                 t.keep, t.attempts) for t in r2.traces]
        assert key1 == key2
        assert all(np.array_equal(a.logits, b.logits)
                   for a, b in zip(r1.traces, r2.traces))


def test_gateway_32_client_mixed_fleet_completes():
    """Acceptance: >=32 clients over mixed link rates drive the gateway
    end to end on CPU; every request is served with ordered timestamps
    and closed-form device energy."""
    specs = mixed_fleet(32, n_requests=2)
    fleet, report = _run(specs, width=8)
    assert len(report.traces) == 64
    assert {t.channel for t in report.traces} == \
        {"wifi", "narrowband", "lossy-wifi"}
    t_compute = fleet.compute_time(fleet.clients[0])
    for t in report.traces:
        assert t.t_born <= t.t_sent - t_compute + 1e-12
        assert t.t_sent < t.t_arrive <= t.t_serve < t.t_done
        assert t.e2e_s == pytest.approx(t.t_done - t.t_born)
        c = fleet.clients[t.client]
        ser = Channel(c.spec.channel).serialize_s(t.payload_bytes)
        expect = (c.device.p_cpu_w * t_compute
                  + c.device.p_tx_w * t.attempts * ser)
        assert t.energy_j == pytest.approx(expect)
    assert report.summary()["e2e_p99_ms"] > 0
    assert report.clients_per_s > 0


def test_adaptive_rate_control_sheds_payload():
    """A narrowband client that can never meet a tight SLO walks down
    the ladder; its later payloads are smaller and cheaper than the
    static configuration's."""
    slow = (ClientSpec(channel=NARROWBAND, n_requests=6, slo_ms=10.0),)
    fleet, report = _run(slow, width=2)
    assert fleet.clients[0].controller.level > 0
    static_bytes = report.traces[0].payload_bytes   # first request: level 0
    assert report.traces[0].bits == 3
    last = max(report.traces, key=lambda t: t.req)
    assert last.bits < 3
    assert last.payload_bytes < static_bytes
    assert last.energy_j < report.traces[0].energy_j
    # an un-SLO'd client on the same link never leaves the static profile
    calm = (ClientSpec(channel=NARROWBAND, n_requests=6, slo_ms=None),)
    fleet2, report2 = _run(calm, width=2)
    assert fleet2.clients[0].controller.level == 0
    assert all(t.bits == 3 for t in report2.traces)


def test_gateway_pool_width_does_not_change_logits():
    """Slot-pool width is a throughput knob: the same fleet served at
    width 2 and width 8 produces identical per-request logits (latency
    may differ)."""
    specs = mixed_fleet(5, n_requests=2, channels=(WIFI_UDP,))
    _, narrow = _run(specs, width=2)
    _, wide = _run(specs, width=8)
    a = {(t.client, t.req): t.logits for t in narrow.traces}
    b = {(t.client, t.req): t.logits for t in wide.traces}
    assert a.keys() == b.keys()
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])

"""Telemetry contracts: closed-form histogram quantiles, span
bookkeeping on a virtual clock, exporter well-formedness, and — the hard
one — the no-subscriber bit-identity guarantee: with telemetry disabled
(or enabled: instrumentation only *reads*) the scheduler's greedy tokens
and the gateway's seeded fault traces are bit-identical to an
uninstrumented run, and the disabled path performs zero clock reads."""
import json
import math

import jax
import numpy as np
import pytest

from repro.serve.telemetry import (
    DEFAULT_BOUNDS,
    Histogram,
    MetricsRegistry,
    Telemetry,
    Tracer,
    exponential,
)

KEY = jax.random.PRNGKey(0)


# --------------------------------------------------------- histograms --


def test_histogram_closed_form_quantiles_hand_computed():
    """bounds (1,2,4), samples {0.5, 1.5, 3, 5}: the cumulative walk plus
    linear interpolation gives p0=min, p50=2.0 (top of bucket 1),
    p100=max — each verifiable by hand."""
    h = Histogram("t", bounds=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 3.0, 5.0):
        h.observe(v)
    assert h.counts == [1, 1, 1, 1]         # one per bucket + overflow
    assert h.count == 4 and h.total == 10.0
    assert h.percentile(0) == 0.5           # tightened to observed min
    assert h.percentile(50) == 2.0          # rank 2 tops out bucket 1
    assert h.percentile(100) == 5.0         # overflow tightened to max
    assert h.mean == 2.5


def test_histogram_percentiles_monotone_and_bounded():
    rng = np.random.RandomState(3)
    h = Histogram("t")
    xs = rng.lognormal(mean=-3.0, sigma=2.0, size=500)
    for v in xs:
        h.observe(float(v))
    qs = [h.percentile(q) for q in (0, 10, 25, 50, 75, 90, 99, 100)]
    assert qs == sorted(qs)
    assert qs[0] == xs.min() and qs[-1] == xs.max()
    assert math.isnan(Histogram("empty").p50())


def test_exact_histogram_matches_np_percentile_bitwise():
    """The bench helpers' percentile dedup must not move row values:
    exact mode defers to np.percentile on the retained samples."""
    rng = np.random.RandomState(7)
    xs = rng.uniform(0.0, 50.0, size=137)
    h = Histogram.exact()
    for v in xs:
        h.observe(float(v))
    for q in (0, 12.5, 50, 99, 100):
        assert h.percentile(q) == float(np.percentile(xs, q))


def test_pctl_helper_is_np_percentile():
    from benchmarks.common import pctl
    xs = np.random.RandomState(9).normal(size=64)
    assert pctl(xs, 99) == float(np.percentile(xs, 99))
    assert pctl(list(xs), 50) == float(np.percentile(xs, 50))


def test_histogram_rejects_unsorted_bounds():
    with pytest.raises(ValueError):
        Histogram("bad", bounds=(2.0, 1.0))
    assert exponential(1.0, 2.0, 4) == (1.0, 2.0, 4.0, 8.0)


# ----------------------------------------------------------- registry --


def test_registry_create_or_get_and_label_identity():
    m = MetricsRegistry()
    a = m.counter("x", path="a")
    assert m.counter("x", path="a") is a            # same labels: same cell
    b = m.counter("x", path="b")
    assert b is not a
    a.inc(2)
    d = m.to_dict()
    assert d["x{path=a}"] == 2 and d["x{path=b}"] == 0


def test_prometheus_text_shape():
    m = MetricsRegistry()
    m.counter("req.count", status="ok").inc(3)
    m.gauge("pool.occupancy").set(5)
    h = m.histogram("lat_s", bounds=(0.1, 1.0))
    for v in (0.05, 0.5, 2.0):
        h.observe(v)
    text = m.prometheus_text()
    assert '# TYPE req_count counter' in text
    assert 'req_count{status="ok"} 3' in text
    assert 'pool_occupancy 5' in text
    # cumulative bucket counts: <=0.1 -> 1, <=1.0 -> 2, +Inf -> 3
    assert 'lat_s_bucket{le="0.1"} 1' in text
    assert 'lat_s_bucket{le="1"} 2' in text
    assert 'lat_s_bucket{le="+Inf"} 3' in text
    assert 'lat_s_count 3' in text


# ------------------------------------------------------------ tracing --


def test_span_nesting_on_virtual_clock():
    """Wall spans stamped off an injected virtual clock nest by interval
    containment and land on the caller's timeline exactly."""
    from repro.serve.frontend import VirtualClock
    vc = VirtualClock()
    tel = Telemetry(enabled=True, clock=vc)
    with tel.span("outer", track="sched"):
        vc.now = 1.0
        with tel.span("inner", track="sched", round=3):
            vc.now = 2.0
        vc.now = 4.0
    inner, outer = tel.trace.spans            # close order: inner first
    assert (inner.name, inner.t0, inner.t1) == ("inner", 1.0, 2.0)
    assert (outer.name, outer.t0, outer.t1) == ("outer", 0.0, 4.0)
    assert outer.t0 <= inner.t0 and inner.t1 <= outer.t1
    assert inner.args == {"round": 3} and inner.dur == 1.0


def test_chrome_trace_well_formed(tmp_path):
    tr = Tracer()
    tr.add("b", 2e-3, 3e-3, track="gw")
    tr.add("a", 1e-3, 4e-3, track="sched", cat="sched", round=1)
    out = tmp_path / "trace.json"
    tr.write(str(out))
    doc = json.loads(out.read_text())
    assert doc["displayTimeUnit"] == "ms"
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {m["args"]["name"] for m in meta} == {"gw", "sched"}
    assert [e["name"] for e in xs] == ["a", "b"]      # sorted by t0
    a = xs[0]
    assert a["ts"] == pytest.approx(1e3) and a["dur"] == pytest.approx(3e3)
    assert a["args"] == {"round": 1}
    tids = {m["args"]["name"]: m["tid"] for m in meta}
    assert xs[0]["tid"] == tids["sched"] and xs[1]["tid"] == tids["gw"]


def test_disabled_telemetry_never_reads_clock():
    """The no-subscriber contract at the facade: a disabled Telemetry
    must not touch its clock (spans are free no-ops)."""
    def boom():
        raise AssertionError("disabled telemetry read the clock")
    tel = Telemetry(enabled=False, clock=boom)
    with tel.span("x", track="t"):
        pass
    assert tel.trace.spans == []
    with pytest.raises(AssertionError):     # sanity: enabled DOES read it
        with Telemetry(enabled=True, clock=boom).span("x"):
            pass


# ---------------------------------------- bit-identity: scheduler -----


@pytest.fixture(scope="module")
def lm_system():
    from repro.configs import get_config
    from repro.models import backbone as bb
    cfg = get_config("qwen2-0.5b").reduced()
    return cfg, bb.init_params(cfg, KEY)


def _sched_tokens(cfg, params, telemetry=None):
    from repro.serve.engine import Request
    from repro.serve.scheduler import ContinuousScheduler, SchedulerConfig
    sched = ContinuousScheduler(
        cfg, params, max_len=48,
        sched=SchedulerConfig(buckets=(8, 16), max_slots=2,
                              prefill_group=2, chunk=2),
        telemetry=telemetry)
    rng = np.random.RandomState(4)
    rids = [sched.submit(Request(tokens=rng.randint(0, cfg.vocab, L),
                                 max_new_tokens=4))
            for L in (8, 16, 11, 8, 16, 5)]
    outs = sched.run()
    return [outs[r].tokens for r in rids]


def test_scheduler_tokens_bit_identical_with_and_without_telemetry(lm_system):
    """Acceptance: instrumentation only reads — greedy tokens from the
    disabled default, a disabled instance, and a fully enabled instance
    are all bitwise equal."""
    cfg, params = lm_system
    base = _sched_tokens(cfg, params)                       # module default
    off = _sched_tokens(cfg, params, Telemetry(enabled=False))
    on = Telemetry(enabled=True)
    instrumented = _sched_tokens(cfg, params, on)
    for a, b, c in zip(base, off, instrumented):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)
    # and the enabled run actually observed the stack
    assert any(s.name == "round" for s in on.trace.spans)
    assert on.compile_count("sched") >= 3


# ---------------------------------------- bit-identity: gateway -------


@pytest.fixture(scope="module")
def gw_system():
    from repro.configs.agilenn_cifar import AgileNNConfig
    from repro.configs.base import AgileSpec
    from repro.core.agile import init_agile_params
    cfg = AgileNNConfig(image_size=16, remote_width=16, remote_blocks=2,
                        reference_width=16, reference_blocks=2,
                        agile=AgileSpec(enabled=True, extractor_channels=24,
                                        k=5, rho=0.8, lam=0.3, ig_steps=2))
    return cfg, init_agile_params(cfg, jax.random.PRNGKey(9))


def _gw_run(cfg, params, *, telemetry=None, faults=None):
    from repro.serve.gateway import (
        Fleet, GatewayConfig, OffloadGateway, mixed_fleet)
    specs = mixed_fleet(6, n_requests=2, slo_ms=8.0, deadline_ms=500.0)
    fleet = Fleet(cfg, params, specs, seed=5)
    return OffloadGateway(cfg, params, fleet, GatewayConfig(batch_width=4),
                          faults=faults, telemetry=telemetry).run()


def _trace_key(report):
    return [(t.client, t.req, t.t_born, t.t_sent, t.t_arrive, t.t_serve,
             t.t_done, t.e2e_s, t.energy_j, t.attempts, t.status)
            for t in report.traces]


def test_gateway_fault_run_bit_identical_with_telemetry(gw_system):
    """Acceptance: a seeded fault run's event-loop timeline, energy and
    statuses are bit-identical whether telemetry observes it or not."""
    from repro.serve.faults import BurstLoss, FaultInjector
    cfg, params = gw_system
    sched = (BurstLoss(0.0, 2.0, p_good_bad=0.3),)
    plain = _gw_run(cfg, params,
                    faults=FaultInjector(sched, seed=11))
    tel = Telemetry(enabled=True)
    seen = _gw_run(cfg, params, telemetry=tel,
                   faults=FaultInjector(sched, seed=11))
    assert _trace_key(plain) == _trace_key(seen)
    assert all(np.array_equal(a.logits, b.logits)
               for a, b in zip(plain.traces, seen.traces))
    assert tel.counter("gateway.requests", status="served").n > 0


def _union_coverage(spans, parent):
    """Fraction of ``parent``'s interval covered by the union of the
    other spans (clipped)."""
    ivs = sorted((max(s.t0, parent.t0), min(s.t1, parent.t1))
                 for s in spans if s is not parent)
    covered, end = 0.0, parent.t0
    for a, b in ivs:
        if b <= end:
            continue
        covered += b - max(a, end)
        end = b
    return covered / parent.dur if parent.dur > 0 else 1.0


def test_gateway_request_spans_cover_e2e_latency(gw_system):
    """Acceptance: per-request hop spans (device compute, radio
    attempts/backoff, uplink, queue wait, remote batch, response)
    account for >= 95% of every request's end-to-end latency."""
    cfg, params = gw_system
    tel = Telemetry(enabled=True)
    report = _gw_run(cfg, params, telemetry=tel)
    tracks = {s.track for s in tel.trace.spans
              if any(p.name == "request" for p in tel.trace.by_track(s.track))}
    assert len(tracks) == len(report.traces)
    for track in tracks:
        spans = tel.trace.by_track(track)
        parent = next(s for s in spans if s.name == "request")
        assert _union_coverage(spans, parent) >= 0.95, \
            f"{track}: uninstrumented gap in the request timeline"


# ------------------------------- profiler spans, TTFT split, row counts --


def _frontend(cfg, params, tel, **sched_kw):
    from repro.serve.frontend import StreamingFrontend
    from repro.serve.scheduler import SchedulerConfig
    kw = dict(buckets=(8, 16, 32), max_slots=4, prefill_group=2, chunk=2,
              page_size=8, prefill_segment=8)
    kw.update(sched_kw)
    return StreamingFrontend(cfg, params, sched=SchedulerConfig(**kw),
                             max_len=48, telemetry=tel)


def _mixed_requests(cfg, seed=5):
    """Bucketed prompts (<= 8 tokens) and staged ones (9-24 tokens)."""
    from repro.serve.engine import Request
    rng = np.random.RandomState(seed)
    return [Request(tokens=rng.randint(0, cfg.vocab, L), max_new_tokens=n)
            for L, n in ((5, 4), (20, 3), (8, 6), (13, 2), (24, 5), (3, 3))]


def _host_events(log_dir, prefixes):
    """(start_ns, end_ns, name) of the host-plane events of the newest
    profile under log_dir whose names start with one of `prefixes`."""
    import glob
    import os
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(prefixes):
                        s = int(ev.start_ns)
                        out.append((s, s + int(ev.duration_ns), ev.name))
    return out


def test_round_spans_reach_the_profiler_host_plane_nested(lm_system,
                                                          tmp_path):
    """Enabled wall spans are also profiler annotations: an overlapped
    round under `jax.profiler.trace` leaves `frontend/step` holding
    `frontend/feed`, `scheduler/round` and `frontend/finish`, the round
    holding its phases, and `evict` holding `wait_done` and `stream`,
    every child inside its parent on the host plane."""
    cfg, params = lm_system
    fe = _frontend(cfg, params, Telemetry(enabled=True))
    for r in _mixed_requests(cfg):
        fe.submit(r)
    with jax.profiler.trace(str(tmp_path)):
        fe.run()
    evs = _host_events(str(tmp_path), ("scheduler/", "frontend/"))
    names = {n for _, _, n in evs}
    assert {"frontend/step", "frontend/feed", "frontend/finish",
            "scheduler/round", "scheduler/prefill_segment",
            "scheduler/admit", "scheduler/evict", "scheduler/decode_chunk",
            "scheduler/wait_done", "scheduler/stream"} <= names

    def parents(child):
        return {n for s, e, n in evs
                if s <= child[0] and child[1] <= e and (s, e, n) != child}

    want = {"frontend/feed": "frontend/step",
            "frontend/finish": "frontend/step",
            "scheduler/round": "frontend/step",
            "scheduler/prefill_segment": "scheduler/round",
            "scheduler/admit": "scheduler/round",
            "scheduler/evict": "scheduler/round",
            "scheduler/decode_chunk": "scheduler/round",
            "scheduler/wait_done": "scheduler/evict",
            "scheduler/stream": "scheduler/evict"}
    for ev in evs:
        if ev[2] in want:
            assert want[ev[2]] in parents(ev), ev
    # properly nested: two spans either hold one another or are apart
    for a in evs:
        for b in evs:
            overlap = a[0] < b[1] and b[0] < a[1]
            nested = (a[0] <= b[0] and b[1] <= a[1]) or \
                (b[0] <= a[0] and a[1] <= b[1])
            assert not overlap or nested, (a, b)


def test_disabled_telemetry_enters_no_annotation(lm_system, monkeypatch):
    """With telemetry disabled the scheduler and frontend enter no
    profiler annotation and read no telemetry clock."""
    import repro.serve.telemetry as telemetry_mod

    def boom(*a, **k):
        raise AssertionError("disabled telemetry was touched")

    monkeypatch.setattr(telemetry_mod, "TraceAnnotation", boom)
    cfg, params = lm_system
    tel = Telemetry(enabled=False, clock=boom)
    fe = _frontend(cfg, params, tel)
    for r in _mixed_requests(cfg):
        fe.submit(r)
    out = fe.run()
    assert all(status == "served" for status, _ in out.values())
    assert tel.trace.spans == [] and not fe.sched.ttft_marks


def test_ttft_split_adds_up_to_each_first_token(lm_system):
    """Each served request's queue_wait + sched_queue + staging +
    first_token_lag equals its FirstToken time minus its submit instant
    (within 1% or 1 ms); staged prompts spend rounds in `staging`."""
    import time
    from repro.serve.frontend import FirstToken
    cfg, params = lm_system
    tel = Telemetry(enabled=True, clock=time.monotonic)
    fe = _frontend(cfg, params, tel)
    reqs = _mixed_requests(cfg)
    t_sub = {}
    for r in reqs:
        t = time.monotonic()
        t_sub[fe.submit(r)] = t
    fe.run()
    firsts = {ev.rid: ev.t for ev in fe.events if isinstance(ev, FirstToken)}
    assert set(firsts) == set(t_sub)
    parts = ("queue_wait", "sched_queue", "staging", "first_token_lag")
    for rid, t_first in firsts.items():
        spans = {s.name: s for s in tel.trace.by_track(f"req {rid}")}
        assert set(parts) | {"ttft"} <= set(spans), sorted(spans)
        total = sum(spans[p].dur for p in parts)
        assert total == pytest.approx(spans["ttft"].dur, abs=1e-9)
        want = t_first - t_sub[rid]
        assert abs(total - want) <= max(0.01 * want, 1e-3), (rid, total,
                                                              want)
        assert all(spans[p].dur >= 0 for p in parts)
    staged = [rid for rid, r in zip(sorted(t_sub), reqs)
              if len(r.tokens) > 8]
    bucketed = [rid for rid, r in zip(sorted(t_sub), reqs)
                if len(r.tokens) <= 8]
    shortest_round = min(s.dur for s in tel.trace.spans
                         if s.name == "round")
    for rid in staged:       # its segments take rounds: one lies inside
        assert {s.name: s for s in tel.trace.by_track(
            f"req {rid}")}["staging"].dur >= shortest_round
    assert not fe._t_ttft and not fe._t_admit and not fe.sched.ttft_marks
    assert bucketed


@pytest.mark.parametrize("overlap", [False, True])
def test_decode_rows_active_hand_count(lm_system, overlap):
    """Three requests admitted together with budgets 3, 5 and 7 decode
    in chunks of 2 steps: the first chunk runs 3 rows, then 2, then 1
    (each finisher leaves before the next chunk, in both modes) —
    3 chunks and 6 rows, on the counters and on the decode_chunk
    spans."""
    from repro.serve.engine import Request
    from repro.serve.scheduler import ContinuousScheduler, SchedulerConfig
    cfg, params = lm_system
    tel = Telemetry(enabled=True)
    sched = ContinuousScheduler(
        cfg, params, max_len=32,
        sched=SchedulerConfig(buckets=(8,), max_slots=4, prefill_group=4,
                              chunk=2, overlap=overlap),
        telemetry=tel)
    rng = np.random.RandomState(2)
    for n in (3, 5, 7):
        sched.submit(Request(tokens=rng.randint(0, cfg.vocab, 8),
                             max_new_tokens=n))
    outs = sched.run()
    assert sorted(len(c.tokens) for c in outs.values()) == [3, 5, 7]
    assert tel.counter("sched.decode_chunks").n == 3
    assert tel.counter("sched.decode_rows_active").n == 6
    rows = [s.args["rows"] for s in tel.trace.spans
            if s.name == "decode_chunk" and "rows" in s.args]
    assert rows == [3, 2, 1]

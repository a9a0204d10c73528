"""Readings of the program's own telemetry: idle charged to the
program's host spans in a profiler trace, the tail's split of time to
first token, and the decode chunks' row use — by hand, and from a whole
run at a CPU size with the program's telemetry on."""
import dataclasses
import math
import os
import types

import pytest

import program_telemetry as pt
import run
import trace_reduce as tr
from repro.serve.telemetry import Telemetry

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _write(tmp_path, name):
    from jax.profiler import ProfileData
    with open(os.path.join(DATA, name)) as f:
        text = f.read()
    run_dir = tmp_path / name / "plugins" / "profile" / "run"
    run_dir.mkdir(parents=True)
    (run_dir / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    return str(tmp_path / name)


def test_program_spans_leave_the_harness_reduction_unchanged(tmp_path):
    """The same trace with the program's spans added reads the same
    summary, field by field: `trace_reduce` charges only `bench/`."""
    plain = tr.load(_write(tmp_path, "small_trace.textproto"), 0.0)
    prog = tr.load(_write(tmp_path, "small_trace_program.textproto"), 0.0)
    for f in dataclasses.fields(tr.TraceSummary):
        assert getattr(prog, f.name) == getattr(plain, f.name), f.name


def test_idle_goes_to_the_innermost_program_span(tmp_path):
    """0-5 ms idle lies under bench/frontend.step and, innermost, under
    scheduler/wait_done (inside evict, inside round, inside
    frontend/step); 35-90 ms under bench/await_arrival, where no program
    span runs.  The program's part adds up to the harness's idle under
    bench/frontend.step."""
    d = _write(tmp_path, "small_trace_program.textproto")
    split = pt.idle_split(d)
    assert split == {"scheduler/wait_done": pytest.approx(0.005),
                     "bench/await_arrival": pytest.approx(0.055)}
    prog = pt.idle_by_program(split)
    assert prog == {"scheduler/wait_done": pytest.approx(0.005)}
    assert sum(prog.values()) == pytest.approx(
        tr.load(d, 0.0).idle_by_host["frontend.step"])


def test_idle_split_of_a_trace_without_program_spans(tmp_path):
    split = pt.idle_split(_write(tmp_path, "small_trace.textproto"))
    assert pt.idle_by_program(split) == {}
    assert split["bench/frontend.step"] == pytest.approx(0.005)


@dataclasses.dataclass
class Rec:
    rid: int
    t_due: float
    t_first: float | None
    measured: bool = True
    t_submit: float = 0.0


def _ctx(tel, records, t0=0.0, t_end=math.inf, slots=4, trace=None):
    return types.SimpleNamespace(
        tel=tel, system=types.SimpleNamespace(records=records, t0=t0,
                                              t_end=t_end),
        config={"serving": {"scheduler": {"max_slots": slots}}},
        trace=trace)


def test_tail_means_by_hand():
    """Eight requests with TTFT 1..8 s: the 75th percentile is 6.25 s, so
    the tail is the requests of 7 and 8 s; an unmeasured request and one
    without spans do not count."""
    tel = Telemetry(enabled=True)
    recs = [Rec(i, 0.0, float(i + 1)) for i in range(8)]
    recs.append(Rec(8, 0.0, 100.0, measured=False))
    for r in recs:
        if r.rid != 6:
            tel.trace.add("staging", 0.0, 0.1 * (r.rid + 1),
                          track=f"req {r.rid}")
    assert [r.rid for r in pt.tail(recs)] == [6, 7]
    ctx = _ctx(tel, recs)
    assert pt.tail_mean_ms(ctx, "staging") == pytest.approx(800.0)
    assert pt.tail_mean_ms(ctx, "sched_queue") is None
    # traced: the window ends at 6.5 s, where the profiler stops; the
    # requests answered before it (1..6 s) have 4.75 s as their 75th
    # percentile, so their tail is the 5 and 6 s ones (rid 4; 5)
    traced = _ctx(tel, recs, t_end=6.5, trace=object())
    assert [r.rid for r in pt.tail(recs, 6.5)] == [4, 5]
    assert pt.tail_mean_ms(traced, "staging") == pytest.approx(550.0)


def test_readers_read_nothing_from_a_program_without_the_spans():
    """A program that records no TTFT parts and no row counts (or runs
    with telemetry off) gives None from every new reader."""
    tel = Telemetry(enabled=True)
    tel.trace.add("decode_chunk", 1.0, 2.0, track="scheduler", round=1)
    ctx = _ctx(tel, [Rec(0, 0.0, 1.0)])
    assert pt.tail_mean_ms(ctx, "first_token_lag") is None
    assert pt.decode_row_use(ctx) is None
    assert pt.tail_mean_ms(_ctx(Telemetry(enabled=False), []), "staging") \
        is None


def test_decode_row_use_by_hand():
    """Chunks of 3, 1 and 2 rows of 4 slots inside the window, one of 4
    rows after it: 6 of 12 rows."""
    tel = Telemetry(enabled=True)
    now = tel.clock()
    for k, rows in enumerate((3, 1, 2, 4)):
        tel.trace.add("decode_chunk", now + k, now + k + 0.5,
                      track="scheduler", rows=rows)
    import time
    off = time.monotonic() - tel.clock()
    ctx = _ctx(tel, [], t0=now + off - 0.1, t_end=now + off + 2.5)
    assert pt.decode_row_use(ctx) == pytest.approx(50.0)


TINY = {"model": {"n_layers": 2, "d_model": 64, "n_heads": 4,
                  "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
                  "vocab": 151936},
        "serving": {"max_len": 96, "scheduler": {
            "buckets": [16, 32, 64], "max_slots": 4, "page_size": 8,
            "prefill_segment": 16, "prefix_hot_pages": 64}},
        "correctness": {"sample_tokens": 64}}
MIX = {"rate_per_s": 8.0, "tail_s": 1,
       "prompt": {"median": 24, "min": 8, "max": 64},
       "output": {"median": 12, "min": 10, "max": 16}}


def test_whole_run_with_telemetry_on_reads_every_new_metric():
    """A run at a CPU size with the program's telemetry on: each tail
    part reads a finite mean, their sum with the generator's lateness
    comes within 5% of the tail's TTFT, and the decode chunks ran
    between one row and all of them."""
    keep = {}

    def tamper(system):
        system.tel.enabled = True
        keep["s"] = system

    run.run_cell("qwen2-0.5b.prefill-mix", 2**33 + 11, 1.5, False,
                 require_chip=False, config_override=TINY,
                 mix_override=MIX, tamper=tamper, log=lambda *a: None)
    s = keep["s"]
    ctx = _ctx(s.tel, s.records, s.t0, s.t_end)
    ctx.config = s.config
    parts = {p: pt.tail_mean_ms(ctx, p) for p in
             ("queue_wait", "sched_queue", "staging", "first_token_lag")}
    assert all(v is not None and v >= 0 for v in parts.values()), parts
    tail = pt.tail(s.records)
    ttft = sum((r.t_first - r.t_due) * 1e3 for r in tail) / len(tail)
    late = sum((r.t_submit - r.t_due) * 1e3 for r in tail) / len(tail)
    assert sum(parts.values()) + late == pytest.approx(ttft, rel=0.05)
    use = pt.decode_row_use(ctx)
    assert 100.0 / 4 <= use <= 100.0

"""The reduction from a profiler trace to busy and idle time, per-op
device time and idle gaps charged to the harness's host spans."""
import os

import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1_000_000


def test_union_and_gaps():
    busy = tr.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert busy == [(0, 3), (5, 8)]
    assert tr.gaps(busy, 0, 10) == [(3, 5), (8, 10)]
    assert tr.gaps(busy, 1, 6) == [(3, 5)]


def test_innermost_host_span():
    h = tr.HostSpans([(0, 100, "traced"), (10, 50, "step"),
                      (20, 30, "submit"), (60, 90, "sleep"),
                      (0, 5, "first")])
    assert h.at(3) == "first"
    assert h.at(25) == "submit"
    assert h.at(40) == "step"
    assert h.at(70) == "sleep"
    assert h.at(95) == "traced"
    assert h.at(150) == "(no bench span)"


def test_reduce_events_by_hand():
    dev = {"/device:TPU:0": {
        "ops": [("fusion.1", 0, 2 * MS), ("custom-call.3:kern", 2 * MS, MS),
                ("while.4", 0, 3 * MS), ("fusion.1", 6 * MS, MS)],
        "modules": [("jit_step", 0, 3 * MS), ("jit_step", 6 * MS, MS)]}}
    host = [(0, 10 * MS, "traced"), (3 * MS, 6 * MS, "sleep")]
    s = tr.reduce_events(dev, host, 0, 10 * MS,
                         {"custom-call.3:kern": "... kern ..."})
    assert s.window_s == pytest.approx(0.010)
    assert s.busy_s == pytest.approx(0.004)          # [0, 3] and [6, 7] ms
    assert s.idle_share() == pytest.approx(0.6)
    assert s.op_s == {"fusion.1": pytest.approx(0.003),
                      "custom-call.3:kern": pytest.approx(0.001)}
    assert s.module_s == {"jit_step": pytest.approx(0.004)}
    assert s.idle_by_host == {"sleep": pytest.approx(0.003),
                              "traced": pytest.approx(0.003)}
    assert s.op_seconds("kern") == pytest.approx(0.001)


def test_op_names():
    text = ('%custom-call.22 = f32[16,2,7,64]{3,2,1,0} custom-call(%x), '
            'custom_call_target="tpu_custom_call", backend_config='
            '{"custom_call_config": {"name": "paged_decode_attention_tpu"}}')
    assert tr._op_name(text) == "custom-call.22:paged_decode_attention_tpu"
    assert tr._op_name("%fusion.3 = f32[2] fusion(%x)") == "fusion.3"
    assert tr._base_name("jit_remote_forward_jit(1098945)") == \
        "jit_remote_forward_jit"


def test_trace_file_in_the_tpu_layout(tmp_path):
    """Read from an .xplane.pb, as a run does: planes, lines and names as
    the TPU v5e profiler writes them (tests/data/small_trace.textproto)."""
    from jax.profiler import ProfileData
    with open(os.path.join(DATA, "small_trace.textproto")) as f:
        text = f.read()
    run_dir = tmp_path / "plugins" / "profile" / "run"
    run_dir.mkdir(parents=True)
    (run_dir / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    s = tr.load(str(tmp_path), 0.0)
    assert s.n_devices == 1
    assert s.window_s == pytest.approx(0.100)
    assert s.busy_s == pytest.approx(0.040)
    assert s.idle_share() == pytest.approx(0.6)
    assert s.module_s == {"jit__chunk_impl": pytest.approx(0.040)}
    assert "while.43" not in s.op_s           # its body is counted instead
    assert s.op_s["fusion.7"] == pytest.approx(0.030)
    assert s.op_seconds("paged_decode") == pytest.approx(0.010)
    # 0-5 ms idle under a step, 35-90 ms under the wait for an arrival
    assert s.idle_by_host == {"frontend.step": pytest.approx(0.005),
                              "await_arrival": pytest.approx(0.055)}
    assert s.top_idle(1) == [("await_arrival", pytest.approx(0.055))]

"""Analytic operation and byte counts against hand counts, and the
readers that divide them by device time."""
import os
import types

import pytest

import flops
import run

TINY = {"n_layers": 2, "d_model": 8, "n_heads": 4, "n_kv_heads": 2,
        "head_dim": 2, "d_ff": 16, "vocab": 10}


def test_lm_counts_by_hand():
    # per layer: q 8*8, k 8*4, v 8*4, o 8*8, ffn 3*8*16 = 576 MACs
    assert flops.lm_matmul_flops_per_token(TINY) == 2 * 2 * 576
    # scores + values: 2 * (ctx * 4 heads * 2) MACs per layer
    assert flops.lm_attention_flops(TINY, 5) == 2 * 4 * 5 * 4 * 2
    assert flops.lm_readout_flops(TINY) == 2 * 8 * 10
    assert flops.lm_decode_flops(TINY, 7) == (
        2304 + flops.lm_attention_flops(TINY, 7) + 160)


def test_decode_attention_bytes_by_hand():
    # per layer: K and V of 5 positions x 2 kv heads x 2 dims, plus the
    # query and output of 4 heads x 2 dims; float32
    assert flops.decode_attention_bytes(TINY, 5) == 2 * 4 * (2 * 5 * 2 * 2
                                                             + 2 * 4 * 2)


def _reader(name):
    return run.load_module(os.path.join(run.HERE, "metrics", name + ".py"),
                           "metric_" + name.replace(".", "_"))


class _Trace:
    def __init__(self, module_s, op_s):
        self.module_s, self.op_s = module_s, op_s

    def op_seconds(self, needle):
        return sum(v for k, v in self.op_s.items() if needle in k)


def _ctx(trace):
    # one request of a 3-token prompt whose tokens streamed at 1, 2 and
    # 9 s; the traced slice is [0.5, 5] s
    rec = types.SimpleNamespace(prompt=[0, 1, 2], token_ts=[1.0, 2.0, 9.0])
    return types.SimpleNamespace(
        trace=trace, trace_t=(0.5, 5.0), config={"model": TINY},
        system=types.SimpleNamespace(records=[rec]),
        peaks={"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 1e3})


def test_decode_mfu_reader_by_hand():
    # the second token (context 3 + 1) is the one decoded in the slice
    tr = _Trace({"jit__chunk_impl": 0.5}, {})
    got = _reader("model.mfu.decode").read(_ctx(tr))
    assert got == pytest.approx(100 * flops.lm_decode_flops(TINY, 4)
                                / (0.5 * 1e6))
    assert _reader("model.mfu.decode").read(_ctx(None)) is None
    assert _reader("model.mfu.decode").read(_ctx(_Trace({}, {}))) is None


def test_decode_attention_roofline_reader_by_hand():
    tr = _Trace({}, {"paged_decode_attention.1": 0.25})
    got = _reader("decode_attention_roofline").read(_ctx(tr))
    assert got == pytest.approx(100 * flops.decode_attention_bytes(TINY, 4)
                                / 1e3 / 0.25)
    assert _reader("decode_attention_roofline").read(
        _ctx(_Trace({}, {}))) is None

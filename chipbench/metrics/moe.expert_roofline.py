"""Roofline share of the held experts' grouped SwiGLU in the decode step,
in the traced slice: the bytes it had to move (`flops_mla_moe.
expert_bytes`: the weights of every held expert some row routed to, per
layer and step, and the routed rows) at the chip's HBM bandwidth, over
the device time of the decode step's expert op.  Experts hit and
routings are the program's counts on the `decode_chunk` spans that start
in the slice (`experts_hit`, `routed_local`).  The op is bound by
bandwidth at decode's few rows per expert."""
import flops_mla_moe
import expert_telemetry

OP = "held_experts_ffn_decode"


def read(ctx):
    tr = ctx.trace
    if tr is None:
        return None
    op_s = tr.op_seconds(OP)
    hit, local = expert_telemetry.chunk_counts(ctx)
    if not op_s or not hit:
        return None
    need = flops_mla_moe.expert_bytes(ctx.system.model, hit, local)
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / op_s

"""Model FLOP/s utilization of a latent-attention, sparse-expert decode
step while it runs: the useful operations of the tokens decoded in the
traced slice (`flops_mla_moe.decode_flops`: each token after a request's
first, at its own context), plus the routed experts' work of the decode
chunks that start in the slice (their `routed_local` counts), over the
device time of the decode-chunk program in that slice and the chip's
bf16 peak (the weights are bfloat16).  Tokens are placed in the slice by
the host instant they streamed, which lags their chunk by at most one
scheduling round."""
import flops_mla_moe
import expert_telemetry

PROGRAM = "jit__chunk_impl"


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.module_s.get(PROGRAM):
        return None
    m = ctx.system.model
    _, local = expert_telemetry.chunk_counts(ctx)
    if local is None:
        return None
    t0, t1 = ctx.trace_t
    total = flops_mla_moe.routed_flops(m, local)
    n = 0
    for r in ctx.system.records:
        T = len(r.prompt)
        for k, t in enumerate(r.token_ts):
            if k and t0 <= t <= t1:
                total += flops_mla_moe.decode_flops(m, T + k)
                n += 1
    if not n:
        return None
    return 100.0 * total / (tr.module_s[PROGRAM]
                            * ctx.peaks["bf16_flops_per_s"])

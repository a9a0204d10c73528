"""Model FLOP/s utilization of the decode step while it runs: the useful
operations of the tokens decoded in the traced slice (analytic counts,
`flops.py`: each token after a request's first, at its own context),
over the device time of the decode-chunk program in that slice and the
chip's bf16 peak.  The float32 weights run at the TPU's default matmul
precision, one bf16 pass, so the bf16 peak is the denominator.  Tokens
are placed in the slice by the host instant they streamed, which lags
their chunk by at most one scheduling round."""
import flops

PROGRAM = "jit__chunk_impl"


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.module_s.get(PROGRAM):
        return None
    m = ctx.config["model"]
    t0, t1 = ctx.trace_t
    total = 0
    for r in ctx.system.records:
        T = len(r.prompt)
        for k, t in enumerate(r.token_ts):
            if k and t0 <= t <= t1:
                total += flops.lm_decode_flops(m, T + k)
    if not total:
        return None
    return 100.0 * total / (tr.module_s[PROGRAM]
                            * ctx.peaks["bf16_flops_per_s"])

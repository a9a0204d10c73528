"""Roofline share of latent attention's decode kernel in the traced
slice: the bytes its calls had to move for the tokens streamed in the
slice (each row's cached latent rows up to its depth, its absorbed query
and output, in every layer; `flops_mla_moe.latent_bytes`) at the chip's
HBM bandwidth, over the kernel's device time.  The kernel is bound by
bandwidth."""
import flops_mla_moe

KERNEL = "mla_decode_attention_op"


def read(ctx):
    tr = ctx.trace
    if tr is None:
        return None
    k_s = tr.op_seconds(KERNEL)
    if not k_s:
        return None
    m = ctx.system.model
    t0, t1 = ctx.trace_t
    need = 0
    for r in ctx.system.records:
        T = len(r.prompt)
        for k, t in enumerate(r.token_ts):
            if k and t0 <= t <= t1:
                need += flops_mla_moe.latent_bytes(m, T + k)
    if not need:
        return None
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / k_s

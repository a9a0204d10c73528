"""75th percentile over the window's finished requests of the time per
output token after the first: (last token - first token) / (tokens - 1).
The 75th is the highest percentile with ten requests beyond it in a
window of 50 s at prefill-mix's 0.85 req/s (42 requests)."""
import stats


def read(ctx):
    vals = [stats.tpot_ms(r) for r in ctx.system.records if r.measured]
    vals = [v for v in vals if v is not None]
    return stats.percentile(vals, 75) if vals else None

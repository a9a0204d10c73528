"""75th percentile of time to first token over every request due in the
window, from its due time to its first streamed token.  A request that
never streamed one ranks above every served request.
The 75th is the highest percentile with ten requests beyond it in a
window of 50 s at prefill-mix's 0.85 req/s (42 requests)."""
import stats


def read(ctx):
    recs = [r for r in ctx.system.records if r.measured]
    return stats.percentile(stats.ttft_values_ms(recs), 75)

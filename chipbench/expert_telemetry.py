"""Reading of the program's expert counts in a traced run.

With telemetry on, the scheduler puts on each `decode_chunk` span the
chunk's expert work: `experts_hit` (held experts with at least one
routing, summed over layers and steps) and `routed_local` (routings that
landed on held experts).  A program that records neither, such as one
serving no expert layer, reads None."""
from __future__ import annotations

import time


def chunk_counts(ctx):
    """(experts hit, routings landed) summed over the decode chunks whose
    span starts in the traced slice; (None, None) where no span in it
    carries them."""
    tel = ctx.tel
    if tel is None or not tel.enabled or ctx.trace is None:
        return None, None
    off = time.monotonic() - tel.clock()      # telemetry clock -> host's
    t0, t1 = ctx.trace_t
    spans = [s for s in tel.trace.spans
             if s.name == "decode_chunk" and "experts_hit" in s.args
             and t0 <= s.t0 + off <= t1]
    if not spans:
        return None, None
    return (sum(s.args["experts_hit"] for s in spans),
            sum(s.args["routed_local"] for s in spans))

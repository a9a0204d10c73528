"""Mean number of requests waiting in the scheduler (queued, or staging a
chunked prefill), sampled after every scheduling round of the window
(the program's `sched.backlog` gauge)."""


def read(ctx):
    g = ctx.system.gauges
    if not g:
        return None
    return sum(x["backlog"] for x in g) / len(g)

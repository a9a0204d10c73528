"""Device time of the prefill programs (one-shot bucketed prefill and
chunked-prefill segments) in the traced slice, per thousand prompt
tokens of the requests whose first token streamed in that slice.

The chunked-prefill program is jitted from a `functools.partial`, which
names it "jit__unknown"; it is the only such program these cells run."""

PROGRAMS = ("jit__prefill", "jit__unknown")


def read(ctx):
    tr = ctx.trace
    if tr is None:
        return None
    dev_s = sum(v for k, v in tr.module_s.items() if k in PROGRAMS)
    t0, t1 = ctx.trace_t
    toks = sum(len(r.prompt) for r in ctx.system.records
               if r.t_first is not None and t0 <= r.t_first <= t1)
    if not toks or not dev_s:
        return None
    return dev_s * 1e3 / (toks / 1e3)

"""Share of the traced slice in which no operation ran on the chip
(profiler trace: 1 - busy / window)."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.idle_share()

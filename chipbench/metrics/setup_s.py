"""Set-up time: process start to the window's first request (imports,
weights, traffic, warm-up and, on a cold cache, compilation)."""


def read(ctx):
    return ctx.setup_s

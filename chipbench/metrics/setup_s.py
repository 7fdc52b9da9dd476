"""From process start to the first timed iteration (host clock): imports,
data, the forward transform and warm-up, compile-cache hits included."""


def read(ctx):
    return ctx.setup_s

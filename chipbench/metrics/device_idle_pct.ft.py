"""Share of the traced window in which no op ran on the device, in
percent: 1 - (union of op intervals / window), mean over chips."""


def read(ctx):
    if not ctx.trace or ctx.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])

"""Window wall time over the iterations completed in it (host clock).

Each iteration ends with its checksum on the host, so this is the time a
user of the loop waits per step, host work and device work together.
"""


def read(ctx):
    w = ctx.window
    return w["elapsed_s"] / w["iterations"] * 1e3

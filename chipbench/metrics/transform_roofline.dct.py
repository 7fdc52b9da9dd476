"""The blocked transform's share of its roofline on the chip, in percent.

Numerator: the least time of each plane's blocked transform, from its
shape and block alone (``work_blocks.least_time_s``: FLOPs at the bf16
peak or the compulsory bytes at HBM peak, whichever is longer), times the
transforms the traced cycle ran (one forward and one inverse of every
plane an iteration).  Denominator: the cycle's device time of every op
that is not one of the benchmark's own programs (union of op intervals).
"""
import os
import runpy


def read(ctx):
    if not ctx.trace or ctx.trace["work_s"] <= 0 or not ctx.traced:
        return None
    work = runpy.run_path(os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "work_blocks.py"))
    wl = ctx.workload
    least = sum(work["least_time_s"](dims, wl.block, ctx.peaks)[0]
                for dims in wl.planes.values())
    runs = ctx.traced["iterations"] * wl.transforms_per_iteration
    return 100.0 * least / len(wl.planes) * runs / ctx.trace["work_s"]

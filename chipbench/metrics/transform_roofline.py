"""The transform's share of its roofline on the chip, in percent.

Numerator: the least time of the dense three-mode transform per chip,
from the grid alone (``work.least_time_s``: FLOPs at the bf16 peak or the
compulsory bytes at HBM peak, whichever is longer), times the inverse
transforms the traced cycle ran, one an iteration.  Denominator: the cycle's device time of every op
that is not one of the benchmark's own programs (union of op intervals,
mean over chips).
"""


def read(ctx):
    if not ctx.trace or ctx.trace["work_s"] <= 0:
        return None
    least, _ = ctx.work.least_time_s(ctx.workload.dims, ctx.peaks,
                                     ctx.workload.chips)
    return 100.0 * least * ctx.traced["iterations"] / ctx.trace["work_s"]

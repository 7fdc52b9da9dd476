"""Host time inside the engine's entry point per iteration: the program's
root ``dxt3d:{kind}`` spans over the traced cycle.  A reading counts only
when the span buffer holds one root span per transform the cycle ran (one
forward and one inverse of every plane an iteration); a program without
the profiler bridge records none, and then the reader gives nothing."""


def read(ctx):
    from repro import obs

    n = ctx.traced["iterations"] if ctx.traced else 0
    roots = [s for s in obs.spans()
             if s.parent_id == 0 and s.name.startswith("dxt3d:")]
    if not n or len(roots) != n * ctx.workload.transforms_per_iteration:
        return None
    return sum(s.dur_ns for s in roots) / n / 1e6

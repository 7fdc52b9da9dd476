"""Host time of the program's own spans per traced iteration.

The traced cycle is the only part of a run in which a profiler session
records, and that session switches the program's ``repro.obs`` spans on,
so obs's buffer holds exactly that cycle's spans.  A reading counts only
when the buffer holds one root ``dxt3d:{kind}`` span per traced
iteration; a program without the profiler bridge records none, and then
every reader gives nothing.
"""


def per_iteration_ms(ctx, name=None):
    """Summed duration of the spans called ``name`` (the root
    ``dxt3d:{kind}`` spans when ``None``) per traced iteration, in ms."""
    from repro import obs

    spans = obs.spans()
    n = ctx.traced["iterations"] if ctx.traced else 0
    roots = [s for s in spans
             if s.parent_id == 0 and s.name.startswith("dxt3d:")]
    if not n or len(roots) != n:
        return None
    picked = roots if name is None else [s for s in spans if s.name == name]
    return sum(s.dur_ns for s in picked) / n / 1e6

"""Host time inside the ``dxt3d(...)`` call per iteration (host clock),
over the traced iterations.  The call returns before the device is done,
so this is the entry point's and the executor's own host work."""


def read(ctx):
    if not ctx.traced:
        return None
    return ctx.traced["host_call_s"] / ctx.traced["iterations"] * 1e3

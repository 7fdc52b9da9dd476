"""Host time inside the engine's entry point per iteration: the program's
root ``dxt3d:{kind}`` spans over the traced cycle.  The inside counterpart
of ``host_call_ms.ft``."""
import os
import runpy


def read(ctx):
    helper = runpy.run_path(os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "program_spans.py"))
    return helper["per_iteration_ms"](ctx, None)

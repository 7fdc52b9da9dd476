"""Host time running the plan's stages per iteration: the program's
``execute`` spans over the traced cycle (the kernels run on after the span
ends)."""
import os
import runpy


def read(ctx):
    helper = runpy.run_path(os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "program_spans.py"))
    return helper["per_iteration_ms"](ctx, "execute")

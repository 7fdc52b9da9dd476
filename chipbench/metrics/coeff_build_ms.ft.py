"""Host time building the transform's three coefficient matrices per
iteration: the program's ``dxt3d.coefficients`` spans over the traced
cycle."""
import os
import runpy


def read(ctx):
    helper = runpy.run_path(os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "program_spans.py"))
    return helper["per_iteration_ms"](ctx, "dxt3d.coefficients")

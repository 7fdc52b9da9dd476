"""Host time fingerprinting coefficient matrices for the plan-cache key
per iteration (read to the host and hashed, on memo misses only): the
program's ``plan.fingerprint`` spans over the traced cycle."""
import os
import runpy


def read(ctx):
    helper = runpy.run_path(os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "program_spans.py"))
    return helper["per_iteration_ms"](ctx, "plan.fingerprint")

"""CPU-only tests of the benchmark's own code.

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests

Four virtual CPU devices stand in for the 2x2 mesh.  The variables are
set here, before any test module imports JAX.
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

"""Shared fixtures.

Tests run on the single real CPU device (the dry-run, and only the
dry-run, uses 512 placeholder devices — see launch/dryrun.py).  Multi-device
tests use the ``virtual_devices`` fixture: jax fixes its device count at
first import, so each multi-device case executes in a fresh subprocess
whose ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` is set before
jax initializes.
"""
import os
import subprocess
import sys
import textwrap

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.fixture(scope="session")
def virtual_devices():
    """Run a code snippet under N virtual CPU devices; returns its stdout.

    Asserts a zero exit status (stdout/stderr are surfaced on failure).
    Used by the distributed GEMT / engine / train-step tests.
    """

    def run(code: str, devices: int = 8) -> str:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"  # virtual devices; never the chip
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
        env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
        r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                           capture_output=True, text=True, env=env,
                           timeout=600)
        assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
        return r.stdout

    return run

"""``correct`` of the block-coding cell at a size a CPU test run can hold:
the program passes, the int8 control and every planted fault fail.

Each case drives a whole run (set-up, window, check) of
``uhd420_dct3d_b8.encode`` with its planes cut to a few blocks a side and
the look for a chip skipped.  The limits and the block are the
configuration's own.  A fault replaces the transform the loop calls; the
loop calls it for the planes in order, forward then inverse.
"""
import importlib.util
import os

import jax.numpy as jnp
import pytest

import control_blocks
import run

SMALL = {"Y": [16, 48, 64], "Cb": [16, 24, 32], "Cr": [16, 24, 32]}
# Wide enough for block-ESOP: 4 tiles of 128 a side in Y, 2 in chroma.
WIDE = {"Y": [16, 256, 512], "Cb": [16, 128, 256], "Cr": [16, 128, 256]}
CELL = "uhd420_dct3d_b8.encode"


def small(planes=SMALL):
    spec = run.load_cell(CELL)
    spec.config = dict(spec.config, planes=planes)
    return spec


def run_small(transform=None, planes=SMALL, trace=False,
              seed: int = 2 ** 33 + 29) -> dict:
    from repro.engine import executor

    executor.clear_plan_cache()  # no program compiled by an earlier case
    rc, res = run.run_cell(small(planes), seed, 0.3, trace=trace,
                           require_tpu=False, transform=transform)
    assert rc == 0
    return res


def program(u, inverse, block=(8, 8, 8)):
    from repro.core import dxt3d

    return dxt3d(u, "dct", inverse=inverse, engine=True, block=block)


def by_call(fault):
    """A transform that hands ``fault(call, u, inverse, forward_outputs)``
    each call's position in the iteration (forward planes first)."""
    state = {"calls": 0, "forward": []}

    def transform(u, inverse):
        call = state["calls"] % (2 * len(SMALL))
        state["calls"] += 1
        if call == 0:
            state["forward"] = []
        y = fault(call, u, inverse, state["forward"])
        if not inverse:
            state["forward"].append(y)
        return y
    return transform


FAULTS = {
    # the transform returns its input
    "identity": lambda: lambda u, inverse: u,
    # the whole extent's transform, not the blocks'
    "unblocked": lambda: lambda u, inverse: program(u, inverse, block=None),
    # the last plane (Cr) is never transformed
    "plane_left_out": lambda: by_call(
        lambda call, u, inverse, fwd: (jnp.zeros_like(u) if call % 3 == 2
                                       else program(u, inverse))),
    # the inverse reads the coefficients before quantisation
    "inverse_fed_unquantised": lambda: by_call(
        lambda call, u, inverse, fwd: program(fwd[call - 3] if inverse
                                              else u, inverse)),
}


def test_program_is_correct():
    res = run_small()
    assert res["correct"], res["checks"]


def test_int8_control_is_not_correct():
    ref = run.load_module(os.path.join(run.HERE, "references",
                                       "dct3_blocks.py"))
    res = run_small(control_blocks.control_transform(ref, (8, 8, 8)))
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(fault):
    res = run_small(FAULTS[fault]())
    assert not res["correct"], res["checks"]


def test_traced_run_reads_the_program_metrics():
    """A traced run at block-ESOP widths: the entry spans and the ESOP
    counters read; the device readers read nothing without a chip."""
    res = run_small(planes=WIDE, trace=True)
    assert res["correct"], res["checks"]
    m = res["metrics"]
    assert m["entry_ms.dct"]["value"] > 0
    assert 50 < m["esop_skip_pct.dct"]["value"] < 100
    assert "transform_roofline.dct" not in m


def test_work_blocks_least_time():
    """3.89 ms a direction over a 4K 4:2:0 buffer's planes, bytes-bound."""
    path = os.path.join(run.HERE, "work_blocks.py")
    spec = importlib.util.spec_from_file_location("work_blocks", path)
    work = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(work)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    planes = run.load_cell(CELL).config["planes"].values()
    times = [work.least_time_s(p, (8, 8, 8), peaks) for p in planes]
    assert {bound for _, bound in times} == {"hbm"}
    assert sum(t for t, _ in times) == pytest.approx(3.889e-3, rel=1e-3)

"""``correct`` at a size a CPU test run can hold: the program passes, the
lower-precision control and every planted fault of the timed path fail.

Each case drives a whole run (set-up, window, check) of a cell whose
configuration keeps everything but the grid and niter, with the look for
a chip skipped.  The limits are the configuration's own.
"""
import functools
import json
import os

import jax
import pytest

import control
import run

GRIDS = {"npb_ft_c.iterate": [64, 32, 48], "npb_ft_d.iterate": [64, 32, 32]}


def small(cell: str):
    """The cell at a small grid.  ``npb_ft_d.iterate``, the class-D
    configuration on its 2x2 mesh under the same traffic, is not in
    BENCHMARK.json (it does not fit the chip yet); it is built here so
    that the mesh path of the harness stays tested."""
    if cell == "npb_ft_d.iterate":
        spec = run.load_cell("npb_ft_c.iterate")
        with open(os.path.join(run.HERE, "configs", "npb_ft_d.json")) as f:
            spec.config = json.load(f)
        spec.cell = dict(spec.cell, name=cell, config="npb_ft_d", chips=4)
    else:
        spec = run.load_cell(cell)
    spec.config = dict(spec.config, grid=GRIDS[cell], niter=3)
    return spec


def correct(cell: str, transform=None, seed: int = 2 ** 33 + 17) -> bool:
    from repro.engine import executor

    executor.clear_plan_cache()  # no program compiled by an earlier case
    rc, res = run.run_cell(small(cell), seed, 0.3, trace=False,
                           require_tpu=False, transform=transform)
    assert rc == 0
    return res["correct"]


def plant(monkeypatch, fault):
    """Apply ``fault(x, y) -> y`` to every result of the engine's entry
    under ``dxt3d`` (input ``x``, result ``y``), on one chip and on the
    mesh alike."""
    from repro.core import gemt

    orig = gemt.gemt3_planned

    def planted(x, *a, **kw):
        return fault(x, orig(x, *a, **kw))
    monkeypatch.setattr(gemt, "gemt3_planned", planted)


@pytest.mark.parametrize("cell", sorted(GRIDS))
def test_program_is_correct(cell):
    assert correct(cell)


@pytest.mark.parametrize("cell", sorted(GRIDS))
def test_int8_control_is_not_correct(cell):
    ref = run.load_module(run.os.path.join(run.HERE, "references",
                                           "dht3.py"))
    assert not correct(cell, control.control_transform(ref))


@pytest.mark.parametrize("fault", ["unchanged", "half_left_out",
                                   "answer_altered"])
def test_planted_fault_is_not_correct(monkeypatch, fault):
    n1, n2, n3 = GRIDS["npb_ft_c.iterate"]
    plant(monkeypatch, {
        # the transform returns its input: a step leaving its state as is
        "unchanged": lambda x, y: x,
        # the second half of the field is never computed
        "half_left_out": lambda x, y: y.at[y.shape[0] // 2:].set(0.0),
        # one value, at the first checksum point, is wrong where produced
        "answer_altered": lambda x, y: y.at[1 % n1, 3 % n2, 5 % n3].add(1.0),
    }[fault])
    assert not correct("npb_ft_c.iterate")


def test_exchange_between_chips_left_out_is_not_correct(monkeypatch):
    """Each chip keeps its own slice of the partial sum: the psum_scatter
    of a sharded stage no longer adds the other chips' parts."""
    def local_slice(x, names, scatter_dimension=0, tiled=False):
        n = jax.lax.psum(1, names)
        size = x.shape[scatter_dimension] // n
        return jax.lax.dynamic_slice_in_dim(
            x, jax.lax.axis_index(names) * size, size, scatter_dimension)

    monkeypatch.setattr(jax.lax, "psum_scatter", local_slice)
    assert not correct("npb_ft_d.iterate")


def test_compiling_inside_the_window_is_not_correct(monkeypatch):
    """A result that goes through a new program on every call compiles
    in the window, which the run reports as not correct."""
    plant(monkeypatch, lambda x, y: jax.jit(
        functools.partial(lambda a, k: a * k, k=1.0))(y))
    assert not correct("npb_ft_c.iterate")

"""The fused pair's tiles, chosen by modeled cost.

``fused_tile_sizes`` searches the legal tilings of a dense pair whose
VMEM footprint fits the budget and keeps the one of least modeled time
(HBM bytes over the chip's bandwidth plus a fixed cost per grid step).
A pair with a block-sparse stream keeps the ESOP-seeded ladder, so the
blocked video planes plan exactly as before.  The kernel at the deep
tiles the search picks (one C_b slab, one Ka block) sums the same
products as the staged path.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gemt3
from repro.core.transforms import (blocked_coefficient_matrix,
                                   coefficient_matrix)
from repro.engine import build_plan, gemt3_planned
from repro.engine.plan import (DEFAULT_VMEM_BUDGET, GRID_STEP_S,
                               HBM_BYTES_PER_S, _fit_vmem, _fused_hbm_bytes,
                               _lane_tile, _pad_up, _pow2_ceil_clamp,
                               _pow2_clamp, fused_tile_sizes,
                               fused_vmem_bytes, lane_tile_ok)
from repro.kernels import ops
from repro.kernels.fused_gemt import kb_padded

RNG = np.random.default_rng(23)


def _rand(*shape):
    return jnp.asarray(RNG.normal(size=shape).astype(np.float32))


def _grid_steps(rows_total, na, ka, nb, tiles):
    bu, bka, bnb, bna, _ = tiles
    return (_pad_up(rows_total, bu) // bu * (_pad_up(ka, bka) // bka)
            * (_pad_up(nb, bnb) // bnb) * (_pad_up(na, bna) // bna))


def _modeled_s(rows_total, na, ka, nb, tiles, itemsize):
    bu, bka, bnb, bna, _ = tiles
    live_a = (_pad_up(na, bna) // bna) * (_pad_up(ka, bka) // bka)
    t_b = _pad_up(nb, bnb) // bnb
    return (_fused_hbm_bytes(rows_total, na, ka, tiles, live_a, t_b,
                             itemsize) / HBM_BYTES_PER_S
            + _grid_steps(rows_total, na, ka, nb, tiles) * GRID_STEP_S)


def _ladder(rows_total, na, ka, nb, kb, itemsize, budget, accum="plain"):
    """The halving ladder the search replaced, for dense pairs."""
    kbp = kb_padded(kb)
    t = _fit_vmem({"bu": _pow2_clamp(rows_total), "bka": _lane_tile(ka),
                   "bnb": _pow2_ceil_clamp(nb, hi=32), "bna": _lane_tile(na)},
                  lambda t: fused_vmem_bytes(t["bu"], t["bka"], t["bnb"],
                                             t["bna"], kbp, itemsize, accum),
                  budget)
    return None if t is None else (t["bu"], t["bka"], t["bnb"], t["bna"], kbp)


def _pow2(v):
    return v > 0 and v & (v - 1) == 0


# --- the 512³ DHT of NPB FT class C ---------------------------------------

def test_dht512_pair_runs_deep_tiles():
    c = coefficient_matrix("dht", 512).T  # the inverse the FT loop runs
    plan = build_plan((512, 512, 512), jnp.float32, c, c, c)
    f = plan.fused
    assert f is not None and plan.fused3 is None
    tiles = (f.bu, f.bka, f.bnb, f.bna, f.kbp)
    rows_total = f.rows
    assert _grid_steps(rows_total, f.na, f.ka, f.nb, tiles) <= 4096
    assert f.vmem_bytes == fused_vmem_bytes(*tiles, 4)
    assert f.vmem_bytes <= DEFAULT_VMEM_BUDGET
    # the halving ladder's tiles (8, 128, 16, 128) modeled 5.10 GB
    old = (8, 128, 16, 128, 512)
    assert _ladder(rows_total, f.na, f.ka, f.nb, f.kb, 4,
                   DEFAULT_VMEM_BUDGET) == old
    old_bytes = _fused_hbm_bytes(rows_total, f.na, f.ka, old, 16, 32, 4)
    assert old_bytes == 5_100_273_664
    assert f.hbm_bytes_fused < old_bytes
    assert f.bnb >= 128  # stage b contracts at least 128 deep


def test_fetch_once_rule():
    """A block whose index stays between grid steps is fetched once."""
    # one C_b slab (t_b 1): C_b moves one (bnb, kbp) block in all
    one_slab = _fused_hbm_bytes(512, 512, 512, (1, 512, 512, 128, 512),
                                4, 1, 4)
    assert one_slab == 4 * (512 ** 3 + 512 * 4 * 128 * 512 + 512 * 512
                            + 512 ** 3)
    # one na block and one slab: X once per u-block; one Ka block too:
    # C_a once in all
    whole = _fused_hbm_bytes(64, 32, 32, (8, 32, 32, 32, 32), 1, 1, 4)
    assert whole == 4 * (64 * 32 * 32 + 32 * 32 + 32 * 32 + 64 * 32 * 32)
    # several slabs: every term keeps its per-step count
    many = _fused_hbm_bytes(512, 512, 512, (8, 128, 16, 128, 512), 16, 32, 4)
    u = 512
    assert many == 4 * (u * 16 * 128 * 16 * 32 + 64 * 32 * 16 * 128 * 128
                        + 64 * 4 * 32 * 16 * 512 + u * 512 * 512)


# --- legality and optimality across shapes and budgets --------------------

SHAPES = [  # (rows_total, na, ka, nb, kb)
    (512, 512, 512, 512, 512),
    (512, 128, 128, 128, 128),
    (512, 256, 256, 256, 256),
    (256, 32, 32, 32, 32),
    (64, 48, 32, 24, 16),
    (13, 17, 9, 11, 10),
    (8, 200, 136, 72, 300),
    (96, 384, 640, 96, 128),
]
BUDGETS = [1 << 19, 1 << 21, 4 << 20, DEFAULT_VMEM_BUDGET, 32 << 20]


@pytest.mark.parametrize("itemsize,accum", [(4, "plain"), (2, "plain"),
                                            (4, "compensated")])
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_search_is_legal_and_no_worse_than_ladder(shape, itemsize, accum):
    rows_total, na, ka, nb, kb = shape
    for budget in BUDGETS:
        tiles = fused_tile_sizes(rows_total, na, ka, nb, kb, itemsize,
                                 budget, accum=accum)
        ladder = _ladder(rows_total, na, ka, nb, kb, itemsize, budget, accum)
        floor = fused_vmem_bytes(1, _lane_tile(ka), 8, _lane_tile(na),
                                 kb_padded(kb), itemsize, accum)
        # the search declines exactly where the floor tiles do not fit
        assert (tiles is None) == (floor > budget), (budget, floor)
        if tiles is None:
            continue
        bu, bka, bnb, bna, kbp = tiles
        assert kbp == kb_padded(kb)
        assert lane_tile_ok(bna, na) and lane_tile_ok(bka, ka), tiles
        assert bna == _lane_tile(na)
        if ka > 128:
            assert bka % 128 == 0 and _pad_up(ka, 128) % bka == 0, tiles
        assert bnb >= 8 and _pow2(bnb) and bnb < 2 * max(nb, 8), tiles
        assert _pow2(bu) and bu <= rows_total, tiles
        assert fused_vmem_bytes(*tiles, itemsize, accum) <= budget
        if ladder is not None and rows_total >= 8:
            # the ladder's tiling lies in the searched space
            assert (_modeled_s(rows_total, na, ka, nb, tiles, itemsize)
                    <= _modeled_s(rows_total, na, ka, nb, ladder, itemsize))


@pytest.mark.parametrize("shape", SHAPES[:4],
                         ids=lambda s: "x".join(map(str, s)))
def test_budget_floor_is_exact(shape):
    rows_total, na, ka, nb, kb = shape
    floor = fused_vmem_bytes(1, _lane_tile(ka), 8, _lane_tile(na),
                             kb_padded(kb), 4)
    assert fused_tile_sizes(rows_total, na, ka, nb, kb, 4, floor) is not None
    assert fused_tile_sizes(rows_total, na, ka, nb, kb, 4, floor - 1) is None


def test_seeded_pair_keeps_the_ladder():
    """A block-sparse stream's ESOP seeds keep today's tiling."""
    rows_total, na, ka, nb, kb = 32, 1920, 1920, 1080, 1080
    kbp = kb_padded(kb)
    want = _fit_vmem({"bu": 32, "bka": 128, "bnb": 32, "bna": 128},
                     lambda t: fused_vmem_bytes(t["bu"], t["bka"], t["bnb"],
                                                t["bna"], kbp, 4),
                     DEFAULT_VMEM_BUDGET)
    got = fused_tile_sizes(rows_total, na, ka, nb, kb, 4,
                           start=(128, 128, 128))
    assert got == (want["bu"], want["bka"], want["bnb"], want["bna"], kbp)


# --- the control: the blocked video planes plan as before -----------------

VIDEO_PLANES = [(32, 2160, 3840), (32, 1080, 1920)]


@pytest.mark.parametrize("inverse", [False, True], ids=["fwd", "inv"])
@pytest.mark.parametrize("shape", VIDEO_PLANES,
                         ids=lambda s: "x".join(map(str, s)))
def test_video_planes_plan_unchanged(shape, inverse):
    cs = [blocked_coefficient_matrix("dct", n, 8) for n in shape]
    if inverse:
        cs = [c.T for c in cs]
    plan = build_plan(shape, jnp.float32, *cs)
    assert plan.fused is None and plan.fused3 is None, plan.events
    assert plan.order == (1, 2, 3)
    assert [(s.mode, s.backend, (s.bm, s.bn, s.bk)) for s in plan.stages] == [
        (1, "einsum", (3840, 32, 32)),
        (2, "esop", (3840, 128, 128)),
        (3, "esop", (3840, 128, 128)),
    ]


# --- the kernel at the deep tiles (interpret mode off-TPU) ----------------

def test_kernel_one_slab_and_whole_ka_matches_staged():
    """bnb covering Nb (t_b 1) and bka = Ka: the fused kernel matches the
    staged engine path."""
    x3, ca, cb = _rand(16, 24, 40), _rand(40, 64), _rand(24, 20)
    y, info = ops.fused_gemt(x3, ca, cb, bu=8, bka=64, bnb=32, bna=64,
                             use_pallas=True)
    assert info["t_steps"] == (1, 1)
    x = x3.reshape(2, 8, 24, 40)  # (batch, untouched, nb, na)
    eye = jnp.eye(8, dtype=jnp.float32)
    staged = gemt3_planned(x, eye, cb, ca, fuse=False, use_pallas=True)
    np.testing.assert_allclose(
        np.asarray(y).reshape(2, 8, 64, 20),
        np.swapaxes(np.asarray(staged), -1, -2), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dims", [(16, 16, 16), (4, 24, 16, 40)],
                         ids=["cube", "batched"])
def test_engine_pair_at_searched_tiles_matches_staged(dims):
    """The planned pair, whose search gives one slab and one Ka block at
    these sizes, matches the staged schedule."""
    x = _rand(*dims)
    cs = [_rand(n, n) for n in dims[-3:]]
    plan = build_plan(x.shape, x.dtype, *cs, fuse="pair")
    f = plan.fused
    assert f is not None
    assert _pad_up(f.nb, f.bnb) == f.bnb and _pad_up(f.ka, f.bka) == f.bka
    y = gemt3_planned(x, *cs, fuse="pair", use_pallas=True)
    staged = gemt3_planned(x, *cs, fuse=False, use_pallas=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(staged),
                               rtol=1e-4, atol=1e-4)
    ref = (gemt3(x, *cs) if x.ndim == 3 else
           jnp.stack([gemt3(t, *cs) for t in x]))
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)

"""Row tiles of the staged SR-GEMM / block-ESOP stages.

``build_plan`` sizes a staged stage's row tile ``bm`` from the VMEM model
(``stage_row_tile``): the largest multiple of 128 that divides the rows
and whose footprint, in each of the three roles the tiles run (forward,
dX, dC), fits the budget.  Only ``bm`` moves: ``(bk, bn)``, and so the
ESOP schedules, the zero fractions and the sparsity signature, stay as
they are at 128-row tiles.  The kernels at the wider tile sum the same
128-deep passes in the same order, so they match the 128-row launch.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.transforms import (blocked_coefficient_matrix,
                                   coefficient_matrix)
from repro.engine.plan import (DEFAULT_VMEM_BUDGET, _gemm_vmem_bytes,
                               _lane_tile, build_plan, lane_tile_ok,
                               sparsity_signature, stage_row_tile,
                               stage_vmem_bytes)
from repro.kernels import ops

BASELINE = (128, 128, 128)


def _dht_512():
    c = coefficient_matrix("dht", 512)
    return (512, 512, 512), [c, c, c]


def _video_plane(shape):
    return shape, [blocked_coefficient_matrix("dct", n, 8) for n in shape]


# The benchmark's deployments: NPB FT class C's 512³ DHT, and the luma and
# chroma planes of one 32-frame 4K 4:2:0 buffer coded in 8×8×8 cubes.
PROBLEMS = {
    "dht512": _dht_512,
    "luma": lambda: _video_plane((32, 2160, 3840)),
    "chroma": lambda: _video_plane((32, 1080, 1920)),
}


@pytest.fixture(scope="module", params=sorted(PROBLEMS))
def planned(request):
    shape, cs = PROBLEMS[request.param]()
    plan = build_plan(shape, jnp.float32, *cs)
    base = build_plan(shape, jnp.float32, *cs, block_sizes=BASELINE)
    return request.param, plan, base, cs


def test_row_tile_divides_rows_and_fits_every_role(planned):
    _, plan, _, _ = planned
    kernel_stages = [s for s in plan.stages if s.backend != "einsum"]
    assert kernel_stages
    for s in kernel_stages:
        assert s.rows % s.bm == 0 and s.bm % 128 == 0, s
        assert lane_tile_ok(s.bm, s.rows), s
        assert s.bm > 128, s  # the mechanism engages on these shapes
        roles = (_gemm_vmem_bytes(s.bm, s.bn, s.bk, 4),   # forward
                 _gemm_vmem_bytes(s.bm, s.bk, s.bn, 4),   # dX
                 _gemm_vmem_bytes(s.bk, s.bn, s.bm, 4))   # dC
        assert max(roles) == stage_vmem_bytes(s.bm, s.bn, s.bk, 4)
        assert max(roles) <= DEFAULT_VMEM_BUDGET, (s, roles)
        # the next multiple of 128 that divides the rows would not fit
        wider = [b for b in range(s.bm + 128, s.rows + 1, 128)
                 if s.rows % b == 0]
        if wider:
            assert (stage_vmem_bytes(wider[0], s.bn, s.bk, 4)
                    > DEFAULT_VMEM_BUDGET), (s, wider[0])


def test_only_the_row_tile_moves(planned):
    """Same order, backends, (bk, bn), zero fractions and sparsity
    signature as at 128-row tiles."""
    _, plan, base, cs = planned
    assert plan.order == base.order
    for s, b in zip(plan.stages, base.stages):
        assert (s.mode, s.backend) == (b.mode, b.backend)
        # (bk, bn) are the lane tiles of C's extents, as before
        assert (s.bk, s.bn) == (_lane_tile(s.n), _lane_tile(s.k)), s
        if s.backend != "einsum":
            assert (s.bn, s.bk) == (b.bn, b.bk)
            assert s.zero_block_frac == b.zero_block_frac
            assert s.macs_effective == b.macs_effective
    # the plan key's digest of the zero structure at those (bk, bn)
    blocks = {s.mode: (_lane_tile(s.n), _lane_tile(s.k)) for s in plan.stages}
    sig = sparsity_signature(dict(enumerate(cs, 1)), blocks)
    assert f"sig={sig}" in plan.key.split("|")
    assert plan.hbm_bytes_staged <= base.hbm_bytes_staged


def test_fusion_decision_stays(planned):
    """NPB FT's 512³ DHT keeps its fused pair on modes 1-2 (the staged
    pair still models more bytes); the video planes still fuse nothing."""
    name, plan, base, _ = planned
    assert plan.fused3 is None and base.fused3 is None
    if name == "dht512":
        assert plan.fused is not None
        assert {plan.fused.mode_a, plan.fused.mode_b} == {1, 2}
        assert (plan.fused.mode_a, plan.fused.mode_b) == (
            base.fused.mode_a, base.fused.mode_b)
        assert plan.fused.hbm_bytes_fused < plan.fused.hbm_bytes_staged
    else:
        assert plan.fused is None and base.fused is None


def test_row_tiles_of_the_benchmark_cells():
    """The tiles the two cells run: 2048 rows on NPB FT's SR-GEMM stage
    (32,768 grid steps at 128 rows, 2,048 now), 3840 on every video ESOP
    stage."""
    shape, cs = _dht_512()
    plan = build_plan(shape, jnp.float32, *cs)
    (s,) = [s for i, s in enumerate(plan.stages)
            if i not in (plan.fused.first, plan.fused.first + 1)]
    assert (s.backend, s.bm, s.bn, s.bk) == ("sr_gemm", 2048, 128, 128)
    for shape in ((32, 2160, 3840), (32, 1080, 1920)):
        shape, cs = _video_plane(shape)
        plan = build_plan(shape, jnp.float32, *cs)
        assert [(s.backend, s.bm) for s in plan.stages
                if s.backend != "einsum"] == [("esop", 3840)] * 2


def test_explicit_block_sizes_are_kept():
    shape, cs = _dht_512()
    plan = build_plan(shape, jnp.float32, *cs, block_sizes=(256, 128, 128),
                      fuse=False)
    assert all((s.bm, s.bn, s.bk) == (256, 128, 128) for s in plan.stages)


@pytest.mark.parametrize("rows,batch", [
    (64, 1),     # under 128: the pow2 ceiling
    (24, 1),
    (64, 4),     # 128 does not divide the per-sample rows
    (240, 1),
    (1000, 3),
])
def test_rows_128_does_not_divide_keep_todays_tile(rows, batch):
    assert (stage_row_tile(rows, batch, 128, 128, 4)
            == _lane_tile(rows * batch))


def test_row_tile_follows_dtype_accum_and_budget():
    rows = 122880  # a luma ESOP stage
    plain = stage_row_tile(rows, 1, 128, 128, 4)
    comp = stage_row_tile(rows, 1, 128, 128, 4, "compensated")
    bf16 = stage_row_tile(rows, 1, 128, 128, 2)
    assert plain == 3840
    assert comp < plain and rows % comp == 0  # the Neumaier scratch
    assert (stage_vmem_bytes(comp, 128, 128, 4, "compensated")
            <= DEFAULT_VMEM_BUDGET)
    assert bf16 >= plain
    assert stage_row_tile(rows, 1, 128, 128, 4, vmem_budget=1 << 16) == 128
    # a divisor of the per-sample rows divides them at every batch
    assert stage_row_tile(rows, 3, 128, 128, 4) == plain


def test_plan_rows_never_pad():
    """A batched plan's row tile divides the rows of every batch."""
    c = coefficient_matrix("dct", 128)
    plan = build_plan((4, 128, 128, 128), jnp.float32, c, c, c, fuse=False)
    for s in plan.stages:
        assert s.bm > 128 and s.rows % s.bm == 0, s


# ---------------------------------------------------------------------------
# kernel parity at the wider row tile (interpret mode on CPU)
# ---------------------------------------------------------------------------

ROWS, N = 1024, 256


def _operands(seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(ROWS, N)).astype(np.float32))
    g = jnp.asarray(rng.normal(size=(ROWS, N)).astype(np.float32))
    return x, g


def _wide_bm():
    bm = stage_row_tile(ROWS, 1, 128, 128, 4)
    assert bm > 128
    return bm


def _close(a, b):
    # Same 128-deep passes in the same k order; the CPU backend may still
    # split a pass's dot differently by its row count, so allow float32
    # rounding of a 256-deep sum at the operands' scale.
    a, b = np.asarray(a), np.asarray(b)
    scale = float(np.max(np.abs(b)))
    np.testing.assert_allclose(a, b, rtol=0,
                               atol=4 * N * np.finfo(np.float32).eps * scale)


@pytest.mark.parametrize("kind", ["dense", "blocked"])
def test_sr_gemm_wide_row_tile_matches_128_with_vjp(kind):
    x, g = _operands(11)
    c = (coefficient_matrix("dct", N) if kind == "dense"
         else blocked_coefficient_matrix("dct", N, 8))
    bm = _wide_bm()

    def run(bm):
        f = lambda x, c: ops.sr_gemm(x, c, bm=bm, bn=128, bk=128,
                                     use_pallas=True)
        y, vjp = jax.vjp(f, x, c)
        return (y, *vjp(g))

    for got, want in zip(run(bm), run(128)):  # y, dX, dC
        _close(got, want)


def test_esop_gemm_wide_row_tile_matches_128_with_vjp():
    """The block-diagonal DCT factor on the ESOP kernel: one live 128-block
    per column at either row tile, and the same output and dX."""
    x, g = _operands(12)
    c = blocked_coefficient_matrix("dct", N, 8)
    bm = _wide_bm()
    assert ops.esop_gemm(x, c, bm=bm, bn=128, bk=128,
                         use_pallas=True)[1]["t_steps"] == 1

    def run(bm):
        f = lambda x: ops.esop_gemm(x, c, bm=bm, bn=128, bk=128,
                                    use_pallas=True)[0]
        y, vjp = jax.vjp(f, x)
        return y, vjp(g)[0]

    for got, want in zip(run(bm), run(128)):  # y, dX
        _close(got, want)

"""Fused two-stage GEMT: kernel vs the gemt3 oracle across dtypes, odd
shapes, batching and block sparsity; plan-level fusion trigger/decline
boundaries; fused autotune; tier-2 bench smoke."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import coefficient_matrix, dxt3d, gemt3
from repro.engine import (AutotuneCache, autotune_fused, build_plan,
                          fused_tile_sizes, fused_vmem_bytes, gemt3_planned)
from repro.kernels import ops

RNG = np.random.default_rng(17)


def _rand(*shape, dtype=np.float32):
    return jnp.asarray(RNG.normal(size=shape).astype(np.float32), dtype=dtype)


def _problem(dims, ranks, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=dims).astype(np.float32), dtype=dtype)
    cs = tuple(jnp.asarray(rng.normal(size=(n, k)).astype(np.float32),
                           dtype=dtype)
               for n, k in zip(dims[-3:], ranks))
    return x, cs


def _block_sparse(n, k, keep, block):
    """Coefficient matrix with the given boolean block-keep pattern
    (``block`` is an edge or a (rows, cols) pair)."""
    dense = RNG.normal(size=(n, k)).astype(np.float32)
    shape = block if isinstance(block, tuple) else (block, block)
    return jnp.asarray(np.kron(keep, np.ones(shape)) * dense)


class TestFusedOp:
    """ops.fused_gemt directly: reference path and interpret-mode Pallas."""

    @pytest.mark.parametrize("use_pallas", [False, True])
    def test_square_matches_einsum(self, use_pallas):
        x3, ca, cb = _rand(24, 32, 32), _rand(32, 32), _rand(32, 32)
        y, info = ops.fused_gemt(x3, ca, cb, bu=8, bka=16, bnb=16, bna=16,
                                 use_pallas=use_pallas)
        ref = jnp.einsum("uba,ak,bl->ukl", x3, ca, cb)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
        assert info["fetch_savings"] == 0.0  # dense: nothing skipped

    @pytest.mark.parametrize("use_pallas", [False, True])
    def test_odd_shapes_padded(self, use_pallas):
        """Non-multiple-of-block extents everywhere."""
        x3, ca, cb = _rand(13, 17, 9), _rand(9, 11), _rand(17, 10)
        y, _ = ops.fused_gemt(x3, ca, cb, bu=8, bka=8, bnb=8, bna=8,
                              use_pallas=use_pallas)
        ref = jnp.einsum("uba,ak,bl->ukl", x3, ca, cb)
        assert y.shape == (13, 11, 10)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("use_pallas", [False, True])
    def test_bf16(self, use_pallas):
        x3 = _rand(16, 32, 32, dtype=jnp.bfloat16)
        ca = _rand(32, 16, dtype=jnp.bfloat16)
        cb = _rand(32, 16, dtype=jnp.bfloat16)
        y, _ = ops.fused_gemt(x3, ca, cb, bu=16, bka=16, bnb=16, bna=16,
                              use_pallas=use_pallas)
        ref = jnp.einsum("uba,ak,bl->ukl", x3.astype(jnp.float32),
                         ca.astype(jnp.float32), cb.astype(jnp.float32))
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(ref), rtol=5e-2, atol=5e-1)

    def test_complex_routes_to_reference(self):
        """DFT coefficients: the real-valued kernel is bypassed either way."""
        x3 = _rand(8, 16, 16).astype(jnp.complex64)
        ca = coefficient_matrix("dft", 16)
        cb = coefficient_matrix("dft", 16)
        y, _ = ops.fused_gemt(x3, ca, cb, bu=8, bka=8, bnb=8, bna=8,
                              use_pallas=True)  # forced: still reference
        ref = jnp.einsum("uba,ak,bl->ukl", x3, ca, cb)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("use_pallas", [False, True])
    def test_sparse_both_streams_skip(self, use_pallas):
        """Zero blocks of C_a and zero slabs of C_b are skipped exactly."""
        keep_a = np.array([[1, 0], [0, 1]]).astype(bool)
        ca = _block_sparse(32, 32, keep_a, 16)
        cb0 = np.zeros((32, 16), np.float32)
        cb0[:16] = RNG.normal(size=(16, 16))  # lower slab entirely zero
        cb = jnp.asarray(cb0)
        x3 = _rand(16, 32, 32)
        y, info = ops.fused_gemt(x3, ca, cb, bu=16, bka=16, bnb=16, bna=16,
                                 use_pallas=use_pallas)
        ref = jnp.einsum("uba,ak,bl->ukl", x3, ca, cb)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
        assert info["blocks_live_a"] == 2 and info["blocks_dense_a"] == 4
        assert info["slabs_live_b"] == 1 and info["slabs_dense_b"] == 2
        assert info["fetch_savings"] == pytest.approx(0.75)

    def test_pallas_info_matches_reference_info(self):
        """Accounting is backend-independent (same dict both paths)."""
        ca = _block_sparse(32, 32, np.array([[1, 0], [1, 1]]).astype(bool), 16)
        cb = _rand(32, 16)
        x3 = _rand(16, 32, 32)
        _, i_ref = ops.fused_gemt(x3, ca, cb, bu=16, bka=16, bnb=16, bna=16,
                                  use_pallas=False)
        _, i_pal = ops.fused_gemt(x3, ca, cb, bu=16, bka=16, bnb=16, bna=16,
                                  use_pallas=True)
        assert i_ref == i_pal


class TestFusedEngine:
    """gemt3_planned with fusion vs the einsum oracle."""

    @pytest.mark.parametrize("dims,ranks", [
        ((16, 16, 16), (16, 16, 16)),   # cube
        ((24, 20, 16), (8, 10, 12)),    # rectangular compressive
        ((13, 17, 9), (9, 10, 11)),     # odd non-multiple-of-block
    ])
    def test_forced_fusion_matches_oracle(self, dims, ranks):
        x, cs = _problem(dims, ranks, seed=1)
        y, info = gemt3_planned(x, *cs, fuse=True, with_info=True)
        assert info["fused"] is not None
        np.testing.assert_allclose(np.asarray(y), np.asarray(gemt3(x, *cs)),
                                   rtol=1e-4, atol=1e-4)

    def test_batched_matches_vmap(self):
        x, cs = _problem((4, 16, 12, 16), (8, 10, 12), seed=2)
        y, info = gemt3_planned(x, *cs, fuse=True, with_info=True)
        assert info["fused"] is not None
        ref = jax.vmap(lambda t: gemt3(t, *cs))(x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

    def test_bf16_engine(self):
        x, cs = _problem((16, 16, 16), (16, 16, 16), seed=3,
                         dtype=jnp.bfloat16)
        y = gemt3_planned(x, *cs, fuse=True)
        # f32 oracle: the fused path accumulates both stages in f32, the
        # bf16 einsum chain rounds between stages — compare to the truth,
        # scaled to the chained-bf16 rounding error
        ref = gemt3(x.astype(jnp.float32),
                    *(c.astype(jnp.float32) for c in cs))
        scale = float(jnp.max(jnp.abs(ref)))
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(ref),
                                   rtol=5e-2, atol=5e-2 * scale)

    def test_complex_declines_but_matches(self):
        """DFT: fusion declines (kernel is real-valued), result unchanged."""
        x = _rand(16, 16, 16)
        y, info = dxt3d(x, "dft", engine=True, fuse=True, with_info=True)
        assert info["fused"] is None
        ref = dxt3d(x, "dft")
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

    def test_sparse_fused_engine(self):
        """Block-sparse C composes with fusion (ESOP on the a-stream)."""
        keep = np.array([[1, 0, 0, 1]] * 4).astype(bool)
        c3 = _block_sparse(128, 128, keep, 32)
        c1, c2 = _rand(16, 16), _rand(16, 16)
        x = _rand(16, 16, 128)
        y, info = gemt3_planned(x, c1, c2, c3, fuse=True, with_info=True)
        assert info["fused"] is not None
        # 128-length contractions reassociated between schedules: ~1e-3 rel
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(gemt3(x, c1, c2, c3)),
                                   rtol=5e-3, atol=5e-4)

    def test_affine_out_applies_after_fusion(self):
        x, cs = _problem((16, 12, 16), (8, 10, 12), seed=4)
        out = _rand(8, 10, 12)
        y = gemt3_planned(x, *cs, out=out, fuse=True)
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(gemt3(x, *cs, out=out)),
                                   rtol=1e-4, atol=1e-4)

    def test_interpret_pallas_through_engine(self):
        """The fused Pallas kernel (interpret off-TPU) inside the engine."""
        x, cs = _problem((16, 16, 16), (16, 16, 16), seed=5)
        y, info = gemt3_planned(x, *cs, fuse=True, use_pallas=True,
                                with_info=True)
        assert info["fused"] is not None
        np.testing.assert_allclose(np.asarray(y), np.asarray(gemt3(x, *cs)),
                                   rtol=2e-4, atol=2e-4)


class TestFusionDecision:
    """Plan-level: fusion triggers/declines on the modeled boundaries."""

    def _serving(self, batch=8, n=32):
        c = coefficient_matrix("dct", n)
        return (batch, n, n, n), (c, c, c)

    def test_triggers_on_serving_shape_with_savings(self):
        # fuse="pair" pins the pair depth: since the whole-transform
        # megakernel landed, auto mode prefers the triple on these shapes
        # (tests/test_fused3_gemt.py covers that boundary).
        shape, cs = self._serving()
        plan = build_plan(shape, jnp.float32, *cs, fuse="pair")
        assert plan.fused is not None
        assert plan.fused.hbm_savings > 1.5
        assert plan.hbm_bytes_moved < plan.hbm_bytes_staged
        # the fused pair covers consecutive stages of the chosen order
        assert plan.fused.first in (0, 1)
        pair = {plan.order[plan.fused.first], plan.order[plan.fused.first + 1]}
        assert pair == {plan.fused.mode_a, plan.fused.mode_b}

    def test_fuse_false_pins_staged(self):
        shape, cs = self._serving()
        plan = build_plan(shape, jnp.float32, *cs, fuse=False)
        assert plan.fused is None
        assert plan.hbm_bytes_moved == plan.hbm_bytes_staged

    def test_declines_when_tiles_cannot_fit_vmem(self):
        shape, cs = self._serving()
        assert build_plan(shape, jnp.float32, *cs, fuse="pair",
                          vmem_budget=1024).fused is None
        # the boundary is monotone: a roomy budget fuses again
        assert build_plan(shape, jnp.float32, *cs, fuse="pair",
                          vmem_budget=64 << 20).fused is not None

    def test_vmem_model_boundary(self):
        """Fusion flips exactly where the modeled footprint crosses."""
        shape, cs = self._serving()
        plan = build_plan(shape, jnp.float32, *cs, fuse="pair")
        need = plan.fused.vmem_bytes
        assert build_plan(shape, jnp.float32, *cs, fuse="pair",
                          vmem_budget=need).fused is not None
        # the minimal-footprint tiling (bu = 1, bnb = 8, the fixed lane
        # tiles) is the true floor
        f = plan.fused
        floor = fused_vmem_bytes(1, f.bka, 8, f.bna, f.kbp, 4)
        assert build_plan(shape, jnp.float32, *cs, fuse="pair",
                          vmem_budget=floor - 1).fused is None

    def test_declines_below_kernel_dims(self):
        """Sub-MIN_KERNEL_DIM extents fall back to staged (einsum) stages."""
        x, cs = _problem((4, 4, 4), (4, 4, 4))
        plan = build_plan(x.shape, x.dtype, *cs, fuse=True)
        assert plan.fused is None

    def test_declines_for_complex(self):
        c = coefficient_matrix("dft", 16)
        plan = build_plan((16, 16, 16), jnp.complex64, c, c, c, fuse=True)
        assert plan.fused is None

    def test_pair_choice_prefers_larger_intermediate(self):
        """Rectangular Tucker: the fused pair is the two compressive modes."""
        dims, ranks = (64, 48, 32), (8, 16, 32)
        x, cs = _problem(dims, ranks, seed=6)
        plan = build_plan(x.shape, x.dtype, *cs)
        assert plan.fused is not None
        # compressive modes 1 and 2 are contracted first and fused
        assert {plan.fused.mode_a, plan.fused.mode_b} == {1, 2}

    def test_sparse_assignment_lands_on_a_stream(self):
        """A compressive sparse C streams as C_a, where 2D skipping works.

        (When K_a is large the model may legitimately prefer the dense
        matrix on the a-stream — X refetches per ka-block outweigh the
        skipping — so this pins the compressive case where ESOP-on-a is
        the clear bytes winner.)
        """
        # 50% zero blocks at a TPU-legal grid: the na tile is X's lane dim,
        # so it is 128 here (a multiple of 128 or the whole extent)
        keep = np.array([[1], [0]]).astype(bool)
        c3 = _block_sparse(256, 64, keep, (128, 64))
        c1, c2 = jnp.asarray(np.eye(64, dtype=np.float32)), _rand(48, 48)
        plan = build_plan((64, 48, 256), jnp.float32, c1, c2, c3, fuse="pair",
                          block_sizes=(128, 64, 128))
        assert plan.fused is not None
        assert plan.fused.mode_a == 3
        assert plan.fused.zero_block_frac_a == pytest.approx(0.5)
        assert plan.fused.zero_block_frac_b == 0.0

    def test_key_distinguishes_fusion_options(self):
        shape, cs = self._serving()
        k0 = build_plan(shape, jnp.float32, *cs).key
        k1 = build_plan(shape, jnp.float32, *cs, fuse=False).key
        k2 = build_plan(shape, jnp.float32, *cs, vmem_budget=1 << 20).key
        assert len({k0, k1, k2}) == 3

    def test_fused_tile_sizes_fit_budget(self):
        for budget in (1 << 18, 1 << 20, 1 << 23):
            tiles = fused_tile_sizes(256, 64, 64, 64, 64, 4, budget)
            if tiles is not None:
                bu, bka, bnb, bna, kbp = tiles
                assert fused_vmem_bytes(bu, bka, bnb, bna, kbp, 4) <= budget


class TestFusedAutotune:
    def test_autotune_fused_caches_and_matches(self, tmp_path):
        cache = AutotuneCache(str(tmp_path / "a.json"))
        x, cs = _problem((16, 16, 16), (16, 16, 16), seed=8)
        y = gemt3_planned(x, *cs, fuse="pair", autotune=True,
                          autotune_cache=cache)
        np.testing.assert_allclose(np.asarray(y), np.asarray(gemt3(x, *cs)),
                                   rtol=1e-4, atol=1e-4)
        assert any(k.startswith("fused:") for k in cache._entries)

    def test_autotune_fused_respects_vmem_budget(self, tmp_path):
        cache = AutotuneCache(str(tmp_path / "a.json"))
        ca, cb = _rand(32, 32), _rand(32, 32)
        budget = fused_vmem_bytes(16, 16, 16, 16, 32, 4)
        bu, bka, bnb = autotune_fused(
            ca, cb, rows=64, dtype=jnp.float32, start=(16, 16, 16),
            bna=16, kbp=32, cache=cache, use_pallas=True, max_steps=1,
            reps=1, vmem_budget=budget)
        assert fused_vmem_bytes(bu, bka, bnb, 16, 32, 4) <= budget


class TestFusedServe:
    def test_serve_session_reports_fusion(self):
        from repro.serve import DxtServeSession
        sess = DxtServeSession(kind="dct")
        b = _rand(4, 16, 16, 16)
        y = sess.transform(b)
        ref = jax.vmap(lambda t: dxt3d(t, "dct"))(b)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)
        assert sess.last_info["fused"] is not None
        assert sess.fused_served == 4
        assert 0 < sess.hbm_bytes_moved < sess.hbm_bytes_staged
        # staged sessions stay available and report zero fused traffic
        sess_staged = DxtServeSession(kind="dct", fuse=False)
        sess_staged.transform(b)
        assert sess_staged.fused_served == 0
        assert sess_staged.hbm_bytes_moved == sess_staged.hbm_bytes_staged


@pytest.mark.bench_smoke
def test_bench_smoke_fused_vs_staged():
    """Tier-2 smoke: one tiny fused-vs-staged comparison, exercised in the
    default run (select just this with ``pytest -m bench_smoke``)."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 16, 16, 16)).astype(np.float32))
    c = coefficient_matrix("dct", 16)
    y_staged, i_staged = gemt3_planned(x, c, c, c, fuse=False, with_info=True)
    y_fused, i_fused = gemt3_planned(x, c, c, c, with_info=True)
    assert i_staged["fused"] is None and i_fused["fused"] is not None
    np.testing.assert_allclose(np.asarray(y_fused), np.asarray(y_staged),
                               rtol=1e-4, atol=1e-4)
    assert i_fused["hbm_bytes_moved"] < i_staged["hbm_bytes_moved"]
    assert i_fused["fused"]["hbm_savings"] > 1.0

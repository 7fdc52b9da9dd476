"""Whole-transform megakernel: kernel vs the gemt3 oracle across dtypes,
odd shapes, batching and block sparsity on all three coefficient streams;
plan-level triple → pair → staged degradation boundaries; the budget-keyed
fused autotune caches; serve integration."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import coefficient_matrix, dxt3d, gemt3
from repro.engine import (AutotuneCache, autotune_fused3, build_plan,
                          fused3_tile_sizes, fused3_vmem_bytes,
                          fused_vmem_bytes, gemt3_planned, make_fused3_key,
                          make_fused_key)
from repro.kernels import ops

RNG = np.random.default_rng(23)


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
    dense = RNG.normal(size=(n, k)).astype(np.float32)
    return jnp.asarray(np.kron(keep, np.ones((block, block))) * dense)


def _ref4(x4, ca, cb, cc):
    return jnp.einsum("ucba,ak,bl,cm->uklm", x4, ca, cb, cc)


class TestFused3Op:
    """ops.fused3_gemt directly: reference path and interpret-mode Pallas."""

    @pytest.mark.parametrize("use_pallas", [False, True])
    def test_square_matches_einsum(self, use_pallas):
        x4 = _rand(8, 16, 16, 16)
        ca, cb, cc = _rand(16, 16), _rand(16, 16), _rand(16, 16)
        y, info = ops.fused3_gemt(x4, ca, cb, cc, bu=8, bka=8, bnb=8, bnc=8,
                                  bna=8, use_pallas=use_pallas)
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(_ref4(x4, ca, cb, cc)),
                                   rtol=2e-4, atol=2e-4)
        assert info["fetch_savings"] == 0.0  # dense: nothing skipped
        assert info["t_steps"] == (2, 2, 2)

    @pytest.mark.parametrize("use_pallas", [False, True])
    def test_odd_shapes_padded(self, use_pallas):
        """Non-multiple-of-block extents on every axis."""
        x4 = _rand(5, 13, 11, 9)
        ca, cb, cc = _rand(9, 10), _rand(11, 7), _rand(13, 12)
        y, _ = ops.fused3_gemt(x4, ca, cb, cc, bu=8, bka=8, bnb=8, bnc=8,
                               bna=8, use_pallas=use_pallas)
        assert y.shape == (5, 10, 7, 12)
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(_ref4(x4, ca, cb, cc)),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("use_pallas", [False, True])
    def test_bf16(self, use_pallas):
        x4 = _rand(8, 16, 16, 16, dtype=jnp.bfloat16)
        cs = [_rand(16, 16, dtype=jnp.bfloat16) for _ in range(3)]
        y, _ = ops.fused3_gemt(x4, *cs, bu=8, bka=16, bnb=16, bnc=16,
                               bna=16, use_pallas=use_pallas)
        ref = _ref4(*(t.astype(jnp.float32) for t in (x4, *cs)))
        # three chained bf16 roundings over a 16^3 contraction: scale the
        # tolerance to the result's magnitude
        scale = float(jnp.max(jnp.abs(ref)))
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(ref), rtol=5e-2,
                                   atol=5e-2 * scale)

    def test_complex_routes_to_reference(self):
        """DFT coefficients: the real-valued kernel is bypassed either way."""
        x4 = _rand(4, 16, 16, 16).astype(jnp.complex64)
        c = coefficient_matrix("dft", 16)
        y, _ = ops.fused3_gemt(x4, c, c, c, bu=8, bka=8, bnb=8, bnc=8,
                               bna=8, use_pallas=True)  # forced: still ref
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(_ref4(x4, c, c, c)),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("use_pallas", [False, True])
    def test_sparse_all_three_streams_skip(self, use_pallas):
        """Zero blocks of C_a and zero slabs of C_b / C_c are skipped, and
        skipping is exact: the sparse result bit-matches the dense product
        of the same matrices (adding 0 is exact in IEEE arithmetic)."""
        keep_a = np.array([[1, 0], [0, 1]]).astype(bool)
        ca = _block_sparse(32, 32, keep_a, 16)
        cb0 = np.zeros((32, 16), np.float32)
        cb0[:16] = RNG.normal(size=(16, 16))  # upper slab live, lower zero
        cc0 = np.zeros((32, 16), np.float32)
        cc0[16:] = RNG.normal(size=(16, 16))  # lower slab live, upper zero
        cb, cc = jnp.asarray(cb0), jnp.asarray(cc0)
        x4 = _rand(8, 32, 32, 32)
        y, info = ops.fused3_gemt(x4, ca, cb, cc, bu=8, bka=16, bnb=16,
                                  bnc=16, bna=16, use_pallas=use_pallas)
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(_ref4(x4, ca, cb, cc)),
                                   rtol=2e-4, atol=2e-4)
        assert info["blocks_live_a"] == 2 and info["blocks_dense_a"] == 4
        assert info["slabs_live_b"] == 1 and info["slabs_dense_b"] == 2
        assert info["slabs_live_c"] == 1 and info["slabs_dense_c"] == 2
        assert info["fetch_savings"] == pytest.approx(1 - 2 / 16)

    def test_pallas_matches_reference_accounting_and_values(self):
        """Accounting is backend-independent (bit-identical info dicts both
        paths), and the interpret-mode kernel agrees with kernels/ref.py to
        f32 reduction-order resolution over the 32³ contraction."""
        ca = _block_sparse(32, 32, np.array([[1, 0], [1, 1]]).astype(bool),
                           16)
        cb, cc = _rand(32, 16), _rand(32, 16)
        x4 = _rand(8, 32, 32, 32)
        y_ref, i_ref = ops.fused3_gemt(x4, ca, cb, cc, bu=8, bka=16, bnb=16,
                                       bnc=16, bna=16, use_pallas=False)
        y_pal, i_pal = ops.fused3_gemt(x4, ca, cb, cc, bu=8, bka=16, bnb=16,
                                       bnc=16, bna=16, use_pallas=True)
        assert i_ref == i_pal
        np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                                   rtol=2e-4, atol=2e-4)

    def test_shape_mismatch_raises(self):
        x4 = _rand(4, 8, 8, 8)
        with pytest.raises(ValueError, match="incompatible"):
            ops.fused3_gemt(x4, _rand(9, 8), _rand(8, 8), _rand(8, 8))


class TestFused3Engine:
    """gemt3_planned with triple fusion vs the einsum oracle."""

    @pytest.mark.parametrize("dims,ranks", [
        ((16, 16, 16), (16, 16, 16)),   # cube
        ((24, 20, 16), (8, 10, 12)),    # rectangular compressive
        ((13, 17, 9), (9, 10, 11)),     # odd non-multiple-of-block
    ])
    def test_forced_triple_matches_oracle(self, dims, ranks):
        x, cs = _problem(dims, ranks, seed=1)
        y, info = gemt3_planned(x, *cs, fuse="triple", with_info=True)
        assert info["fused"] is not None
        assert len(info["fused"]["modes"]) == 3
        assert info["backends_executed"] == (
            "fused" + str(info["fused"]["modes"]),)
        np.testing.assert_allclose(np.asarray(y), np.asarray(gemt3(x, *cs)),
                                   rtol=1e-4, atol=1e-4)

    def test_batched_matches_vmap(self):
        x, cs = _problem((4, 16, 12, 16), (8, 10, 12), seed=2)
        y, info = gemt3_planned(x, *cs, fuse="triple", with_info=True)
        assert info["fused"] is not None
        ref = jax.vmap(lambda t: gemt3(t, *cs))(x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

    def test_bf16_engine(self):
        x, cs = _problem((8, 16, 16, 16), (16, 16, 16), seed=3,
                         dtype=jnp.bfloat16)
        y = gemt3_planned(x, *cs, fuse="triple")
        ref = jax.vmap(lambda t: gemt3(t, *(c.astype(jnp.float32)
                                            for c in cs)))(
            x.astype(jnp.float32))
        scale = float(jnp.max(jnp.abs(ref)))
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(ref),
                                   rtol=5e-2, atol=5e-2 * scale)

    def test_complex_declines_but_matches(self):
        """DFT: triple fusion declines (kernel is real-valued), result
        unchanged."""
        x = _rand(16, 16, 16)
        y, info = dxt3d(x, "dft", engine=True, fuse=True, with_info=True)
        assert info["fused"] is None
        np.testing.assert_allclose(np.asarray(y), np.asarray(dxt3d(x, "dft")),
                                   rtol=1e-4, atol=1e-4)

    def test_sparse_esop_triple_fusion(self):
        """Block-sparse coefficients compose with triple fusion: the ESOP
        schedule skips dead work on whichever stream the planner assigns
        the sparse matrix to, and skipping is exact (zero blocks contribute
        exactly zero, so the fused result matches the staged dense one)."""
        # half of C3's 16-row slabs are entirely zero, so slab-level
        # skipping engages even if C3 lands on the b/c slab streams
        keep = np.array([[1, 0, 0, 1], [0, 0, 0, 0],
                         [0, 0, 0, 0], [1, 0, 0, 1]]).astype(bool)
        c3 = _block_sparse(64, 64, keep, 16)
        c1, c2 = _rand(16, 16), _rand(16, 16)
        x = _rand(8, 16, 16, 64)
        # 16-wide stage blocks so the zero pattern is visible to the planner
        # (the default pow2 clamp would grid this C as one 64x64 block)
        y, info = gemt3_planned(x, c1, c2, c3, fuse="triple",
                                block_sizes=(8, 16, 16), with_info=True)
        f = info["fused"]
        assert f is not None and len(f["modes"]) == 3
        assert info["fetch_savings"] > 0  # dead blocks/slabs never fetched
        assert f["blocks_live"] < f["blocks_dense"]
        y_dense = gemt3_planned(x, c1, c2, c3, fuse=False)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_dense),
                                   rtol=5e-3, atol=5e-4)
        # and the interpret-mode Pallas kernel agrees with the reference path
        y_pal = gemt3_planned(x, c1, c2, c3, fuse="triple",
                              block_sizes=(8, 16, 16), use_pallas=True)
        np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y),
                                   rtol=2e-4, atol=2e-4)

    def test_sparse_compressive_prefers_skipping_stream(self):
        """A strongly block-sparse compressive matrix ends up on a stream
        where its zero blocks are modeled as skipped (nonzero
        zero_block_frac on its assigned slot)."""
        keep = np.array([[1], [0], [0], [1]]).astype(bool)  # 50% zero slabs
        c3 = _block_sparse(256, 64, keep, 64)
        c1, c2 = _rand(64, 64), _rand(48, 48)
        plan = build_plan((8, 64, 48, 256), jnp.float32, c1, c2, c3,
                          fuse="triple", block_sizes=(128, 64, 64))
        assert plan.fused3 is not None
        ft = plan.fused3
        slot = {ft.mode_a: ft.zero_block_frac_a,
                ft.mode_b: ft.zero_block_frac_b,
                ft.mode_c: ft.zero_block_frac_c}
        assert slot[3] == pytest.approx(0.5)  # C3's zeros stay skippable

    def test_affine_out_applies_after_fusion(self):
        x, cs = _problem((8, 16, 12, 16), (8, 10, 12), seed=4)
        out = _rand(8, 8, 10, 12)
        y = gemt3_planned(x, *cs, out=out, fuse="triple")
        ref = jax.vmap(lambda t, o: gemt3(t, *cs, out=o))(x, out)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

    def test_interpret_pallas_through_engine(self):
        x, cs = _problem((8, 16, 16, 16), (16, 16, 16), seed=5)
        y, info = gemt3_planned(x, *cs, fuse="triple", use_pallas=True,
                                with_info=True)
        assert info["fused"] is not None
        ref = jax.vmap(lambda t: gemt3(t, *cs))(x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)


class TestTripleDecision:
    """Plan-level: triple → pair → staged degradation on the modeled
    boundaries."""

    def _serving(self, batch=8, n=32):
        c = coefficient_matrix("dct", n)
        return (batch, n, n, n), (c, c, c)

    def test_auto_prefers_triple_on_serving_shape(self):
        shape, cs = self._serving()
        plan = build_plan(shape, jnp.float32, *cs)
        assert plan.fused3 is not None and plan.fused is None
        pair = build_plan(shape, jnp.float32, *cs, fuse="pair")
        assert plan.hbm_bytes_moved < pair.hbm_bytes_moved
        assert plan.hbm_bytes_moved < plan.hbm_bytes_staged
        assert plan.fused3.hbm_savings > 2.5

    def test_degradation_triple_pair_staged(self):
        """Shrinking the VMEM budget walks the schedule down the ladder:
        triple at the default budget, pair when the triple's accumulator
        no longer fits, staged when nothing does."""
        shape, cs = self._serving()
        full = build_plan(shape, jnp.float32, *cs)
        assert full.fused3 is not None  # triple fits the default budget
        # below the triple's minimal footprint (bu = 1, bnb = bnc = 8, the
        # fixed lane tiles) but above the pair's
        ft = full.fused3
        t_floor = fused3_vmem_bytes(1, ft.bka, 8, 8, ft.bna, ft.kbp, ft.kcp,
                                    4)
        mid = build_plan(shape, jnp.float32, *cs, fuse=True,
                         vmem_budget=t_floor - 1)
        assert mid.fused3 is None and mid.fused is not None
        # below the pair's minimal footprint: fully staged
        fp = mid.fused
        p_floor = fused_vmem_bytes(1, fp.bka, 8, fp.bna, fp.kbp, 4)
        low = build_plan(shape, jnp.float32, *cs, fuse=True,
                         vmem_budget=min(t_floor, p_floor) - 1)
        assert low.fused3 is None and low.fused is None
        # the modeled bytes are monotone along the ladder
        assert (full.hbm_bytes_moved < mid.hbm_bytes_moved
                <= low.hbm_bytes_moved == low.hbm_bytes_staged)

    def test_auto_degrades_to_pair_when_triple_models_more_bytes(self):
        """A budget-starved triple (bu shrunk → the C_b/C_c slabs are
        re-streamed once per u-block) loses to the pair on the byte model
        even though it still *fits* — auto mode must pick the pair then."""
        shape, cs = self._serving(batch=2, n=16)
        t_budget = None
        for shift in range(18, 25):  # find a budget where triple fits ...
            budget = 1 << shift
            p = build_plan(shape, jnp.float32, *cs, fuse="triple",
                           vmem_budget=budget)
            if p.fused3 is None:
                continue
            auto = build_plan(shape, jnp.float32, *cs, vmem_budget=budget)
            pair = build_plan(shape, jnp.float32, *cs, fuse="pair",
                              vmem_budget=budget)
            if (pair.fused is not None
                    and pair.hbm_bytes_moved < p.hbm_bytes_moved):
                # ... but models more bytes than the pair: auto takes pair
                assert auto.fused3 is None and auto.fused is not None
                t_budget = budget
                break
        assert t_budget is not None, "no boundary budget found"

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtype_sweep_boundaries(self, dtype):
        """The degradation ladder exists for every kernel dtype; complex64
        never fuses at any budget."""
        shape, cs = self._serving(batch=8, n=16)
        cs = tuple(c.astype(dtype) for c in cs)
        assert build_plan(shape, dtype, *cs,
                          fuse="triple").fused3 is not None
        assert build_plan(shape, dtype, *cs, fuse="triple",
                          vmem_budget=1024).fused3 is None

    def test_complex64_never_fuses(self):
        c = coefficient_matrix("dft", 16)
        for budget in (1 << 20, 1 << 30):
            p = build_plan((8, 16, 16, 16), jnp.complex64, c, c, c,
                           fuse=True, vmem_budget=budget)
            assert p.fused3 is None and p.fused is None

    def test_fuse_false_and_pair_pin_depth(self):
        shape, cs = self._serving()
        assert build_plan(shape, jnp.float32, *cs, fuse=False).fused3 is None
        p = build_plan(shape, jnp.float32, *cs, fuse="pair")
        assert p.fused3 is None and p.fused is not None
        with pytest.raises(ValueError, match="fuse must be one of"):
            build_plan(shape, jnp.float32, *cs, fuse="both")

    def test_key_distinguishes_fuse_modes(self):
        shape, cs = self._serving()
        keys = {build_plan(shape, jnp.float32, *cs, fuse=f).key
                for f in (None, False, "pair", "triple")}
        assert len(keys) == 4

    def test_vmem_model_boundary_is_exact(self):
        """Triple fusion flips exactly where the modeled footprint crosses."""
        shape, cs = self._serving()
        ft = build_plan(shape, jnp.float32, *cs, fuse="triple").fused3
        assert build_plan(shape, jnp.float32, *cs, fuse="triple",
                          vmem_budget=ft.vmem_bytes).fused3 is not None
        floor = fused3_vmem_bytes(1, ft.bka, 8, 8, ft.bna, ft.kbp, ft.kcp, 4)
        assert build_plan(shape, jnp.float32, *cs, fuse="triple",
                          vmem_budget=floor - 1).fused3 is None

    def test_fused3_tile_sizes_fit_budget(self):
        for budget in (1 << 19, 1 << 21, 1 << 23):
            tiles = fused3_tile_sizes(8, 64, 64, 64, 64, 64, 64, 4, budget)
            if tiles is not None:
                assert fused3_vmem_bytes(*tiles, 4) <= budget

    def test_unbatched_u_padding_is_modeled(self):
        """batch=1 pads U 1→8 in the kernel; the byte model carries the ×8
        and forcing still computes correctly."""
        x, cs = _problem((16, 16, 16), (16, 16, 16), seed=7)
        y, info = gemt3_planned(x, *cs, fuse="triple", with_info=True)
        assert info["fused"] is not None
        np.testing.assert_allclose(np.asarray(y), np.asarray(gemt3(x, *cs)),
                                   rtol=1e-4, atol=1e-4)


class TestFused3Autotune:
    def test_autotune_fused3_caches_and_matches(self, tmp_path):
        cache = AutotuneCache(str(tmp_path / "a.json"))
        x, cs = _problem((8, 16, 16, 16), (16, 16, 16), seed=8)
        y = gemt3_planned(x, *cs, fuse="triple", autotune=True,
                          autotune_cache=cache)
        ref = jax.vmap(lambda t: gemt3(t, *cs))(x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)
        assert any(k.startswith("fused3:") for k in cache._entries)

    def test_autotune_fused3_respects_vmem_budget(self, tmp_path):
        cache = AutotuneCache(str(tmp_path / "a.json"))
        ca, cb, cc = _rand(32, 32), _rand(32, 32), _rand(32, 32)
        budget = fused3_vmem_bytes(8, 16, 16, 16, 16, 32, 32, 4)
        bu, bka, bnb, bnc = autotune_fused3(
            ca, cb, cc, rows=16, dtype=jnp.float32, start=(8, 16, 16, 16),
            bna=16, kbp=32, kcp=32, cache=cache, use_pallas=True,
            max_steps=1, reps=1, vmem_budget=budget)
        assert fused3_vmem_bytes(bu, bka, bnb, bnc, 16, 32, 32, 4) <= budget

    def test_budget_is_part_of_the_cache_key(self):
        """Regression (PR 4 satellite): the plan cache keyed ``vb=`` but the
        autotune cache did not, so tiles tuned under a roomy budget could
        replay under a stricter one and exceed it."""
        a = make_fused_key(64, 32, 32, 32, 32, jnp.float32, "s",
                           vmem_budget=1 << 23)
        b = make_fused_key(64, 32, 32, 32, 32, jnp.float32, "s",
                           vmem_budget=1 << 20)
        # v1 (unbudgeted), v2 (pre-differentiable timings), v3
        # (pre-adjoint-role tile sharing) and v4 (pre-accum-mode) orphaned
        assert a != b and a.startswith("fused:v5:")
        a3 = make_fused3_key(8, 32, 32, 32, 32, 32, 32, jnp.float32, "s",
                             vmem_budget=1 << 23)
        b3 = make_fused3_key(8, 32, 32, 32, 32, 32, 32, jnp.float32, "s",
                             vmem_budget=1 << 20)
        assert a3 != b3 and a3.startswith("fused3:")

    def test_distinct_budgets_tune_distinct_entries(self, tmp_path):
        cache = AutotuneCache(str(tmp_path / "a.json"))
        ca, cb, cc = _rand(32, 32), _rand(32, 32), _rand(32, 32)
        kw = dict(rows=16, dtype=jnp.float32, start=(8, 16, 16, 16),
                  bna=16, kbp=32, kcp=32, cache=cache)
        autotune_fused3(ca, cb, cc, vmem_budget=1 << 23, **kw)
        autotune_fused3(ca, cb, cc, vmem_budget=1 << 22, **kw)
        assert len(cache._entries) == 2


class TestFused3Serve:
    def test_serve_session_reports_triple(self):
        from repro.serve import DxtServeSession
        sess = DxtServeSession(kind="dct")
        b = _rand(4, 16, 16, 16)
        y = sess.transform(b)
        ref = jax.vmap(lambda t: dxt3d(t, "dct"))(b)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)
        assert sess.last_info["fused"] is not None
        assert sess.fused_served == 4 and sess.fused3_served == 4
        assert 0 < sess.hbm_bytes_moved < sess.hbm_bytes_staged
        # pinning the pair keeps the old behaviour reachable
        sess_pair = DxtServeSession(kind="dct", fuse="pair")
        sess_pair.transform(b)
        assert sess_pair.fused_served == 4 and sess_pair.fused3_served == 0

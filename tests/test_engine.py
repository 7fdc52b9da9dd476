"""Engine subsystem: planner optimality, lowering correctness vs the einsum
oracle, block-ESOP dispatch, batching, autotune cache round trip."""
import itertools
import json
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import coefficient_matrix, dxt3d, gemt3, gemt3_outer, prune
from repro.engine import (AutotuneCache, autotune_gemm, build_plan,
                          gemt3_planned, macs_for_order, mode_fold,
                          mode_unfold, order_costs, plan_gemt3)
from repro.kernels import ops

RNG = np.random.default_rng(11)


def _rand(*shape):
    return jnp.asarray(RNG.normal(size=shape).astype(np.float32))


def _rect_problem(dims, ranks, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=dims).astype(np.float32))
    cs = tuple(jnp.asarray(rng.normal(size=(n, k)).astype(np.float32))
               for n, k in zip(dims[-3:], ranks))  # dims may carry a batch
    return x, cs


class TestPlanner:
    @pytest.mark.parametrize("dims,ranks", [
        ((64, 32, 16), (4, 16, 16)),   # compressive mode 1
        ((16, 64, 32), (16, 4, 16)),   # compressive mode 2
        ((32, 16, 64), (16, 16, 4)),   # compressive mode 3
        ((48, 48, 48), (4, 12, 24)),   # graded compression
        ((8, 8, 8), (32, 16, 8)),      # expansion
        ((24, 20, 28), (24, 20, 28)),  # square: all orders tie on MACs
    ])
    def test_picks_mac_minimizing_order(self, dims, ranks):
        """The chosen order matches the brute-force MAC minimum (all six)."""
        x, cs = _rect_problem(dims, ranks)
        plan = build_plan(x.shape, x.dtype, *cs)
        brute = min(macs_for_order(dims, ranks, o)
                    for o in itertools.permutations((1, 2, 3)))
        assert plan.macs == brute
        assert plan.macs <= macs_for_order(dims, ranks, (3, 1, 2))

    def test_order_costs_enumerates_all_six(self):
        x, cs = _rect_problem((16, 12, 8), (4, 12, 8))
        costs = order_costs((16, 12, 8), {1: cs[0], 2: cs[1], 3: cs[2]})
        assert len(costs) == 6
        for order, c in costs.items():
            assert c["macs"] == macs_for_order((16, 12, 8), (4, 12, 8), order)

    def test_explicit_order_is_pinned(self):
        x, cs = _rect_problem((32, 16, 16), (4, 16, 16))
        plan = build_plan(x.shape, x.dtype, *cs, order=(3, 1, 2))
        assert plan.order == (3, 1, 2)

    def test_esop_backend_from_block_sparsity(self):
        """>=50% zero blocks in C selects the block-ESOP backend."""
        rng = np.random.default_rng(5)
        keep = rng.random((4, 4)) < 0.5
        while keep.mean() > 0.5 or not keep.any():
            keep = rng.random((4, 4)) < 0.5
        c3 = jnp.asarray((np.kron(keep, np.ones((32, 32)))
                          * rng.normal(size=(128, 128))).astype(np.float32))
        c1, c2 = jnp.eye(16), jnp.eye(16)
        plan = build_plan((16, 16, 128), jnp.float32, c1, c2, c3,
                          block_sizes=(128, 32, 32))
        (stage3,) = [s for s in plan.stages if s.mode == 3]
        assert stage3.backend == "esop"
        assert stage3.zero_block_frac >= 0.5
        # sparsity discounts the effective MACs
        assert plan.macs_effective < plan.macs

    def test_esop_discount_survives_small_rows(self):
        """Effective MACs stay discounted when rows are far below bm."""
        rng = np.random.default_rng(21)
        keep = np.array([[1, 0, 0, 1]] * 4).astype(bool)
        c3 = jnp.asarray((np.kron(keep, np.ones((64, 64)))
                          * rng.normal(size=(256, 256))).astype(np.float32))
        c1, c2 = jnp.eye(4), jnp.eye(4)
        plan = build_plan((4, 4, 256), jnp.float32, c1, c2, c3,
                          block_sizes=(128, 64, 64))
        (s3,) = [s for s in plan.stages if s.mode == 3]
        assert s3.backend == "esop"
        assert s3.macs_effective < s3.macs  # rows<bm must not saturate

    def test_batched_rows_reach_kernels(self):
        """Backend choice sees batch-folded GEMM rows, not per-sample rows."""
        x, cs = _rect_problem((64, 2, 2, 64), (2, 2, 32), seed=8)
        plan = build_plan(x.shape, x.dtype, *cs)
        (stage3,) = [s for s in plan.stages if s.mode == 3]
        assert stage3.backend == "sr_gemm"  # 4 rows/sample, 256 batched
        unbatched = build_plan(x.shape[1:], x.dtype, *cs)
        (u3,) = [s for s in unbatched.stages if s.mode == 3]
        assert u3.backend == "einsum"

    def test_complex_falls_back_to_einsum(self):
        c = coefficient_matrix("dft", 16)
        plan = build_plan((16, 16, 16), jnp.complex64, c, c, c)
        assert plan.backends == ("einsum", "einsum", "einsum")

    def test_plan_validation(self):
        x, cs = _rect_problem((8, 8, 8), (8, 8, 8))
        with pytest.raises(ValueError):
            build_plan((8, 8), jnp.float32, *cs)
        with pytest.raises(ValueError):
            build_plan((8, 8, 9), jnp.float32, *cs)
        with pytest.raises(ValueError):
            build_plan((8, 8, 8), jnp.float32, *cs, order=(1, 1, 2))


class TestLowering:
    @pytest.mark.parametrize("mode", [1, 2, 3])
    def test_unfold_fold_roundtrip(self, mode):
        x = _rand(4, 5, 6)
        m, lead = mode_unfold(x, mode)
        assert m.shape == (x.size // x.shape[mode - 1], x.shape[mode - 1])
        np.testing.assert_array_equal(np.asarray(mode_fold(m, lead, mode)),
                                      np.asarray(x))

    @pytest.mark.parametrize("mode", [1, 2, 3])
    def test_unfold_fold_batched(self, mode):
        x = _rand(3, 4, 5, 6)
        m, lead = mode_unfold(x, mode)
        np.testing.assert_array_equal(np.asarray(mode_fold(m, lead, mode)),
                                      np.asarray(x))

    def test_dense_matches_oracles(self):
        """Engine == gemt3 einsum oracle == gemt3_outer, dense rectangular."""
        x, cs = _rect_problem((24, 20, 16), (8, 10, 12), seed=1)
        y = gemt3_planned(x, *cs)
        ref = gemt3(x, *cs)
        outer = gemt3_outer(x, *cs)
        np.testing.assert_allclose(y, ref, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(y, outer, rtol=1e-4, atol=1e-4)

    def test_block_sparse_matches_oracle_with_savings(self):
        rng = np.random.default_rng(9)
        keep = np.array([[1, 0, 0, 1]] * 4).astype(bool)  # 50% zero blocks
        c3 = jnp.asarray((np.kron(keep, np.ones((32, 32)))
                          * rng.normal(size=(128, 128))).astype(np.float32))
        x = jnp.asarray(rng.normal(size=(16, 16, 128)).astype(np.float32))
        c1, c2 = _rand(16, 16), _rand(16, 16)
        # fuse=False pins the staged schedule this test is about (the fused
        # kernel may legitimately prefer a dense assignment here — sparse
        # *fused* execution is covered in test_fused_gemt.py)
        y, info = gemt3_planned(x, c1, c2, c3, block_sizes=(128, 32, 32),
                                fuse=False, with_info=True)
        np.testing.assert_allclose(y, gemt3(x, c1, c2, c3),
                                   rtol=1e-4, atol=1e-4)
        assert "esop" in info["backends"]
        assert info["fetch_savings"] > 0
        # the default (auto-fusion) schedule stays numerically identical, up
        # to float32 rounding: a schedule that reassociates the 128-deep
        # sums moves outputs of magnitude ~850 by ~4e-4 (a skipped live
        # block would move them by ~100), so the absolute tolerance is the
        # plan's a-priori rounding bound at the output's scale
        yf = gemt3_planned(x, c1, c2, c3, block_sizes=(128, 32, 32))
        scale = float(jnp.max(jnp.abs(y)))
        np.testing.assert_allclose(
            yf, y, rtol=1e-4, atol=info["numerics"]["error_bound"] * scale)

    def test_pruned_sparse_matches_oracle(self):
        x, cs = _rect_problem((32, 32, 32), (16, 16, 16), seed=2)
        cs = tuple(prune(c, 0.8) for c in cs)  # heavy elementwise pruning
        y = gemt3_planned(x, *cs, block_sizes=(32, 8, 8))
        ref = gemt3(x, *cs)
        tol = 1e-4 * float(jnp.max(jnp.abs(ref)))
        np.testing.assert_allclose(y, ref, rtol=1e-4, atol=max(tol, 1e-5))

    def test_affine_out(self):
        x, cs = _rect_problem((12, 10, 8), (6, 5, 4), seed=3)
        out = _rand(6, 5, 4)
        np.testing.assert_allclose(gemt3_planned(x, *cs, out=out),
                                   gemt3(x, *cs, out=out),
                                   rtol=1e-4, atol=1e-4)
        with pytest.raises(TypeError):
            # out is keyword-only: gemt3's 5th positional is `order`, and a
            # positional tuple must not silently become the affine term.
            gemt3_planned(x, *cs, (1, 2, 3))

    def test_batched_matches_vmap(self):
        x, cs = _rect_problem((4, 12, 10, 8), (6, 5, 4), seed=4)
        y = gemt3_planned(x, *cs)
        ref = jax.vmap(lambda t: gemt3(t, *cs))(x)
        assert y.shape == (4, 6, 5, 4)
        np.testing.assert_allclose(y, ref, rtol=1e-4, atol=1e-4)


class TestDxtEngine:
    @pytest.mark.parametrize("kind", ["dct", "dht", "dwht", "dft"])
    def test_all_kinds_match(self, kind):
        """dxt3d(engine=True) == dxt3d for the whole DXT family (<=1e-4)."""
        x = _rand(16, 8, 4)
        y = dxt3d(x, kind, engine=True)
        ref = dxt3d(x, kind)
        scale = float(jnp.max(jnp.abs(ref)))
        assert float(jnp.max(jnp.abs(y - ref))) <= 1e-4 * max(scale, 1.0)

    def test_engine_roundtrip(self):
        x = _rand(8, 8, 8)
        xr = dxt3d(dxt3d(x, "dct", engine=True), "dct", inverse=True,
                   engine=True)
        np.testing.assert_allclose(xr, x, rtol=2e-4, atol=2e-4)


class TestAutotune:
    def test_cache_json_roundtrip(self, tmp_path):
        path = str(tmp_path / "autotune.json")
        cache = AutotuneCache(path)
        key = "v4:64x128x32|float32|dense|fwd|plain|s"
        cache.put(key, {"bm": 64, "bn": 128, "bk": 32, "us": 1.5})
        cache.save()
        reloaded = AutotuneCache(path)
        assert reloaded.get(key) == {"bm": 64, "bn": 128, "bk": 32, "us": 1.5}
        assert len(reloaded) == 1
        with open(path) as f:
            assert key in json.load(f)

    def test_cache_prunes_stale_keys_on_load(self, tmp_path):
        # pre-role/stale-version keys are dropped on load (and counted),
        # live-schema keys survive
        from repro import obs

        path = str(tmp_path / "autotune.json")
        live = "fused:v5:16x16x16|float32|dense|fwd|plain|s|vb4194304"
        stale = {"16x16x16|float32|dense|s": {"bm": 8},  # pre-role, no version
                 "v3:16x16x16|float32|dense|fwd|plain|s": {"bm": 8},
                 "fused:v4:16x16x16|float32|dense|fwd|plain|s": {"bm": 8}}
        with open(path, "w") as f:
            json.dump({live: {"bu": 8, "bka": 8, "bnb": 8}, **stale}, f)
        with obs.session() as s:
            cache = AutotuneCache(path)
            assert len(cache) == 1 and cache.get(live) is not None
            assert s.registry.value("autotune.cache.pruned") == len(stale)
            # prune() is idempotent once the rubble is gone
            assert cache.prune() == 0

    def test_corrupt_cache_tolerated(self, tmp_path):
        path = str(tmp_path / "autotune.json")
        with open(path, "w") as f:
            f.write("{not json")
        cache = AutotuneCache(path)
        assert len(cache) == 0

    def test_autotune_returns_valid_blocks_and_caches(self, tmp_path):
        cache = AutotuneCache(str(tmp_path / "a.json"))
        x, c = _rand(64, 32), _rand(32, 64)
        cfg = autotune_gemm(x, c, "sr_gemm", cache=cache, max_steps=2, reps=1)
        assert all(8 <= b <= 512 for b in cfg)
        # second call is a pure cache hit (same result, no timing)
        assert autotune_gemm(x, c, "sr_gemm", cache=cache) == cfg
        assert len(AutotuneCache(cache.path)) == 1  # persisted

    def test_autotuned_execution_matches_oracle(self, tmp_path):
        x, cs = _rect_problem((32, 24, 16), (8, 12, 16), seed=6)
        cache = AutotuneCache(str(tmp_path / "a.json"))
        y = gemt3_planned(x, *cs, autotune=True, autotune_cache=cache)
        np.testing.assert_allclose(y, gemt3(x, *cs), rtol=1e-4, atol=1e-4)


class TestExecutorCache:
    def test_plan_cache_hit(self):
        from repro.engine import clear_plan_cache, plan_cache_info
        clear_plan_cache()
        x, cs = _rect_problem((16, 12, 8), (4, 6, 8), seed=7)
        p1 = plan_gemt3(x.shape, x.dtype, *cs)
        assert plan_cache_info()["entries"] == 1
        p2 = plan_gemt3(x.shape, x.dtype, *cs)
        assert p1 is p2  # memoized
        # different zero structure => different plan entry
        p3 = plan_gemt3(x.shape, x.dtype, prune(cs[0], 1.0), cs[1], cs[2])
        assert plan_cache_info()["entries"] == 2


class TestKernelOpsInfo:
    def test_esop_ref_path_reports_real_savings(self):
        """Satellite: the non-Pallas esop_gemm path computes real stats."""
        rng = np.random.default_rng(13)
        keep = np.array([[1, 0], [0, 1]]).astype(bool)
        c = jnp.asarray((np.kron(keep, np.ones((32, 32)))
                         * rng.normal(size=(64, 64))).astype(np.float32))
        x = _rand(32, 64)
        y, info = ops.esop_gemm(x, c, bm=32, bn=32, bk=32, use_pallas=False)
        assert info["blocks_dense"] == 4
        assert info["blocks_live"] == 2
        assert info["fetch_savings"] == pytest.approx(0.5)
        np.testing.assert_allclose(
            y, jnp.dot(x, c), rtol=1e-5, atol=1e-5)


class TestServe:
    def test_dxt_serve_session_batched(self):
        from repro.serve import DxtServeSession
        sess = DxtServeSession(kind="dct")
        b = _rand(5, 16, 12, 8)
        y = sess.transform(b)
        ref = jax.vmap(lambda t: dxt3d(t, "dct"))(b)
        np.testing.assert_allclose(y, ref, rtol=1e-4, atol=1e-4)
        assert sess.requests_served == 5
        # plan is memoized in the engine across calls (coeff identity stable)
        from repro.engine import plan_cache_info
        n_plans = plan_cache_info()["entries"]
        sess.transform(b)
        assert plan_cache_info()["entries"] == n_plans
        with pytest.raises(ValueError):
            sess.transform(_rand(4, 4, 4))

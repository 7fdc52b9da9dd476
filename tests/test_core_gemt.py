"""Core TriADA GEMT/DXT correctness: Eq.(1) oracle, all parenthesizations,
outer-product equivalence, transform family round trips, Parseval, Tucker."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from _hypothesis_compat import given, settings, st

from repro.core import (PAREN_ORDERS, clear_coefficient_cache,
                        coefficient_matrix, dxt3d, gemt3, gemt3_outer, hosvd,
                        inverse_coefficient_matrix, macs, mode_product,
                        time_steps, tucker_compress, tucker_expand,
                        tucker_roundtrip_error)
from repro.core import gemt as gemt_mod

RNG = np.random.default_rng(0)


def _rand(*shape):
    return jnp.asarray(RNG.normal(size=shape).astype(np.float32))


def _direct(x, c1, c2, c3):
    """Element-wise 6D-index-space oracle of Eq. (1)."""
    return jnp.einsum("abc,ax,by,cz->xyz", x, c1, c2, c3)


class TestGemt:
    def test_all_orders_match_direct(self):
        x = _rand(5, 6, 7)
        cs = [coefficient_matrix("dct", n) for n in x.shape]
        ref = _direct(x, *cs)
        for order in PAREN_ORDERS:
            np.testing.assert_allclose(gemt3(x, *cs, order=order), ref,
                                       rtol=3e-5, atol=3e-5)

    def test_outer_equals_inner(self):
        x = _rand(4, 5, 6)
        cs = [coefficient_matrix("dht", n) for n in x.shape]
        np.testing.assert_allclose(gemt3_outer(x, *cs), gemt3(x, *cs),
                                   rtol=3e-5, atol=3e-5)

    def test_affine_accumulate(self):
        """Eq. (1) is affine: += initialization."""
        x = _rand(4, 4, 4)
        out = _rand(4, 4, 4)
        cs = [coefficient_matrix("dct", 4)] * 3
        np.testing.assert_allclose(
            gemt3(x, *cs, out=out), _direct(x, *cs) + out, rtol=3e-5, atol=3e-5)

    def test_rectangular_gemt(self):
        """Non-square C: tensor expansion & compression (paper §2.3)."""
        x = _rand(4, 5, 6)
        c1, c2, c3 = _rand(4, 8), _rand(5, 2), _rand(6, 3)
        y = gemt3(x, c1, c2, c3)
        assert y.shape == (8, 2, 3)
        ref = jnp.einsum("abc,ax,by,cz->xyz", x, c1, c2, c3)
        np.testing.assert_allclose(y, ref, rtol=1e-4, atol=1e-4)

    def test_mode_product_validation(self):
        x = _rand(3, 4, 5)
        with pytest.raises(ValueError):
            mode_product(x, _rand(4, 4), 1)  # wrong extent
        with pytest.raises(ValueError):
            mode_product(x, _rand(3, 3), 4)  # bad mode

    def test_complexity_model(self):
        assert macs(4, 5, 6) == 4 * 5 * 6 * 15
        assert time_steps(4, 5, 6) == 15


class TestTransforms:
    @pytest.mark.parametrize("kind", ["dct", "dht", "dft"])
    def test_roundtrip(self, kind):
        x = _rand(5, 6, 7)
        xr = dxt3d(dxt3d(x, kind), kind, inverse=True)
        np.testing.assert_allclose(
            xr.real if jnp.iscomplexobj(xr) else xr, x, rtol=2e-4, atol=2e-4)

    def test_dwht_roundtrip_pow2(self):
        x = _rand(4, 8, 2)
        np.testing.assert_allclose(dxt3d(dxt3d(x, "dwht"), "dwht", inverse=True),
                                   x, rtol=2e-4, atol=2e-4)
        with pytest.raises(ValueError):
            coefficient_matrix("dwht", 6)

    def test_dft_matches_fftn(self):
        x = _rand(4, 6, 5)  # non-square, non-pow2: no FFT-style size limits
        np.testing.assert_allclose(np.asarray(dxt3d(x, "dft")),
                                   np.fft.fftn(np.asarray(x), norm="ortho"),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("kind", ["dct", "dht", "dwht"])
    def test_orthonormality(self, kind):
        n = 8
        c = np.asarray(coefficient_matrix(kind, n))
        np.testing.assert_allclose(c.T @ c, np.eye(n), atol=1e-5)

    @settings(max_examples=20, deadline=None)
    @given(st.integers(2, 9), st.integers(2, 9), st.integers(2, 9),
           st.sampled_from(["dct", "dht"]))
    def test_parseval_property(self, n1, n2, n3, kind):
        """Orthogonal transforms are isometries: ||DXT(x)|| == ||x||."""
        rng = np.random.default_rng(n1 * 100 + n2 * 10 + n3)
        x = jnp.asarray(rng.normal(size=(n1, n2, n3)).astype(np.float32))
        y = dxt3d(x, kind)
        np.testing.assert_allclose(float(jnp.linalg.norm(y.ravel())),
                                   float(jnp.linalg.norm(x.ravel())),
                                   rtol=1e-4)

    @settings(max_examples=15, deadline=None)
    @given(st.integers(2, 7), st.integers(2, 7), st.integers(2, 7))
    def test_linearity_property(self, n1, n2, n3):
        rng = np.random.default_rng(n1 + n2 * 7 + n3 * 49)
        x = jnp.asarray(rng.normal(size=(n1, n2, n3)).astype(np.float32))
        y = jnp.asarray(rng.normal(size=(n1, n2, n3)).astype(np.float32))
        a = 2.5
        np.testing.assert_allclose(dxt3d(a * x + y, "dct"),
                                   a * dxt3d(x, "dct") + dxt3d(y, "dct"),
                                   rtol=2e-3, atol=2e-4)


class TestCoefficientCache:
    """``dxt3d`` keeps its coefficient matrices across eager calls."""

    @pytest.mark.parametrize("inverse", [False, True])
    @pytest.mark.parametrize("kind", ["dft", "dht", "dct", "dwht"])
    def test_cached_matrices_are_the_builders_bits(self, kind, inverse):
        x = _rand(8, 4, 16)
        build = inverse_coefficient_matrix if inverse else coefficient_matrix
        cs = [build(kind, n) for n in x.shape]
        xc = x.astype(cs[0].dtype)
        want = np.asarray(gemt3(xc, *cs))
        clear_coefficient_cache()
        for _ in range(2):  # a miss, then a hit
            got = np.asarray(dxt3d(x, kind, inverse=inverse))
            assert got.dtype == want.dtype
            np.testing.assert_array_equal(got, want)

    def test_bounded_lru(self):
        clear_coefficient_cache()
        for n in range(2, 42):
            dxt3d(_rand(n, 1, 1), "dct")
        assert len(gemt_mod._COEFF_CACHE) == gemt_mod._COEFF_CACHE_SIZE
        assert ("dct", 41, False, None) in gemt_mod._COEFF_CACHE
        assert ("dct", 1, False, None) in gemt_mod._COEFF_CACHE  # kept hot
        assert ("dct", 2, False, None) not in gemt_mod._COEFF_CACHE

    def test_outer_traces_neither_read_nor_store_the_cache(self):
        """Jitted and differentiated calls, before and after an eager call
        of the same shape, build traced matrices afresh: no tracer leaks
        into the cache and the results match the eager call."""
        x = _rand(8, 8, 8)
        clear_coefficient_cache()

        def traced_calls():  # fresh functions, so each call retraces
            y = jax.jit(lambda x: dxt3d(x, "dct", engine=True))(x)
            g = jax.grad(lambda x: jnp.sum(
                dxt3d(x, "dct", engine=True, differentiable=True) ** 2))(x)
            const = jax.jit(lambda: dxt3d(x, "dct", engine=True))()
            return y, g, const

        before = traced_calls()
        assert not gemt_mod._COEFF_CACHE  # the closed-over call: not stored
        eager = dxt3d(x, "dct", engine=True)
        assert len(gemt_mod._COEFF_CACHE) == 1
        after = traced_calls()
        for y, g, const in (before, after):
            np.testing.assert_allclose(y, eager, atol=1e-5, rtol=1e-5)
            np.testing.assert_allclose(const, eager, atol=1e-5, rtol=1e-5)
            # orthonormal DCT: d/dx sum((C x)^2) = 2x
            np.testing.assert_allclose(g, 2 * x, atol=1e-5, rtol=1e-5)
        np.testing.assert_array_equal(before[1], after[1])
        assert not any(isinstance(c, jax.core.Tracer)
                       for c in gemt_mod._COEFF_CACHE.values())
        assert all(isinstance(c, jax.Array)
                   for c in gemt_mod._COEFF_CACHE.values())


class TestTucker:
    def test_full_rank_roundtrip(self):
        x = _rand(5, 6, 7)
        err = tucker_roundtrip_error(x, (5, 6, 7))
        assert err["rel_fro_err"] < 1e-5

    def test_low_rank_compresses_lowrank_tensor(self):
        """A genuinely rank-(2,2,2) tensor reconstructs exactly."""
        g = _rand(2, 2, 2)
        us = (_rand(8, 2), _rand(9, 2), _rand(10, 2))
        x = gemt3(g, us[0].T, us[1].T, us[2].T)
        factors = hosvd(x, (2, 2, 2))
        xr = tucker_expand(tucker_compress(x, factors), factors)
        np.testing.assert_allclose(xr, x, rtol=1e-3, atol=1e-3)

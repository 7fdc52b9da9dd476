"""Blocked 3-D transforms: ``dxt3d(..., block=(b1, b2, b3))``.

Every b1×b2×b3 block of the last three axes gets the kind's b-point
transform (the 3-D DCT block coding of video).  The engine runs it as a
GEMT over block-diagonal factors ``I ⊗ C_b``, whose zero blocks its
block-ESOP stages skip; these tests hold it to a plain reference that
never forms those factors.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import obs
from repro.core import coefficient_matrix, dxt3d, gemt
from repro.engine import gemt3_planned

RNG = np.random.default_rng(17)
BLOCK = (8, 8, 8)


def _dct2(b):
    n = np.arange(b)[:, None]
    k = np.arange(b)[None, :]
    c = np.cos(np.pi * (2 * n + 1) * k / (2 * b)) * np.sqrt(2.0 / b)
    c[:, 0] = np.sqrt(1.0 / b)
    return c.astype(np.float32)


def _reference(x, block, inverse=False):
    """The blocked orthonormal DCT-II, float32 at "highest": each plane
    reshaped to (T/b1, b1, H/b2, b2, W/b3, b3), each b-axis contracted with
    its C_b by einsum (no block-diagonal matrix is formed)."""
    cs = [jnp.asarray(_dct2(b).T if inverse else _dct2(b)) for b in block]
    lead, (t, h, w) = x.shape[:-3], x.shape[-3:]
    (b1, b2, b3) = block
    y = x.reshape(*lead, t // b1, b1, h // b2, b2, w // b3, b3)
    with jax.default_matmul_precision("highest"):
        y = jnp.einsum("...anbmcl,nk,mj,li->...akbjci", y, *cs)
    return y.reshape(x.shape)


def _frames(*shape):
    """Level-shifted 8-bit samples, as a codec holds them."""
    return jnp.asarray(RNG.integers(-128, 128, size=shape).astype(np.float32))


# Shapes: two planes of a 4:2:0 buffer in miniature, and a leading batch.
SHAPES = [(16, 48, 64), (16, 24, 32), (2, 16, 24, 32)]


@pytest.mark.parametrize("shape,engine", [
    (s, e) for s in SHAPES for e in (True, False) if e or len(s) == 3],
    ids=lambda v: ("x".join(map(str, v)) if isinstance(v, tuple)
                   else ("engine" if v else "einsum")))
def test_blocked_matches_reference(shape, engine):
    """Forward, inverse and the round trip against the plain reference.

    Samples lie in [-128, 127] and each coefficient is an orthonormal
    512-term sum of them (|c| <= 128 * sqrt(512) ~ 2.9e3), so float32
    rounding of the staged sums stays below ~1e-3 absolute: 2e-3 covers
    it with room, and a wrong block or a missed mode moves values by
    tens.  The round trip returns integers to within the same rounding.
    The einsum path (``engine=False``) takes 3-D tensors only.
    """
    x = _frames(*shape)
    y = dxt3d(x, "dct", engine=engine, block=BLOCK)
    np.testing.assert_allclose(y, _reference(x, BLOCK), rtol=0, atol=2e-3)
    back = dxt3d(y, "dct", inverse=True, engine=engine, block=BLOCK)
    np.testing.assert_allclose(back, _reference(y, BLOCK, inverse=True),
                               rtol=0, atol=2e-3)
    np.testing.assert_allclose(back, x, rtol=0, atol=2e-3)


def test_other_kinds_agree_between_paths():
    """The engine and the einsum path build the same block factors for
    every real kind (the DFT's complex stages run einsum either way)."""
    x = _frames(16, 24, 32)
    for kind in ("dht", "dwht", "dft"):
        np.testing.assert_allclose(
            dxt3d(x, kind, engine=True, block=BLOCK),
            dxt3d(x, kind, block=BLOCK), rtol=1e-5, atol=2e-3)


def test_block_none_is_the_unblocked_path_bit_for_bit():
    """``block=None``, and a block spanning every extent, give today's
    unblocked transform exactly."""
    x = _frames(16, 24, 32)
    cs = [coefficient_matrix("dct", n) for n in x.shape]
    y = dxt3d(x, "dct", engine=True)
    np.testing.assert_array_equal(y, gemt3_planned(x, *cs))
    np.testing.assert_array_equal(
        y, dxt3d(x, "dct", engine=True, block=x.shape))


def test_long_modes_run_block_esop():
    """At 4 or more 128-wide tiles a side, the block-diagonal modes reach
    block-ESOP through the planner's zero-fraction rule, and the skipped
    work shows in the counters.  ``fuse=False`` runs the stages one by
    one, as the planner does where no fusion fits (a 4K plane); at this
    size it would fuse them."""
    x = _frames(8, 512, 512)
    with obs.session(enable_tracing=False) as s:
        y, info = dxt3d(x, "dct", engine=True, block=BLOCK, fuse=False,
                        with_info=True)
        assert s.registry.value("engine.stage.esop") == 2
    backends = dict(zip(info["order"], info["backends"]))
    assert backends[2] == backends[3] == "esop", info["backends"]
    assert info["macs_effective"] < info["macs"] / 3
    np.testing.assert_allclose(y, _reference(x, BLOCK), rtol=0, atol=2e-3)
    np.testing.assert_allclose(dxt3d(x, "dct", engine=True, block=BLOCK),
                               y, rtol=0, atol=2e-3)


def test_macs_effective_counter_matches_info():
    """``engine.macs_effective`` sums exactly to the calls' info field."""
    with obs.session(enable_tracing=False) as s:
        total = 0
        for shape, inverse in (((8, 512, 512), False), ((16, 24, 32), True),
                               ((2, 16, 24, 32), False)):
            _, info = dxt3d(_frames(*shape), "dct", inverse=inverse,
                            engine=True, block=BLOCK, with_info=True)
            total += info["macs_effective"]
        assert s.registry.value("engine.macs_effective") == total
        assert total < s.registry.value("engine.macs")


def test_blocked_and_unblocked_keys_do_not_collide():
    """The coefficient cache keeps blocked and unblocked factors apart,
    and a repeated blocked call hands back the same arrays."""
    gemt.clear_coefficient_cache()
    c_full = gemt._dxt_coefficients("dct", 32, False, False)
    c_blk = gemt._dxt_coefficients("dct", 32, False, False, block=8)
    assert c_blk is not c_full
    assert not np.allclose(c_blk, c_full)
    assert gemt._dxt_coefficients("dct", 32, False, False, block=8) is c_blk
    assert gemt._dxt_coefficients("dct", 32, True, False, block=8) \
        is not c_blk
    assert len(gemt._COEFF_CACHE) == 3
    np.testing.assert_allclose(
        c_blk, np.kron(np.eye(4, dtype=np.float32), _dct2(8)), atol=1e-7)


@pytest.mark.parametrize("block", [(8, 8, 7), (8, 8), (8, 0, 8)])
def test_block_that_does_not_divide_raises(block):
    with pytest.raises(ValueError):
        dxt3d(_frames(16, 24, 32), "dct", engine=True, block=block)

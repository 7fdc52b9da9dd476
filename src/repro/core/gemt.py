"""3-mode generalized matrix-by-tensor multiplication (3D-GEMT) and 3D-DXT.

Implements the paper's §2–§3:

* ``mode_product``       — one n_s-mode contraction X ×_s C (Kolda–Bader).
* ``gemt3``              — the chained three-stage GEMT, any of the paper's
                           six parenthesization orders (§3), rectangular
                           coefficient matrices allowed (expansion/compression,
                           i.e. Tucker, §2.3), affine ``+=`` init supported.
* ``gemt3_outer``        — the *outer-product (low-rank) formulation*,
                           Eqs. (6.1)–(6.3): each stage as an explicit
                           lax.scan of rank-1 updates.  This is the faithful
                           algorithmic form the TriADA device executes; it is
                           numerically identical to ``gemt3`` and serves as
                           the oracle for the cell simulator and kernels.
* ``dxt3d``              — forward/inverse trilinear orthogonal transform for
                           the DFT/DHT/DCT/DWHT family.
* complexity model       — MACs = N1·N2·N3·(N1+N2+N3); time-steps = N1+N2+N3.

Index convention matches the paper: X[n1, n2, n3]; C_s maps n_s → k_s with
C_s[n_s, k_s]; the forward transform is ẍ = Σ x·C1[n1,k1]·C2[n2,k2]·C3[n3,k3].
"""
from __future__ import annotations

import itertools
from collections import OrderedDict
from typing import Sequence

import jax
import jax.numpy as jnp

__all__ = [
    "mode_product",
    "gemt3",
    "gemt3_outer",
    "gemt3_planned",
    "dxt3d",
    "clear_coefficient_cache",
    "macs",
    "time_steps",
    "PAREN_ORDERS",
]

# The six admissible stage orders (§3: which mode is contracted 1st/2nd/3rd).
PAREN_ORDERS: tuple[tuple[int, int, int], ...] = tuple(itertools.permutations((1, 2, 3)))

_EINSUM = {
    1: "abc,ax->xbc",
    2: "abc,bx->axc",
    3: "abc,cx->abx",
}


def mode_product(x: jnp.ndarray, c: jnp.ndarray, mode: int) -> jnp.ndarray:
    """n_s-mode product X ×_s C: contract axis ``mode-1`` of x with axis 0 of c.

    ``c`` has shape (N_s, K_s); rectangular K_s ≠ N_s gives tensor
    expansion/compression (paper §2.3).
    """
    if mode not in (1, 2, 3):
        raise ValueError(f"mode must be 1, 2 or 3, got {mode}")
    if x.ndim != 3:
        raise ValueError(f"x must be a 3-mode tensor, got ndim={x.ndim}")
    if x.shape[mode - 1] != c.shape[0]:
        raise ValueError(
            f"mode-{mode} extent {x.shape[mode - 1]} != coefficient rows {c.shape[0]}"
        )
    return jnp.einsum(_EINSUM[mode], x, c)


def gemt3(
    x: jnp.ndarray,
    c1: jnp.ndarray,
    c2: jnp.ndarray,
    c3: jnp.ndarray,
    order: Sequence[int] = (3, 1, 2),
    out: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Three-mode GEMT ẍ = X ×₁C1 ×₂C2 ×₃C3 (+ out), staged per ``order``.

    ``order`` is the contraction order of the modes; the paper's reference
    chain (Eqs. 4/6: horizontal slicing first, then frontal reslice) is
    (3, 1, 2).  All orders produce identical results up to float rounding.
    ``out`` (if given) is the affine ``+=`` initialization of Eq. (1).
    """
    order = tuple(order)
    if sorted(order) != [1, 2, 3]:
        raise ValueError(f"order must be a permutation of (1,2,3), got {order}")
    cs = {1: c1, 2: c2, 3: c3}
    y = x
    for mode in order:
        y = mode_product(y, cs[mode], mode)
    if out is not None:
        y = out + y
    return y


def _stage_outer(resident: jnp.ndarray, coeff: jnp.ndarray, mode: int) -> jnp.ndarray:
    """One GEMT stage as a lax.scan over rank-1 (outer-product) updates.

    Faithful to Eqs. (6.1)–(6.3): at time-step n the actuator streams
    coefficient row c(n) (vector of length K_s) to the core; the pivotal
    cells (the n-th mode-s slice of the resident tensor) broadcast the data
    vector; every cell does one MAC.  The resident tensor never moves.

    The scan axis *is* the paper's discrete-time axis: the stage takes
    exactly N_s time-steps.
    """
    # Move the contracted mode to the front: resident -> (N_s, A, B)
    r = jnp.moveaxis(resident, mode - 1, 0)
    n_s, a, b = r.shape
    k_s = coeff.shape[1]
    acc0 = jnp.zeros(r.shape[1:] + (k_s,), dtype=jnp.result_type(r.dtype, coeff.dtype))

    def step(acc, inputs):
        x_slice, c_row = inputs  # (A, B), (K_s,)
        # rank-1 update per (a, b) fibre: acc[a, b, :] += x_slice[a, b] * c_row
        return acc + x_slice[..., None] * c_row[None, None, :], None

    acc, _ = jax.lax.scan(step, acc0, (r, coeff))
    # acc: (A, B, K_s) where (A, B) are the two untouched modes in order.
    return jnp.moveaxis(acc, -1, mode - 1)


def gemt3_outer(
    x: jnp.ndarray,
    c1: jnp.ndarray,
    c2: jnp.ndarray,
    c3: jnp.ndarray,
    order: Sequence[int] = (3, 1, 2),
    out: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Outer-product (low-rank) 3-stage GEMT — the TriADA algorithm proper."""
    order = tuple(order)
    if sorted(order) != [1, 2, 3]:
        raise ValueError(f"order must be a permutation of (1,2,3), got {order}")
    cs = {1: c1, 2: c2, 3: c3}
    y = x
    for mode in order:
        y = _stage_outer(y, cs[mode], mode)
    if out is not None:
        y = out + y
    return y


def gemt3_planned(
    x: jnp.ndarray,
    c1: jnp.ndarray,
    c2: jnp.ndarray,
    c3: jnp.ndarray,
    *,
    out: jnp.ndarray | None = None,
    **engine_kwargs,
):
    """Engine-scheduled GEMT: cost-model order search + kernel lowering.

    Thin re-export of :func:`repro.engine.gemt3_planned` (lazy import keeps
    ``core`` free of a hard dependency on the engine/kernels layers).  Unlike
    ``gemt3`` it accepts a leading batch axis and, with ``with_info=True``,
    returns per-stage dispatch accounting.  ``differentiable=True`` makes
    the call ``jax.grad``-safe with a backward pass that re-enters the
    engine: the X-cotangent is the adjoint GEMT over the transposed
    coefficients (for the orthonormal DXT families of §2.2 that is the
    inverse transform) and the coefficient cotangents are mode-unfolded
    rank-k SR-GEMM updates — see docs/engine.md ("Differentiation").
    """
    from ..engine import gemt3_planned as _planned

    return _planned(x, c1, c2, c3, out=out, **engine_kwargs)


# dxt3d's coefficient matrices, kept across calls: a hit returns the same
# array object, so the engine's identity-keyed memos (fingerprints, ESOP
# schedules, transposes) hit as well.  The public builders keep returning
# fresh arrays, which callers may own and donate.  The key holds no dtype:
# each kind's builder fixes its dtype.
_COEFF_CACHE_SIZE = 32
_COEFF_CACHE: "OrderedDict[tuple, jax.Array]" = OrderedDict()


def clear_coefficient_cache() -> None:
    """Drop the coefficient matrices ``dxt3d`` keeps across calls."""
    _COEFF_CACHE.clear()


def _dxt_coefficients(kind: str, n: int, inverse: bool,
                      traced: bool) -> jax.Array:
    """``dxt3d``'s coefficient matrix for one mode, from a bounded LRU cache
    keyed on ``(kind, n, inverse, default device)``.  Under an outer trace
    (``traced``) it is built afresh and the cache is neither read nor
    written; a tracer is never stored."""
    from ..obs import metrics as _metrics
    from .transforms import coefficient_matrix, inverse_coefficient_matrix

    build = inverse_coefficient_matrix if inverse else coefficient_matrix
    if traced:
        return build(kind, n)
    key = (kind.lower(), n, inverse, jax.config.jax_default_device)
    c = _COEFF_CACHE.get(key)
    if c is not None:
        _COEFF_CACHE.move_to_end(key)
        _metrics.inc("dxt3d.coeff_cache.hits")
        return c
    _metrics.inc("dxt3d.coeff_cache.misses")
    c = build(kind, n)
    if not isinstance(c, jax.core.Tracer):
        _COEFF_CACHE[key] = c
        if len(_COEFF_CACHE) > _COEFF_CACHE_SIZE:
            _COEFF_CACHE.popitem(last=False)
    return c


def dxt3d(
    x: jnp.ndarray,
    kind: str = "dct",
    inverse: bool = False,
    order: Sequence[int] = (3, 1, 2),
    out: jnp.ndarray | None = None,
    outer: bool = False,
    engine: bool = False,
    **engine_kwargs,
) -> jnp.ndarray:
    """Forward/inverse separable 3D discrete orthogonal transform (Eq. 1/2).

    ``engine=True`` routes through the planned execution engine
    (``repro.engine``): the stage order is chosen by the cost model (the
    ``order`` argument is ignored), each stage runs on the Pallas kernel
    dispatch, and ``x`` may carry a leading batch axis; ``engine_kwargs``
    (e.g. ``autotune=True``, ``with_info=True``, or ``differentiable=True``
    for a ``jax.grad``-safe engine-lowered backward pass) pass through.

    The coefficient matrices are kept across eager calls (see
    :func:`clear_coefficient_cache`); under an outer trace (``x`` a tracer)
    they are built afresh, as traced values.
    """
    from ..obs import trace as _trace

    sp = _trace.NULL_SPAN
    if _trace.enabled():
        sp = _trace.span(f"dxt3d:{kind}",
                         {"kind": kind, "inverse": bool(inverse),
                          "engine": bool(engine), "shape": tuple(x.shape)})
    with sp:
        n1, n2, n3 = x.shape[-3:] if engine else x.shape
        sp_c = _trace.NULL_SPAN
        if _trace.enabled():
            sp_c = _trace.span("dxt3d.coefficients",
                               {"kind": kind, "inverse": bool(inverse),
                                "sizes": (n1, n2, n3)})
        with sp_c:
            traced = isinstance(x, jax.core.Tracer)
            c1, c2, c3 = (_dxt_coefficients(kind, n, bool(inverse), traced)
                          for n in (n1, n2, n3))
        if jnp.iscomplexobj(c1) and not jnp.iscomplexobj(x):
            x = x.astype(c1.dtype)
        if engine:
            return gemt3_planned(x, c1, c2, c3, out=out, **engine_kwargs)
        fn = gemt3_outer if outer else gemt3
        return fn(x, c1, c2, c3, order=order, out=out)


def macs(n1: int, n2: int, n3: int) -> int:
    """Hypercubic arithmetic complexity of the staged GEMT (paper §3)."""
    return n1 * n2 * n3 * (n1 + n2 + n3)


def time_steps(n1: int, n2: int, n3: int) -> int:
    """Linear number of TriADA time-steps (paper §5.4)."""
    return n1 + n2 + n3

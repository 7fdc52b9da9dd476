"""Coefficient (change-of-basis) matrix builders for the 3D-DXT family.

The paper (§2.2) parameterizes the whole family of trilinear discrete
orthogonal transforms by the square, invertible coefficient matrix C:

  * DFT  — complex, symmetric, unitary:      c[n,k] = exp(-2πi·nk/N)/√N
  * DHT  — real, symmetric, orthogonal:      c[n,k] = (cos+sin)(2π·nk/N)/√N
  * DCT  — real, orthogonal (DCT-II):        c[n,k] = s_k·cos(π(2n+1)k/2N)
  * DWHT — ±1, symmetric, orthogonal:        Hadamard/√N (N = power of two)

All builders return *orthonormal* matrices so that the inverse transform is
the (conjugate) transpose — `C⁻¹ = C*ᵀ` — and `forward ∘ inverse = id` holds
to float tolerance.  None of them require N to be a power of two (except the
Walsh–Hadamard transform, where pow-2 is intrinsic to the transform itself,
not to the algorithm — paper §1 & §3 stress this generality).
"""
from __future__ import annotations

import functools

import jax.numpy as jnp
import numpy as np

__all__ = [
    "dft_matrix",
    "dht_matrix",
    "dct2_matrix",
    "dwht_matrix",
    "coefficient_matrix",
    "inverse_coefficient_matrix",
    "blocked_coefficient_matrix",
    "TRANSFORM_KINDS",
]

TRANSFORM_KINDS = ("dft", "dht", "dct", "dwht")


@functools.lru_cache(maxsize=64)
def _grid(n: int) -> np.ndarray:
    i = np.arange(n)
    return np.outer(i, i)


def dft_matrix(n: int, dtype=jnp.complex64) -> jnp.ndarray:
    """Unitary DFT matrix: C[n,k] = exp(-2πi nk / N) / sqrt(N)."""
    nk = _grid(n)
    mat = np.exp(-2j * np.pi * nk / n) / np.sqrt(n)
    return jnp.asarray(mat, dtype=dtype)


def dht_matrix(n: int, dtype=jnp.float32) -> jnp.ndarray:
    """Orthonormal Hartley matrix: C[n,k] = cas(2π nk/N)/sqrt(N), cas = cos+sin."""
    ang = 2.0 * np.pi * _grid(n) / n
    mat = (np.cos(ang) + np.sin(ang)) / np.sqrt(n)
    return jnp.asarray(mat, dtype=dtype)


def dct2_matrix(n: int, dtype=jnp.float32) -> jnp.ndarray:
    """Orthonormal DCT-II matrix: C[n,k] = s_k cos(π (2n+1) k / 2N).

    s_0 = sqrt(1/N), s_k = sqrt(2/N) for k > 0.  C is orthogonal but (unlike
    DFT/DHT) not symmetric: C ≠ Cᵀ (paper §2.2).
    """
    n_idx = np.arange(n)[:, None]
    k_idx = np.arange(n)[None, :]
    mat = np.cos(np.pi * (2 * n_idx + 1) * k_idx / (2 * n))
    scale = np.full((1, n), np.sqrt(2.0 / n))
    scale[0, 0] = np.sqrt(1.0 / n)
    return jnp.asarray(mat * scale, dtype=dtype)


def dwht_matrix(n: int, dtype=jnp.float32) -> jnp.ndarray:
    """Orthonormal Walsh–Hadamard matrix (natural/Hadamard order); N must be 2^k."""
    if n & (n - 1):
        raise ValueError(f"DWHT requires power-of-two size, got {n}")
    h = np.array([[1.0]])
    while h.shape[0] < n:
        h = np.block([[h, h], [h, -h]])
    return jnp.asarray(h / np.sqrt(n), dtype=dtype)


_BUILDERS = {
    "dft": dft_matrix,
    "dht": dht_matrix,
    "dct": dct2_matrix,
    "dwht": dwht_matrix,
}


def coefficient_matrix(kind: str, n: int, dtype=None) -> jnp.ndarray:
    """Forward coefficient matrix for a named transform kind."""
    kind = kind.lower()
    if kind not in _BUILDERS:
        raise ValueError(f"unknown transform kind {kind!r}; choose from {TRANSFORM_KINDS}")
    if dtype is None:
        return _BUILDERS[kind](n)
    return _BUILDERS[kind](n, dtype=dtype)


def inverse_coefficient_matrix(kind: str, n: int, dtype=None) -> jnp.ndarray:
    """Inverse = conjugate transpose (orthonormal builders)."""
    c = coefficient_matrix(kind, n, dtype=dtype)
    return jnp.conj(c).T


def blocked_coefficient_matrix(kind: str, n: int, block: int, *,
                               inverse: bool = False,
                               dtype=None) -> jnp.ndarray:
    """Block-diagonal ``I_{n/block} ⊗ C_block``: the kind's ``block``-point
    transform applied to each run of ``block`` consecutive indices (the
    forward matrix, or with ``inverse`` its inverse — the blockwise
    conjugate transpose).  ``block`` must divide ``n``."""
    if block < 1 or n % block:
        raise ValueError(f"block {block} does not divide extent {n}")
    build = inverse_coefficient_matrix if inverse else coefficient_matrix
    cb = np.asarray(build(kind, block, dtype=dtype))
    m = n // block
    out = np.zeros((m, block, m, block), cb.dtype)
    out[np.arange(m), :, np.arange(m), :] = cb
    return jnp.asarray(out.reshape(n, n))

"""Plain reference of the blocked 3-D DCT-II of video block coding.

Every b1 x b2 x b3 block of a (T, H, W) plane is taken to its orthonormal
DCT-II coefficients, ``Y[blk][k1,k2,k3] = sum X[blk][n1,n2,n3] C1[n1,k1]
C2[n2,k2] C3[n3,k3]`` with ``C[n,k] = s_k cos(pi (2n+1) k / 2b)``,
``s_0 = sqrt(1/b)``, ``s_k = sqrt(2/b)``; the inverse contracts with the
transposes.  The plane is reshaped to (T/b1, b1, H/b2, b2, W/b3, b3) and
each b-axis is contracted with its C_b by one ``jnp.einsum``, float32 at
precision "highest".  The block-diagonal matrix of the whole extent is
never formed.

On a TPU the six-axis array pads its minor axis (b3) to 128 lanes, so a
caller there transforms one group of b1 frames at a time (a 4K one takes
~4.5 GB of temporaries).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def dct2(b: int) -> np.ndarray:
    """The orthonormal b-point DCT-II matrix ``C[n, k]``, built in float64."""
    n = np.arange(b)[:, None]
    k = np.arange(b)[None, :]
    c = np.cos(np.pi * (2 * n + 1) * k / (2 * b)) * np.sqrt(2.0 / b)
    c[:, 0] = np.sqrt(1.0 / b)
    return c.astype(np.float32)


@functools.partial(jax.jit, static_argnames=("block", "inverse"))
def transform(x, block, inverse: bool = False):
    """The blocked transform of plane ``x`` (T, H, W); ``block`` a tuple."""
    b1, b2, b3 = block
    t, h, w = x.shape
    c1, c2, c3 = (jnp.asarray(dct2(b).T if inverse else dct2(b))
                  for b in block)
    y = x.reshape(t // b1, b1, h // b2, b2, w // b3, b3)
    y = jnp.einsum("anbmcl,nk,mj,li->akbjci", y, c1, c2, c3,
                   precision="highest")
    return y.reshape(t, h, w)

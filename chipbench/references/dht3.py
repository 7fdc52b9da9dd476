"""Plain reference of the 3D discrete Hartley transform.

``Y[i,j,k] = sum_abc X[a,b,c] H1[a,i] H2[b,j] H3[c,k]`` with the orthonormal
Hartley matrix ``H[n,k] = (cos + sin)(2 pi n k / N) / sqrt(N)``.  H is
symmetric and its own inverse, so the inverse transform is the same
product.  Built in float64 on the host, applied as three float32 mode
products at ``precision="highest"``: one ``jnp.einsum`` each, jitted one
mode at a time so that at most two fields are live.  On a mesh each
product keeps the field's sharding and XLA places the collectives.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

SPECS = ("abc,ai->ibc", "abc,bj->ajc", "abc,ck->abk")


def hartley(n: int) -> np.ndarray:
    ang = 2.0 * np.pi * np.outer(np.arange(n), np.arange(n)) / n
    return ((np.cos(ang) + np.sin(ang)) / np.sqrt(n)).astype(np.float32)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _mode(x, h, mode, sharding):
    y = jnp.einsum(SPECS[mode], x, h, precision="highest")
    if sharding is not None:
        y = jax.lax.with_sharding_constraint(y, sharding)
    return y


def transform(x, mats):
    """``x`` through the three mode products; ``mats`` are (N, N) arrays."""
    sharding = x.sharding if isinstance(x.sharding, NamedSharding) else None
    for mode, h in enumerate(mats):
        x = _mode(x, h, mode, sharding)
    return x

"""Distributed 3D-GEMT: the TriADA dataflow on a TPU mesh.

The paper's central distribution insight (§4–§5): the data tensor is
**stationary** — it keeps one placement through all three stages — while the
small square coefficient matrices are **streamed/broadcast** into the
processing space.  On a TPU mesh this becomes:

  * the 3-mode tensor is sharded once, e.g. ``P('data', 'model', None)``
    (single-pod) or ``P('data', 'model', 'pod')`` (multi-pod: the mesh *is*
    the 3D processing space — mode-s ↔ mesh-axis isomorphism, paper Eq. 7),
  * coefficient matrices are replicated (``P()``): the ICI broadcast is the
    Actuator's operand-bus multicast,
  * a stage contracting an *unsharded* mode is entirely local,
  * a stage contracting a *sharded* mode computes local partial rank-k
    updates (the outer-product schedule restricted to the local coefficient
    rows) and combines them with a single ``psum_scatter`` over that axis —
    the output lands with exactly the input's sharding.  **No resharding,
    no transposition, no tensor movement between stages.**

Two implementations:

  * ``gemt3_shardmap`` — the TriADA schedule (shard_map + psum_scatter,
    collectives hand-placed).  Since PR 3 it **delegates to the execution
    engine** (``repro.engine.gemt3_planned(mesh=...)``): the local stages
    run the planned Pallas kernel dispatch (sr_gemm / block-ESOP / fused
    VMEM pairs where shard-local) instead of raw einsum, and the planner's
    sharded cost model owns the stage ordering.  ``engine=False`` keeps
    the original pure-einsum schedule as a measurable baseline,
  * ``gemt3_auto``     — jit + sharding constraints (XLA GSPMD chooses the
    collectives) — the baseline the roofline compares against.

Mesh recipes and the per-stage data-movement walkthrough live in
``docs/distributed.md``; the paper↔module map in ``docs/architecture.md``.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["gemt3_shardmap", "gemt3_auto", "tensor_spec"]

AxisName = str | tuple[str, ...] | None


def tensor_spec(axes: Sequence[AxisName]) -> P:
    """PartitionSpec for the stationary tensor from per-mode mesh axes."""
    return P(*axes)


def _axis_size(mesh: Mesh, axis: AxisName) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        return int(jnp.prod(jnp.array([mesh.shape[a] for a in axis])))
    return mesh.shape[axis]


def _local_stage(y_local: jnp.ndarray, coeff: jnp.ndarray, mode: int,
                 axis: AxisName, mesh: Mesh) -> jnp.ndarray:
    """One GEMT stage on the local shard; combine over ``axis`` if sharded."""
    from .gemt import mode_product

    if axis is None:
        # Unsharded contraction mode: stage is fully local (the streamed
        # coefficient matrix is already replicated on every device).
        return mode_product(y_local, coeff, mode)

    # Sharded contraction mode: this device owns rows
    # [idx*local_n, (idx+1)*local_n) of the contracted extent.  It executes
    # the outer-product schedule for *its* coefficient rows — a partial
    # rank-(local_n) update of the full output extent — and one
    # psum_scatter re-distributes k_s over the same mesh axis: the tensor
    # never moves, only partial sums are combined.
    names = axis if isinstance(axis, tuple) else (axis,)
    idx = jnp.zeros((), jnp.int32)
    for name in names:  # row-major linear index over the (possibly tuple) axis
        idx = idx * mesh.shape[name] + jax.lax.axis_index(name)
    local_n = y_local.shape[mode - 1]
    rows = jax.lax.dynamic_slice_in_dim(coeff, idx * local_n, local_n, 0)
    partial = mode_product(y_local, rows, mode)  # full K_s extent, partial sum
    moved = jnp.moveaxis(partial, mode - 1, 0)
    combined = jax.lax.psum_scatter(moved, names, scatter_dimension=0, tiled=True)
    return jnp.moveaxis(combined, 0, mode - 1)


def gemt3_shardmap(
    mesh: Mesh,
    axes: Sequence[AxisName] = ("data", "model", None),
    order: Sequence[int] | None = (3, 1, 2),
    *,
    engine: bool = True,
    **engine_kwargs,
):
    """Build the TriADA-scheduled distributed GEMT: f(x, c1, c2, c3) -> y.

    ``axes[s-1]`` is the mesh axis sharding mode s of the stationary tensor
    (None = unsharded).  Every mode extent (and, for sharded modes, the
    coefficient output extent K_s) must divide its axis size.

    ``engine=True`` (default) delegates to the topology-aware execution
    engine: the identical collective schedule, with the local stages
    lowered through the planned Pallas kernel dispatch and ``order=None``
    unlocking the sharded cost-model order search.  ``engine_kwargs``
    (``use_pallas``, ``fuse``, ``autotune``, ``batch_axis``, …) pass
    through to :func:`repro.engine.gemt3_planned`.  ``engine=False`` is
    the original einsum-only schedule (benchmark baseline).  Both return
    arrays sharded on the Auto-axis form of ``mesh``, so their results
    combine with each other and with :func:`gemt3_auto`'s.
    """
    from ..launch.mesh import auto_axes

    mesh = auto_axes(mesh)
    if engine:
        from ..engine import gemt3_planned as _planned

        axes_t = tuple(tuple(a) if isinstance(a, list) else a for a in axes)
        order_t = tuple(order) if order is not None else None

        def f(x, c1, c2, c3):
            return _planned(x, c1, c2, c3, mesh=mesh, axes=axes_t,
                            order=order_t, **engine_kwargs)

        return f

    if engine_kwargs:
        raise TypeError(f"engine=False takes no engine kwargs, "
                        f"got {sorted(engine_kwargs)}")
    spec = tensor_spec(axes)

    def f(x, c1, c2, c3):
        cs = {1: c1, 2: c2, 3: c3}
        y = x
        for mode in order:
            y = _local_stage(y, cs[mode], mode, axes[mode - 1], mesh)
        return y

    return shard_map(
        f,
        mesh=mesh,
        in_specs=(spec, P(), P(), P()),
        out_specs=spec,
        check_vma=False,
    )


def gemt3_auto(
    mesh: Mesh,
    axes: Sequence[AxisName] = ("data", "model", None),
    order: Sequence[int] = (3, 1, 2),
):
    """GSPMD baseline: same stationary-spec pinning, XLA picks collectives."""
    from ..launch.mesh import auto_axes

    mesh = auto_axes(mesh)  # with_sharding_constraint needs Auto axes
    spec = tensor_spec(axes)

    def f(x, c1, c2, c3):
        from .gemt import mode_product

        cs = {1: c1, 2: c2, 3: c3}
        y = jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
        for mode in order:
            y = mode_product(y, cs[mode], mode)
            y = jax.lax.with_sharding_constraint(y, NamedSharding(mesh, spec))
        return y

    return jax.jit(
        f,
        in_shardings=(NamedSharding(mesh, spec),) + (NamedSharding(mesh, P()),) * 3,
        out_shardings=NamedSharding(mesh, spec),
    )

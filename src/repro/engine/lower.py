"""Lowering: mode-s contractions as 2D GEMMs on the repo's Pallas kernels.

A mode-s contraction of a (optionally batched) 3-mode tensor is exactly the
unfolded GEMM ``(B·A·B', N_s) @ (N_s, K_s)`` (Kolda–Bader mode-unfolding
with the contracted mode innermost).  ``lower_stage`` performs one planned
stage: unfold → dispatch to ``kernels.ops.sr_gemm`` / ``esop_gemm`` / an
einsum fallback → fold.  Batched execution folds the leading batch axis
into the GEMM rows, so a whole service batch is one kernel launch per
stage.

``lower_sharded_stage`` is the distributed counterpart, meant to run
*inside* a ``shard_map`` body (paper §4–§5, ``docs/distributed.md``): it
slices this device's coefficient rows by mesh position, runs the same
unfold→kernel→fold local GEMM as a partial rank-k update of the full
output extent, and combines shards with one ``psum_scatter`` — the TriADA
schedule with the planned Pallas kernels doing the local work.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core import gemt as _gemt
from ..kernels import ops
from ..obs import trace as _trace
from .plan import FusedPairPlan, FusedTriplePlan, StagePlan, _ceil_div

__all__ = ["mode_unfold", "mode_fold", "lower_stage", "lower_fused_pair",
           "lower_fused_triple", "lower_chain_pair", "lower_chain_triple",
           "lower_sharded_stage", "lower_coeff_grad",
           "lower_coeff_grad_batch", "coeff_grad_backend"]

# The einsum backend contracts in place (XLA folds the relayout into one
# dot_general) instead of the unfold→matmul→fold chain, whose
# reshape-of-transpose materializes two copies — measurably slower exactly
# where the planner picks einsum, i.e. stages too small to amortize a
# kernel launch.  Specs are mode_product's table plus a leading batch axis.
_EINSUM3 = _gemt._EINSUM


def _batched_spec(spec: str) -> str:
    lhs, rest = spec.split(",")
    c, out = rest.split("->")
    return f"z{lhs},{c}->z{out}"


_EINSUM4 = {m: _batched_spec(s) for m, s in _EINSUM3.items()}


def _einsum_stage(x: jnp.ndarray, c: jnp.ndarray, mode: int,
                  accum: str = "plain") -> jnp.ndarray:
    spec = (_EINSUM4 if x.ndim == 4 else _EINSUM3)[mode]
    if accum != "plain" and not jnp.iscomplexobj(x):
        # Promoted accumulation on the einsum fallback: contract in f32 and
        # keep the f32 result (no Neumaier variant here — einsum stages are
        # the planner's tiny/complex fallback; see docs/numerics.md).
        return jnp.einsum(spec, x.astype(jnp.float32),
                          c.astype(jnp.float32))
    return jnp.einsum(spec, x, c)


def mode_unfold(x: jnp.ndarray, mode: int) -> tuple[jnp.ndarray, tuple[int, ...]]:
    """Unfold tensor ``x`` for a mode-``mode`` contraction.

    The last three axes are the tensor modes (a leading batch axis, if any,
    is folded into the rows).  Returns ``(matrix (rows, N_s), lead_shape)``
    where ``lead_shape`` re-folds the rows.
    """
    if x.ndim not in (3, 4):
        raise ValueError(f"x must be 3D or 4D-batched, got ndim={x.ndim}")
    ax = x.ndim - 3 + (mode - 1)
    xm = jnp.moveaxis(x, ax, -1)
    return xm.reshape(-1, xm.shape[-1]), xm.shape[:-1]


def mode_fold(y2d: jnp.ndarray, lead_shape: tuple[int, ...], mode: int) -> jnp.ndarray:
    """Inverse of :func:`mode_unfold` with the new extent K_s in place."""
    ndim = len(lead_shape) + 1
    ax = ndim - 3 + (mode - 1)
    y = y2d.reshape(*lead_shape, y2d.shape[-1])
    return jnp.moveaxis(y, -1, ax)


@functools.partial(jax.jit, static_argnames=(
    "mode", "backend", "bm", "bn", "bk", "t_steps", "accum", "use_pallas"))
def _kernel_stage(x, c, counts, idx, *, mode, backend, bm, bn, bk, t_steps,
                  accum, use_pallas):
    """unfold → kernel → fold as one program.  Run eagerly, each step
    (transpose, reshape, pad, crop, the fold's reshape and transpose)
    materializes a copy of the tensor; compiled together, the reshapes and
    the crop become bitcasts, and only the transposes and the operand's
    padding are written."""
    x2d, lead = mode_unfold(x, mode)
    if backend == "esop":
        y2d, _ = ops.esop_gemm(x2d, c, bm=bm, bn=bn, bk=bk,
                               use_pallas=use_pallas,
                               plan=(counts, idx, t_steps, {}), accum=accum)
    else:
        y2d = ops.sr_gemm(x2d, c, bm=bm, bn=bn, bk=bk,
                          use_pallas=use_pallas, accum=accum)
    return mode_fold(y2d, lead, mode)


def _stage_grid_steps(rows: int, stage: StagePlan, t_steps: int) -> int:
    """Grid steps of a staged kernel launch over ``rows`` unfolded rows:
    ``(rows/bm, K/bn, t)`` after padding, where ``t`` is the ESOP
    schedule's ``t_steps`` or, dense (``t_steps=0``), ``N/bk``."""
    t = t_steps or _ceil_div(stage.n, stage.bk)
    return _ceil_div(rows, stage.bm) * _ceil_div(stage.k, stage.bn) * t


def lower_stage(
    x: jnp.ndarray,
    c: jnp.ndarray,
    stage: StagePlan,
    *,
    use_pallas: bool | None = None,
    esop_plan: tuple | None = None,
) -> tuple[jnp.ndarray, dict]:
    """Execute one planned contraction stage.  Returns ``(y, info)``.

    ``info`` carries the backend actually used, the kernel's
    ``grid_steps`` (0 on einsum) and the block-ESOP fetch accounting when
    that path engages (backend-independent: the reference path reports
    the same steps and savings the TPU kernel realizes).  ``esop_plan``
    optionally supplies the precomputed ``esop_plan_cached`` tuple — the
    distributed executor computes it host-side before entering the
    ``shard_map`` body, where ``c`` is a tracer.
    """
    sp = _trace.NULL_SPAN
    if _trace.enabled():
        sp = _trace.span(f"stage:m{stage.mode}:{stage.backend}",
                         {"mode": stage.mode, "backend": stage.backend,
                          "macs": stage.macs, "shape": tuple(x.shape)})
    with sp:
        rows = x.size // max(x.shape[x.ndim - 3 + stage.mode - 1], 1)
        info: dict = {"mode": stage.mode, "backend": stage.backend,
                      "rows": int(rows), "macs": stage.macs,
                      "grid_steps": 0}
        if stage.backend == "einsum":
            return _einsum_stage(x, c, stage.mode, stage.accum), info
        if stage.backend == "esop":
            plan = (esop_plan if esop_plan is not None
                    else ops.esop_plan_cached(c, stage.bk, stage.bn))
            info.update(plan[3])
        elif stage.backend == "sr_gemm":
            plan = (None, None, 0)
        else:
            raise ValueError(f"unknown backend {stage.backend!r}")
        info["grid_steps"] = _stage_grid_steps(rows, stage, plan[2])
        y = _kernel_stage(x, c, plan[0], plan[1], mode=stage.mode,
                          backend=stage.backend, bm=stage.bm, bn=stage.bn,
                          bk=stage.bk, t_steps=plan[2], accum=stage.accum,
                          use_pallas=use_pallas)
        return y, info


def lower_sharded_stage(
    x: jnp.ndarray,
    c: jnp.ndarray,
    stage: StagePlan,
    mesh,
    *,
    use_pallas: bool | None = None,
) -> tuple[jnp.ndarray, dict]:
    """One sharded-mode stage inside a ``shard_map`` body: local partial
    rank-k update + one ``psum_scatter`` over the stage's mesh axis.

    ``x`` is the local shard; ``c`` the (replicated) full coefficient
    matrix.  This device contracts its rows ``[idx·n, (idx+1)·n)`` of
    ``c`` — the outer-product schedule restricted to the local coefficient
    rows, producing the full ``K_s`` extent as a partial sum — then the
    tiled ``psum_scatter`` reduces across the axis and lands each device's
    ``K_s / shards`` chunk in place.  The tensor never moves; only partial
    sums do (paper §5's stationary-tensor invariant).
    """
    sp = _trace.NULL_SPAN
    if _trace.enabled():  # trace-time inside shard_map: structure is exact
        sp = _trace.span(f"stage:m{stage.mode}:{stage.backend}:sharded",
                         {"mode": stage.mode, "backend": stage.backend,
                          "macs": stage.macs, "axis": str(stage.axis),
                          "shards": stage.shards,
                          "collective_bytes": stage.collective_bytes})
    with sp:
        names = stage.axis if isinstance(stage.axis, tuple) else (stage.axis,)
        idx = jnp.zeros((), jnp.int32)
        for name in names:  # row-major linear index over the (tuple) axis
            idx = idx * mesh.shape[name] + jax.lax.axis_index(name)
        c_rows = jax.lax.dynamic_slice_in_dim(c, idx * stage.n, stage.n, 0)

        rows = x.size // max(x.shape[x.ndim - 3 + stage.mode - 1], 1)
        info: dict = {"mode": stage.mode, "backend": stage.backend,
                      "rows": int(rows), "macs": stage.macs,
                      "axis": stage.axis, "shards": stage.shards,
                      "collective_bytes": stage.collective_bytes,
                      "grid_steps": 0}
        if stage.backend == "einsum":
            partial = _einsum_stage(x, c_rows, stage.mode, stage.accum)
        elif stage.backend == "sr_gemm":
            info["grid_steps"] = _stage_grid_steps(rows, stage, 0)
            x2d, lead = mode_unfold(x, stage.mode)
            y2d = ops.sr_gemm(x2d, c_rows, bm=stage.bm, bn=stage.bn,
                              bk=stage.bk, use_pallas=use_pallas,
                              accum=stage.accum)
            partial = mode_fold(y2d, lead, stage.mode)
        else:
            # The planner never assigns esop here: the row slice is selected
            # by axis_index at run time, so its zero structure is
            # device-dependent and the host-side block schedule cannot exist.
            raise ValueError(
                f"backend {stage.backend!r} cannot run a sharded-mode stage")
        # partial holds the full K_s extent as a partial sum
        ax = partial.ndim - 3 + (stage.mode - 1)
        moved = jnp.moveaxis(partial, ax, 0)
        csp = _trace.NULL_SPAN
        if _trace.enabled():
            csp = _trace.span("collective:psum_scatter",
                              {"mode": stage.mode, "axis": str(stage.axis),
                               "collective_bytes": stage.collective_bytes})
        with csp:
            combined = jax.lax.psum_scatter(moved, names,
                                            scatter_dimension=0, tiled=True)
        return jnp.moveaxis(combined, 0, ax), info


def coeff_grad_backend(rows_total: int, n: int, k: int, dtype) -> str:
    """Backend for a coefficient-cotangent GEMM ``(N_s, rows) @ (rows, K_s)``.

    The cotangent of a coefficient matrix is a mode-unfolded rank-``rows``
    product — dense regardless of C's zero structure (the linearization in
    C does not inherit its sparsity), so the menu is SR-GEMM vs the einsum
    fallback, by the same complex-dtype and minimum-extent rules as
    forward stages.
    """
    from .plan import MIN_KERNEL_DIM

    if jnp.issubdtype(jnp.dtype(dtype), jnp.complexfloating):
        return "einsum"
    if min(rows_total, n, k) < MIN_KERNEL_DIM:
        return "einsum"
    return "sr_gemm"


def lower_coeff_grad(
    a: jnp.ndarray,
    g: jnp.ndarray,
    mode: int,
    *,
    use_pallas: bool | None = None,
    backend: str | None = None,
) -> tuple[jnp.ndarray, dict]:
    """Coefficient cotangent ``dC_s = unfold_s(A)ᵀ @ unfold_s(G)``.

    ``a`` is the forward stage's *input* tensor (mode ``s`` still at extent
    N_s) and ``g`` the cotangent of that stage's *output* (mode ``s`` at
    K_s); every other axis — including a leading batch — is identical on
    both sides and folds into the contraction rows, so the whole update is
    one SR-GEMM rank-``rows`` product.  Returns ``(dC, info)`` with the
    ``kind="coeff_grad"`` dispatch accounting the VJP executor aggregates
    into the ``grad_*`` counters.

    ``backend`` overrides :func:`coeff_grad_backend` — the sharded
    executor pins ``"einsum"`` because its operands are *global* sharded
    arrays outside any ``shard_map``: only a plain ``dot_general`` gives
    GSPMD something it can partition (and psum across shards); a
    ``pallas_call`` on multi-device operands has no SPMD rule.
    """
    from .plan import _lane_tile

    a2d, _ = mode_unfold(a, mode)
    g2d, _ = mode_unfold(g, mode)
    rows, n = a2d.shape
    k = g2d.shape[1]
    if backend is None:
        backend = coeff_grad_backend(rows, n, k,
                                     jnp.result_type(a2d.dtype, g2d.dtype))
    info = {"mode": mode, "backend": backend, "kind": "coeff_grad",
            "rows": int(rows), "macs": int(rows) * int(n) * int(k)}
    sp = _trace.NULL_SPAN
    if _trace.enabled():
        sp = _trace.span(f"coeff_grad:m{mode}:{backend}",
                         {"mode": mode, "backend": backend,
                          "rows": int(rows), "macs": info["macs"]})
    with sp:
        if backend == "einsum":
            dc = jnp.swapaxes(a2d, 0, 1) @ g2d
        else:
            dc = ops.sr_gemm(jnp.swapaxes(a2d, 0, 1), g2d,
                             bm=_lane_tile(n), bn=_lane_tile(k),
                             bk=_lane_tile(rows), use_pallas=use_pallas)
    return dc, info


def lower_fused_pair(
    x: jnp.ndarray,
    ca: jnp.ndarray,
    cb: jnp.ndarray,
    fp: FusedPairPlan,
    *,
    use_pallas: bool | None = None,
    plans: tuple | None = None,
) -> tuple[jnp.ndarray, dict]:
    """Execute a fused consecutive stage pair.  Returns ``(y, info)``.

    Unfolds ``x`` into the u-major ``(U, Nb, Na)`` layout the fused kernel
    streams (batch and the untouched mode fold into U), runs both
    contractions in one launch — the stage-a partial never leaves VMEM, so
    there is no intermediate fold/unfold transpose between them — and
    folds ``(U, Ka, Kb)`` back into tensor modes.  ``plans`` optionally
    carries the two precomputed ``esop_plan_cached`` tuples (a/b), for
    callers whose ``ca``/``cb`` are tracers inside a ``shard_map`` body.
    """
    if x.ndim not in (3, 4):
        raise ValueError(f"x must be 3D or 4D-batched, got ndim={x.ndim}")
    axa = x.ndim - 3 + (fp.mode_a - 1)
    axb = x.ndim - 3 + (fp.mode_b - 1)
    sp = _trace.NULL_SPAN
    if _trace.enabled():
        sp = _trace.span(f"fused_pair:m{fp.mode_a}{fp.mode_b}",
                         {"modes": (fp.mode_a, fp.mode_b), "macs": fp.macs,
                          "vmem_bytes": fp.vmem_bytes,
                          "hbm_bytes_fused": fp.hbm_bytes_fused,
                          "shape": tuple(x.shape)})
    with sp:
        xm = jnp.moveaxis(x, (axb, axa), (-2, -1))
        lead = xm.shape[:-2]
        x3 = xm.reshape(-1, xm.shape[-2], xm.shape[-1])
        y3, kinfo = ops.fused_gemt(x3, ca, cb, bu=fp.bu, bka=fp.bka,
                                   bnb=fp.bnb, bna=fp.bna,
                                   use_pallas=use_pallas, plans=plans,
                                   accum=fp.accum)
        y = jnp.moveaxis(y3.reshape(*lead, fp.ka, fp.kb), (-2, -1),
                         (axa, axb))
    info: dict = {"modes": (fp.mode_a, fp.mode_b), "backend": "fused",
                  "rows": int(x3.shape[0]), "macs": fp.macs,
                  "vmem_bytes": fp.vmem_bytes,
                  "hbm_bytes_staged": fp.hbm_bytes_staged,
                  "hbm_bytes_fused": fp.hbm_bytes_fused,
                  "hbm_savings": fp.hbm_savings}
    info.update(kinfo)
    t_a, t_b = kinfo["t_steps"]
    info["grid_steps"] = (_ceil_div(x3.shape[0], fp.bu)
                          * _ceil_div(fp.ka, fp.bka) * t_b * t_a)
    return y, info


def lower_chain_pair(
    x: jnp.ndarray,
    ca: jnp.ndarray,
    cb: jnp.ndarray,
    mode_a: int,
    mode_b: int,
    tiles: tuple,
    *,
    use_pallas: bool | None = None,
    plan_a: tuple | None = None,
    accum: str = "plain",
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Two consecutive stages as one chain launch, the inter-stage
    intermediate emitted.  Returns ``(y, y1)`` folded back into tensor
    modes (``y1`` has mode ``a`` at its new extent K_a, mode ``b``
    untouched).

    Deliberately span/info-free: the backward walk traces this into a
    cached jitted program, where a span would fire once at trace time and
    then lie — the executor wraps the *call* instead.  ``tiles`` is the
    chain plan's ``(bu, bka, bnb, bna, kbp)``; ``plan_a`` the precomputed
    a-side ESOP schedule (required when ``ca`` is a tracer).
    """
    if x.ndim not in (3, 4):
        raise ValueError(f"x must be 3D or 4D-batched, got ndim={x.ndim}")
    axa = x.ndim - 3 + (mode_a - 1)
    axb = x.ndim - 3 + (mode_b - 1)
    ka, kb = ca.shape[1], cb.shape[1]
    xm = jnp.moveaxis(x, (axb, axa), (-2, -1))
    lead = xm.shape[:-2]
    nb = xm.shape[-2]
    x3 = xm.reshape(-1, xm.shape[-2], xm.shape[-1])
    bu, bka, bnb, bna = tiles[0], tiles[1], tiles[2], tiles[3]
    y3, y13, _ = ops.chain_gemt(x3, ca, cb, bu=bu, bka=bka, bnb=bnb,
                                bna=bna, use_pallas=use_pallas,
                                plan_a=plan_a, accum=accum)
    y = jnp.moveaxis(y3.reshape(*lead, ka, kb), (-2, -1), (axa, axb))
    y1 = jnp.moveaxis(y13.reshape(*lead, nb, ka), (-2, -1), (axb, axa))
    return y, y1


def lower_chain_triple(
    x: jnp.ndarray,
    ca: jnp.ndarray,
    cb: jnp.ndarray,
    cc: jnp.ndarray,
    mode_a: int,
    mode_b: int,
    mode_c: int,
    tiles: tuple,
    *,
    use_pallas: bool | None = None,
    plan_a: tuple | None = None,
    accum: str = "plain",
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """All three stages as one chain launch with both intermediates
    emitted.  Returns ``(y, y1, y2)`` folded back into tensor modes
    (``y1``: mode ``a`` contracted; ``y2``: modes ``a`` and ``b``).

    Span/info-free for the same reason as :func:`lower_chain_pair`.
    ``tiles`` is the chain plan's ``(bu, bka, bnb, bnc, bna, kbp, kcp)``.
    """
    if x.ndim not in (3, 4):
        raise ValueError(f"x must be 3D or 4D-batched, got ndim={x.ndim}")
    off = x.ndim - 3
    axa = off + mode_a - 1
    axb = off + mode_b - 1
    axc = off + mode_c - 1
    ka, kb, kc = ca.shape[1], cb.shape[1], cc.shape[1]
    xm = jnp.moveaxis(x, (axc, axb, axa), (-3, -2, -1))
    lead = xm.shape[:-3]
    nc, nb = xm.shape[-3], xm.shape[-2]
    x4 = xm.reshape(-1, *xm.shape[-3:])
    bu, bka, bnb, bnc, bna = (tiles[0], tiles[1], tiles[2], tiles[3],
                              tiles[4])
    y4, y14, y24, _ = ops.chain3_gemt(x4, ca, cb, cc, bu=bu, bka=bka,
                                      bnb=bnb, bnc=bnc, bna=bna,
                                      use_pallas=use_pallas, plan_a=plan_a,
                                      accum=accum)
    y = jnp.moveaxis(y4.reshape(*lead, ka, kb, kc), (-3, -2, -1),
                     (axa, axb, axc))
    y1 = jnp.moveaxis(y14.reshape(*lead, nc, nb, ka), (-3, -2, -1),
                      (axc, axb, axa))
    y2 = jnp.moveaxis(y24.reshape(*lead, nc, ka, kb), (-3, -2, -1),
                      (axc, axa, axb))
    return y, y1, y2


def lower_coeff_grad_batch(
    as_: list,
    gs: list,
    modes: tuple,
    *,
    use_pallas: bool | None = None,
) -> list:
    """All three coefficient cotangents in one batched launch.

    ``as_[i]`` / ``gs[i]`` / ``modes[i]`` pair the stage-input tensor and
    stage-output cotangent of one forward stage (same operand contract as
    :func:`lower_coeff_grad`); the mode-unfolded rank-k products run as a
    single stacked kernel (``ops.coeff_grad_batch``).  Span/info-free for
    the same reason as :func:`lower_chain_pair` — the executor owns the
    accounting.

    Off-TPU (and for complex operands) the three products lower as direct
    full-tensor contractions instead: the operand pair shares every axis
    except the contracted mode, so one einsum per mode contracts in place
    — no unfold/pad/stack copies of batch-sized tensors (~1.2x on CPU).
    """
    live = use_pallas if use_pallas is not None else ops.on_tpu()
    if any(jnp.iscomplexobj(t) for t in (*as_, *gs)):
        live = False
    if live:
        a2ds = [mode_unfold(a, m)[0] for a, m in zip(as_, modes)]
        g2ds = [mode_unfold(g, m)[0] for g, m in zip(gs, modes)]
        return ops.coeff_grad_batch(a2ds, g2ds, use_pallas=use_pallas)
    out = []
    for a, g, m in zip(as_, gs, modes):
        ax = a.ndim - 3 + m - 1
        shared = [chr(ord("a") + i) for i in range(a.ndim)]
        la, lg = shared.copy(), shared.copy()
        la[ax], lg[ax] = "n", "k"
        spec = f"{''.join(la)},{''.join(lg)}->nk"
        dt = jnp.result_type(a.dtype, g.dtype)
        if jnp.issubdtype(dt, jnp.complexfloating):
            out.append(jnp.einsum(spec, a, g).astype(dt))
        else:
            out.append(jnp.einsum(
                spec, a, g,
                preferred_element_type=jnp.float32).astype(dt))
    return out


def lower_fused_triple(
    x: jnp.ndarray,
    ca: jnp.ndarray,
    cb: jnp.ndarray,
    cc: jnp.ndarray,
    ft: FusedTriplePlan,
    *,
    use_pallas: bool | None = None,
    plans: tuple | None = None,
) -> tuple[jnp.ndarray, dict]:
    """Execute the whole transform as one fused launch.  Returns ``(y, info)``.

    Unfolds ``x`` into the u-major ``(U, Nc, Nb, Na)`` layout the
    megakernel streams (only the batch folds into U — every tensor mode is
    contracted), runs all three contractions in one launch — neither
    inter-stage intermediate ever exists in HBM, so both fold/unfold
    transposes dissolve into the kernel's BlockSpec index maps — and folds
    ``(U, Ka, Kb, Kc)`` back into tensor modes.  ``plans`` optionally
    carries the three precomputed ``esop_plan_cached`` tuples (a/b/c), for
    callers whose coefficients are tracers inside a ``shard_map`` body.
    """
    if x.ndim not in (3, 4):
        raise ValueError(f"x must be 3D or 4D-batched, got ndim={x.ndim}")
    off = x.ndim - 3
    axa = off + ft.mode_a - 1
    axb = off + ft.mode_b - 1
    axc = off + ft.mode_c - 1
    sp = _trace.NULL_SPAN
    if _trace.enabled():
        sp = _trace.span(f"fused_triple:m{ft.mode_a}{ft.mode_b}{ft.mode_c}",
                         {"modes": (ft.mode_a, ft.mode_b, ft.mode_c),
                          "macs": ft.macs, "vmem_bytes": ft.vmem_bytes,
                          "hbm_bytes_fused": ft.hbm_bytes_fused,
                          "shape": tuple(x.shape)})
    with sp:
        xm = jnp.moveaxis(x, (axc, axb, axa), (-3, -2, -1))
        lead = xm.shape[:-3]
        x4 = xm.reshape(-1, *xm.shape[-3:])
        y4, kinfo = ops.fused3_gemt(x4, ca, cb, cc, bu=ft.bu, bka=ft.bka,
                                    bnb=ft.bnb, bnc=ft.bnc, bna=ft.bna,
                                    use_pallas=use_pallas, plans=plans,
                                    accum=ft.accum)
        y = jnp.moveaxis(y4.reshape(*lead, ft.ka, ft.kb, ft.kc),
                         (-3, -2, -1), (axa, axb, axc))
    info: dict = {"modes": (ft.mode_a, ft.mode_b, ft.mode_c),
                  "backend": "fused", "rows": int(x4.shape[0]),
                  "macs": ft.macs, "vmem_bytes": ft.vmem_bytes,
                  "hbm_bytes_staged": ft.hbm_bytes_staged,
                  "hbm_bytes_fused": ft.hbm_bytes_fused,
                  "hbm_savings": ft.hbm_savings}
    info.update(kinfo)
    t_a, t_b, t_c = kinfo["t_steps"]
    info["grid_steps"] = (_ceil_div(x4.shape[0], ft.bu)
                          * _ceil_div(ft.ka, ft.bka) * t_c * t_b * t_a)
    return y, info

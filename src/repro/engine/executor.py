"""Plan caching + execution — the engine's public entry points.

``gemt3_planned`` is the drop-in, data-driven counterpart of
``core.gemt.gemt3``: it builds (or fetches from the in-process plan cache) a
:class:`~repro.engine.plan.GemtPlan`, optionally autotunes per-stage block
sizes against the persisted JSON cache, and executes the three lowered
stages through the Pallas kernel dispatch.  Batched inputs (a leading batch
axis) run each stage as a single fused GEMM.

With ``mesh=``/``axes=`` the same entry point runs the TriADA distributed
schedule (paper §4–§5): the planned per-shard stages execute inside a
``shard_map`` body — Pallas/interpret kernels on the local shards, one
``psum_scatter`` per sharded-mode stage — and ``info`` splits the byte
accounting into per-shard local HBM traffic and modeled collective ICI
bytes.  See ``docs/distributed.md``.

``differentiable=True`` makes the execution boundary a ``jax.custom_vjp``
whose backward pass re-enters the engine (docs/engine.md,
"Differentiation"): the X-cotangent runs as the *adjoint plan* — another
planned GEMT over the transposed coefficients, derived from (and cached
off) the forward plan — and the three coefficient cotangents as
mode-unfolded rank-k SR-GEMM updates.  ``info`` gains ``grad_*`` fields
and ``grad_stats()`` counts the executed backward dispatch.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..core.gemt import clear_coefficient_cache
from ..kernels import ops
from ..kernels.ops import _memo_sink
from ..memo import ArrayMemo
from ..obs import metrics as _metrics
from ..obs import trace as _trace
from .autotune import (AutotuneCache, autotune_fused, autotune_fused3,
                       autotune_gemm, make_key)
from .lower import (coeff_grad_backend, lower_chain_pair, lower_chain_triple,
                    lower_coeff_grad, lower_coeff_grad_batch,
                    lower_fused_pair, lower_fused_triple,
                    lower_sharded_stage, lower_stage)
from .plan import (DEFAULT_ESOP_THRESHOLD, DEFAULT_VMEM_BUDGET,
                   AdjointChainPlan, FusedPairPlan, FusedTriplePlan,
                   GemtPlan, StagePlan, _is_traced, build_plan,
                   derive_adjoint_plan, normalize_axes, plan_adjoint_chain,
                   plan_hbm_bytes, refresh_fused_pair, refresh_fused_triple)

__all__ = [
    "plan_gemt3",
    "execute",
    "execute_with_info",
    "execute_sharded_with_info",
    "gemt3_planned",
    "clear_plan_cache",
    "invalidate_plans",
    "plan_cache_info",
    "grad_stats",
    "reset_grad_stats",
]

_PLAN_CACHE: dict[tuple, GemtPlan] = {}
_ADJ_PLAN_CACHE: dict[tuple, GemtPlan] = {}  # forward plan key -> adjoint
_CHAIN_PLAN_CACHE: dict[tuple, AdjointChainPlan] = {}  # backward walk fusion
_TUNED_PLAN_CACHE: dict[tuple, GemtPlan] = {}  # post-autotune variants
# programs: plan+cs -> [jitted shard_map, infos, info]; backward walks
# ("vjp_walk", plan key, pieces, ...) -> jitted walk
_SHARDED_FN_CACHE: dict[tuple, object] = {}
# per-array-identity digests: plan-cache hits stay cheap
_FP_MEMO = ArrayMemo(on_event=_memo_sink("memo.fingerprint."))

# Host-side proof that backward passes actually lower through the engine —
# incremented while the VJP body runs in Python, never from plan metadata.
# "kernel" counts SR-GEMM / block-ESOP / fused launches, "einsum" the
# planned fallback stages; the coeff_* split covers the three coefficient
# cotangents' rank-k updates.  The counters live in the *current* metrics
# registry under the ``grad.`` namespace (``obs.session()`` scoping
# applies); ``grad_stats``/``reset_grad_stats`` are kept as thin shims.
_GRAD_KEYS = (
    "backward_calls",
    "kernel_stages",
    "einsum_stages",
    "coeff_kernel",
    "coeff_einsum",
    "fused_launches",
)


def grad_stats() -> dict:
    """Engine-wide backward-pass dispatch counters (``grad.*`` namespace).

    Counted when the VJP's Python body runs: once per eager backward
    call, but only once per *compilation* under ``jax.jit`` (cached
    executions never re-enter Python).  The counters prove what the
    backward lowers to — kernel vs einsum dispatch — not how many jitted
    steps executed; count steps at the training loop if needed.

    Shim over the current :class:`repro.obs.MetricsRegistry` — prefer
    ``obs.get_registry().snapshot()`` for new code.
    """
    reg = _metrics.get_registry()
    return {k: reg.value("grad." + k) for k in _GRAD_KEYS}


def reset_grad_stats() -> None:
    """Zero the ``grad.*`` counters in the current registry (shim —
    prefer ``obs.get_registry().reset("grad.")``)."""
    _metrics.get_registry().reset("grad.")


def _fingerprint(c: jnp.ndarray) -> str:
    """Digest of a coefficient matrix's shape/dtype/zero structure.

    Memoized on array identity so a hot loop reusing the same coefficient
    arrays doesn't pay a device sync + full-matrix hash per call.  Tracers
    (an outer jit is planning through us) digest to a shape/dtype tag —
    consistent with the planner, whose traced plans are dense-only and
    depend on nothing else.
    """
    if isinstance(c, jax.core.Tracer):
        return f"traced:{tuple(c.shape)}:{jnp.dtype(c.dtype).name}"

    def compute():
        sp = _trace.NULL_SPAN
        if _trace.enabled():
            sp = _trace.span("plan.fingerprint",
                             {"shape": tuple(c.shape),
                              "dtype": jnp.dtype(c.dtype).name})
        with sp:
            cn = np.asarray(c)
            h = hashlib.sha1(f"{cn.shape}|{cn.dtype}".encode())
            h.update(np.packbits(cn != 0).tobytes())
            return h.hexdigest()[:16]

    return _FP_MEMO.get_or_compute(c, "fp", compute)


def clear_plan_cache() -> None:
    """Drop every cached plan and program, and ``dxt3d``'s coefficient
    matrices: a kept matrix would keep the identity-keyed memos downstream
    of it (fingerprints, ESOP schedules) warm across a cold start."""
    clear_coefficient_cache()
    _PLAN_CACHE.clear()
    _ADJ_PLAN_CACHE.clear()
    _CHAIN_PLAN_CACHE.clear()
    _TUNED_PLAN_CACHE.clear()
    _SHARDED_FN_CACHE.clear()


def _mesh_desc(mesh, axes=None, batch_axis=None):
    """Hashable mesh description used in plan-cache keys (shape + axis
    assignment; device identity is not part of the key)."""
    if mesh is None:
        return None
    return (tuple(mesh.shape.items()), normalize_axes(axes), batch_axis)


def invalidate_plans(predicate=None, *, mesh=None) -> int:
    """Selectively drop cached plans; returns how many primary entries fell.

    ``predicate(key, plan)`` picks ``_PLAN_CACHE`` entries (the cache key's
    last element is the ``_mesh_desc`` — ``None`` for single-device plans);
    ``mesh=`` is the common case and matches every plan built for a mesh of
    that shape.  Derived state — adjoint plans, autotuned variants, and the
    jitted ``shard_map`` programs whose closures capture the old mesh's
    devices — is dropped alongside its forward plan, so a re-meshed session
    (``docs/serving.md``) replans from scratch instead of dispatching onto
    dead devices.  With no arguments everything goes (a counted
    :func:`clear_plan_cache`).  Counted in ``plan.invalidations``.
    """
    if predicate is None and mesh is None:
        n = len(_PLAN_CACHE)
        clear_plan_cache()
        _metrics.inc("plan.invalidations", n)
        return n
    if predicate is None:
        shape = tuple(mesh.shape.items())

        def predicate(key, plan):
            return key[-1] is not None and key[-1][0] == shape

    dropped: set[str] = set()
    n = 0
    for key, plan in list(_PLAN_CACHE.items()):
        if predicate(key, plan):
            del _PLAN_CACHE[key]
            dropped.add(plan.key)
            n += 1
    if dropped:
        for key, adj in list(_ADJ_PLAN_CACHE.items()):
            if key[0] in dropped:
                del _ADJ_PLAN_CACHE[key]
                dropped.add(adj.key)  # tuned adjoint variants key off it
        for key in list(_CHAIN_PLAN_CACHE):
            if key[0] in dropped:
                del _CHAIN_PLAN_CACHE[key]
        for cache in (_TUNED_PLAN_CACHE, _SHARDED_FN_CACHE):
            for key in list(cache):
                pk = key[1] if key[0] == "vjp_walk" else key[0]
                if pk in dropped:
                    del cache[key]
    _metrics.inc("plan.invalidations", n)
    return n


def plan_cache_info() -> dict:
    return {"entries": len(_PLAN_CACHE), "adjoint": len(_ADJ_PLAN_CACHE),
            "chain": len(_CHAIN_PLAN_CACHE),
            "tuned": len(_TUNED_PLAN_CACHE),
            "sharded_fns": len(_SHARDED_FN_CACHE)}


def default_mode_axes(mesh, batch_axis=None) -> tuple:
    """Default per-mode axis assignment: mesh axes in order, modes beyond
    the mesh rank unsharded — e.g. a ``("data", "model")`` mesh shards
    modes 1–2 and keeps mode 3 local (the paper's single-pod placement).
    Axes claimed by ``batch_axis`` are excluded (an axis can shard only
    one dim of the stationary tensor)."""
    taken = (set() if batch_axis is None else
             set(batch_axis if isinstance(batch_axis, tuple)
                 else (batch_axis,)))
    names = tuple(a for a in mesh.axis_names if a not in taken)
    return (names + (None, None, None))[:3]


def plan_gemt3(
    x_shape: tuple[int, ...],
    x_dtype,
    c1: jnp.ndarray,
    c2: jnp.ndarray,
    c3: jnp.ndarray,
    *,
    order: tuple[int, int, int] | None = None,
    esop_threshold: float = DEFAULT_ESOP_THRESHOLD,
    block_sizes: tuple[int, int, int] | None = None,
    fuse: bool | str | None = None,  # see FUSE_MODES
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
    backend: str | None = None,  # pin every stage ("einsum"); None = auto
    accum: str | None = None,  # accumulation mode; see engine.numerics
    error_budget: float | None = None,  # max a-priori relative error bound
    mesh=None,
    axes=None,
    batch_axis=None,
) -> GemtPlan:
    """Build (or fetch) the plan for this problem; memoized in-process."""
    key = (
        tuple(x_shape), jnp.dtype(x_dtype).name,
        tuple(order) if order is not None else None,
        esop_threshold, block_sizes, fuse, vmem_budget, backend,
        accum, error_budget,
        _fingerprint(c1), _fingerprint(c2), _fingerprint(c3),
        _mesh_desc(mesh, axes, batch_axis),
    )
    plan = _PLAN_CACHE.get(key)
    if plan is None:
        sp = _trace.NULL_SPAN
        if _trace.enabled():
            sp = _trace.span("plan", {"shape": tuple(x_shape), "fuse": fuse,
                                      "vmem_budget": vmem_budget})
        with sp:
            plan = build_plan(x_shape, x_dtype, c1, c2, c3, order=order,
                              esop_threshold=esop_threshold,
                              block_sizes=block_sizes, fuse=fuse,
                              vmem_budget=vmem_budget, backend=backend,
                              accum=accum, error_budget=error_budget,
                              mesh=mesh, axes=axes,
                              batch_axis=batch_axis)
        _PLAN_CACHE[key] = plan
        _metrics.inc("plan.builds")
        fusion_events = [e for e in plan.events
                         if e.get("kind") != "numerics_degradation"]
        if fusion_events:
            _metrics.inc("plan.fusion_degradations", len(fusion_events))
        numerics_events = [e for e in plan.events
                           if e.get("kind") == "numerics_degradation"]
        if numerics_events:
            _metrics.inc("plan.numerics_degradations", len(numerics_events))
    else:
        _metrics.inc("plan.cache_hits")
    return plan


def _autotuned_plan(
    plan: GemtPlan,
    cs: dict[int, jnp.ndarray],
    batch: int,
    cache: AutotuneCache,
    use_pallas: bool | None,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
    x_dtype=jnp.float32,
) -> GemtPlan:
    """Replace each kernel stage's (and the fused pair's/triple's) tiles
    with tuned ones.

    Adjoint plans (``key`` suffix ``|adjoint`` from ``derive_adjoint_plan``)
    tune under their own autotune role: forward-tuned tiles must never
    replay for the backward's transposed problems (see ``make_key``).
    """
    adjoint = plan.key.endswith("|adjoint")
    fused_idx = (set() if plan.fused is None
                 else {plan.fused.first, plan.fused.first + 1})
    if plan.fused3 is not None:
        fused_idx = {0, 1, 2}  # the megakernel covers the whole schedule
    stages = []
    for i, st in enumerate(plan.stages):
        if st.backend == "einsum" or i in fused_idx:
            # fused stages never run their staged tiles — don't probe them
            stages.append(st)
            continue
        rows = st.rows * max(batch, 1)
        c = cs[st.mode]
        sig = _fingerprint(c)
        key = make_key(rows, st.k, st.n, c.dtype, st.backend, sig,
                       adjoint=adjoint, accum=st.accum)
        hit = cache.get(key)
        knobs_live = use_pallas is True or ops.on_tpu()
        # Warm-cache fast path (no probe allocation) — unless the entry is
        # an untuned off-TPU default and the knobs are live here.
        if hit is not None and (hit.get("tuned", True) or not knobs_live):
            bm, bn, bk = int(hit["bm"]), int(hit["bn"]), int(hit["bk"])
        else:
            probe = jnp.ones((rows, st.n), dtype=c.dtype)
            # Sharded-mode stages contract an N_s/P row slice of C; probe
            # with a representative slice so shapes match the local GEMM.
            c_arg = c if int(c.shape[0]) == st.n else c[: st.n]
            bm, bn, bk = autotune_gemm(probe, c_arg, st.backend, sig=sig,
                                       cache=cache, use_pallas=use_pallas,
                                       adjoint=adjoint, accum=st.accum)
        stages.append(dataclasses.replace(st, bm=bm, bn=bn, bk=bk))

    fused = plan.fused
    fused3 = plan.fused3
    isz = jnp.dtype(x_dtype).itemsize
    if fused3 is not None:
        ca, cb, cc = cs[fused3.mode_a], cs[fused3.mode_b], cs[fused3.mode_c]
        bu, bka, bnb, bnc = autotune_fused3(
            ca, cb, cc, rows=fused3.rows * max(batch, 1), dtype=x_dtype,
            start=(fused3.bu, fused3.bka, fused3.bnb, fused3.bnc),
            bna=fused3.bna, kbp=fused3.kbp, kcp=fused3.kcp,
            sig=":".join(_fingerprint(c) for c in (ca, cb, cc)), cache=cache,
            use_pallas=use_pallas, vmem_budget=vmem_budget, adjoint=adjoint,
            accum=fused3.accum)
        if (bu, bka, bnb, bnc) != (fused3.bu, fused3.bka, fused3.bnb,
                                   fused3.bnc):
            fused3 = refresh_fused_triple(
                dataclasses.replace(fused3, bu=bu, bka=bka, bnb=bnb,
                                    bnc=bnc),
                ca, cb, cc, batch, isz)
    if fused is not None:
        ca, cb = cs[fused.mode_a], cs[fused.mode_b]
        bu, bka, bnb = autotune_fused(
            ca, cb, rows=fused.rows * max(batch, 1), dtype=x_dtype,
            start=(fused.bu, fused.bka, fused.bnb),
            bna=fused.bna, kbp=fused.kbp,
            sig=f"{_fingerprint(ca)}:{_fingerprint(cb)}", cache=cache,
            use_pallas=use_pallas, vmem_budget=vmem_budget, adjoint=adjoint,
            accum=fused.accum)
        if (bu, bka, bnb) != (fused.bu, fused.bka, fused.bnb):
            fused = refresh_fused_pair(
                dataclasses.replace(fused, bu=bu, bka=bka, bnb=bnb),
                ca, cb, batch, isz)
    # Tuning moved tiles, so the byte model must be re-evaluated on what
    # will actually run — stale numbers describe a configuration that never
    # executes (the revisit factors depend on bm/bn and the fused tiles).
    # x's itemsize keeps the units identical to build_plan's model.
    stages_t = tuple(stages)
    return dataclasses.replace(
        plan, stages=stages_t, fused=fused, fused3=fused3,
        hbm_bytes_staged=plan_hbm_bytes(stages_t, None, batch, isz),
        hbm_bytes_moved=plan_hbm_bytes(stages_t, fused, batch, isz,
                                       fused3=fused3))


def _launches(plan: GemtPlan) -> list:
    """The plan's launches in order: the fused triple, or the fused pair at
    its first stage and one launch per other stage."""
    if plan.fused3 is not None:
        return [plan.fused3]
    out, i = [], 0
    while i < len(plan.stages):
        if plan.fused is not None and i == plan.fused.first:
            out.append(plan.fused)
            i += 2
        else:
            out.append(plan.stages[i])
            i += 1
    return out


def _launch_schedule(launch, cs: dict):
    """The ESOP schedule(s) ``launch`` reads, built host-side from concrete
    coefficients — for callers that run it where they are tracers (a
    ``shard_map`` body, a jitted backward walk).  None for dense stages."""
    esop = ops.esop_plan_cached
    if isinstance(launch, FusedTriplePlan):
        return (esop(cs[launch.mode_a], launch.bna, launch.bka),
                esop(cs[launch.mode_b], launch.bnb, launch.kbp),
                esop(cs[launch.mode_c], launch.bnc, launch.kcp))
    if isinstance(launch, FusedPairPlan):
        return (esop(cs[launch.mode_a], launch.bna, launch.bka),
                esop(cs[launch.mode_b], launch.bnb, launch.kbp))
    if launch.backend == "esop":
        return esop(cs[launch.mode], launch.bk, launch.bn)
    return None


def _run_launches(launches, y, cs: dict, *, use_pallas, mesh=None,
                  schedules=None):
    """Run ``launches`` (from :func:`_launches`, or a run of stages) on
    ``y``, yielding each launch's ``(output, info)`` as it is dispatched.

    Every executor walks its stages here: the eager forward (no
    ``schedules`` — each lowering looks its own up), the ``shard_map``
    body (``mesh`` set: a sharded-mode stage runs its local partial
    product + ``psum_scatter``) and the backward's staged pieces.  A
    generator, so the eager forward holds no intermediate past the launch
    that consumes it.
    """
    for j, ln in enumerate(launches):
        sched = None if schedules is None else schedules[j]
        if isinstance(ln, FusedTriplePlan):
            y, info = lower_fused_triple(y, cs[ln.mode_a], cs[ln.mode_b],
                                         cs[ln.mode_c], ln,
                                         use_pallas=use_pallas, plans=sched)
        elif isinstance(ln, FusedPairPlan):
            y, info = lower_fused_pair(y, cs[ln.mode_a], cs[ln.mode_b], ln,
                                       use_pallas=use_pallas, plans=sched)
        elif mesh is not None and ln.axis is not None:
            y, info = lower_sharded_stage(y, cs[ln.mode], ln, mesh,
                                          use_pallas=use_pallas)
        else:
            y, info = lower_stage(y, cs[ln.mode], ln, use_pallas=use_pallas,
                                  esop_plan=sched)
        yield y, info


def execute_with_info(
    plan: GemtPlan,
    x: jnp.ndarray,
    c1: jnp.ndarray,
    c2: jnp.ndarray,
    c3: jnp.ndarray,
    out: jnp.ndarray | None = None,
    *,
    use_pallas: bool | None = None,
) -> tuple[jnp.ndarray, dict]:
    """Run a plan; returns ``(y, info)`` with per-stage dispatch accounting.

    When the plan carries a fused pair, those two stages run as one fused
    kernel launch (``info["fused"]`` reports its modes, VMEM footprint and
    the modeled pair-traffic saving); the surrounding stages run staged.
    ``info["hbm_bytes_moved"]`` / ``"hbm_bytes_staged"`` expose the modeled
    traffic of the executed vs. the all-staged schedule.
    """
    sp = _trace.NULL_SPAN
    if _trace.enabled():
        sp = _trace.span("execute", {"order": plan.order,
                                     "backends": plan.backends,
                                     "macs": plan.macs,
                                     "hbm_bytes_moved": plan.hbm_bytes_moved,
                                     "shape": tuple(x.shape),
                                     "key": plan.key})
    with sp:
        stage_infos = []
        for y, si in _run_launches(_launches(plan), x, {1: c1, 2: c2, 3: c3},
                                   use_pallas=use_pallas):
            stage_infos.append(si)
        if out is not None:
            y = out + y
        info = _assemble_info(plan, stage_infos)
    _record_execution(info)
    return y, info


def _record_execution(info: dict) -> None:
    """Mirror one execution's ``info`` accounting into the current
    metrics registry (``engine.*`` namespace) — the counter totals stay
    in exact parity with summing the per-call ``info`` fields."""
    reg = _metrics.get_registry()
    reg.inc("engine.executions")
    reg.inc("engine.macs", info["macs"])
    reg.inc("engine.macs_effective", info["macs_effective"])
    reg.inc("engine.grid_steps", info["grid_steps"])
    reg.inc("engine.hbm_bytes_moved", info["hbm_bytes_moved"])
    reg.inc("engine.hbm_bytes_staged", info["hbm_bytes_staged"])
    reg.inc("engine.collective_bytes", info["collective_bytes"])
    for si in info["stages"]:
        backend = si.get("backend")
        if backend == "fused":
            reg.inc("engine.fused3_launches"
                    if len(si.get("modes", ())) == 3
                    else "engine.fused_launches")
        reg.inc(f"engine.stage.{backend}")


def _assemble_info(plan: GemtPlan, stage_infos: list[dict]) -> dict:
    """Shared info-dict builder for the local and sharded executors.

    Byte accounting is three-way: ``hbm_bytes_moved`` /
    ``hbm_bytes_staged`` are the modeled (per-shard, under a mesh) HBM
    traffic of the executed vs. all-staged schedule, ``hbm_bytes_local``
    aliases the executed number explicitly, and ``collective_bytes`` is
    the modeled per-device psum_scatter ICI traffic (0 on a single
    device).
    """
    fused_info = next((i for i in stage_infos if i.get("backend") == "fused"),
                      None)
    # Aggregate fetch savings over *staged* stages only: the fused pair's
    # counts live in a product space (C_a blocks × C_b slabs) whose units
    # don't sum with per-stage grids — its own savings are under
    # info["fused"]["fetch_savings"].
    staged_infos = [i for i in stage_infos if i.get("backend") != "fused"]
    dense = sum(i.get("blocks_dense", 0) for i in staged_infos)
    live = sum(i.get("blocks_live", 0) for i in staged_infos)
    return {
        "order": plan.order,
        "backends": plan.backends,  # the per-stage (staged-fallback) plan
        # what actually ran: the fused pair collapses to one entry
        "backends_executed": tuple(
            ("fused" + str(i["modes"]) if i.get("backend") == "fused"
             else i["backend"]) for i in stage_infos),
        "macs": plan.macs,
        "macs_effective": plan.macs_effective,
        # Pallas grid steps of the launches (fused ones count their own)
        "grid_steps": sum(i["grid_steps"] for i in stage_infos),
        "stages": stage_infos,
        "fused": fused_info,
        "axes": plan.axes,
        "shards": plan.shards,
        "batch_axis": plan.batch_axis,
        "hbm_bytes_staged": plan.hbm_bytes_staged,
        "hbm_bytes_moved": plan.hbm_bytes_moved,
        "hbm_bytes_local": plan.hbm_bytes_moved,
        "collective_bytes": plan.collective_bytes,
        "fetch_savings": ((1.0 - live / dense) if dense
                          else (fused_info or {}).get("fetch_savings", 0.0)),
        # Bounded ESOP-schedule memo accounting (LRU; see kernels.ops) —
        # serve telemetry uses this to prove the host-side cache behaves.
        "esop_memo": ops.esop_memo_stats(),
        # Planner events (fusion degradations) replayed from the plan —
        # present on cache hits too, so serving sees why a tier demoted.
        "events": list(plan.events),
        # Guarded-numerics accounting: the resolved accumulation mode, the
        # a-priori staged rounding bound it was held to, and any budget
        # escalations/demotions (docs/numerics.md).
        "numerics": {
            "accum": plan.accum,
            "error_bound": plan.error_bound,
            "error_budget": plan.error_budget,
            "events": [e for e in plan.events
                       if e.get("kind") == "numerics_degradation"],
        },
    }


def _spec(plan: GemtPlan, batched: bool):
    """The stationary tensor's partition spec (the same at every stage
    boundary, forward and adjoint: only N_s↔K_s extents change)."""
    return P(plan.batch_axis, *plan.axes) if batched else P(*plan.axes)


def _shard_map_program(launches, schedules, spec, mesh, use_pallas,
                       every: bool = False):
    """``shard_map`` ``launches`` over ``mesh`` (not jitted).  The program
    takes ``(x, c1, c2, c3)`` — the tensor sharded per ``spec``, the
    coefficients replicated — and returns the last launch's output, or
    with ``every`` each launch's (the backward's stage boundaries).
    ``schedules`` are :func:`_launch_schedule`'s, built before the body,
    where the coefficients are tracers.  Returns ``(fn, stage_infos)``;
    ``stage_infos`` is filled at trace time.
    """
    stage_infos: list[dict] = []

    def body(x_l, c1_l, c2_l, c3_l):
        ys, infos = zip(*_run_launches(
            launches, x_l, {1: c1_l, 2: c2_l, 3: c3_l}, use_pallas=use_pallas,
            mesh=mesh, schedules=schedules))
        stage_infos[:] = infos  # body re-traces refill, they never duplicate
        return ys if every else ys[-1]

    out_specs = (spec,) * len(launches) if every else spec
    fn = shard_map(body, mesh=mesh, in_specs=(spec, P(), P(), P()),
                   out_specs=out_specs, check_vma=False)
    return fn, stage_infos


def _sharded_callable(plan: GemtPlan, mesh, use_pallas,
                      cs: dict[int, jnp.ndarray], batched: bool):
    """Build the jitted ``shard_map`` program executing ``plan`` on ``mesh``.

    ESOP / fused-pair prefetch schedules are precomputed host-side from the
    concrete coefficient matrices *before* entering the body — inside it
    the replicated operands are tracers (traced plans carry no such stages,
    so they precompute nothing).  Returns ``(fn, stage_infos)`` where
    ``stage_infos`` is populated at trace time (all entries are static
    host-side accounting, identical for every call of this program).
    """
    launches = _launches(plan)
    fn, stage_infos = _shard_map_program(
        launches, [_launch_schedule(ln, cs) for ln in launches],
        _spec(plan, batched), mesh, use_pallas)
    return jax.jit(fn), stage_infos


def execute_sharded_with_info(
    plan: GemtPlan,
    mesh,
    x: jnp.ndarray,
    c1: jnp.ndarray,
    c2: jnp.ndarray,
    c3: jnp.ndarray,
    out: jnp.ndarray | None = None,
    *,
    use_pallas: bool | None = None,
) -> tuple[jnp.ndarray, dict]:
    """Run a mesh plan through the TriADA ``shard_map`` schedule.

    The jitted program is cached per (plan, coefficient content,
    ``use_pallas``), so serving hot loops pay neither the shard_map
    retrace nor the ESOP schedule recompute.  ``info`` matches the
    single-device executor's, with ``collective_bytes`` > 0 for sharded
    stages and all HBM numbers per-shard.
    """
    if plan.axes == (None, None, None) and plan.batch_axis is None:
        # Nothing is sharded: the shard_map program would just replicate
        # the whole computation on every device — run the local executor.
        return execute_with_info(plan, x, c1, c2, c3, out,
                                 use_pallas=use_pallas)
    # The autotuner replaces tiles without touching plan.key, so the tile
    # state must be part of the program key — a tuned plan may not reuse
    # the untuned plan's compiled stages (and vice versa).
    tiles = tuple((s.bm, s.bn, s.bk) for s in plan.stages)
    ftiles = (None if plan.fused is None else
              (plan.fused.bu, plan.fused.bka, plan.fused.bnb))
    f3tiles = (None if plan.fused3 is None else
               (plan.fused3.bu, plan.fused3.bka, plan.fused3.bnb,
                plan.fused3.bnc))
    key = (plan.key, tiles, ftiles, f3tiles, use_pallas, x.ndim,
           _fingerprint(c1), _fingerprint(c2), _fingerprint(c3))
    hit = _SHARDED_FN_CACHE.get(key)
    if hit is None:
        fn, stage_infos = _sharded_callable(
            plan, mesh, use_pallas, {1: c1, 2: c2, 3: c3},
            batched=x.ndim == 4)
        hit = [fn, stage_infos, None]  # assembled info filled post-trace
        _SHARDED_FN_CACHE[key] = hit
    fn, stage_infos, info = hit
    sp = _trace.NULL_SPAN
    if _trace.enabled():
        sp = _trace.span("execute.sharded",
                         {"order": plan.order, "backends": plan.backends,
                          "axes": tuple(str(a) for a in plan.axes),
                          "macs": plan.macs,
                          "collective_bytes": plan.collective_bytes,
                          "shape": tuple(x.shape), "key": plan.key})
    with sp:
        y = fn(x, c1, c2, c3)
        if out is not None:
            y = out + y
    if info is None:
        # stage_infos is static trace-time accounting, identical for every
        # call of this program — assemble once, not per request (the
        # serving hot loop measured the per-call dict building).
        info = _assemble_info(plan, list(stage_infos))
        hit[2] = info
    info = dict(info)
    info["esop_memo"] = ops.esop_memo_stats()  # live, not cache-frozen
    _record_execution(info)
    return y, info


def execute(plan, x, c1, c2, c3, out=None, *, use_pallas=None):
    """Run a plan, result only."""
    y, _ = execute_with_info(plan, x, c1, c2, c3, out, use_pallas=use_pallas)
    return y


# --------------------------------------------------------------------------
# Differentiation: the engine's custom VJP (the backward pass re-enters the
# engine as another planned trilinear transform — see docs/engine.md,
# "Differentiation").
# --------------------------------------------------------------------------


def _transposed(c: jnp.ndarray) -> jnp.ndarray:
    return ops.transposed_cached(c)


def _match_cotangent(t: jnp.ndarray, like: jnp.ndarray) -> jnp.ndarray:
    """Cast a cotangent to its primal's dtype (custom_vjp requires it).

    A real primal feeding a complex computation (DFT stages promote) gets
    the real part — the transpose of the real→complex embedding, matching
    jax's ``convert_element_type`` transpose rule.
    """
    if t.dtype == like.dtype:  # hot path: no-op cast still dispatches
        return t
    if (jnp.issubdtype(t.dtype, jnp.complexfloating)
            and not jnp.issubdtype(like.dtype, jnp.complexfloating)):
        t = jnp.real(t)
    return t.astype(like.dtype)


def _rebatched_plan(plan: GemtPlan, batch: int, isz: int) -> GemtPlan:
    """Re-evaluate a plan's byte model for a different batch size.

    Stage schedules are batch-independent (``StagePlan.rows`` excludes the
    batch axis; the executors fold the actual batch in at dispatch), so a
    plan built for one batch size executes correctly for any other — only
    the modeled ``hbm_bytes_*`` totals scale with the batch.  The serving
    layer's batched-entry reuse (``DxtServeSession.warmup``) plans once
    per *bucket* and rescales here, so coalesced launches of varying size
    never rebuild a plan.
    """
    return dataclasses.replace(
        plan,
        hbm_bytes_staged=plan_hbm_bytes(plan.stages, None, batch, isz),
        hbm_bytes_moved=plan_hbm_bytes(plan.stages, plan.fused, batch, isz,
                                       fused3=plan.fused3))


def _tuned_plan(plan: GemtPlan, cs: dict[int, jnp.ndarray], batch: int,
                autotune_cache, use_pallas, vmem_budget: int,
                x_dtype) -> GemtPlan:
    """Memoized autotuned variant of ``plan`` (forward and adjoint share
    this path, so adjoint shapes hit the same JSON cache)."""
    cache = (autotune_cache if isinstance(autotune_cache, AutotuneCache)
             else AutotuneCache(autotune_cache))
    # Memoize the tuned variant: a warm hot loop must not pay the cache
    # probes + fused-mask refresh (a device pad + host sync) per call.
    # plan.key only digests the zero *structure*, so the content
    # fingerprints are added — different coefficient matrices of identical
    # sparsity must still tune under their own sigs.
    tkey = (plan.key, cache.path, batch, use_pallas,
            _fingerprint(cs[1]), _fingerprint(cs[2]), _fingerprint(cs[3]))
    tuned = _TUNED_PLAN_CACHE.get(tkey)
    if tuned is None:
        sp = _trace.NULL_SPAN
        if _trace.enabled():
            sp = _trace.span("autotune.plan",
                             {"key": plan.key, "batch": batch})
        with sp:
            tuned = _autotuned_plan(plan, cs, batch, cache, use_pallas,
                                    vmem_budget=vmem_budget, x_dtype=x_dtype)
        _TUNED_PLAN_CACHE[tkey] = tuned
        _metrics.inc("plan.tuned_builds")
    return tuned


def _adjoint_plan(plan: GemtPlan, g_shape, g_dtype,
                  cts: dict[int, jnp.ndarray], *, esop_threshold, block_sizes,
                  fuse, vmem_budget, mesh) -> GemtPlan:
    """Derive (or fetch) the adjoint plan keyed off the forward plan."""
    key = (plan.key, tuple(g_shape), jnp.dtype(g_dtype).name, esop_threshold,
           block_sizes, fuse, vmem_budget,
           _fingerprint(cts[1]), _fingerprint(cts[2]), _fingerprint(cts[3]))
    adj = _ADJ_PLAN_CACHE.get(key)
    if adj is None:
        sp = _trace.NULL_SPAN
        if _trace.enabled():
            sp = _trace.span("plan.adjoint",
                             {"key": plan.key, "shape": tuple(g_shape)})
        with sp:
            adj = derive_adjoint_plan(plan, g_shape, g_dtype, cts[1], cts[2],
                                      cts[3], esop_threshold=esop_threshold,
                                      block_sizes=block_sizes, fuse=fuse,
                                      vmem_budget=vmem_budget, mesh=mesh)
        _ADJ_PLAN_CACHE[key] = adj
        _metrics.inc("plan.adjoint_builds")
    return adj


def _chain_plan(plan: GemtPlan, adj: GemtPlan, g_shape, g_dtype, fuse,
                vmem_budget) -> AdjointChainPlan:
    """Derive (or fetch) the backward walk's fusion schedule.

    Shared by the backward executor and the forward-time ``grad_*``
    accounting, so both see the *same* decision.  Keyed off the **untuned**
    adjoint plan — the chain tiles come from the chain's own VMEM ladder,
    not the per-stage autotuner, and the byte-model comparison must not
    flip between the info prediction and the execution.
    """
    key = (plan.key, adj.key, tuple(g_shape), jnp.dtype(g_dtype).name,
           fuse, vmem_budget)
    chain = _CHAIN_PLAN_CACHE.get(key)
    if chain is None:
        sp = _trace.NULL_SPAN
        if _trace.enabled():
            sp = _trace.span("plan.adjoint_chain",
                             {"key": plan.key, "shape": tuple(g_shape)})
        with sp:
            chain = plan_adjoint_chain(plan, adj, g_shape, g_dtype,
                                       fuse=fuse, vmem_budget=vmem_budget)
        _CHAIN_PLAN_CACHE[key] = chain
        _metrics.inc("plan.adjoint_chain_builds")
        if chain.events:
            _metrics.inc("plan.adjoint_fusion_degradations",
                         len(chain.events))
    return chain


def _kernels_live(use_pallas, *arrays) -> bool:
    """Would the chain ops dispatch the Pallas path for these operands?"""
    return ((use_pallas is True or (use_pallas is None and ops.on_tpu()))
            and not any(jnp.issubdtype(a.dtype, jnp.complexfloating)
                        for a in arrays))


class _Piece(NamedTuple):
    """One launch of the backward walk (:func:`_backward_pieces`)."""

    kind: str  # grad_recompute | grad_x | grad_chain | coeff_grad
    backend: str  # fused | sr_gemm | esop | einsum
    modes: tuple
    stage: StagePlan | None = None  # a staged launch: the stage it runs
    tiles: tuple | None = None  # a chain launch: the chain plan's tiles

    @property
    def label(self) -> str:
        return f"{self.kind}:{self.backend}:m{''.join(map(str, self.modes))}"


def _coeff_rows(plan: GemtPlan, batch: int, mode: int) -> int:
    """Rows of dC_s's rank-k product: every non-s extent of the stage's
    input (modes contracted before s at K, after it at N) times the
    batch."""
    rows = batch
    for m in (1, 2, 3):
        if m != mode:
            before = plan.order.index(m) < plan.order.index(mode)
            rows *= (plan.out_shape if before else plan.in_shape)[m - 1]
    return rows


def _backward_pieces(plan: GemtPlan, adj: GemtPlan, chain: AdjointChainPlan,
                     g_shape, g_dtype, *, sharded: bool,
                     traced: bool) -> tuple:
    """The backward's launches, in order — what the walk runs, what
    ``info["grad_*"]`` predicts and what the ``grad.*`` counters count.

    Three phases (docs/engine.md, "Differentiation"):

    1. *recompute* ``y1, y2`` (residuals are just ``(x, C_s)``): one chain
       pair when ``chain.rec_fused``, else the staged prefix
       ``plan.stages[:-1]``;
    2. *adjoint* ``dX = g ×C₃ᵀ ×C₂ᵀ ×C₁ᵀ`` with the boundary cotangents
       ``g1, g2``: a chain triple (depth 3), a chain pair plus one staged
       tail (depth 2), or the staged chain over ``adj.stages``;
    3. *coefficient cotangents* ``dC_s = unfold(y_{i-1})ᵀ @ unfold(g_i)``:
       one batched launch (fused walk), one per mode (staged), or one
       global einsum program (mesh — its operands are global sharded
       arrays, where only ``dot_general`` partitions).

    The fused walk needs ``chain.depth >= 2`` and neither a mesh (each
    sharded stage needs its ``psum_scatter``) nor traced inputs (its
    programs are built around host-side ESOP schedules).
    """
    fused = chain.depth >= 2 and not (sharded or traced)

    def staged(kind, stages):
        return [_Piece(kind, st.backend, (st.mode,), stage=st)
                for st in stages]

    if fused and chain.rec_fused:
        pieces = [_Piece("grad_recompute", "fused", chain.rec_modes,
                         tiles=chain.rec_tiles)]
    else:
        pieces = staged("grad_recompute", plan.stages[:-1])
    if not fused:
        pieces += staged("grad_x", adj.stages)
    elif chain.depth == 3:
        pieces.append(_Piece("grad_x", "fused", chain.modes,
                             tiles=chain.tiles))
    else:
        pieces.append(_Piece("grad_x", "fused", chain.modes[:2],
                             tiles=chain.tiles))
        pieces += staged("grad_chain", adj.stages[2:])
    if fused:
        pieces.append(_Piece("coeff_grad", "fused", plan.order))
    elif sharded:
        pieces.append(_Piece("coeff_grad", "einsum", plan.order))
    else:
        batch = int(g_shape[0]) if len(g_shape) == 4 else 1
        pieces += [_Piece("coeff_grad", coeff_grad_backend(
            _coeff_rows(plan, batch, m), plan.in_shape[m - 1],
            plan.out_shape[m - 1], g_dtype), (m,)) for m in plan.order]
    return tuple(pieces)


def _piece_schedule(p: _Piece, cs: dict, cts: dict, use_pallas):
    """The ESOP schedule a piece's launch reads, built host-side (inside
    the walk's program its coefficients are tracers)."""
    coeffs = cs if p.kind == "grad_recompute" else cts
    if p.stage is not None:
        return _launch_schedule(p.stage, coeffs)
    if p.tiles is not None and _kernels_live(
            use_pallas, *(coeffs[m] for m in p.modes)):
        bna = p.tiles[3 if len(p.modes) == 2 else 4]
        return ops.esop_plan_cached(coeffs[p.modes[0]], bna, p.tiles[1])
    return None


def _walk(pieces: tuple, schedules: tuple, x, g, cs: dict, cts: dict, *,
          order, mesh, spec, use_pallas) -> tuple:
    """Run the backward's pieces.  Returns ``(dx, dcs)``.

    ``ys`` collects the forward stage inputs ``[x, y1, y2]`` and ``gs``
    the cotangent chain ``[g, g1, g2, dX]``; a chain launch emits its
    intermediates, and a run of staged pieces goes through the forward's
    launch loop — inside a ``shard_map`` program under a mesh.
    """
    ys, gs, dcs = [x], [g], {}
    i = 0
    while i < len(pieces):
        p = pieces[i]
        if p.kind == "coeff_grad":
            if p.backend == "fused":
                dcs.update(zip(order, lower_coeff_grad_batch(
                    ys[:3], gs[2::-1], order, use_pallas=use_pallas)))
            else:
                for m in p.modes:
                    j = order.index(m)
                    dcs[m] = lower_coeff_grad(ys[j], gs[2 - j], m,
                                              use_pallas=use_pallas,
                                              backend=p.backend)[0]
            i += 1
            continue
        out, coeffs = (ys, cs) if p.kind == "grad_recompute" else (gs, cts)
        if p.stage is None:  # one chain launch: (y, y1[, y2])
            m = p.modes
            if len(m) == 3:
                res = lower_chain_triple(
                    out[-1], coeffs[m[0]], coeffs[m[1]], coeffs[m[2]], *m,
                    p.tiles, use_pallas=use_pallas, plan_a=schedules[i])
            else:
                res = lower_chain_pair(
                    out[-1], coeffs[m[0]], coeffs[m[1]], *m, p.tiles,
                    use_pallas=use_pallas, plan_a=schedules[i])
            out += [*res[1:], res[0]]
            i += 1
            continue
        j = i
        while (j < len(pieces) and pieces[j].stage is not None
               and pieces[j].kind == p.kind):
            j += 1
        stages = [q.stage for q in pieces[i:j]]
        if mesh is None:
            out += [y for y, _ in _run_launches(
                stages, out[-1], coeffs, use_pallas=use_pallas,
                schedules=schedules[i:j])]
        else:
            fn, _ = _shard_map_program(stages, schedules[i:j], spec, mesh,
                                       use_pallas, every=True)
            out += fn(out[-1], coeffs[1], coeffs[2], coeffs[3])
        i = j
    return gs[3], dcs


def _count_grad_dispatch(pieces) -> dict:
    counts = {"kernel_stages": 0, "einsum_stages": 0, "coeff_kernel": 0,
              "coeff_einsum": 0, "fused_launches": 0}
    for p in pieces:
        kernel = p.backend != "einsum"
        if p.kind == "coeff_grad":
            counts["coeff_kernel" if kernel else "coeff_einsum"] += 1
            continue
        if p.backend == "fused":
            counts["fused_launches"] += 1
        counts["kernel_stages" if kernel else "einsum_stages"] += 1
    return counts


def _is_sharded(plan: GemtPlan) -> bool:
    return (any(a is not None for a in plan.axes)
            or plan.batch_axis is not None)


def _vjp_backward(plan: GemtPlan, mesh, x, c1, c2, c3, g, *, use_pallas,
                  esop_threshold, block_sizes, fuse, vmem_budget,
                  autotune, autotune_cache):
    """The custom-VJP backward: re-enters the engine and returns the four
    cotangents ``(dx, dc1, dc2, dc3)``.

    Runs :func:`_backward_pieces` through :func:`_walk`: with concrete
    inputs as one cached jitted program (on TPU every ``pallas_call``
    inside it is still its own launch), under an outer trace inline.
    Which pieces run depends on the plans and the inputs only — never on
    whether a tracer, fault hook or profiler is watching; the
    ``vjp.backward`` span lists them (``pieces``).
    """
    sp = _trace.NULL_SPAN
    if _trace.enabled():
        sp = _trace.span("vjp.backward",
                         {"key": plan.key, "shape": tuple(g.shape),
                          "sharded": mesh is not None})
    with sp:
        cs = {1: c1, 2: c2, 3: c3}
        cts = {m: _transposed(cs[m]) for m in (1, 2, 3)}
        adj = _adjoint_plan(plan, g.shape, g.dtype, cts,
                            esop_threshold=esop_threshold,
                            block_sizes=block_sizes, fuse=fuse,
                            vmem_budget=vmem_budget, mesh=mesh)
        # The chain plan derives from the untuned adjoint (see _chain_plan)
        # so the forward-time grad_* prediction and the execution agree.
        chain = _chain_plan(plan, adj, g.shape, g.dtype, fuse, vmem_budget)
        if autotune and not _is_traced(c1, c2, c3):
            batch = ((int(g.shape[0]) if g.ndim == 4 else 1)
                     // max(adj.batch_shards, 1))
            adj = _tuned_plan(adj, cts, batch, autotune_cache, use_pallas,
                              vmem_budget, g.dtype)
        sharded = mesh is not None and _is_sharded(plan)
        traced = _is_traced(x, g, c1, c2, c3)
        pieces = _backward_pieces(plan, adj, chain, g.shape, g.dtype,
                                  sharded=sharded, traced=traced)
        if sp:
            sp.set(pieces=[p.label for p in pieces])
        key = ("vjp_walk", plan.key, pieces, use_pallas, x.ndim,
               _fingerprint(c1), _fingerprint(c2), _fingerprint(c3))
        fn = None if traced else _SHARDED_FN_CACHE.get(key)
        if fn is None:
            fn = functools.partial(
                _walk, pieces,
                tuple(_piece_schedule(p, cs, cts, use_pallas)
                      for p in pieces),
                order=plan.order, mesh=mesh if sharded else None,
                spec=_spec(plan, x.ndim == 4), use_pallas=use_pallas)
            if not traced:
                fn = _SHARDED_FN_CACHE[key] = jax.jit(fn)
        dx, dcs = fn(x, g, cs, cts)
        _metrics.inc("grad.backward_calls")
        for k, v in _count_grad_dispatch(pieces).items():
            _metrics.inc("grad." + k, v)
        return (_match_cotangent(dx, x),
                _match_cotangent(dcs[1], c1),
                _match_cotangent(dcs[2], c2),
                _match_cotangent(dcs[3], c3))


def _grad_info_fields(plan: GemtPlan, adj: GemtPlan,
                      chain: AdjointChainPlan, g_shape, g_dtype) -> dict:
    """Forward-time ``grad_*`` accounting: what the backward pass will run.

    Derived from the (cached) adjoint + chain plans, so ``info`` can prove
    — before any gradient is pulled — that the backward lowers through the
    engine (nonzero kernel counters, no silent einsum fallback on
    kernel-capable shapes).  The counts come from the same
    :func:`_backward_pieces` list the backward runs and counts, so one
    backward call with concrete inputs moves the ``grad.*`` counters by
    exactly these amounts.  (A backward pulled under an outer jit runs the
    staged pieces instead.)
    """
    pieces = _backward_pieces(plan, adj, chain, g_shape, g_dtype,
                              sharded=_is_sharded(plan), traced=False)
    fused_walk = pieces[-1].backend == "fused"
    coeff = {m: p.backend for p in pieces if p.kind == "coeff_grad"
             for m in p.modes}
    batch = int(g_shape[0]) if len(g_shape) == 4 else 1
    counts = _count_grad_dispatch(pieces)
    return {
        "grad_order": adj.order,
        "grad_backends": adj.backends,
        "grad_backends_executed": tuple(
            "fused" + str(p.modes) if p.backend == "fused" else p.backend
            for p in pieces if p.kind in ("grad_x", "grad_chain")),
        "grad_coeff_backends": tuple(coeff[m] for m in (1, 2, 3)),
        "grad_kernel_stages": counts["kernel_stages"],
        "grad_einsum_stages": counts["einsum_stages"],
        "grad_coeff_kernel": counts["coeff_kernel"],
        "grad_coeff_einsum": counts["coeff_einsum"],
        "grad_fused_launches": counts["fused_launches"],
        "grad_launches": len(pieces),
        "grad_chain_depth": chain.depth if fused_walk else 0,
        "grad_rec_fused": fused_walk and chain.rec_fused,
        "grad_fused": fused_walk,
        "grad_events": list(chain.events),
        "grad_macs": adj.macs + sum(
            _coeff_rows(plan, batch, m) * plan.in_shape[m - 1]
            * plan.out_shape[m - 1] for m in (1, 2, 3)),
        "grad_hbm_bytes_moved": (chain.hbm_bytes_fused if fused_walk
                                 else adj.hbm_bytes_staged),
        "grad_collective_bytes": adj.collective_bytes,
    }


def _execute_differentiable(plan: GemtPlan, mesh, x, c1, c2, c3, *,
                            use_pallas, grad_opts: dict):
    """Run ``plan`` under the engine's custom VJP.  Returns ``(y, info)``.

    The primal is the ordinary executor; the backward re-enters the engine
    (``_vjp_backward``): the X-cotangent as the derived adjoint plan over
    ``C_sᵀ`` (planned GEMT — staged/pair/triple fusion, ESOP, autotune all
    apply) and the coefficient cotangents as mode-unfolded rank-k SR-GEMM
    updates.  ``info`` gains the forward-time ``grad_*`` fields.
    """
    info_cell: dict = {}

    def prim(x, c1, c2, c3):
        if mesh is not None:
            y, info = execute_sharded_with_info(plan, mesh, x, c1, c2, c3,
                                                use_pallas=use_pallas)
        else:
            y, info = execute_with_info(plan, x, c1, c2, c3,
                                        use_pallas=use_pallas)
        info_cell.update(info)
        return y

    @jax.custom_vjp
    def f(x, c1, c2, c3):
        return prim(x, c1, c2, c3)

    def bwd(res, g):
        xr, c1r, c2r, c3r = res
        return _vjp_backward(plan, mesh, xr, c1r, c2r, c3r, g,
                             use_pallas=use_pallas, **grad_opts)

    f.defvjp(lambda x, c1, c2, c3: (prim(x, c1, c2, c3), (x, c1, c2, c3)),
             bwd)
    y = f(x, c1, c2, c3)
    info = dict(info_cell)
    # Forward-time grad accounting: derive the adjoint plan now (cached —
    # the backward reuses it) so info proves what the VJP will lower.
    g_shape = plan.out_shape if x.ndim == 3 else (x.shape[0],) + plan.out_shape
    g_dtype = jnp.result_type(x.dtype, c1.dtype)
    cts = {m: _transposed(c) for m, c in ((1, c1), (2, c2), (3, c3))}
    adj = _adjoint_plan(plan, g_shape, g_dtype, cts,
                        esop_threshold=grad_opts["esop_threshold"],
                        block_sizes=grad_opts["block_sizes"],
                        fuse=grad_opts["fuse"],
                        vmem_budget=grad_opts["vmem_budget"], mesh=mesh)
    chain = _chain_plan(plan, adj, g_shape, g_dtype, grad_opts["fuse"],
                        grad_opts["vmem_budget"])
    info.update(_grad_info_fields(plan, adj, chain, g_shape, g_dtype))
    return y, info


def gemt3_planned(
    x: jnp.ndarray,
    c1: jnp.ndarray,
    c2: jnp.ndarray,
    c3: jnp.ndarray,
    *,
    out: jnp.ndarray | None = None,  # keyword-only: gemt3's 5th positional
    order: tuple[int, int, int] | None = None,  # is `order`, not `out`
    esop_threshold: float = DEFAULT_ESOP_THRESHOLD,
    block_sizes: tuple[int, int, int] | None = None,
    fuse: bool | str | None = None,  # see FUSE_MODES
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
    backend: str | None = None,  # pin every stage ("einsum"); None = auto
    accum: str | None = None,  # "plain" | "f32" | "compensated"
    error_budget: float | None = None,  # max a-priori relative error bound
    autotune: bool = False,
    autotune_cache: AutotuneCache | str | None = None,
    use_pallas: bool | None = None,
    with_info: bool = False,
    differentiable: bool = False,
    mesh=None,
    axes=None,
    batch_axis=None,
    batch_bucket: int | None = None,
):
    """Planned three-mode GEMT ẍ = X ×₁C1 ×₂C2 ×₃C3 (+ out).

    Numerically equivalent to :func:`repro.core.gemt.gemt3` (any order gives
    the same result up to float rounding) but the stage order, per-stage
    dense/block-sparse backend, stage fusion and kernel tile sizes are
    chosen by the cost model instead of hard-coded.  ``fuse=None``
    auto-selects the deepest fusion that models the fewest HBM bytes —
    the whole-transform megakernel (all three contractions in one launch,
    both intermediates resident in VMEM) when its tiles fit
    ``vmem_budget``, degrading to the fused pair and then to staged;
    ``"pair"``/``"triple"`` pin the depth, ``True`` forces the deepest
    feasible, ``False`` stages everything.  ``backend="einsum"`` pins
    every stage to the XLA einsum lowering (fusion off, no Pallas) — the
    serving runtime's last-resort degradation tier (``docs/serving.md``);
    the pin applies to the forward plan (the adjoint keeps its own backend
    choice).  ``x`` may carry a leading batch axis.

    ``accum`` selects the guarded-numerics accumulation mode
    (``"plain"``/``"f32"``/``"compensated"`` — docs/numerics.md): ``"f32"``
    keeps float32 partials through every stage boundary, ``"compensated"``
    adds a Neumaier-compensated reduction in the kernels.  ``error_budget``
    holds the plan's a-priori staged rounding bound to a ceiling — the
    planner escalates the accumulation mode (and, through the VMEM
    footprint, may demote fusion depth) until the bound fits, recording
    ``numerics_degradation`` events; ``info["numerics"]`` reports the
    resolved mode and bound.

    ``mesh`` switches to the TriADA distributed schedule: ``x`` (global)
    is sharded per ``axes`` (default: mesh axes in order, e.g.
    ``("data", "model", None)`` on a 2-axis mesh; ``batch_axis``
    optionally shards a leading batch dim), coefficients are replicated,
    and the planned per-shard stages run inside one ``shard_map`` program
    — shard-local stages on the Pallas kernel dispatch, sharded-mode
    stages as local partial products combined by ``psum_scatter``.  The
    result matches the single-device path up to float reduction order.
    Traced coefficients (calling this under an outer ``jit``) degrade
    planning to dense sr_gemm/einsum backends and skip autotuning — zero
    structure is unreadable from a tracer.

    ``batch_bucket`` (single-device, 4-D inputs) plans and autotunes as if
    the leading batch axis had the bucket's size: stage schedules are
    batch-independent, so every batch size that maps to the same bucket
    reuses one plan-cache entry and one tuned variant, and only the byte
    model is re-evaluated for the actual batch.  This is the engine half
    of the serving layer's shape-bucketed warmup + request coalescing
    (``docs/serving.md``, "Throughput") — a warmed bucket's coalesced
    launches pay zero plan/probe work regardless of how many requests were
    stacked.

    ``differentiable=True`` wraps the execution in the engine's custom VJP
    (docs/engine.md, "Differentiation"): ``jax.grad``/``jax.vjp`` then
    lower the backward pass *through the engine* — the X-cotangent as the
    derived adjoint plan (another planned GEMT over the transposed
    coefficients, with the same fusion tiers / ESOP schedules / autotune
    caches) and the three coefficient cotangents as mode-unfolded rank-k
    SR-GEMM updates.  ``info`` gains ``grad_*`` fields describing the
    planned backward; ``grad_stats()`` counts executed backward passes.
    """
    if mesh is not None:
        from ..launch.mesh import auto_axes

        # The schedule places its own collectives; on Auto axes its output
        # stays an ordinary sharded array that callers may reshape freely.
        mesh = auto_axes(mesh)
        if axes is None:
            axes = default_mode_axes(mesh, batch_axis)
    # Batched-entry plan reuse: ``batch_bucket`` plans (and tunes) as if the
    # batch were the bucket size, so coalesced launches of varying batch
    # share one plan-cache entry — the serving layer's warmed buckets
    # (docs/serving.md, "Throughput").  Single-device only: under a mesh
    # the per-shard batch is part of the schedule.
    plan_shape = tuple(x.shape)
    if (batch_bucket is not None and mesh is None and x.ndim == 4
            and int(batch_bucket) != int(x.shape[0])):
        plan_shape = (int(batch_bucket),) + tuple(x.shape[1:])
    plan = plan_gemt3(plan_shape, x.dtype, c1, c2, c3, order=order,
                      esop_threshold=esop_threshold, block_sizes=block_sizes,
                      fuse=fuse, vmem_budget=vmem_budget, backend=backend,
                      accum=accum, error_budget=error_budget,
                      mesh=mesh, axes=axes, batch_axis=batch_axis)
    if autotune and not _is_traced(c1, c2, c3):
        # Per-shard batch: the tuned tiles must see the local GEMM rows
        # (the bucket batch when bucketed, so tuned variants are shared).
        batch = ((plan_shape[0] if len(plan_shape) == 4 else 1)
                 // max(plan.batch_shards, 1))
        plan = _tuned_plan(plan, {1: c1, 2: c2, 3: c3}, batch,
                           autotune_cache, use_pallas, vmem_budget, x.dtype)
    if plan_shape != tuple(x.shape):
        plan = _rebatched_plan(plan, int(x.shape[0]),
                               jnp.dtype(x.dtype).itemsize)
    if differentiable:
        y, info = _execute_differentiable(
            plan, mesh, x, c1, c2, c3, use_pallas=use_pallas,
            grad_opts=dict(esop_threshold=esop_threshold,
                           block_sizes=block_sizes, fuse=fuse,
                           vmem_budget=vmem_budget, autotune=autotune,
                           autotune_cache=autotune_cache))
        if out is not None:
            y = out + y  # differentiates natively: d(out) = g
        return (y, info) if with_info else y
    if mesh is not None:
        y, info = execute_sharded_with_info(plan, mesh, x, c1, c2, c3, out,
                                            use_pallas=use_pallas)
    else:
        y, info = execute_with_info(plan, x, c1, c2, c3, out,
                                    use_pallas=use_pallas)
    return (y, info) if with_info else y

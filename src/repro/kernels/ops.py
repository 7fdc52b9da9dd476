"""jit'd public wrappers around the Pallas kernels.

Handle padding to block multiples, dtype policy, and the CPU/TPU dispatch:
on a TPU backend the kernels run compiled; elsewhere they run in
``interpret=True`` mode (bit-faithful emulation) unless ``use_pallas=False``
routes to the jnp reference (the default inside the big-model dry-run, where
interpret-mode loops would bloat compile times).  Paper anchor: §5–§6
(streaming outer-product cell array + ESOP skipping); the engine-facing
contract is documented in ``docs/engine.md`` ("Lowering").
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from ..memo import ArrayMemo
from ..obs import metrics as _metrics
from ..obs import trace as _trace
from . import ref
from .attention import flash_attention_pallas
from .esop_gemm import esop_gemm_pallas, esop_plan
from .fused3_gemt import fused3_gemt_pallas
from .fused_chain import (chain3_gemt_pallas, chain_gemt_pallas,
                          coeff_grad_batch_pallas)
from .fused_gemt import fused_gemt_pallas, kb_padded
from .sr_gemm import sr_gemm_pallas

__all__ = ["sr_gemm", "esop_gemm", "fused_gemt", "fused3_gemt",
           "chain_gemt", "chain3_gemt", "coeff_grad_batch",
           "flash_attention", "esop_plan_cached", "esop_memo_stats",
           "set_esop_memo_size", "transposed_cached", "on_tpu"]

# Host-side ESOP schedules are memoized per coefficient-matrix identity.
# Long-running serve sessions stream *distinct* matrices through, so the
# memo is LRU-bounded (satellite of the differentiable-engine PR); the knob
# is REPRO_ESOP_MEMO_SIZE (entries, default 256) or set_esop_memo_size().
_ESOP_MEMO_DEFAULT = int(os.environ.get("REPRO_ESOP_MEMO_SIZE", "256"))


def _memo_sink(prefix: str):
    """Mirror a memo's hit/miss/evict events into the *current* metrics
    registry (resolved per event, so ``obs.session()`` scoping applies)."""
    def sink(event: str) -> None:
        _metrics.inc(prefix + event)
    return sink


_ESOP_PLAN_MEMO = ArrayMemo(maxsize=_ESOP_MEMO_DEFAULT,
                            on_event=_memo_sink("memo.esop."))
# Adjoint reuse: the VJP paths contract against C^T.  Recomputing the
# transpose per backward call would give it a fresh identity every time and
# defeat every identity-keyed memo downstream (esop plans, fingerprints,
# plan caches) — so the transpose itself is memoized on C's identity.
_TRANSPOSED_MEMO = ArrayMemo(maxsize=_ESOP_MEMO_DEFAULT,
                             on_event=_memo_sink("memo.transposed."))


def esop_memo_stats() -> dict:
    """Hit/miss/evict accounting of the bounded ESOP-schedule memo.

    Surfaced in the engine's ``info["esop_memo"]`` so serve telemetry can
    prove the schedule cache is neither thrashing nor growing unbounded.
    """
    return {"entries": len(_ESOP_PLAN_MEMO),
            "maxsize": _ESOP_PLAN_MEMO.maxsize,
            **_ESOP_PLAN_MEMO.stats}


def set_esop_memo_size(maxsize: int | None) -> None:
    """Re-bound the ESOP-schedule (and transpose) memos; LRU-evicts now."""
    _ESOP_PLAN_MEMO.set_maxsize(maxsize)
    _TRANSPOSED_MEMO.set_maxsize(maxsize)


def transposed_cached(c: jnp.ndarray) -> jnp.ndarray:
    """``C^T`` memoized on C's identity (tracers transpose uncached).

    The adjoint of every GEMT stage contracts against the transposed
    coefficient matrix; returning the *same* transposed array object per
    forward matrix keeps the identity-keyed ESOP/plan/fingerprint memos hot
    across backward passes.
    """
    if isinstance(c, jax.core.Tracer):
        return jnp.swapaxes(c, 0, 1)
    return _TRANSPOSED_MEMO.get_or_compute(
        c, "T", lambda: jnp.swapaxes(c, 0, 1))


def esop_plan_cached(c: jnp.ndarray, bk: int, bn: int):
    """Padded block-ESOP schedule for C, memoized on C's identity.

    Returns ``(counts, idx, t_steps, stats)``: the scalar-prefetch operands
    as device arrays plus the host-side accounting dict.  The ``esop_plan``
    sweep (a device sync + block compaction) and the host→device upload run
    once per distinct ``(C, block)`` — not once per call — so hot loops
    reusing the same coefficient matrices pay nothing, on the reference
    *and* the Pallas path alike.
    """
    def compute():
        sp = _trace.NULL_SPAN
        if _trace.enabled():  # memo misses only: the sweep + upload cost
            sp = _trace.span("esop.plan",
                             {"shape": tuple(c.shape), "bk": bk, "bn": bn})
        with sp:
            cp = _pad_to(c, (bk, bn))
            counts, idx, t_steps = esop_plan(cp, bk, bn)
            dense_blocks = (cp.shape[0] // bk) * (cp.shape[1] // bn)
            live_blocks = int(counts.sum())
            stats = {
                "blocks_dense": dense_blocks,
                "blocks_live": live_blocks,
                "fetch_savings": 1.0 - live_blocks / max(dense_blocks, 1),
                "t_steps": t_steps,
                "t_steps_dense": cp.shape[0] // bk,
            }
            return jnp.asarray(counts), jnp.asarray(idx), t_steps, stats

    return _ESOP_PLAN_MEMO.get_or_compute(c, (bk, bn), compute)


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_to(x: jnp.ndarray, mults: tuple[int, ...]) -> jnp.ndarray:
    pads = [(0, (-d) % m) for d, m in zip(x.shape, mults)]
    if any(p[1] for p in pads):
        return jnp.pad(x, pads)
    return x


def _is_traced(*arrays) -> bool:
    return any(isinstance(a, jax.core.Tracer) for a in arrays)


_ACCUM_MODES = ("plain", "f32", "compensated")


def _norm_accum(accum, *arrays) -> str:
    """Default/validate an ``accum`` knob; complex operands force
    ``"plain"`` (the kernels and the compensation algebra are real-valued —
    the planner pins complex stages to einsum anyway)."""
    accum = "plain" if accum is None else accum
    if accum not in _ACCUM_MODES:
        raise ValueError(
            f"accum must be one of {_ACCUM_MODES} (or None), got {accum!r}")
    if accum != "plain" and any(
            a is not None and jnp.iscomplexobj(a) for a in arrays):
        return "plain"
    return accum


def _linear_custom_vjp(prim, bwd_x, bwd_c, x, c, out):
    """Wrap the bilinear kernel dispatch ``prim(x, c, out)`` in a custom VJP.

    ``pallas_call`` defines no differentiation rule, so without this any
    ``jax.grad`` touching the kernel dispatch would fail (compiled) or
    differentiate through kernel internals (interpret mode).  The wrapper
    makes every public op VJP-safe: the backward GEMMs re-enter the same
    kernel dispatch (``bwd_x``/``bwd_c`` callables), so a gradient never
    silently leaves the kernel path.  ``out``'s cotangent is ``g`` itself
    (the affine seed adds straight through, Eq. 1's ``+=``).

    Built per call because ESOP's ``prim`` closes over unhashable
    prefetch-plan device arrays; SR-GEMM, the forward hot path, gets the
    memoized :func:`_sr_gemm_vjp` factory instead.

    Cotangents are cast back to the primal dtypes: under a promoted
    ``accum`` the forward output (hence ``g``) is float32 while the
    operands may be bf16 — ``custom_vjp`` requires matching avals.  The
    casts are identities on the plain path.
    """
    if out is None:
        @jax.custom_vjp
        def f(x, c):
            return prim(x, c, None)

        f.defvjp(lambda x, c: (prim(x, c, None), (x, c)),
                 lambda res, g: (bwd_x(g, res[1]).astype(res[0].dtype),
                                 bwd_c(res[0], g).astype(res[1].dtype)))
        return f(x, c)

    odt = out.dtype

    @jax.custom_vjp
    def fo(x, c, out):
        return prim(x, c, out)

    fo.defvjp(lambda x, c, out: (prim(x, c, out), (x, c)),
              lambda res, g: (bwd_x(g, res[1]).astype(res[0].dtype),
                              bwd_c(res[0], g).astype(res[1].dtype),
                              g.astype(odt)))
    return fo(x, c, out)


def _sr_dispatch(x: jnp.ndarray, c: jnp.ndarray, out: jnp.ndarray | None,
                 bm: int, bn: int, bk: int, use_pallas: bool,
                 accum: str = "plain") -> jnp.ndarray:
    """Raw (non-differentiable) SR-GEMM dispatch: pad → kernel → crop."""
    if not use_pallas:
        return ref.ref_sr_gemm(x, c, out, accum=accum)
    interpret = not on_tpu()
    m, n = x.shape[0], c.shape[1]
    xp = _pad_to(x, (bm, bk))
    cp = _pad_to(c, (bk, bn))
    op = _pad_to(out, (bm, bn)) if out is not None else None
    y = sr_gemm_pallas(xp, cp, op, bm=bm, bn=bn, bk=bk, interpret=interpret,
                       accum=accum)
    return y[:m, :n]


@functools.lru_cache(maxsize=None)
def _sr_gemm_vjp(bm: int, bn: int, bk: int, use_pallas: bool,
                 has_out: bool, accum: str = "plain"):
    """Module-level custom-VJP builder for SR-GEMM, memoized per static
    config.

    SR-GEMM is the engine's dense workhorse and runs on forward-only
    serving hot loops too, so — unlike the rarer ESOP/fused ops, whose
    unhashable prefetch-plan operands force per-call closures — its
    wrapper is built once per ``(tiles, dispatch, out, accum)`` config,
    not per call.  The backward GEMMs always run plain accumulation (the
    cotangent is already float32 under a promoted forward) and cast back
    to the primal dtypes — identities on the plain path.
    """
    def prim(x, c, out):
        return _sr_dispatch(x, c, out, bm, bn, bk, use_pallas, accum=accum)

    def bwd_x(g, c):
        # dX (m, k) = g (m, n) @ C^T (n, k): output cols k, contraction n.
        return _sr_dispatch(g, transposed_cached(c), None, bm, bk, bn,
                            use_pallas)

    def bwd_c(x, g):
        # dC (k, n) = X^T (k, m) @ g (m, n): rows k, contraction m.
        return _sr_dispatch(jnp.swapaxes(x, 0, 1), g, None, bk, bn, bm,
                            use_pallas)

    if has_out:
        @jax.custom_vjp
        def fo(x, c, out):
            return prim(x, c, out)

        fo.defvjp(lambda x, c, out: (prim(x, c, out), (x, c, out)),
                  lambda res, g: (bwd_x(g, res[1]).astype(res[0].dtype),
                                  bwd_c(res[0], g).astype(res[1].dtype),
                                  g.astype(res[2].dtype)))
        return fo

    @jax.custom_vjp
    def f(x, c):
        return prim(x, c, None)

    f.defvjp(lambda x, c: (prim(x, c, None), (x, c)),
             lambda res, g: (bwd_x(g, res[1]).astype(res[0].dtype),
                             bwd_c(res[0], g).astype(res[1].dtype)))
    return f


def sr_gemm(x: jnp.ndarray, c: jnp.ndarray, out: jnp.ndarray | None = None,
            bm: int = 128, bn: int = 128, bk: int = 128,
            use_pallas: bool | None = None,
            accum: str | None = None) -> jnp.ndarray:
    """Y = (out +) X @ C via the streaming outer-product kernel.

    VJP-safe: ``dX = g @ C^T`` and ``dC = X^T @ g`` run the same kernel
    dispatch with the tile roles swapped.  ``accum`` selects the
    accumulation mode (``docs/numerics.md``): promoted modes flush in
    float32 instead of rounding back to the operand dtype.
    """
    if use_pallas is None:
        use_pallas = on_tpu()
    accum = _norm_accum(accum, x, c, out)
    f = _sr_gemm_vjp(bm, bn, bk, use_pallas, out is not None, accum)
    return f(x, c, out) if out is not None else f(x, c)


def esop_gemm(x: jnp.ndarray, c: jnp.ndarray, out: jnp.ndarray | None = None,
              bm: int = 128, bn: int = 128, bk: int = 128,
              use_pallas: bool | None = None, plan: tuple | None = None,
              accum: str | None = None):
    """Block-ESOP Y = (out +) X @ C skipping zero C blocks. Returns (y, info).

    The block schedule and its accounting are memoized on C's identity
    (``esop_plan_cached``); the reference path reports the same
    streamed-block savings the Pallas kernel realizes.  ``plan`` optionally
    supplies that ``(counts, idx, t_steps, stats)`` tuple precomputed from
    the concrete matrix — required when ``c`` here is a tracer (e.g. a
    replicated operand inside a ``shard_map`` body).  ``accum`` as in
    :func:`sr_gemm` (``docs/numerics.md``).
    """
    if use_pallas is None:
        use_pallas = on_tpu()
    accum = _norm_accum(accum, x, c, out)
    counts, idx, t_steps, stats = (plan if plan is not None
                                   else esop_plan_cached(c, bk, bn))

    def prim(x, c, out):
        if not use_pallas:
            return ref.ref_esop_gemm(x, c, (bk, bn), out, accum=accum)
        interpret = not on_tpu()
        m, n = x.shape[0], c.shape[1]
        xp = _pad_to(x, (bm, bk))
        cp = _pad_to(c, (bk, bn))
        op = _pad_to(out, (bm, bn)) if out is not None else None
        yk, _ = esop_gemm_pallas(xp, cp, op, bm=bm, bn=bn, bk=bk,
                                 interpret=interpret,
                                 plan=(counts, idx, t_steps), accum=accum)
        return yk[:m, :n]

    def bwd_x(g, c):
        # dX = g @ C^T reuses block skipping on the transposed structure
        # (same zero blocks, transposed grid).  A traced C has no
        # host-readable schedule — dense SR-GEMM then (still the kernel).
        if _is_traced(c):
            return _sr_dispatch(g, jnp.swapaxes(c, 0, 1), None,
                                bm, bk, bn, use_pallas)
        dx, _ = esop_gemm(g, transposed_cached(c), bm=bm, bn=bk, bk=bn,
                          use_pallas=use_pallas)
        return dx

    def bwd_c(x, g):
        # dC = X^T @ g is dense regardless of C's zeros: the linearization
        # of Y = X @ C in C does not inherit C's sparsity.
        return _sr_dispatch(jnp.swapaxes(x, 0, 1), g, None, bk, bn, bm,
                            use_pallas)

    # dict(stats): the memoized entry is shared across calls — handing the
    # caller the cached object would let an info-dict mutation poison it
    return _linear_custom_vjp(prim, bwd_x, bwd_c, x, c, out), dict(stats)


def fused_gemt(x3: jnp.ndarray, ca: jnp.ndarray, cb: jnp.ndarray,
               bu: int = 128, bka: int = 128, bnb: int = 32, bna: int = 128,
               use_pallas: bool | None = None, plans: tuple | None = None,
               accum: str | None = None):
    """Fused two-stage GEMT ``Y = (X3 ×_a C_a) ×_b C_b``. Returns (y, info).

    ``x3`` is the u-major unfolding ``(U, Nb, Na)`` (``engine.lower``
    produces it); the result is ``(U, Ka, Kb)``.  The stage-a partial
    product never touches HBM — see ``kernels/fused_gemt.py``.  Complex
    coefficients (DFT) route to the einsum reference (the kernel is
    real-valued), with identical accounting.  ``plans`` optionally supplies
    the two precomputed ``esop_plan_cached`` tuples ``(plan_a, plan_b)``
    for tracer ``ca``/``cb`` (inside a ``shard_map`` body).
    """
    if use_pallas is None:
        use_pallas = on_tpu()
    if jnp.iscomplexobj(x3) or jnp.iscomplexobj(ca) or jnp.iscomplexobj(cb):
        use_pallas = False
    accum = _norm_accum(accum, x3, ca, cb)
    u, nb, na = x3.shape
    # Validate before padding: post-pad extents can line up by accident and
    # the kernel would silently contract against garbage rows.
    if ca.shape[0] != na or cb.shape[0] != nb:
        raise ValueError(
            f"x3 {x3.shape} incompatible with C_a {ca.shape} (na) / "
            f"C_b {cb.shape} (nb)")
    ka, kb = ca.shape[1], cb.shape[1]
    kbp = kb_padded(kb)
    # Both schedules memoized on the coefficient identities: C_a's 2D block
    # compaction and C_b's nb-slab compaction (one "column" of width kbp).
    counts_a, idx_a, t_a, stats_a = (plans[0] if plans is not None
                                     else esop_plan_cached(ca, bna, bka))
    # counts_b is unused: the slab stream is a single block column, so every
    # t_b step is live by construction — the kernel needs no b-side guard.
    _counts_b, idx_b, t_b, stats_b = (plans[1] if plans is not None
                                      else esop_plan_cached(cb, bnb, kbp))
    info = {
        "blocks_dense_a": stats_a["blocks_dense"],
        "blocks_live_a": stats_a["blocks_live"],
        "slabs_dense_b": stats_b["blocks_dense"],
        "slabs_live_b": stats_b["blocks_live"],
        # The streamed grid is the product space (C_a blocks × C_b slabs):
        # a dead entry on either axis skips the fetch.  blocks_dense/_live
        # use the same keys as esop_gemm so per-call savings aggregate.
        "blocks_dense": stats_a["blocks_dense"] * stats_b["blocks_dense"],
        "blocks_live": stats_a["blocks_live"] * max(stats_b["blocks_live"], 1),
        "t_steps": (t_a, t_b),
        "t_steps_dense": (stats_a["t_steps_dense"], stats_b["t_steps_dense"]),
    }
    info["fetch_savings"] = 1.0 - (info["blocks_live"]
                                   / max(info["blocks_dense"], 1))

    def prim(x3, ca, cb):
        if not use_pallas:
            return ref.ref_fused_gemt(x3, ca, cb, accum=accum)
        interpret = not on_tpu()
        xp = _pad_to(x3, (bu, bnb, bna))
        cap = _pad_to(ca, (bna, bka))
        cbp = _pad_to(cb, (bnb, kbp))
        yk, _ = fused_gemt_pallas(
            xp, cap, cbp, bu=bu, bka=bka, bnb=bnb, bna=bna,
            interpret=interpret, plan=(counts_a, idx_a, t_a, idx_b, t_b),
            accum=accum)
        return yk[:u, :ka, :kb]

    @jax.custom_vjp
    def f(x3, ca, cb):
        return prim(x3, ca, cb)

    def bwd(res, g):
        x3r, car, cbr = res
        # dX3 is itself a fused two-stage GEMT over the transposed
        # coefficients (the orthonormal-transform adjoint, paper §2.2):
        # the (Ka, Kb) output modes slide into the kernel's (na', nb')
        # slots.  Traced coefficients have no host-readable ESOP schedule,
        # so they take the fused jnp oracle instead of the kernel.
        gsw = jnp.swapaxes(g, 1, 2)  # (U, Kb, Ka)
        if _is_traced(car, cbr):
            dx3 = ref.ref_fused_gemt(gsw, jnp.swapaxes(car, 0, 1),
                                     jnp.swapaxes(cbr, 0, 1))
        else:
            dx3, _ = fused_gemt(gsw, transposed_cached(car),
                                transposed_cached(cbr), bu=bu,
                                use_pallas=use_pallas)
        dx3 = jnp.swapaxes(dx3, 1, 2).astype(x3r.dtype)
        # Coefficient cotangents are mode-unfolded rank-k products; the
        # engine-level VJP owns the training hot path with planned kernels,
        # this direct-op safety net contracts them in place.  Casts are
        # identities unless a promoted accum made g float32.
        dca = jnp.einsum("uba,ukl,bl->ak", x3r, g, cbr).astype(car.dtype)
        dcb = jnp.einsum("uba,ak,ukl->bl", x3r, car, g).astype(cbr.dtype)
        return dx3, dca, dcb

    f.defvjp(lambda x3, ca, cb: (prim(x3, ca, cb), (x3, ca, cb)), bwd)
    return f(x3, ca, cb), info


def fused3_gemt(x4: jnp.ndarray, ca: jnp.ndarray, cb: jnp.ndarray,
                cc: jnp.ndarray, bu: int = 8, bka: int = 128, bnb: int = 16,
                bnc: int = 16, bna: int = 128,
                use_pallas: bool | None = None, plans: tuple | None = None,
                accum: str | None = None):
    """Whole-transform fused GEMT ``Y = ((X4 ×_a C_a) ×_b C_b) ×_c C_c``.
    Returns (y, info).

    ``x4`` is the u-major unfolding ``(U, Nc, Nb, Na)`` (``engine.lower``
    produces it; U is the folded batch); the result is ``(U, Ka, Kb, Kc)``.
    Neither intermediate ever touches HBM — see ``kernels/fused3_gemt.py``.
    Complex coefficients (DFT) route to the einsum reference (the kernel is
    real-valued), with identical accounting.  ``plans`` optionally supplies
    the three precomputed ``esop_plan_cached`` tuples ``(a, b, c)`` for
    tracer coefficients (inside a ``shard_map`` body).
    """
    if use_pallas is None:
        use_pallas = on_tpu()
    if any(jnp.iscomplexobj(t) for t in (x4, ca, cb, cc)):
        use_pallas = False
    accum = _norm_accum(accum, x4, ca, cb, cc)
    u, nc, nb, na = x4.shape
    # Validate before padding: post-pad extents can line up by accident and
    # the kernel would silently contract against garbage rows.
    if ca.shape[0] != na or cb.shape[0] != nb or cc.shape[0] != nc:
        raise ValueError(
            f"x4 {x4.shape} incompatible with C_a {ca.shape} (na) / "
            f"C_b {cb.shape} (nb) / C_c {cc.shape} (nc)")
    ka, kb, kc = ca.shape[1], cb.shape[1], cc.shape[1]
    kbp, kcp = kb_padded(kb), kb_padded(kc)
    # All three schedules memoized on the coefficient identities: C_a's 2D
    # block compaction, C_b's nb-slab and C_c's nc-slab compactions (each a
    # single block column of the padded slab width).
    counts_a, idx_a, t_a, stats_a = (plans[0] if plans is not None
                                     else esop_plan_cached(ca, bna, bka))
    # counts_b/c are unused in-kernel: the slab streams are single block
    # columns, so every t_b / t_c step is live by construction.
    _cb_counts, idx_b, t_b, stats_b = (plans[1] if plans is not None
                                       else esop_plan_cached(cb, bnb, kbp))
    _cc_counts, idx_c, t_c, stats_c = (plans[2] if plans is not None
                                       else esop_plan_cached(cc, bnc, kcp))
    live_bc = max(stats_b["blocks_live"], 1) * max(stats_c["blocks_live"], 1)
    info = {
        "blocks_dense_a": stats_a["blocks_dense"],
        "blocks_live_a": stats_a["blocks_live"],
        "slabs_dense_b": stats_b["blocks_dense"],
        "slabs_live_b": stats_b["blocks_live"],
        "slabs_dense_c": stats_c["blocks_dense"],
        "slabs_live_c": stats_c["blocks_live"],
        # The streamed grid is the product space (C_a blocks × C_b slabs ×
        # C_c slabs): a dead entry on any axis skips the fetch.
        # blocks_dense/_live use the same keys as esop_gemm so per-call
        # savings aggregate.
        "blocks_dense": (stats_a["blocks_dense"] * stats_b["blocks_dense"]
                         * stats_c["blocks_dense"]),
        "blocks_live": stats_a["blocks_live"] * live_bc,
        "t_steps": (t_a, t_b, t_c),
        "t_steps_dense": (stats_a["t_steps_dense"], stats_b["t_steps_dense"],
                          stats_c["t_steps_dense"]),
    }
    info["fetch_savings"] = 1.0 - (info["blocks_live"]
                                   / max(info["blocks_dense"], 1))

    def prim(x4, ca, cb, cc):
        if not use_pallas:
            return ref.ref_fused3_gemt(x4, ca, cb, cc, accum=accum)
        interpret = not on_tpu()
        xp = _pad_to(x4, (bu, bnc, bnb, bna))
        cap = _pad_to(ca, (bna, bka))
        cbp = _pad_to(cb, (bnb, kbp))
        ccp = _pad_to(cc, (bnc, kcp))
        yk, _ = fused3_gemt_pallas(
            xp, cap, cbp, ccp, bu=bu, bka=bka, bnb=bnb, bnc=bnc, bna=bna,
            interpret=interpret,
            plan=(counts_a, idx_a, t_a, idx_b, t_b, idx_c, t_c),
            accum=accum)
        return yk[:u, :ka, :kb, :kc]

    @jax.custom_vjp
    def f(x4, ca, cb, cc):
        return prim(x4, ca, cb, cc)

    def bwd(res, g):
        x4r, car, cbr, ccr = res
        # dX4 is the whole-transform adjoint — another fused triple over
        # the transposed coefficients, with the (Ka, Kb, Kc) output modes
        # reversed into the kernel's (nc', nb', na') streaming slots.
        gsw = jnp.transpose(g, (0, 3, 2, 1))  # (U, Kc, Kb, Ka)
        if _is_traced(car, cbr, ccr):
            dx4 = ref.ref_fused3_gemt(gsw, jnp.swapaxes(car, 0, 1),
                                      jnp.swapaxes(cbr, 0, 1),
                                      jnp.swapaxes(ccr, 0, 1))
        else:
            dx4, _ = fused3_gemt(gsw, transposed_cached(car),
                                 transposed_cached(cbr),
                                 transposed_cached(ccr), bu=bu,
                                 use_pallas=use_pallas)
        dx4 = jnp.transpose(dx4, (0, 3, 2, 1)).astype(x4r.dtype)
        dca = jnp.einsum("ucba,uklm,bl,cm->ak",
                         x4r, g, cbr, ccr).astype(car.dtype)
        dcb = jnp.einsum("ucba,ak,uklm,cm->bl",
                         x4r, car, g, ccr).astype(cbr.dtype)
        dcc = jnp.einsum("ucba,ak,bl,uklm->cm",
                         x4r, car, cbr, g).astype(ccr.dtype)
        return dx4, dca, dcb, dcc

    f.defvjp(lambda x4, ca, cb, cc: (prim(x4, ca, cb, cc), (x4, ca, cb, cc)),
             bwd)
    return f(x4, ca, cb, cc), info


def chain_gemt(x3: jnp.ndarray, ca: jnp.ndarray, cb: jnp.ndarray,
               bu: int = 128, bka: int = 128, bnb: int = 32, bna: int = 128,
               use_pallas: bool | None = None, plan_a: tuple | None = None,
               accum: str | None = None):
    """Chain pair ``y, y1 = (X3 ×_a C_a) ×_b C_b`` with the intermediate
    emitted.  Returns ``(y, y1, info)``; layouts ``(U, Ka, Kb)`` /
    ``(U, Nb, Ka)``.

    The backward-walk workhorse: the recompute prefix and the contraction
    that consumes it share one launch, so ``y1`` crosses HBM once as a
    result instead of round-tripping (``kernels/fused_chain.py``).  The b
    stream is dense by construction; a-side ESOP compaction applies.
    ``plan_a`` optionally supplies the precomputed ``esop_plan_cached``
    tuple for a tracer ``ca`` (inside a jitted backward program).  Not
    VJP-wrapped: this op *is* a VJP building block.
    """
    if use_pallas is None:
        use_pallas = on_tpu()
    if jnp.iscomplexobj(x3) or jnp.iscomplexobj(ca) or jnp.iscomplexobj(cb):
        use_pallas = False
    accum = _norm_accum(accum, x3, ca, cb)
    u, nb, na = x3.shape
    if ca.shape[0] != na or cb.shape[0] != nb:
        raise ValueError(
            f"x3 {x3.shape} incompatible with C_a {ca.shape} (na) / "
            f"C_b {cb.shape} (nb)")
    if use_pallas and plan_a is None and _is_traced(ca):
        use_pallas = False  # no host-readable ESOP schedule for a tracer
    if not use_pallas:
        y, y1 = ref.ref_chain_gemt(x3, ca, cb, accum=accum)
        return y, y1, {"t_steps_dense": (-(-na // bna), nb // bnb)}
    ka, kb = ca.shape[1], cb.shape[1]
    kbp = kb_padded(kb)
    counts_a, idx_a, t_a, stats_a = (plan_a if plan_a is not None
                                     else esop_plan_cached(ca, bna, bka))
    xp = _pad_to(x3, (bu, bnb, bna))
    cap = _pad_to(ca, (bna, bka))
    cbp = _pad_to(cb, (bnb, kbp))
    yk, y1k, _ = chain_gemt_pallas(
        xp, cap, cbp, bu=bu, bka=bka, bnb=bnb, bna=bna,
        interpret=not on_tpu(), plan_a=(counts_a, idx_a, t_a), accum=accum)
    info = {
        "blocks_dense_a": stats_a["blocks_dense"],
        "blocks_live_a": stats_a["blocks_live"],
        "t_steps": (t_a, xp.shape[1] // bnb),
        "t_steps_dense": (stats_a["t_steps_dense"], xp.shape[1] // bnb),
    }
    return yk[:u, :ka, :kb], y1k[:u, :nb, :ka], info


def chain3_gemt(x4: jnp.ndarray, ca: jnp.ndarray, cb: jnp.ndarray,
                cc: jnp.ndarray, bu: int = 8, bka: int = 128, bnb: int = 16,
                bnc: int = 16, bna: int = 128,
                use_pallas: bool | None = None, plan_a: tuple | None = None,
                accum: str | None = None):
    """Chain triple ``y, y1, y2 = ((X4 ×_a C_a) ×_b C_b) ×_c C_c`` with both
    intermediates emitted.  Returns ``(y, y1, y2, info)``; layouts
    ``(U, Ka, Kb, Kc)`` / ``(U, Nc, Nb, Ka)`` / ``(U, Nc, Ka, Kb)``.

    One launch replaces the staged backward's two recompute launches and
    the cotangent chain's intermediate round-trips.  The b and c streams
    are dense by construction; a-side ESOP compaction applies.  ``plan_a``
    as in :func:`chain_gemt`.  Not VJP-wrapped.
    """
    if use_pallas is None:
        use_pallas = on_tpu()
    if any(jnp.iscomplexobj(t) for t in (x4, ca, cb, cc)):
        use_pallas = False
    accum = _norm_accum(accum, x4, ca, cb, cc)
    u, nc, nb, na = x4.shape
    if ca.shape[0] != na or cb.shape[0] != nb or cc.shape[0] != nc:
        raise ValueError(
            f"x4 {x4.shape} incompatible with C_a {ca.shape} (na) / "
            f"C_b {cb.shape} (nb) / C_c {cc.shape} (nc)")
    if use_pallas and plan_a is None and _is_traced(ca):
        use_pallas = False
    if not use_pallas:
        y, y1, y2 = ref.ref_chain3_gemt(x4, ca, cb, cc, accum=accum)
        return y, y1, y2, {"t_steps_dense": (-(-na // bna), nb // bnb,
                                             nc // bnc)}
    ka, kb, kc = ca.shape[1], cb.shape[1], cc.shape[1]
    kbp, kcp = kb_padded(kb), kb_padded(kc)
    counts_a, idx_a, t_a, stats_a = (plan_a if plan_a is not None
                                     else esop_plan_cached(ca, bna, bka))
    xp = _pad_to(x4, (bu, bnc, bnb, bna))
    cap = _pad_to(ca, (bna, bka))
    cbp = _pad_to(cb, (bnb, kbp))
    ccp = _pad_to(cc, (bnc, kcp))
    yk, y1k, y2k, _ = chain3_gemt_pallas(
        xp, cap, cbp, ccp, bu=bu, bka=bka, bnb=bnb, bnc=bnc, bna=bna,
        interpret=not on_tpu(), plan_a=(counts_a, idx_a, t_a), accum=accum)
    info = {
        "blocks_dense_a": stats_a["blocks_dense"],
        "blocks_live_a": stats_a["blocks_live"],
        "t_steps": (t_a, xp.shape[2] // bnb, xp.shape[1] // bnc),
        "t_steps_dense": (stats_a["t_steps_dense"], xp.shape[2] // bnb,
                          xp.shape[1] // bnc),
    }
    return (yk[:u, :ka, :kb, :kc], y1k[:u, :nc, :nb, :ka],
            y2k[:u, :nc, :ka, :kb], info)


def coeff_grad_batch(as_list, gs_list, br: int = 128,
                     use_pallas: bool | None = None):
    """The three coefficient cotangents ``dC_s = A_sᵀ @ G_s`` in one
    multi-output launch.  ``as_list`` / ``gs_list`` are the per-mode
    unfolded operands ``(R_s, N_s)`` / ``(R_s, K_s)``; returns the list of
    three ``(N_s, K_s)`` cotangents.

    The operands are zero-padded to a common ``(R, N, K)`` envelope and
    stacked on a leading s-axis (zero rows contribute nothing to the
    products), replacing three rank-k SR-GEMM dispatches with a single
    grid ``(3, T_r)`` kernel.  Complex operands route to the einsum
    reference.  Not VJP-wrapped.
    """
    if use_pallas is None:
        use_pallas = on_tpu()
    if any(jnp.iscomplexobj(t) for t in (*as_list, *gs_list)):
        use_pallas = False
    rmax = max(a.shape[0] for a in as_list)
    nmax = max(a.shape[1] for a in as_list)
    kmax = max(g.shape[1] for g in gs_list)
    # TPU tiling rule: br is the blocks' sublane dim (kb_padded gives a
    # multiple of 8); the lane dims N/K are whole padded extents.
    br_eff = min(br, kb_padded(rmax))
    rp = -(-rmax // br_eff) * br_eff
    np_, kp = kb_padded(nmax), kb_padded(kmax)

    def pad2(t, rows, cols):
        return jnp.pad(t, ((0, rows - t.shape[0]), (0, cols - t.shape[1])))

    a = jnp.stack([pad2(t, rp, np_) for t in as_list])
    g = jnp.stack([pad2(t, rp, kp) for t in gs_list])
    if use_pallas:
        out_dtype = jnp.result_type(*(t.dtype for t in (*as_list, *gs_list)))
        dc = coeff_grad_batch_pallas(a, g, br=br_eff,
                                     interpret=not on_tpu(),
                                     out_dtype=out_dtype)
    else:
        dc = ref.ref_coeff_grad_batch(a, g)
    return [dc[i, :as_list[i].shape[1], :gs_list[i].shape[1]]
            for i in range(len(as_list))]


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, bq: int = 128, bkv: int = 128,
                    use_pallas: bool | None = None) -> jnp.ndarray:
    """(B, H, S, D) flash attention; jnp blockwise reference off-TPU by default."""
    if use_pallas is None:
        use_pallas = on_tpu()
    if use_pallas is False:
        return ref.ref_attention(q, k, v, causal=causal)
    b, h, s, d = q.shape
    fold = lambda t: t.reshape(b * h, s, d)
    y = flash_attention_pallas(fold(q), fold(k), fold(v), bq=bq, bkv=bkv,
                               causal=causal, interpret=not on_tpu())
    return y.reshape(b, h, s, d)

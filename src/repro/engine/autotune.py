"""Timing-based block-size autotuner with a JSON-persisted cache.

Kernel tile sizes (``bm``/``bn``/``bk``) are a hardware- and shape-dependent
choice; hard-coding 128³ leaves VMEM and MXU utilization on the table for
skinny Tucker stages.  ``autotune_gemm`` hill-climbs the (power-of-two)
block-size lattice by measuring the actual dispatch (``kernels.ops.sr_gemm``
or ``esop_gemm``) and persists the winner in an :class:`AutotuneCache` keyed
on ``(m, n, k, dtype, kind, sparsity signature)`` — the same signature the
planner uses, so a C matrix with a different zero structure never reuses a
stale ESOP tuning.

The cache is a plain JSON file (default ``~/.cache/repro/autotune.json``,
overridable via ``REPRO_AUTOTUNE_CACHE`` or the ``path`` argument), tolerant
of missing/corrupt files so a cold or broken cache never fails a run.

Paper anchor: §5.1 (the P³-cell tiling the tiles discretize).  See
``docs/engine.md`` ("Autotune"); under a mesh the tuned shapes are the
*per-shard* GEMMs (``docs/distributed.md``).
"""
from __future__ import annotations

import json
import os
import tempfile
import time

import jax
import jax.numpy as jnp

from ..kernels import ops
from ..obs import metrics as _metrics
from ..obs import trace as _trace

__all__ = ["AutotuneCache", "autotune_gemm", "autotune_fused",
           "autotune_fused3", "default_cache_path", "make_key",
           "make_fused_key", "make_fused3_key"]

_BOUNDS = (8, 512)  # power-of-two block-size lattice bounds
_MIN_GAIN = 0.02  # relative speedup required to accept a move


def default_cache_path() -> str:
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "repro",
                        "autotune.json")


def make_key(m: int, n: int, k: int, dtype, kind: str, sig: str = "",
             adjoint: bool = False, accum: str = "plain") -> str:
    """Autotune-cache key for a staged GEMM (cache version v4).

    ``adjoint`` gives the backward pass its own tuning role: earlier
    versions let adjoint stages hit the forward entries ("a transposed
    square problem matches a forward one"), but the measured dispatch is
    not the same — the adjoint contracts against ``C_sᵀ``, whose *column*
    zero structure drives a different ESOP compaction, and the backward
    runs the stage inside the chain/recompute walk with different operand
    residency.  Forward-tuned tiles replaying for the adjoint was a live
    bug (tile-sharing), so the role is part of the key and the v3 bump
    orphans every v2 entry that was written without one.

    ``accum`` (v4) keys the guarded-numerics accumulation mode: a
    compensated dispatch carries an extra comp scratch and per-step adds,
    so its best tiles are not the plain dispatch's best tiles.
    """
    role = "adj" if adjoint else "fwd"
    return (f"v4:{m}x{n}x{k}|{jnp.dtype(dtype).name}|{kind}|{role}"
            f"|{accum}|{sig}")


def make_fused_key(u: int, na: int, ka: int, nb: int, kb: int,
                   dtype, sig: str = "",
                   vmem_budget: int | None = None,
                   adjoint: bool = False, accum: str = "plain") -> str:
    """Autotune-cache key for the fused pair kernel (cache version v5).

    The VMEM budget is part of the problem, exactly as in the plan cache's
    ``vb=`` component: tiles tuned under a roomy budget must never replay
    under a stricter one (the budget filter would not re-run on a cache
    hit).  The v4 bump adds the forward/adjoint role — see
    :func:`make_key` — and orphans role-less v3 entries; v5 adds the
    accumulation mode (the comp scratch changes the footprint the budget
    filter sees).
    """
    role = "adj" if adjoint else "fwd"
    return (f"fused:v5:{u}x{na}x{ka}x{nb}x{kb}|{jnp.dtype(dtype).name}"
            f"|{role}|{accum}|{sig}|vb{vmem_budget}")


def make_fused3_key(u: int, na: int, ka: int, nb: int, kb: int,
                    nc: int, kc: int, dtype, sig: str = "",
                    vmem_budget: int | None = None,
                    adjoint: bool = False, accum: str = "plain") -> str:
    """Autotune-cache key for the whole-transform megakernel (v3 adds the
    forward/adjoint role and orphans role-less v2 entries, v4 the
    accumulation mode — see :func:`make_key`)."""
    role = "adj" if adjoint else "fwd"
    return (f"fused3:v4:{u}x{na}x{ka}x{nb}x{kb}x{nc}x{kc}"
            f"|{jnp.dtype(dtype).name}|{role}|{accum}|{sig}|vb{vmem_budget}")


# Key prefixes the current key builders emit.  Anything else in a loaded
# cache file is an orphan from an earlier key version (the v3/v4/v5 bumps
# that added the adjoint role and the accumulation mode) — those entries
# can never be hit again and only bloat the file, so load() prunes them.
_LIVE_KEY_PREFIXES = ("v4:", "fused:v5:", "fused3:v4:")


class AutotuneCache:
    """JSON-backed ``key -> {bm, bn, bk, us}`` store."""

    def __init__(self, path: str | None = None):
        self.path = path or default_cache_path()
        self._entries: dict[str, dict] = {}
        self.load()

    def load(self) -> None:
        try:
            with open(self.path) as f:
                data = json.load(f)
        except OSError:
            self._entries = {}  # cold cache: no file yet (or unreadable)
            return
        except ValueError:
            # Corrupt JSON (e.g. a torn write from a pre-atomic-rename
            # version, or external truncation): recover to empty rather
            # than fail the run, and count it so operators can see it.
            self._entries = {}
            _metrics.inc("autotune.cache.corrupt_recovered")
            return
        if isinstance(data, dict):
            self._entries = {k: v for k, v in data.items()
                             if isinstance(v, dict)}
        else:
            self._entries = {}
            _metrics.inc("autotune.cache.corrupt_recovered")
            return
        self.prune()
        _metrics.inc("autotune.cache.loads")

    def prune(self) -> int:
        """Drop entries whose key no longer matches a live key version.

        The v3/v4/v5 key bumps (adjoint role, accumulation mode) orphaned
        every entry written under the old scheme — they are unreachable by
        ``get`` yet were re-persisted on every ``save``, growing the file
        forever.  Runs on every ``load``; counted in
        ``autotune.cache.pruned``.  Returns how many entries fell.
        """
        stale = [k for k in self._entries
                 if not k.startswith(_LIVE_KEY_PREFIXES)]
        for k in stale:
            del self._entries[k]
        if stale:
            _metrics.inc("autotune.cache.pruned", len(stale))
        return len(stale)

    def save(self) -> None:
        """Atomically persist: write a *uniquely named* temp file in the
        destination directory, then ``os.replace``.  A fixed temp name
        would let two concurrent savers interleave (one renames the
        other's half-written file); mkstemp gives each writer its own."""
        d = os.path.dirname(self.path) or "."
        os.makedirs(d, exist_ok=True)
        fd, tmp = tempfile.mkstemp(
            dir=d, prefix=os.path.basename(self.path) + ".", suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(self._entries, f, indent=1, sort_keys=True)
            os.replace(tmp, self.path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        _metrics.inc("autotune.cache.writes")

    def get(self, key: str) -> dict | None:
        entry = self._entries.get(key)
        _metrics.inc("autotune.cache.hits" if entry is not None
                     else "autotune.cache.misses")
        return entry

    def put(self, key: str, entry: dict) -> None:
        self._entries[key] = entry

    def __len__(self) -> int:
        return len(self._entries)


def _time_us(fn, reps: int = 2) -> float:
    jax.block_until_ready(fn())  # warmup / compile
    t0 = time.perf_counter()
    for _ in range(reps):
        r = fn()
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / reps * 1e6


def _pow2_floor(d: int) -> int:
    return 1 << (max(int(d), 1).bit_length() - 1)


def _neighbors(cfg: tuple[int, ...],
               caps: tuple[int, ...]) -> list[tuple[int, ...]]:
    lo, hi = _BOUNDS
    out = []
    for i in range(len(cfg)):
        for factor in (2, 0.5):
            v = int(cfg[i] * factor)
            if lo <= v <= min(hi, caps[i]):
                cand = list(cfg)
                cand[i] = v
                if tuple(cand) != cfg:
                    out.append(tuple(cand))
    return out


def autotune_gemm(
    x: jnp.ndarray,
    c: jnp.ndarray,
    kind: str = "sr_gemm",
    *,
    sig: str = "",
    cache: AutotuneCache | None = None,
    max_steps: int = 6,
    reps: int = 2,
    use_pallas: bool | None = None,
    adjoint: bool = False,
    accum: str = "plain",
) -> tuple[int, int, int]:
    """Hill-climb (bm, bn, bk) for ``x @ c`` under dispatch ``kind``.

    Returns the best block sizes; a cache hit skips all measurement.
    ``adjoint`` selects the backward tuning role (its own cache entries —
    see :func:`make_key`); ``accum`` keys and measures the guarded
    accumulation mode's dispatch.  Every tile is a lane dim in one of the
    forward/backward dispatches, so candidates that break
    :func:`~repro.engine.plan.lane_tile_ok` are never probed or returned.
    """
    from .plan import _lane_tile, lane_tile_ok

    m, kdim = x.shape
    n = c.shape[1]
    dims = (m, n, kdim)

    def legal(cfg):
        return all(lane_tile_ok(t, d) for t, d in zip(cfg, dims))

    cache = cache if cache is not None else AutotuneCache()
    key = make_key(m, n, kdim, x.dtype, kind, sig, adjoint=adjoint,
                   accum=accum)
    knobs_live = use_pallas is True or ops.on_tpu()
    hit = cache.get(key)
    # An untuned entry (defaults recorded off-TPU) must not suppress real
    # tuning once the cache file reaches a host where the knobs matter.
    if hit is not None and (hit.get("tuned", True) or not knobs_live):
        cfg = (int(hit["bm"]), int(hit["bn"]), int(hit["bk"]))
        if legal(cfg):
            return cfg

    lo, _hi = _BOUNDS
    caps = tuple(max(lo, _pow2_floor(d)) for d in dims)

    if not knobs_live:
        # The reference paths ignore bm/bn/bk, so timing candidates here
        # would hill-climb on pure noise and persist a meaningless winner.
        # Cache the planner's defaults instead (still shape-correct for the
        # Pallas path if this cache later reaches a TPU host).
        cfg = tuple(_lane_tile(d) for d in dims)
        cache.put(key, {"bm": cfg[0], "bn": cfg[1], "bk": cfg[2],
                        "us": 0.0, "kind": kind, "tuned": False})
        try:
            cache.save()
        except OSError:
            pass
        return cfg

    dispatch = {"sr_gemm": ops.sr_gemm, "esop": ops.esop_gemm,
                "esop_gemm": ops.esop_gemm}[kind]

    def measure(cfg):
        bm, bn, bk = cfg

        def call():
            y = dispatch(x, c, bm=bm, bn=bn, bk=bk, use_pallas=use_pallas,
                         accum=accum)
            return y[0] if isinstance(y, tuple) else y

        sp = _trace.NULL_SPAN
        if _trace.enabled():
            sp = _trace.span("autotune.probe",
                             {"kind": kind, "cfg": cfg, "key": key})
        with sp:
            return _time_us(call, reps=reps)

    cur = tuple(_lane_tile(d) for d in dims)
    cur_us = measure(cur)
    for _ in range(max_steps):
        moved = False
        for cand in _neighbors(cur, caps):
            if not legal(cand):
                continue
            us = measure(cand)
            if us < cur_us * (1.0 - _MIN_GAIN):
                cur, cur_us, moved = cand, us, True
        if not moved:
            break
    cache.put(key, {"bm": cur[0], "bn": cur[1], "bk": cur[2],
                    "us": round(cur_us, 2), "kind": kind, "tuned": True})
    try:
        cache.save()
    except OSError:
        pass  # read-only FS: tuning still applies in-process
    return cur


def autotune_fused(
    ca: jnp.ndarray,
    cb: jnp.ndarray,
    *,
    rows: int,
    dtype,
    start: tuple[int, int, int],
    bna: int,
    kbp: int,
    sig: str = "",
    cache: AutotuneCache | None = None,
    max_steps: int = 4,
    reps: int = 2,
    use_pallas: bool | None = None,
    vmem_budget: int | None = None,
    adjoint: bool = False,
    accum: str = "plain",
) -> tuple[int, int, int]:
    """Hill-climb the fused kernel's ``(bu, bka, bnb)`` tile triple.

    ``rows``/``dtype`` describe the u-major input ``(rows, Nb, Na)``; the
    ones-probe is only materialized when a measurement actually runs, so a
    warm cache costs no device allocation.  ``start`` is the planner's
    (VMEM-feasible) choice; every candidate is re-checked against the
    footprint model so tuning can never climb out of the budget.
    ``bna``/``kbp`` stay pinned (Kb is not grid-blocked and the na tile
    only trades partial-width for step count); ``bka`` candidates must
    obey :func:`~repro.engine.plan.lane_tile_ok`.
    """
    from .plan import DEFAULT_VMEM_BUDGET, fused_vmem_bytes, lane_tile_ok

    u = int(rows)
    na, ka = ca.shape
    nb, kb = cb.shape
    budget = DEFAULT_VMEM_BUDGET if vmem_budget is None else vmem_budget
    cache = cache if cache is not None else AutotuneCache()
    # bna/kbp are part of the problem too: a hit tuned with a different
    # pinned na tile must not leak mismatched tiles (the budget itself is
    # keyed inside make_fused_key since the v2 bump).
    key = (make_fused_key(u, na, ka, nb, kb, dtype, sig, vmem_budget=budget,
                          adjoint=adjoint, accum=accum)
           + f"|bna{bna}|kbp{kbp}")
    isz = jnp.dtype(dtype).itemsize
    lo, _hi = _BOUNDS
    caps = tuple(max(lo, _pow2_floor(d)) for d in (u, ka, nb))

    def fits(cfg):
        return (lane_tile_ok(cfg[1], ka)
                and fused_vmem_bytes(cfg[0], cfg[1], cfg[2], bna, kbp,
                                     isz, accum) <= budget)

    knobs_live = use_pallas is True or ops.on_tpu()
    hit = cache.get(key)
    if hit is not None and (hit.get("tuned", True) or not knobs_live):
        cfg = (int(hit["bu"]), int(hit["bka"]), int(hit["bnb"]))
        if fits(cfg):  # belt-and-braces: never trust a cache into VMEM OOM
            return cfg

    cur = tuple(start)
    if not knobs_live:
        cache.put(key, {"bu": cur[0], "bka": cur[1], "bnb": cur[2],
                        "us": 0.0, "kind": "fused", "tuned": False})
        try:
            cache.save()
        except OSError:
            pass
        return cur

    x3 = jnp.ones((u, nb, na), dtype=dtype)  # probe: measured path only

    def measure(cfg):
        bu, bka, bnb = cfg

        def call():
            y, _ = ops.fused_gemt(x3, ca, cb, bu=bu, bka=bka, bnb=bnb,
                                  bna=bna, use_pallas=use_pallas,
                                  accum=accum)
            return y

        sp = _trace.NULL_SPAN
        if _trace.enabled():
            sp = _trace.span("autotune.probe",
                             {"kind": "fused", "cfg": cfg, "key": key})
        with sp:
            return _time_us(call, reps=reps)

    cur_us = measure(cur)
    for _ in range(max_steps):
        moved = False
        for cand in _neighbors(cur, caps):
            if not fits(cand):
                continue
            us = measure(cand)
            if us < cur_us * (1.0 - _MIN_GAIN):
                cur, cur_us, moved = cand, us, True
        if not moved:
            break
    cache.put(key, {"bu": cur[0], "bka": cur[1], "bnb": cur[2],
                    "us": round(cur_us, 2), "kind": "fused", "tuned": True})
    try:
        cache.save()
    except OSError:
        pass
    return cur


def autotune_fused3(
    ca: jnp.ndarray,
    cb: jnp.ndarray,
    cc: jnp.ndarray,
    *,
    rows: int,
    dtype,
    start: tuple[int, int, int, int],
    bna: int,
    kbp: int,
    kcp: int,
    sig: str = "",
    cache: AutotuneCache | None = None,
    max_steps: int = 4,
    reps: int = 2,
    use_pallas: bool | None = None,
    vmem_budget: int | None = None,
    adjoint: bool = False,
    accum: str = "plain",
) -> tuple[int, int, int, int]:
    """Hill-climb the megakernel's ``(bu, bka, bnb, bnc)`` tile quadruple.

    ``rows``/``dtype`` describe the u-major input ``(rows, Nc, Nb, Na)``;
    the ones-probe is only materialized when a measurement actually runs.
    ``start`` is the planner's (VMEM-feasible) choice; every candidate is
    re-checked against the footprint model so tuning can never climb out
    of the budget.  ``bna``/``kbp``/``kcp`` stay pinned (Kb/Kc are not
    grid-blocked and the na tile only trades partial-width for step
    count); ``bka`` candidates must obey
    :func:`~repro.engine.plan.lane_tile_ok`.
    """
    from .plan import DEFAULT_VMEM_BUDGET, fused3_vmem_bytes, lane_tile_ok

    u = int(rows)
    na, ka = ca.shape
    nb, kb = cb.shape
    nc, kc = cc.shape
    budget = DEFAULT_VMEM_BUDGET if vmem_budget is None else vmem_budget
    cache = cache if cache is not None else AutotuneCache()
    key = (make_fused3_key(u, na, ka, nb, kb, nc, kc, dtype, sig,
                           vmem_budget=budget, adjoint=adjoint, accum=accum)
           + f"|bna{bna}|kbp{kbp}|kcp{kcp}")
    isz = jnp.dtype(dtype).itemsize
    lo, _hi = _BOUNDS
    caps = tuple(max(lo, _pow2_floor(d)) for d in (u, ka, nb, nc))

    def fits(cfg):
        return (lane_tile_ok(cfg[1], ka)
                and fused3_vmem_bytes(cfg[0], cfg[1], cfg[2], cfg[3], bna,
                                      kbp, kcp, isz, accum) <= budget)

    knobs_live = use_pallas is True or ops.on_tpu()
    hit = cache.get(key)
    if hit is not None and (hit.get("tuned", True) or not knobs_live):
        cfg = (int(hit["bu"]), int(hit["bka"]), int(hit["bnb"]),
               int(hit["bnc"]))
        if fits(cfg):  # belt-and-braces: never trust a cache into VMEM OOM
            return cfg

    cur = tuple(start)
    if not knobs_live:
        cache.put(key, {"bu": cur[0], "bka": cur[1], "bnb": cur[2],
                        "bnc": cur[3], "us": 0.0, "kind": "fused3",
                        "tuned": False})
        try:
            cache.save()
        except OSError:
            pass
        return cur

    x4 = jnp.ones((u, nc, nb, na), dtype=dtype)  # probe: measured path only

    def measure(cfg):
        bu, bka, bnb, bnc_ = cfg

        def call():
            y, _ = ops.fused3_gemt(x4, ca, cb, cc, bu=bu, bka=bka, bnb=bnb,
                                   bnc=bnc_, bna=bna, use_pallas=use_pallas,
                                   accum=accum)
            return y

        sp = _trace.NULL_SPAN
        if _trace.enabled():
            sp = _trace.span("autotune.probe",
                             {"kind": "fused3", "cfg": cfg, "key": key})
        with sp:
            return _time_us(call, reps=reps)

    cur_us = measure(cur)
    for _ in range(max_steps):
        moved = False
        for cand in _neighbors(cur, caps):
            if not fits(cand):
                continue
            us = measure(cand)
            if us < cur_us * (1.0 - _MIN_GAIN):
                cur, cur_us, moved = cand, us, True
        if not moved:
            break
    cache.put(key, {"bu": cur[0], "bka": cur[1], "bnb": cur[2],
                    "bnc": cur[3], "us": round(cur_us, 2), "kind": "fused3",
                    "tuned": True})
    try:
        cache.save()
    except OSError:
        pass
    return cur

"""GEMT schedule planner — cost model over the six stage orders (paper §3).

The paper enumerates six parenthesizations of the 3-stage GEMT; with
rectangular coefficient matrices (Tucker expansion/compression, §2.3) the
order changes both the MAC count and the intermediate-tensor sizes by large
factors — contracting compressive modes (K_s < N_s) first shrinks everything
downstream.  Deinsum-style planning: the cost of contracting mode ``s`` on a
tensor of current dims ``d`` is

    MACs(s) = prod(d) / d[s] * N_s * K_s        (rows · N_s · K_s)

and the intermediate after the stage has ``d[s] -> K_s``.  The planner
scores every order by (effective MACs, peak intermediate bytes) and also
chooses a per-stage backend from the coefficient matrix's *block* sparsity
(``block_nonzero_mask``, shared with the Pallas block-ESOP kernel):

  * ``esop``    — zero-block fraction >= ``esop_threshold``: the block-ESOP
                  kernel skips fetching/multiplying those blocks, so the
                  stage's effective MACs scale by the live-block fraction;
  * ``sr_gemm`` — dense streaming outer-product kernel;
  * ``einsum``  — fallback for complex dtypes (DFT), tiny operands where
                  kernel/padding overhead dominates, and modes narrower
                  than a lane in a tensor whose minor mode is lane-wide
                  (the kernels' unfold would pad them in HBM).

``build_plan`` is pure and host-side: it never touches device values beyond
reading the coefficient matrices' zero structure.

**Topology-aware planning** (``mesh=``/``axes=``): when a
:class:`jax.sharding.Mesh` and a per-mode axis assignment are given, the
plan describes the *per-shard* schedule of the TriADA distribution
(``core/distributed.py``, paper §4–§5 / Eq. 7): the tensor is stationary
with mode ``s`` sharded over ``axes[s-1]``; a stage contracting an
unsharded mode is fully local; a stage contracting a sharded mode runs a
local partial rank-k update against this device's coefficient rows and
combines with one ``psum_scatter`` over that axis.  The cost model then
scores orders by ``(effective per-shard MACs, collective bytes, peak local
bytes)`` — contracting compressive *unsharded* modes first shrinks the
partial that the sharded stage must scatter, so the planner prefers
shard-local stages early.  Fusion is offered only when both modes of the
pair are shard-local (the fused kernel has no collective between its two
contractions).  See ``docs/distributed.md``.
"""
from __future__ import annotations

import dataclasses
import hashlib
import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..core.esop import block_nonzero_mask
from ..kernels.fused_gemt import kb_padded
from .numerics import enforce_error_budget, normalize_accum, plan_error_bound

AxisName = str | tuple[str, ...] | None

__all__ = [
    "StagePlan",
    "FusedPairPlan",
    "FusedTriplePlan",
    "GemtPlan",
    "build_plan",
    "derive_adjoint_plan",
    "AdjointChainPlan",
    "plan_adjoint_chain",
    "order_costs",
    "macs_for_order",
    "sparsity_signature",
    "lane_tile_ok",
    "fused_tile_sizes",
    "fused_vmem_bytes",
    "fused3_tile_sizes",
    "fused3_vmem_bytes",
    "chain_tile_sizes",
    "chain_vmem_bytes",
    "chain3_tile_sizes",
    "chain3_vmem_bytes",
    "stage_vmem_bytes",
    "stage_row_tile",
    "refresh_fused_pair",
    "refresh_fused_triple",
    "stage_hbm_bytes",
    "staged_pair_hbm_bytes",
    "plan_hbm_bytes",
    "mesh_axis_size",
    "normalize_axes",
    "DEFAULT_ESOP_THRESHOLD",
    "DEFAULT_VMEM_BUDGET",
    "MIN_KERNEL_DIM",
    "SHARDED_EINSUM_BREAKEVEN_MACS",
    "FUSE_MODES",
]

DEFAULT_ESOP_THRESHOLD = 0.3  # zero-block fraction at which block-ESOP wins
MIN_KERNEL_DIM = 8  # below this, padding overhead beats the kernels
# VMEM the fused kernels may claim, as the footprint model counts it (the
# double-buffered blocks, scratch and the largest product temporary, in the
# (8, 128)-tiled layout): three quarters of the 16 MiB scoped-VMEM limit
# the TPU compiler enforces by default, the rest left for the internal
# scratch the model does not count.
DEFAULT_VMEM_BUDGET = 12 * 1024 * 1024
# Per-shard stages below this many (batched) MACs run the einsum fallback:
# at these sizes the kernel launch + unfold padding overhead beats any
# streaming win (BENCH_distributed_engine D3_dense_32 measured the kernel
# path at 0.82x vs einsum before this break-even existed).
SHARDED_EINSUM_BREAKEVEN_MACS = 1 << 20
# Valid values of the ``fuse`` knob (build_plan / gemt3_planned):
#   None     auto — deepest fusion that models the fewest HBM bytes
#   True     force the deepest feasible fusion (triple, else pair)
#   False    never fuse (all-staged schedule)
#   "pair"   pair fusion only (never the whole-transform megakernel)
#   "triple" whole-transform fusion or nothing (no pair fallback)
FUSE_MODES = (None, True, False, "pair", "triple")


def _pow2_clamp(d: int, lo: int = 8, hi: int = 128) -> int:
    """Largest power of two <= d, clamped to [lo, hi]."""
    if d <= lo:
        return lo
    return min(hi, 1 << (int(d).bit_length() - 1))


def _pow2_ceil_clamp(d: int, lo: int = 8, hi: int = 128) -> int:
    """Smallest power of two >= d, clamped to [lo, hi].

    Tile choices that set the padding granularity round *up*: a 48-extent
    tiled at 64 is one padded block, while flooring to 32 pads to the same
    64 but fetches it in two visits (and the revisit factors multiply).
    """
    if d <= lo:
        return lo
    return min(hi, 1 << (int(d) - 1).bit_length())


def _pad_up(d: int, b: int) -> int:
    return -(-d // b) * b


# TPU tiling rule for a Pallas block (Mosaic refuses anything else): the
# last dim is a multiple of 128 lanes or spans the whole array dim, the
# second-to-last a multiple of 8 sublanes or the whole dim.  Every tile
# here is a power of two >= 8 or, for a staged stage's row tile ``bm``
# (:func:`stage_row_tile`), a multiple of 128, so the sublane half always
# holds.
LANE = 128


def lane_tile_ok(tile: int, extent: int) -> bool:
    """True when ``tile`` may be a block's lane (last) dim over ``extent``.

    Operands are zero-padded to a multiple of their tile, so a tile of at
    least the extent is one block spanning the whole padded dim.
    """
    return tile % LANE == 0 or tile >= extent


def _lane_padded(extent: int, lane_extent: int) -> bool:
    """True when a kernel layout would put ``extent``, narrower than a
    lane, into the minor dim of an operand whose tensor keeps a lane-wide
    minor extent (``lane_extent``).  HBM tiles the minor dim to 128 lanes,
    so that operand would take LANE/extent times the tensor's bytes, where
    an in-place einsum keeps its lane-dense layout."""
    return lane_extent >= LANE and extent < LANE


def _lane_tile(d: int, seed: int | None = None) -> int:
    """Lane-dim tile for extent ``d``: ``seed`` (an ESOP-aligned grid) when
    it obeys :func:`lane_tile_ok`, else the pow2 ceiling clamped to 128."""
    t = min(seed or LANE, _pow2_ceil_clamp(d))
    return t if lane_tile_ok(t, d) else _pow2_ceil_clamp(d)


def _fit_vmem(tiles: dict[str, int], footprint, vmem_budget: int
              ) -> dict[str, int] | None:
    """Halve the largest of ``bu``/``bnb``/``bnc`` until ``footprint(tiles)``
    fits ``vmem_budget``; None when even the floor tiles do not fit.

    Only the leading and sublane tiles shrink: the lane tiles (``bna``,
    ``bka``) are already the smallest lane-legal choice for their extent,
    so VMEM pressure can only come off the rows and the nb/nc slabs.
    ``bu`` is a leading block dim in every fused kernel, so it may go down
    to 1; ``bnb``/``bnc`` are sublane dims and stop at 8.
    """
    floor = {"bu": 1, "bnb": 8, "bnc": 8}
    while footprint(tiles) > vmem_budget:
        shrinkable = [k for k in floor if tiles.get(k, 0) > floor[k]]
        if not shrinkable:
            return None
        k = max(shrinkable, key=lambda k: tiles[k])
        tiles[k] = 1 << ((tiles[k] - 1).bit_length() - 1)
    return tiles


@dataclasses.dataclass(frozen=True)
class StagePlan:
    """One lowered mode-s contraction: ``(rows, N_s) @ (N_s, K_s)``.

    Under a mesh (``axis`` not None) the fields describe the *per-shard*
    GEMM: ``n`` is this device's slice of the contracted extent
    (``N_s / shards``), ``k`` stays the **full** output extent — the stage
    produces a partial sum that one ``psum_scatter`` over ``axis`` reduces
    and re-shards to ``k / shards`` local.  ``collective_bytes`` models
    that scatter's per-device ICI traffic.
    """

    mode: int  # which tensor mode (1, 2, 3) this stage contracts
    n: int  # contraction extent N_s (per-shard slice when sharded)
    k: int  # output extent K_s (always the full extent)
    rows: int  # unfolded GEMM rows (prod of untouched dims, excl. batch)
    backend: str  # "sr_gemm" | "esop" | "einsum"
    macs: int  # dense MACs = rows * n * k
    macs_effective: int  # after live-block scaling (== macs unless esop)
    zero_block_frac: float  # fraction of (bk, bn) blocks of C_s that are 0
    bm: int
    bn: int
    bk: int
    axis: AxisName = None  # mesh axis sharding this mode (None = local stage)
    shards: int = 1  # size of that axis (1 = unsharded)
    collective_bytes: int = 0  # modeled per-device psum_scatter ICI bytes
    accum: str = "plain"  # accumulation mode (engine/numerics.py)

    @property
    def k_local(self) -> int:
        """Per-shard output extent after the stage's psum_scatter."""
        return self.k // self.shards


@dataclasses.dataclass(frozen=True)
class FusedPairPlan:
    """Two consecutive stages fused into one kernel: ``(X ×_a C_a) ×_b C_b``.

    ``first`` indexes the pair's first stage within ``GemtPlan.order`` /
    ``.stages``; the two ``StagePlan`` entries it covers stay in the plan
    untouched — they are the documented (and runtime) staged fallback.
    """

    first: int  # index of the pair's first stage in the order (0 or 1)
    mode_a: int  # contracted first (innermost stream)
    mode_b: int  # contracted second (slab stream)
    rows: int  # untouched u-major GEMM rows U (excl. batch)
    na: int
    ka: int
    nb: int
    kb: int
    bu: int  # fused tile sizes (the autotunable triple is bu/bka/bnb)
    bka: int
    bnb: int
    bna: int
    kbp: int  # padded full-width Kb slab resident in VMEM
    vmem_bytes: int  # modeled on-chip footprint at these tiles
    hbm_bytes_staged: int  # modeled pair traffic if executed staged
    hbm_bytes_fused: int  # modeled pair traffic fused
    macs: int  # dense MACs of the two covered stages
    zero_block_frac_a: float
    zero_block_frac_b: float
    accum: str = "plain"  # accumulation mode (folds comp scratch into VMEM)

    @property
    def hbm_savings(self) -> float:
        """Staged-over-fused modeled HBM traffic ratio (>1 means fusing wins)."""
        return self.hbm_bytes_staged / max(self.hbm_bytes_fused, 1)


@dataclasses.dataclass(frozen=True)
class FusedTriplePlan:
    """All three stages fused into one whole-transform megakernel:
    ``Y = ((X ×_a C_a) ×_b C_b) ×_c C_c`` with both intermediates resident
    in VMEM (``kernels/fused3_gemt.py``).

    Covers the entire ``GemtPlan.order`` (there is no "first" index — the
    triple always starts at stage 0 and ends the schedule); the three
    ``StagePlan`` entries stay in the plan untouched as the staged
    fallback.  ``mode_a`` is contracted first (innermost stream, full 2D
    ESOP skipping), ``mode_b`` second and ``mode_c`` third (slab-resident,
    slab-level skipping).
    """

    mode_a: int
    mode_b: int
    mode_c: int
    rows: int  # untouched GEMM rows excl. batch — always 1 (all modes fuse)
    na: int
    ka: int
    nb: int
    kb: int
    nc: int
    kc: int
    bu: int  # fused tiles (the autotunable quadruple is bu/bka/bnb/bnc)
    bka: int
    bnb: int
    bnc: int
    bna: int
    kbp: int  # padded full-width Kb slab resident in VMEM
    kcp: int  # padded full-width Kc slab resident in VMEM
    vmem_bytes: int  # modeled on-chip footprint at these tiles
    hbm_bytes_staged: int  # modeled whole-schedule traffic executed staged
    hbm_bytes_fused: int  # modeled whole-schedule traffic fused
    macs: int  # dense MACs of the three covered stages (per sample)
    zero_block_frac_a: float
    zero_block_frac_b: float
    zero_block_frac_c: float
    accum: str = "plain"  # accumulation mode (folds comp scratch into VMEM)

    @property
    def hbm_savings(self) -> float:
        """Staged-over-fused modeled HBM traffic ratio (>1 means fusing wins)."""
        return self.hbm_bytes_staged / max(self.hbm_bytes_fused, 1)


@dataclasses.dataclass(frozen=True)
class GemtPlan:
    """A fully scheduled 3-stage GEMT: order + per-stage lowering choices."""

    order: tuple[int, int, int]
    stages: tuple[StagePlan, ...]
    in_shape: tuple[int, int, int]
    out_shape: tuple[int, int, int]
    macs: int  # total dense MACs over the three stages
    macs_effective: int  # with block-sparsity scaling
    peak_intermediate_bytes: int
    key: str  # cache key this plan was built under
    fused: FusedPairPlan | None = None  # stage pair run as one kernel
    fused3: FusedTriplePlan | None = None  # all 3 stages as one megakernel
    hbm_bytes_staged: int = 0  # modeled traffic of the all-staged schedule
    hbm_bytes_moved: int = 0  # modeled traffic of the planned schedule
    # --- topology (all defaults = single-device; byte fields above are
    # *per-shard* when a mesh is planned) ---
    axes: tuple[AxisName, AxisName, AxisName] = (None, None, None)
    shards: tuple[int, int, int] = (1, 1, 1)  # axis sizes per mode
    batch_axis: AxisName = None  # mesh axis sharding the leading batch dim
    batch_shards: int = 1
    collective_bytes: int = 0  # modeled per-device ICI bytes (psum_scatters)
    # Plan-time degradation record: fusion demotions (triple→pair→staged)
    # forced by the VMEM budget or the byte model, each with the numbers
    # that forced it, plus numerics_degradation accumulation escalations
    # (engine/numerics.py).  Replayed as info["events"] on every execution
    # of this (cached) plan — see docs/observability.md.
    events: tuple = ()
    # --- guarded numerics (engine/numerics.py, docs/numerics.md) ---
    accum: str = "plain"  # resolved accumulation mode (after budget walk)
    error_bound: float = 0.0  # a-priori staged-schedule rounding bound
    error_budget: float | None = None  # the knob the bound was held to

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["stages"] = [dataclasses.asdict(s) for s in self.stages]
        return d

    @property
    def backends(self) -> tuple[str, ...]:
        return tuple(s.backend for s in self.stages)


def mesh_axis_size(mesh, axis: AxisName) -> int:
    """Total device count of a (possibly tuple) mesh axis; 1 for None."""
    if mesh is None or axis is None:
        return 1
    names = axis if isinstance(axis, tuple) else (axis,)
    return math.prod(int(mesh.shape[a]) for a in names)


def normalize_axes(axes) -> tuple[AxisName, AxisName, AxisName]:
    """Canonicalize a 3-entry per-mode axis assignment (lists → tuples)."""
    if axes is None:
        return (None, None, None)
    axes = tuple(tuple(a) if isinstance(a, list) else a for a in axes)
    if len(axes) != 3:
        raise ValueError(f"axes must name one mesh axis per mode, got {axes}")
    return axes


def _is_traced(*arrays) -> bool:
    """True when any coefficient is an abstract tracer (planning under jit).

    Traced coefficients have shape/dtype but no host-readable values, so
    every zero-structure decision (ESOP backends, fusion masks, sparsity
    signatures) degrades to the dense assumption.
    """
    return any(isinstance(a, jax.core.Tracer) for a in arrays)


def macs_for_order(
    dims: tuple[int, int, int],
    ks: tuple[int, int, int],
    order: tuple[int, int, int],
) -> int:
    """Dense MAC count of staging ``order`` on input dims with C_s: N_s→K_s."""
    d = list(dims)
    total = 0
    for mode in order:
        rows = math.prod(d) // d[mode - 1]
        total += rows * dims[mode - 1] * ks[mode - 1]
        d[mode - 1] = ks[mode - 1]
    return total


def sparsity_signature(cs: dict[int, jnp.ndarray],
                       blocks: dict[int, tuple[int, int]]) -> str:
    """Stable digest of the coefficient matrices' block-zero structure.

    Two problems with the same shapes but different zero patterns must not
    share an autotune/plan cache entry — the ESOP schedule differs.
    Traced coefficients (planning under an outer jit) digest to a shared
    ``"traced"`` tag — correct because traced plans are dense-only, so they
    depend on nothing beyond shapes and dtype.
    """
    if _is_traced(*cs.values()):
        return "traced"
    h = hashlib.sha1()
    for mode in (1, 2, 3):
        c = cs[mode]
        bk, bn = blocks[mode]
        mask = np.asarray(_padded_block_mask(c, bk, bn))
        h.update(f"{mode}:{c.shape}:{bk}x{bn}:".encode())
        h.update(np.packbits(mask).tobytes())
    return h.hexdigest()[:16]


def _padded_block_mask(c: jnp.ndarray, bk: int, bn: int) -> jnp.ndarray:
    n, k = c.shape
    pad = ((0, (-n) % bk), (0, (-k) % bn))
    cp = jnp.pad(c, pad) if any(p[1] for p in pad) else c
    return block_nonzero_mask(cp, (bk, bn))


def _stage_blocks(rows: int, n: int, k: int,
                  block_sizes: tuple[int, int, int] | None) -> tuple[int, int, int]:
    if block_sizes is not None:
        return block_sizes
    # Default: MXU-aligned 128, shrunk to the pow2 ceiling of small
    # operands so block-sparsity detection and padding stay proportionate.
    # All three are lane-legal (the backward dispatches swap tile roles, so
    # bm becomes a lane dim there too).  build_plan then widens bm from
    # the VMEM model (stage_row_tile) once the accumulation mode is known.
    return (_lane_tile(rows), _lane_tile(k), _lane_tile(n))


def _plan_stage(
    mode: int,
    rows: int,
    c: jnp.ndarray,
    *,
    batch: int,
    esop_threshold: float,
    block_sizes: tuple[int, int, int] | None,
    mask_cache: dict[int, np.ndarray] | None = None,
    axis: AxisName = None,
    shards: int = 1,
    itemsize_total: int = 4,
    lane_extent: int = 0,
) -> StagePlan:
    n, k = c.shape
    if shards > 1:
        # Sharded contraction mode: the local GEMM contracts this device's
        # N_s/P slice of the coefficient rows into the FULL K_s extent (a
        # partial sum); one psum_scatter over `axis` then reduces and
        # re-shards it.  The slice is selected by axis_index at run time,
        # so its zero structure is device-dependent — block-ESOP (whose
        # schedule is host-side per-matrix) is off the table; the stage
        # runs sr_gemm or einsum.
        n = n // shards
    # The lowering folds any batch axis into the GEMM rows, so backend and
    # tile choices must see the batched row count (a large batch of skinny
    # tensors is still a big GEMM).  MAC fields stay per-sample: the batch
    # scales every order equally and cancels in the order search.
    rows_total = rows * max(batch, 1)
    bm, bn, bk = _stage_blocks(rows_total, n, k, block_sizes)
    dense_macs = rows * n * k
    # psum_scatter per-device ICI bytes: each device sends (P-1)/P of its
    # (rows, K_s) partial (itemsize_total folds the batch factor in).
    coll = (rows * k * itemsize_total * (shards - 1)) // shards

    if jnp.iscomplexobj(c):
        # The Pallas kernels are real-valued; DFT stages stay on einsum.
        return StagePlan(mode, n, k, rows, "einsum", dense_macs, dense_macs,
                         0.0, bm, bn, bk, axis, shards, coll)

    if shards > 1 or _is_traced(c):
        # Break-even fallback (sharded modes only): the per-shard GEMM of a
        # small serving tensor is too little work to amortize the kernel
        # dispatch + unfold padding, and the row slice rules out ESOP
        # anyway — the modeled size decides, not a hard-coded backend.
        # Off-TPU every sharded kernel stage is below break-even by
        # construction: the reference dispatch is the same matmul plus the
        # unfold's transpose copies, so einsum strictly dominates
        # (BENCH_distributed_engine D3 measured 0.82x before this existed).
        from ..kernels import ops
        below_breakeven = (shards > 1 and
                           (not ops.on_tpu()
                            or rows_total * n * k
                            < SHARDED_EINSUM_BREAKEVEN_MACS))
        backend = ("einsum" if below_breakeven
                   or min(rows_total, n, k) < MIN_KERNEL_DIM
                   else "sr_gemm")
        return StagePlan(mode, n, k, rows, backend, dense_macs, dense_macs,
                         0.0, bm, bn, bk, axis, shards, coll)

    # (bk, bn) depend only on C's shape, never on the stage order, so the
    # mask (a device pad + host sync) is shared across all six candidates.
    if mask_cache is not None and mode in mask_cache:
        mask = mask_cache[mode]
    else:
        mask = np.asarray(_padded_block_mask(c, bk, bn))
        if mask_cache is not None:
            mask_cache[mode] = mask
    zero_frac = 1.0 - float(mask.mean()) if mask.size else 0.0

    # A kernel stage unfolds its mode into the minor dim of its input and
    # output, where the tensor keeps ``lane_extent`` (mode 3 is there
    # already, and its output's minor extent is k).
    if (min(rows_total, n, k) < MIN_KERNEL_DIM
            or _lane_padded(n, lane_extent)
            or _lane_padded(k, k if mode == 3 else lane_extent)):
        backend = "einsum"
        eff = dense_macs
    elif zero_frac >= esop_threshold:
        backend = "esop"
        # Live blocks bound the executed MACs (block granularity on the
        # streamed C grid; rows scale both sides equally, so they stay
        # unpadded — padding them to bm would saturate the discount to
        # dense for small-row/batched stages).
        padded_c = _pad_up(n, bk) * _pad_up(k, bn)
        eff = min(dense_macs, int(rows * padded_c * float(mask.mean())))
    else:
        backend = "sr_gemm"
        eff = dense_macs
    return StagePlan(mode, n, k, rows, backend, dense_macs, eff, zero_frac,
                     bm, bn, bk)


def _plan_for_order(
    dims: tuple[int, int, int],
    cs: dict[int, jnp.ndarray],
    order: tuple[int, int, int],
    *,
    batch: int,
    itemsize: int,
    esop_threshold: float,
    block_sizes: tuple[int, int, int] | None,
    mask_cache: dict[int, np.ndarray] | None = None,
    axes: tuple[AxisName, AxisName, AxisName] = (None, None, None),
    shards: tuple[int, int, int] = (1, 1, 1),
) -> tuple[tuple[StagePlan, ...], int, int, int, int]:
    """Plan one order over the (per-shard) ``dims``; returns
    ``(stages, macs, macs_effective, peak_bytes, collective_bytes)``."""
    d = list(dims)
    stages = []
    peak_bytes = 0
    coll_bytes = 0
    for mode in order:
        rows = math.prod(d) // d[mode - 1]
        st = _plan_stage(mode, rows, cs[mode], batch=batch,
                         esop_threshold=esop_threshold,
                         block_sizes=block_sizes, mask_cache=mask_cache,
                         axis=axes[mode - 1], shards=shards[mode - 1],
                         itemsize_total=itemsize, lane_extent=d[2])
        stages.append(st)
        # A sharded stage materializes the full-K_s partial before the
        # scatter shrinks it to K_s/P local — that partial is the stage's
        # peak, not the post-scatter tensor.
        peak_bytes = max(peak_bytes, rows * st.k * itemsize)
        coll_bytes += st.collective_bytes
        d[mode - 1] = st.k_local
        peak_bytes = max(peak_bytes, math.prod(d) * itemsize)
    macs = sum(s.macs for s in stages)
    eff = sum(s.macs_effective for s in stages)
    return tuple(stages), macs, eff, peak_bytes, coll_bytes


def order_costs(
    dims: tuple[int, int, int],
    cs: dict[int, jnp.ndarray],
    *,
    batch: int = 1,
    itemsize: int = 4,
    esop_threshold: float = DEFAULT_ESOP_THRESHOLD,
    block_sizes: tuple[int, int, int] | None = None,
    mesh=None,
    axes=None,
) -> dict[tuple[int, int, int], dict]:
    """Cost-model summary for all six orders (introspection/benchmarks).

    With ``mesh``/``axes``, ``dims`` are the **global** extents; the
    summary reports per-shard MACs/bytes plus the modeled psum_scatter
    ``collective_bytes`` of each order.
    """
    out = {}
    axes = normalize_axes(axes)
    shards = tuple(mesh_axis_size(mesh, a) for a in axes)
    for mode in (1, 2, 3):
        if dims[mode - 1] % shards[mode - 1]:
            raise ValueError(
                f"mode-{mode} extent {dims[mode - 1]} not divisible by "
                f"axis {axes[mode - 1]!r} (size {shards[mode - 1]})")
    local = tuple(d // p for d, p in zip(dims, shards))
    mask_cache: dict[int, np.ndarray] = {}
    for order in itertools.permutations((1, 2, 3)):
        _, macs, eff, peak, coll = _plan_for_order(
            local, cs, order, batch=batch, itemsize=itemsize,
            esop_threshold=esop_threshold, block_sizes=block_sizes,
            mask_cache=mask_cache, axes=axes, shards=shards)
        out[order] = {"macs": macs, "macs_effective": eff,
                      "peak_intermediate_bytes": peak,
                      "collective_bytes": coll}
    return out


def _vmem(shape: tuple[int, ...], itemsize: int) -> int:
    """Bytes of one VMEM buffer of ``shape`` in Mosaic's tiled layout: the
    last two dims are padded to (8 rows × 32-bit packing, 128 lanes)."""
    *lead, sub, lane = shape
    rows = 8 * max(1, 4 // itemsize)
    return math.prod(lead) * _pad_up(sub, rows) * _pad_up(lane, LANE) * itemsize


def _gemm_vmem_bytes(bm: int, bn: int, bk: int, itemsize: int,
                    accum: str = "plain") -> int:
    """Modeled VMEM footprint of one SR-GEMM / block-ESOP launch.

    Counted as :func:`fused_vmem_bytes` counts the pair: double-buffered
    X ``(bm, bk)``, C ``(bk, bn)`` and output ``(bm, bn)`` blocks, the f32
    accumulator and the product temporary, plus the Neumaier comp scratch
    under ``accum="compensated"``, all in the (8, 128)-tiled layout.
    """
    out_isz = itemsize if accum == "plain" else 4
    tile = _vmem((bm, bn), 4)
    return (2 * _vmem((bm, bk), itemsize)
            + 2 * _vmem((bk, bn), itemsize)
            + 2 * _vmem((bm, bn), out_isz)
            + tile * (3 if accum == "compensated" else 2))


def stage_vmem_bytes(bm: int, bn: int, bk: int, itemsize: int,
                     accum: str = "plain") -> int:
    """Largest footprint of the three launches a staged stage's tiles run:
    the forward ``(bm, bn, bk)`` and the VJP's dX ``(bm, bk, bn)`` and dC
    ``(bk, bn, bm)`` (``kernels/ops.py:_sr_gemm_vjp``), where ``bm`` is the
    contraction and lane dim.  The backward accumulates plain, on a
    cotangent that is float32 under a promoted ``accum``."""
    g_isz = itemsize if accum == "plain" else 4
    return max(_gemm_vmem_bytes(bm, bn, bk, itemsize, accum),
               _gemm_vmem_bytes(bm, bk, bn, g_isz),
               _gemm_vmem_bytes(bk, bn, bm, g_isz))


def stage_row_tile(rows: int, batch: int, bn: int, bk: int, itemsize: int,
                   accum: str = "plain",
                   vmem_budget: int = DEFAULT_VMEM_BUDGET) -> int:
    """Row tile ``bm`` of a staged kernel stage over ``rows`` (per sample).

    The largest multiple of 128 that divides ``rows`` and whose
    :func:`stage_vmem_bytes` fits ``vmem_budget``: each grid step then
    does as much work as VMEM admits, and the rows need no padding copy at
    any batch (a bucketed plan runs smaller batches than it was planned
    for).  Rows that 128 does not divide keep the lane tile of the batched
    rows, and a budget too small for more keeps 128.  Being a multiple of
    128, ``bm`` stays lane-legal in the backward, where it is the lane dim.
    """
    if rows % LANE:
        return _lane_tile(rows * max(batch, 1))
    bm = LANE
    while (bm + LANE <= rows
           and stage_vmem_bytes(bm + LANE, bn, bk, itemsize, accum)
           <= vmem_budget):
        bm += LANE
    while rows % bm:
        bm -= LANE
    return bm


def fused_vmem_bytes(bu: int, bka: int, bnb: int, bna: int, kbp: int,
                     itemsize: int, accum: str = "plain") -> int:
    """Modeled VMEM footprint of the fused kernel at these tile sizes.

    Streamed operands and the output tile are double-buffered by the
    Pallas pipeline (×2); the stage-a partial and the output accumulator
    are fp32 scratch, and stage b's product is one more accumulator-sized
    fp32 temporary.  Every buffer is counted in its (8, 128)-tiled layout,
    which is what the TPU compiler allocates (a 64-wide last dim costs 128
    lanes).  ``accum="compensated"`` adds the Neumaier comp register
    mirroring the output accumulator (engine/numerics.py) — the footprint
    the budget ladder sees, so forcing compensation can itself demote
    fusion depth.
    """
    acc = _vmem((bu, bka, kbp), 4)
    out_isz = itemsize if accum == "plain" else 4
    return (2 * _vmem((bu, bnb, bna), itemsize)   # streamed X slab
            + 2 * _vmem((bna, bka), itemsize)     # streamed C_a block
            + 2 * _vmem((bnb, kbp), itemsize)     # resident C_b slab
            + _vmem((bu, bnb, bka), 4)            # stage-a partial (f32)
            + acc * (3 if accum == "compensated" else 2)  # acc, temp, comp
            + 2 * _vmem((bu, bka, kbp), out_isz))  # output tile


def fused_tile_sizes(
    rows_total: int, na: int, ka: int, nb: int, kb: int,
    itemsize: int, vmem_budget: int = DEFAULT_VMEM_BUDGET,
    start: tuple[int, int, int] | None = None,
    accum: str = "plain",
) -> tuple[int, int, int, int, int] | None:
    """Pick ``(bu, bka, bnb, bna, kbp)`` fitting the VMEM budget, or None.

    ``start`` optionally seeds ``(bka, bna, bnb)`` (the planner aligns them
    with the staged stages' ESOP block grids so sparse skipping composes);
    a lane seed that breaks :func:`lane_tile_ok` falls back to the default.
    Kb is not blocked (the accumulator holds the full padded slab width so
    stage b never revisits a partial), which is what bounds fusability:
    when no power-of-two shrink of ``bu``/``bnb`` fits, the pair must run
    staged.
    """
    kbp = kb_padded(kb)
    bka0, bna0, bnb0 = start if start is not None else (None, None, None)
    tiles = _fit_vmem({
        "bu": _pow2_clamp(rows_total),
        "bka": _lane_tile(ka, bka0),
        # bnb only sizes the on-chip partial (total traffic is bnb-
        # independent), so it starts small
        "bnb": min(bnb0 or 32, _pow2_ceil_clamp(nb, hi=32)),
        "bna": _lane_tile(na, bna0),
    }, lambda t: fused_vmem_bytes(t["bu"], t["bka"], t["bnb"], t["bna"], kbp,
                                  itemsize, accum), vmem_budget)
    if tiles is None:
        return None
    return tiles["bu"], tiles["bka"], tiles["bnb"], tiles["bna"], kbp


def fused3_vmem_bytes(bu: int, bka: int, bnb: int, bnc: int, bna: int,
                      kbp: int, kcp: int, itemsize: int,
                      accum: str = "plain") -> int:
    """Modeled VMEM footprint of the whole-transform megakernel.

    Counted as :func:`fused_vmem_bytes` counts the pair: double-buffered
    streamed operands and output tile, fp32 partials and accumulator, one
    accumulator-sized stage-3 product, all in the (8, 128)-tiled layout.
    The ``bu·bka·Kbp·Kcp`` accumulator terms dominate and are what bound
    triple fusability as the transform extents grow —
    ``accum="compensated"`` adds one more (the Neumaier comp register),
    the numerics lever that demotes triple → pair under a tight budget.
    """
    acc = _vmem((bu, bka, kbp, kcp), 4)
    out_isz = itemsize if accum == "plain" else 4
    return (2 * _vmem((bu, bnc, bnb, bna), itemsize)  # streamed X slab
            + 2 * _vmem((bna, bka), itemsize)         # streamed C_a block
            + 2 * _vmem((bnb, kbp), itemsize)         # resident C_b slab
            + 2 * _vmem((bnc, kcp), itemsize)         # resident C_c slab
            + _vmem((bu, bnc, bnb, bka), 4)           # stage-1 partial
            + _vmem((bu, bnc, bka, kbp), 4)           # stage-2 partial
            + acc * (3 if accum == "compensated" else 2)  # acc, temp, comp
            + 2 * _vmem((bu, bka, kbp, kcp), out_isz))  # output tile


def fused3_tile_sizes(
    rows_total: int, na: int, ka: int, nb: int, kb: int, nc: int, kc: int,
    itemsize: int, vmem_budget: int = DEFAULT_VMEM_BUDGET,
    start: tuple[int, int, int, int] | None = None,
    accum: str = "plain",
) -> tuple[int, int, int, int, int, int, int] | None:
    """Pick ``(bu, bka, bnb, bnc, bna, kbp, kcp)`` fitting the VMEM budget,
    or None.

    ``start`` optionally seeds ``(bka, bna, bnb, bnc)`` (the planner aligns
    them with the staged stages' ESOP block grids so sparse skipping
    composes).  Kb and Kc are not blocked (the partials/accumulator hold
    the full padded slab widths so stages 2–3 never revisit a partial);
    the lane tiles ``bka``/``bna`` are fixed by :func:`lane_tile_ok`, so
    ``bu``/``bnb``/``bnc`` take the VMEM pressure, floor 8.
    """
    kbp, kcp = kb_padded(kb), kb_padded(kc)
    bka0, bna0, bnb0, bnc0 = start if start is not None else (None,) * 4
    tiles = _fit_vmem({
        "bu": _pow2_clamp(rows_total),
        "bka": _lane_tile(ka, bka0),
        # bnb/bnc only size the on-chip partials (total traffic is
        # independent of both), so they start small
        "bnb": min(bnb0 or 16, _pow2_ceil_clamp(nb, hi=16)),
        "bnc": min(bnc0 or 16, _pow2_ceil_clamp(nc, hi=16)),
        "bna": _lane_tile(na, bna0),
    }, lambda t: fused3_vmem_bytes(t["bu"], t["bka"], t["bnb"], t["bnc"],
                                   t["bna"], kbp, kcp, itemsize, accum),
        vmem_budget)
    if tiles is None:
        return None
    return (tiles["bu"], tiles["bka"], tiles["bnb"], tiles["bnc"],
            tiles["bna"], kbp, kcp)


def _fused3_hbm_bytes(rows_total: int, ka: int,
                      tiles: tuple[int, int, int, int, int, int, int],
                      live_a: int, live_b: int, live_c: int,
                      itemsize: int) -> int:
    """Modeled HBM traffic of the megakernel (dense grid × live blocks).

    X and C_a are fetched once per live ``(j, t_c, t_b, t_a)`` step and
    u-block; C_b once per live slab and (i, j, t_c); C_c once per live
    slab and (i, j); both intermediates move zero bytes.  The only revisit
    factor is ``Ka/bka`` on X — the price of blocking one output mode so
    the accumulator fits VMEM.
    """
    bu, bka, bnb, bnc, bna, kbp, kcp = tiles
    u_p = _pad_up(rows_total, bu)
    ka_p = _pad_up(ka, bka)
    t_b = max(live_b, 1)
    t_c = max(live_c, 1)
    x_bytes = u_p * bnc * bnb * bna * live_a * t_b * t_c
    ca_bytes = (u_p // bu) * t_c * t_b * live_a * bna * bka
    cb_bytes = (u_p // bu) * (ka_p // bka) * t_c * t_b * bnb * kbp
    cc_bytes = (u_p // bu) * (ka_p // bka) * t_c * bnc * kcp
    y_bytes = u_p * ka_p * kbp * kcp
    return (x_bytes + ca_bytes + cb_bytes + cc_bytes + y_bytes) * itemsize


def stage_hbm_bytes(stage: StagePlan, batch: int, itemsize: int) -> int:
    """Modeled HBM traffic of one staged contraction.

    Kernel stages refetch X once per output column-block and C once per
    output row-block (the BlockSpec revisit factors); only ESOP stages
    skip zero C blocks — SR-GEMM streams every block regardless of the
    zero fraction.  The einsum fallback is modeled as a fully fused single
    pass.  ``itemsize`` is the raw element size (batch is folded into the
    rows here, unlike the planner's peak-bytes accounting).
    """
    rows = stage.rows * max(batch, 1)
    n, k = stage.n, stage.k
    if stage.backend == "einsum":
        return (rows * n + n * k + rows * k) * itemsize
    live = 1.0 - stage.zero_block_frac if stage.backend == "esop" else 1.0
    # ESOP skips the X fetch on dead steps too (the dead-step index repeats
    # the last live block, so the revisit is elided), hence both scale.
    x_bytes = int(rows * n * _ceil_div(k, stage.bn) * live)
    c_bytes = int(n * k * live) * _ceil_div(rows, stage.bm)
    return (x_bytes + c_bytes + rows * k) * itemsize


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def staged_pair_hbm_bytes(stage_a: StagePlan, stage_b: StagePlan,
                          batch: int, itemsize: int) -> int:
    """Modeled HBM traffic of running a consecutive pair staged.

    The inter-stage boundary costs a full read+write of the intermediate:
    the fold of one unfolding into the next is a ``moveaxis``+``reshape``
    transpose copy materialized between the two kernel launches.
    """
    t_elems = stage_a.rows * max(batch, 1) * stage_a.k
    return (stage_hbm_bytes(stage_a, batch, itemsize)
            + 2 * t_elems * itemsize
            + stage_hbm_bytes(stage_b, batch, itemsize))


def _fused_hbm_bytes(rows_total: int, ka: int,
                     tiles: tuple[int, int, int, int, int],
                     live_a: int, live_b: int, itemsize: int) -> int:
    """Modeled HBM traffic of the fused kernel (dense grid × live blocks).

    X and C_a are fetched once per live ``(j, t_b, t_a)`` step and u-block;
    C_b once per live slab and (i, j); the intermediate moves zero bytes.
    """
    bu, bka, bnb, bna, kbp = tiles
    u_p = _pad_up(rows_total, bu)
    ka_p = _pad_up(ka, bka)
    t_b = max(live_b, 1)
    x_bytes = u_p * bnb * bna * live_a * t_b
    ca_bytes = (u_p // bu) * t_b * live_a * bna * bka
    cb_bytes = (u_p // bu) * (ka_p // bka) * t_b * bnb * kbp
    y_bytes = u_p * ka_p * kbp
    return (x_bytes + ca_bytes + cb_bytes + y_bytes) * itemsize


def plan_hbm_bytes(stages: tuple[StagePlan, ...],
                   fused: FusedPairPlan | None,
                   batch: int, itemsize: int,
                   fused3: FusedTriplePlan | None = None) -> int:
    """Modeled HBM bytes of executing the schedule (with optional fusion).

    Every boundary between executed steps adds the intermediate's transpose
    copy; the fused pair replaces its two stages *and* their internal
    boundary with the fused kernel's traffic.  A ``fused3`` triple covers
    the whole schedule — its modeled traffic *is* the plan's.  Under a
    mesh the stage fields are per-shard, so the total is the per-device
    local HBM traffic (a sharded stage's boundary intermediate is its
    *post-scatter* ``k_local`` extent; the scatter's ICI bytes live in
    ``collective_bytes``, not here).
    """
    if fused3 is not None:
        return fused3.hbm_bytes_fused
    b = max(batch, 1)
    total = 0
    i = 0
    while i < len(stages):
        if fused is not None and i == fused.first:
            total += fused.hbm_bytes_fused
            nxt = i + 2
        else:
            total += stage_hbm_bytes(stages[i], batch, itemsize)
            nxt = i + 1
        if nxt < len(stages):
            total += (2 * stages[nxt - 1].rows * b
                      * stages[nxt - 1].k_local * itemsize)
        i = nxt
    return total


def refresh_fused_pair(fp: FusedPairPlan, ca: jnp.ndarray, cb: jnp.ndarray,
                       batch: int, itemsize: int) -> FusedPairPlan:
    """Recompute a FusedPairPlan's modeled accounting for its current tiles.

    The autotuner replaces (bu, bka, bnb) after planning; the VMEM
    footprint, fused HBM bytes and block masks must follow, or the
    reported numbers describe a configuration that never ran.
    """
    rows_total = fp.rows * max(batch, 1)
    mask_a = np.asarray(_padded_block_mask(ca, fp.bna, fp.bka))
    mask_b = np.asarray(_padded_block_mask(cb, fp.bnb, fp.kbp))
    live_a, dense_a = int(mask_a.sum()), max(mask_a.size, 1)
    live_b, dense_b = int(mask_b.sum()), max(mask_b.size, 1)
    tiles = (fp.bu, fp.bka, fp.bnb, fp.bna, fp.kbp)
    return dataclasses.replace(
        fp,
        vmem_bytes=fused_vmem_bytes(*tiles, itemsize, fp.accum),
        hbm_bytes_fused=_fused_hbm_bytes(rows_total, fp.ka, tiles, live_a,
                                         live_b, itemsize),
        zero_block_frac_a=1.0 - live_a / dense_a,
        zero_block_frac_b=1.0 - live_b / dense_b,
    )


def refresh_fused_triple(ft: FusedTriplePlan, ca: jnp.ndarray,
                         cb: jnp.ndarray, cc: jnp.ndarray,
                         batch: int, itemsize: int) -> FusedTriplePlan:
    """Recompute a FusedTriplePlan's modeled accounting for its current tiles.

    The autotuner replaces (bu, bka, bnb, bnc) after planning; the VMEM
    footprint, fused HBM bytes and block masks must follow, or the
    reported numbers describe a configuration that never ran.
    """
    rows_total = ft.rows * max(batch, 1)
    mask_a = np.asarray(_padded_block_mask(ca, ft.bna, ft.bka))
    mask_b = np.asarray(_padded_block_mask(cb, ft.bnb, ft.kbp))
    mask_c = np.asarray(_padded_block_mask(cc, ft.bnc, ft.kcp))
    live_a, dense_a = int(mask_a.sum()), max(mask_a.size, 1)
    live_b, dense_b = int(mask_b.sum()), max(mask_b.size, 1)
    live_c, dense_c = int(mask_c.sum()), max(mask_c.size, 1)
    tiles = (ft.bu, ft.bka, ft.bnb, ft.bnc, ft.bna, ft.kbp, ft.kcp)
    return dataclasses.replace(
        ft,
        vmem_bytes=fused3_vmem_bytes(*tiles, itemsize, ft.accum),
        hbm_bytes_fused=_fused3_hbm_bytes(rows_total, ft.ka, tiles, live_a,
                                          live_b, live_c, itemsize),
        zero_block_frac_a=1.0 - live_a / dense_a,
        zero_block_frac_b=1.0 - live_b / dense_b,
        zero_block_frac_c=1.0 - live_c / dense_c,
    )


def _plan_fusion3(
    order: tuple[int, int, int],
    stages: tuple[StagePlan, ...],
    cs: dict[int, jnp.ndarray],
    *,
    batch: int,
    itemsize: int,
    vmem_budget: int,
    force: bool,
    axes: tuple[AxisName, AxisName, AxisName] = (None, None, None),
    events: list | None = None,
    accum: str = "plain",
) -> FusedTriplePlan | None:
    """Evaluate fusing the whole three-stage transform into the megakernel.

    All six (a, b, c) mode assignments are scored — the a-stream carries
    full 2D ESOP skipping while b/c get slab-level skipping only, so a
    block-sparse coefficient matrix wants the a slot — and the one moving
    the fewest modeled HBM bytes (MACs break ties) wins.  Returns the
    candidate when it is kernel-capable, fits the VMEM budget and (unless
    ``force``) moves strictly fewer modeled bytes than the all-staged
    schedule; None declines and the planner degrades to pair fusion.

    **Fusion-under-sharding rule**: every mode must be shard-local — the
    megakernel has no collective anywhere inside, and a sharded mode's
    contraction needs its psum_scatter between stages.  A sharded *batch*
    axis is fine (the rows just split).  Traced coefficients and complex
    dtypes decline as for the pair.  ``rows_total`` (= the local batch) is
    exempt from the MIN_KERNEL_DIM floor: the u-padding cost is already in
    the byte model, which decides honestly.
    """
    if any(a is not None for a in axes):
        return None  # a sharded mode needs its collective between stages
    if _is_traced(*cs.values()):
        return None
    if any(jnp.iscomplexobj(c) for c in cs.values()):
        return None  # DFT stages stay on einsum — the kernel is real-valued
    rows_total = max(batch, 1)
    stage_of = {s.mode: s for s in stages}
    staged = plan_hbm_bytes(stages, None, batch, itemsize)

    best = None
    vmem_floors = []  # minimal-tile footprints of VMEM-declined candidates
    for mode_a, mode_b, mode_c in itertools.permutations((1, 2, 3)):
        ca, cb, cc = cs[mode_a], cs[mode_b], cs[mode_c]
        na, ka = ca.shape
        nb, kb = cb.shape
        nc, kc = cc.shape
        if min(na, ka, nb, kb, nc, kc) < MIN_KERNEL_DIM:
            continue  # padding overhead beats the kernel
        if not force and (_lane_padded(na, cs[3].shape[0])
                          or _lane_padded(kc, cs[3].shape[1])):
            continue  # X4's minor dim is na, Y's is kc
        st_a = stage_of[mode_a]
        tiles = fused3_tile_sizes(
            rows_total, na, ka, nb, kb, nc, kc, itemsize, vmem_budget,
            start=(st_a.bn if st_a.zero_block_frac > 0 else None,
                   st_a.bk if st_a.zero_block_frac > 0 else None,
                   None, None),
            accum=accum)
        if tiles is None:
            # no tiling keeps both partials on-chip: record the footprint
            # at the floor tiles (bu = 1, bnb = bnc = 8, the lane tiles) —
            # the smallest this assignment could ever need vs the budget
            vmem_floors.append(fused3_vmem_bytes(
                1, _lane_tile(ka), 8, 8, _lane_tile(na), kb_padded(kb),
                kb_padded(kc), itemsize, accum))
            continue
        bu, bka, bnb, bnc, bna, kbp, kcp = tiles
        mask_a = np.asarray(_padded_block_mask(ca, bna, bka))
        mask_b = np.asarray(_padded_block_mask(cb, bnb, kbp))
        mask_c = np.asarray(_padded_block_mask(cc, bnc, kcp))
        live_a, dense_a = int(mask_a.sum()), max(mask_a.size, 1)
        live_b, dense_b = int(mask_b.sum()), max(mask_b.size, 1)
        live_c, dense_c = int(mask_c.sum()), max(mask_c.size, 1)
        fused = _fused3_hbm_bytes(rows_total, ka, tiles, live_a, live_b,
                                  live_c, itemsize)
        macs = nc * nb * na * ka + nc * ka * nb * kb + ka * kb * nc * kc
        cand = FusedTriplePlan(
            mode_a=mode_a, mode_b=mode_b, mode_c=mode_c, rows=1,
            na=na, ka=ka, nb=nb, kb=kb, nc=nc, kc=kc,
            bu=bu, bka=bka, bnb=bnb, bnc=bnc, bna=bna, kbp=kbp, kcp=kcp,
            vmem_bytes=fused3_vmem_bytes(*tiles, itemsize, accum),
            hbm_bytes_staged=staged, hbm_bytes_fused=fused, macs=macs,
            zero_block_frac_a=1.0 - live_a / dense_a,
            zero_block_frac_b=1.0 - live_b / dense_b,
            zero_block_frac_c=1.0 - live_c / dense_c,
            accum=accum,
        )
        if best is None or ((cand.hbm_bytes_fused, cand.macs)
                            < (best.hbm_bytes_fused, best.macs)):
            best = cand
    if best is None:
        if events is not None and vmem_floors:
            events.append({
                "kind": "fusion_degradation", "from": "triple",
                "reason": "vmem_budget",
                "vmem_bytes_min": min(vmem_floors),
                "vmem_budget": vmem_budget,
            })
        return None
    if not force and best.hbm_bytes_fused >= staged:
        if events is not None:
            events.append({
                "kind": "fusion_degradation", "from": "triple",
                "reason": "byte_model",
                "hbm_bytes_fused": best.hbm_bytes_fused,
                "hbm_bytes_staged": staged,
                "vmem_bytes": best.vmem_bytes,
                "vmem_budget": vmem_budget,
            })
        return None
    return best


def _plan_fusion(
    first: int,
    order: tuple[int, int, int],
    stages: tuple[StagePlan, ...],
    dims: tuple[int, int, int],
    cs: dict[int, jnp.ndarray],
    *,
    batch: int,
    itemsize: int,
    vmem_budget: int,
    force: bool,
    axes: tuple[AxisName, AxisName, AxisName] = (None, None, None),
    shards: tuple[int, int, int] = (1, 1, 1),
    events: list | None = None,
    accum: str = "plain",
) -> FusedPairPlan | None:
    """Evaluate fusing the consecutive pair starting at stage ``first``.

    The kernel is algebraically symmetric in which mode streams as C_a
    (2D-blocked, full ESOP skipping) vs C_b (slab-resident, slab-level
    skipping only), so both assignments are scored and the one moving
    fewer modeled bytes wins — a block-sparse coefficient matrix lands on
    the a-stream where its zero blocks are never fetched.  Returns the
    candidate when it is kernel-capable, fits the VMEM budget and (unless
    ``force``) moves strictly fewer modeled HBM bytes than the staged
    pair; None declines.

    **Fusion-under-sharding rule**: both modes of the pair must be
    shard-local (``axes[m-1] is None``).  A sharded mode's contraction
    needs a psum_scatter between the two stages, and the fused kernel has
    no collective inside — fusing across it would silently drop the
    cross-device partial sums.  Traced coefficients also decline (the
    fused kernel's ESOP prefetch schedules need host-readable values).
    """
    pair = (order[first], order[first + 1])
    if any(axes[m - 1] is not None for m in pair):
        return None  # sharded mode: a collective must run between stages
    if _is_traced(*(cs[m] for m in pair)):
        return None
    if any(jnp.iscomplexobj(cs[m]) for m in pair):
        return None  # DFT stages stay on einsum — the kernel is real-valued
    d = list(dims)
    for m in order[:first]:
        d[m - 1] = cs[m].shape[1] // shards[m - 1]
    rows = math.prod(d) // (d[pair[0] - 1] * d[pair[1] - 1])
    rows_total = rows * max(batch, 1)
    stage_of = {stages[first].mode: stages[first],
                stages[first + 1].mode: stages[first + 1]}
    staged = staged_pair_hbm_bytes(stages[first], stages[first + 1], batch,
                                   itemsize)

    best = None
    vmem_floors = []  # minimal-tile footprints of VMEM-declined candidates
    for mode_a, mode_b in (pair, pair[::-1]):
        ca, cb = cs[mode_a], cs[mode_b]
        na, ka = ca.shape
        nb, kb = cb.shape
        if min(rows_total, na, ka, nb, kb) < MIN_KERNEL_DIM:
            continue  # padding overhead beats the kernel, as for single stages
        if not force and (_lane_padded(na, d[2]) or _lane_padded(
                kb, cs[3].shape[1] if 3 in pair else d[2])):
            continue  # X3's minor dim is na, Y's is kb
        # For *sparse* coefficients, seed the streamed-side grid from the
        # staged stage's ESOP blocks so the fused mask sees the same zero
        # structure the planner scored; dense stages take the pow2-ceil
        # defaults (one padded block per visit, no extra revisit factor).
        st_a, st_b = stage_of[mode_a], stage_of[mode_b]
        sparse_a = st_a.zero_block_frac > 0
        tiles = fused_tile_sizes(
            rows_total, na, ka, nb, kb, itemsize, vmem_budget,
            start=(st_a.bn if sparse_a else None,
                   st_a.bk if sparse_a else None,
                   st_b.bk if st_b.zero_block_frac > 0 else None),
            accum=accum)
        if tiles is None:
            # no tiling keeps the resident slab on-chip: record the floor
            # footprint (bu = 1, bnb = 8, the lane tiles) vs the budget
            vmem_floors.append(
                fused_vmem_bytes(1, _lane_tile(ka), 8, _lane_tile(na),
                                 kb_padded(kb), itemsize, accum))
            continue
        bu, bka, bnb, bna, kbp = tiles
        mask_a = np.asarray(_padded_block_mask(ca, bna, bka))
        mask_b = np.asarray(_padded_block_mask(cb, bnb, kbp))
        live_a, dense_a = int(mask_a.sum()), max(mask_a.size, 1)
        live_b, dense_b = int(mask_b.sum()), max(mask_b.size, 1)
        fused = _fused_hbm_bytes(rows_total, ka, tiles, live_a, live_b,
                                 itemsize)
        cand = FusedPairPlan(
            first=first, mode_a=mode_a, mode_b=mode_b, rows=rows,
            na=na, ka=ka, nb=nb, kb=kb,
            bu=bu, bka=bka, bnb=bnb, bna=bna, kbp=kbp,
            vmem_bytes=fused_vmem_bytes(bu, bka, bnb, bna, kbp, itemsize,
                                        accum),
            hbm_bytes_staged=staged, hbm_bytes_fused=fused,
            macs=rows * (nb * na * ka + nb * ka * kb),
            zero_block_frac_a=1.0 - live_a / dense_a,
            zero_block_frac_b=1.0 - live_b / dense_b,
            accum=accum,
        )
        if best is None or cand.hbm_bytes_fused < best.hbm_bytes_fused:
            best = cand
    if best is None:
        if events is not None and vmem_floors:
            events.append({
                "kind": "fusion_degradation", "from": "pair",
                "reason": "vmem_budget", "first": first,
                "vmem_bytes_min": min(vmem_floors),
                "vmem_budget": vmem_budget,
            })
        return None
    if not force and best.hbm_bytes_fused >= staged:
        if events is not None:
            events.append({
                "kind": "fusion_degradation", "from": "pair",
                "reason": "byte_model", "first": first,
                "hbm_bytes_fused": best.hbm_bytes_fused,
                "hbm_bytes_staged": staged,
                "vmem_bytes": best.vmem_bytes,
                "vmem_budget": vmem_budget,
            })
        return None
    return best


def derive_adjoint_plan(
    plan: GemtPlan,
    g_shape: tuple[int, ...],
    g_dtype,
    c1t: jnp.ndarray,
    c2t: jnp.ndarray,
    c3t: jnp.ndarray,
    *,
    esop_threshold: float = DEFAULT_ESOP_THRESHOLD,
    block_sizes: tuple[int, int, int] | None = None,
    fuse: bool | str | None = None,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
    mesh=None,
) -> GemtPlan:
    """Plan the backward 3D-GEMT of ``plan`` — the X-cotangent problem.

    The VJP of ``Y = X ×₁C1 ×₂C2 ×₃C3`` with respect to X is itself a
    three-stage GEMT over the transposed coefficient matrices,
    ``dX = g ×₁C1ᵀ ×₂C2ᵀ ×₃C3ᵀ`` (for the paper's orthonormal transforms,
    §2.2, ``Cᵀ = C⁻¹`` — the backward pass *is* the inverse transform) —
    so it re-enters the same planner, fusion tiers, ESOP schedules and
    autotune caches as any forward problem.

    The stage order is **pinned to the reverse of the forward order**, not
    searched: the adjoint chain's intermediates ``g_i`` (cotangents of the
    forward stage boundaries) are exactly what the three coefficient
    cotangents contract against, and only the reversed order produces
    them.  It is also the cost-symmetric choice — compressive forward
    modes (planned early) become expansive adjoint modes (planned late).

    Topology: the adjoint inherits the forward plan's ``axes`` and
    ``batch_axis`` verbatim — the cotangent carries the output's sharding,
    which equals the input's (the stationary-tensor invariant), and the
    forward divisibility checks (N_s *and* K_s divide the axis) already
    guarantee the adjoint's.  The derived plan's key is the forward key
    plus an ``|adjoint`` tag, so forward and backward programs share the
    plan cache without colliding.
    """
    adj = build_plan(
        g_shape, g_dtype, c1t, c2t, c3t, order=plan.order[::-1],
        esop_threshold=esop_threshold, block_sizes=block_sizes, fuse=fuse,
        vmem_budget=vmem_budget, mesh=mesh,
        axes=plan.axes if mesh is not None else None,
        batch_axis=plan.batch_axis if mesh is not None else None,
        accum=plan.accum, error_budget=plan.error_budget)
    return dataclasses.replace(adj, key=plan.key + "|adjoint")


def chain_vmem_bytes(bu: int, bka: int, bnb: int, bna: int, kbp: int,
                     itemsize: int, accum: str = "plain") -> int:
    """Modeled VMEM footprint of the chain-pair kernel at these tiles.

    The fused-pair footprint plus the double-buffered ``y1`` output tile:
    emitting the intermediate costs one extra ``(bu, bnb, bka)`` output
    window, nothing else — the partial it is copied from already exists.
    """
    return (fused_vmem_bytes(bu, bka, bnb, bna, kbp, itemsize, accum)
            + 2 * _vmem((bu, bnb, bka), itemsize))


def chain_tile_sizes(
    rows_total: int, na: int, ka: int, nb: int, kb: int,
    itemsize: int, vmem_budget: int = DEFAULT_VMEM_BUDGET,
    accum: str = "plain",
) -> tuple[int, int, int, int, int] | None:
    """Pick ``(bu, bka, bnb, bna, kbp)`` for the chain-pair kernel, or None.

    Same ladder as :func:`fused_tile_sizes` under the chain
    footprint (:func:`chain_vmem_bytes`).  No ESOP seeds: the chain's b
    stream is dense by construction (every emitted ``y1`` block must be
    written), so only the a-side compaction applies and the default
    lattice is the right one.
    """
    kbp = kb_padded(kb)
    tiles = _fit_vmem({
        "bu": _pow2_clamp(rows_total),
        "bka": _lane_tile(ka),
        "bnb": _pow2_ceil_clamp(nb, hi=32),
        "bna": _lane_tile(na),
    }, lambda t: chain_vmem_bytes(t["bu"], t["bka"], t["bnb"], t["bna"], kbp,
                                  itemsize, accum), vmem_budget)
    if tiles is None:
        return None
    return tiles["bu"], tiles["bka"], tiles["bnb"], tiles["bna"], kbp


def chain3_vmem_bytes(bu: int, bka: int, bnb: int, bnc: int, bna: int,
                      kbp: int, kcp: int, itemsize: int,
                      accum: str = "plain") -> int:
    """Modeled VMEM footprint of the chain-triple kernel at these tiles.

    The megakernel footprint plus the double-buffered ``y1`` and ``y2``
    output tiles — the price of emitting both intermediates, and what
    makes the chain triple degrade to the pair earlier than the forward
    triple does (the documented N=64 boundary).
    """
    return (fused3_vmem_bytes(bu, bka, bnb, bnc, bna, kbp, kcp, itemsize,
                              accum)
            + 2 * _vmem((bu, bnc, bnb, bka), itemsize)
            + 2 * _vmem((bu, bnc, bka, kbp), itemsize))


def chain3_tile_sizes(
    rows_total: int, na: int, ka: int, nb: int, kb: int, nc: int, kc: int,
    itemsize: int, vmem_budget: int = DEFAULT_VMEM_BUDGET,
    accum: str = "plain",
) -> tuple[int, int, int, int, int, int, int] | None:
    """Pick ``(bu, bka, bnb, bnc, bna, kbp, kcp)`` for the chain triple,
    or None — the :func:`fused3_tile_sizes` ladder under the chain
    footprint (:func:`chain3_vmem_bytes`)."""
    kbp, kcp = kb_padded(kb), kb_padded(kc)
    tiles = _fit_vmem({
        "bu": _pow2_clamp(rows_total),
        "bka": _lane_tile(ka),
        "bnb": _pow2_ceil_clamp(nb, hi=16),
        "bnc": _pow2_ceil_clamp(nc, hi=16),
        "bna": _lane_tile(na),
    }, lambda t: chain3_vmem_bytes(t["bu"], t["bka"], t["bnb"], t["bnc"],
                                   t["bna"], kbp, kcp, itemsize, accum),
        vmem_budget)
    if tiles is None:
        return None
    return (tiles["bu"], tiles["bka"], tiles["bnb"], tiles["bnc"],
            tiles["bna"], kbp, kcp)


def _chain_hbm_bytes(rows_total: int, ka: int, nb: int,
                     tiles: tuple[int, int, int, int, int],
                     live_a: int, itemsize: int) -> int:
    """Modeled HBM traffic of the chain-pair kernel.

    The fused-pair traffic at a **dense** b stream (every slab is live —
    the emitted intermediate forbids slab skipping) plus the single write
    of ``y1``: the intermediate crosses HBM once as a result, against the
    staged pair's write+transpose-read round-trip.
    """
    bu, bka, bnb, bna, kbp = tiles
    t_b = _pad_up(nb, bnb) // bnb
    u_p = _pad_up(rows_total, bu)
    ka_p = _pad_up(ka, bka)
    y1_bytes = u_p * t_b * bnb * ka_p * itemsize
    return (_fused_hbm_bytes(rows_total, ka, tiles, live_a, t_b, itemsize)
            + y1_bytes)


def _chain3_hbm_bytes(rows_total: int, ka: int, nb: int, nc: int,
                      tiles: tuple[int, int, int, int, int, int, int],
                      live_a: int, itemsize: int) -> int:
    """Modeled HBM traffic of the chain-triple kernel: megakernel traffic
    at dense b/c streams plus the single writes of ``y1`` and ``y2``."""
    bu, bka, bnb, bnc, bna, kbp, kcp = tiles
    t_b = _pad_up(nb, bnb) // bnb
    t_c = _pad_up(nc, bnc) // bnc
    u_p = _pad_up(rows_total, bu)
    ka_p = _pad_up(ka, bka)
    y1_bytes = u_p * t_c * bnc * t_b * bnb * ka_p
    y2_bytes = u_p * t_c * bnc * ka_p * kbp
    return (_fused3_hbm_bytes(rows_total, ka, tiles, live_a, t_b, t_c,
                              itemsize)
            + (y1_bytes + y2_bytes) * itemsize)


@dataclasses.dataclass(frozen=True)
class AdjointChainPlan:
    """The backward walk's fusion schedule, derived from a forward plan and
    its adjoint plan (``plan_adjoint_chain``).

    ``depth`` is how many of the three adjoint stages run inside one chain
    launch: 3 (chain triple — ``dX`` plus both cotangent intermediates in
    one ``pallas_call``), 2 (chain pair plus one staged tail stage), or 0
    (the walk stays on the legacy staged schedule).  ``rec_fused`` says
    whether the forward-prefix recompute (``y1``, ``y2``) runs as one
    chain-pair launch instead of two staged ones.  ``launches`` is the
    predicted backward kernel-launch count including the batched
    coefficient-cotangent launch — the number the G1 bench gates.
    """

    depth: int  # 3 | 2 fused adjoint stages, 0 = staged backward walk
    rec_fused: bool  # recompute prefix fused into one chain-pair launch
    launches: int  # predicted backward launches (recompute + chain + coeff)
    modes: tuple  # adjoint stage order (= forward order reversed)
    rec_modes: tuple  # recompute chain modes (forward order[:2])
    tiles: tuple | None  # chain kernel tiles (None when depth == 0)
    rec_tiles: tuple | None  # recompute chain-pair tiles
    vmem_bytes: int  # chain kernel footprint at those tiles
    rec_vmem_bytes: int
    hbm_bytes_staged: int  # adjoint plan's modeled all-staged traffic
    hbm_bytes_fused: int  # modeled chain traffic (+ staged tail at depth 2)
    events: tuple = ()  # adjoint_fusion_degradation records


def plan_adjoint_chain(
    plan: GemtPlan,
    adj: GemtPlan,
    g_shape: tuple[int, ...],
    g_dtype,
    *,
    fuse: bool | str | None = None,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
) -> AdjointChainPlan:
    """Extend the pair/triple fusion decision to the backward walk.

    Scores fusing the adjoint chain ``dX = g ×C₃ᵀ ×C₂ᵀ ×C₁ᵀ`` into one
    chain-triple launch (emitting the cotangent intermediates ``g1, g2``
    for the coefficient cotangents) or a chain-pair launch plus a staged
    tail, and fusing the forward-prefix recompute ``y1, y2`` into one
    chain-pair launch.  The same VMEM ladder and HBM byte model as the
    forward fusion tiers decide (``_chain_hbm_bytes`` vs the adjoint
    plan's staged traffic), honoring the ``fuse`` knob (``False`` pins the
    legacy staged walk; ``"pair"``/``"triple"``/``True`` force tiers).

    The chain's mode assignment is **pinned to the adjoint stage order**
    (not permutation-searched like the forward triple): the emitted
    intermediates must be the stage-boundary cotangents, and only the
    stage-order assignment produces them.  Sharded plans decline — the
    chain has no collective inside, and each sharded adjoint stage needs
    its psum_scatter (the sharded walk keeps its one-program schedule).
    Einsum-pinned adjoint stages (complex DFT factors, tiny extents)
    decline too: the planner already judged those modes kernel-hostile.
    """
    itemsize = jnp.dtype(g_dtype).itemsize
    batch = g_shape[0] if len(g_shape) == 4 else 1
    rows_total = max(batch, 1)
    events: list = []
    modes = tuple(adj.order)
    rec_modes = (plan.order[0], plan.order[1])
    # Chain footprints inherit the plans' accumulation modes: the comp
    # scratch of a compensated walk is real VMEM the ladder must budget.
    accum = adj.accum
    rec_accum = plan.accum
    sharded = (any(a is not None for a in plan.axes)
               or plan.batch_axis is not None)

    def declined(reason_events=()):
        return AdjointChainPlan(
            depth=0, rec_fused=False, launches=2 + 3 + 3, modes=modes,
            rec_modes=rec_modes, tiles=None, rec_tiles=None, vmem_bytes=0,
            rec_vmem_bytes=0, hbm_bytes_staged=adj.hbm_bytes_staged,
            hbm_bytes_fused=0, events=tuple(reason_events))

    if fuse is False or sharded:
        return declined()
    a0, a1, a2 = adj.stages
    if a0.backend == "einsum" or a1.backend == "einsum":
        return declined()

    # Recompute-prefix feasibility is independent of the adjoint depth.
    s0, s1 = plan.stages[0], plan.stages[1]
    rec_rows = rows_total * plan.stages[2].n
    rec_fused, rec_tiles, rec_vmem = False, None, 0
    if (s0.backend != "einsum" and s1.backend != "einsum"
            and min(rec_rows, s0.n, s0.k, s1.n, s1.k) >= MIN_KERNEL_DIM):
        rt = chain_tile_sizes(rec_rows, s0.n, s0.k, s1.n, s1.k, itemsize,
                              vmem_budget, accum=rec_accum)
        if rt is not None:
            # One launch, no inter-stage round-trip: always fewer bytes
            # than the staged recompute pair — no byte compare needed.
            rec_fused, rec_tiles = True, rt
            rec_vmem = chain_vmem_bytes(*rt, itemsize, rec_accum)

    def live_a_blocks(stage, bna, bka):
        dense = ((_pad_up(stage.n, bna) // bna)
                 * (_pad_up(stage.k, bka) // bka))
        return max(1, round(dense * (1.0 - stage.zero_block_frac)))

    # Depth 3: the whole adjoint chain in one chain-triple launch.
    if (fuse in (None, True, "triple") and a2.backend != "einsum"
            and min(a0.n, a0.k, a1.n, a1.k, a2.n, a2.k) >= MIN_KERNEL_DIM):
        t3 = chain3_tile_sizes(rows_total, a0.n, a0.k, a1.n, a1.k,
                               a2.n, a2.k, itemsize, vmem_budget,
                               accum=accum)
        if t3 is None:
            events.append({
                "kind": "adjoint_fusion_degradation", "from": "triple",
                "reason": "vmem_budget",
                "vmem_bytes_min": chain3_vmem_bytes(
                    1, _lane_tile(a0.k), 8, 8, _lane_tile(a0.n),
                    kb_padded(a1.k), kb_padded(a2.k), itemsize, accum),
                "vmem_budget": vmem_budget,
            })
        else:
            fused_bytes = _chain3_hbm_bytes(
                rows_total, a0.k, a1.n, a2.n, t3,
                live_a_blocks(a0, t3[4], t3[1]), itemsize)
            if fuse in (True, "triple") or fused_bytes < adj.hbm_bytes_staged:
                return AdjointChainPlan(
                    depth=3, rec_fused=rec_fused,
                    launches=(1 if rec_fused else 2) + 1 + 1,
                    modes=modes, rec_modes=rec_modes, tiles=t3,
                    rec_tiles=rec_tiles,
                    vmem_bytes=chain3_vmem_bytes(*t3, itemsize, accum),
                    rec_vmem_bytes=rec_vmem,
                    hbm_bytes_staged=adj.hbm_bytes_staged,
                    hbm_bytes_fused=fused_bytes, events=tuple(events))
            events.append({
                "kind": "adjoint_fusion_degradation", "from": "triple",
                "reason": "byte_model", "hbm_bytes_fused": fused_bytes,
                "hbm_bytes_staged": adj.hbm_bytes_staged,
                "vmem_budget": vmem_budget,
            })
    if fuse == "triple":
        return declined(events)

    # Depth 2: chain pair over the first two adjoint stages + staged tail.
    rows2 = rows_total * a2.n
    if min(rows2, a0.n, a0.k, a1.n, a1.k) >= MIN_KERNEL_DIM:
        t2 = chain_tile_sizes(rows2, a0.n, a0.k, a1.n, a1.k, itemsize,
                              vmem_budget, accum=accum)
        if t2 is None:
            events.append({
                "kind": "adjoint_fusion_degradation", "from": "pair",
                "reason": "vmem_budget",
                "vmem_bytes_min": chain_vmem_bytes(
                    1, _lane_tile(a0.k), 8, _lane_tile(a0.n),
                    kb_padded(a1.k), itemsize, accum),
                "vmem_budget": vmem_budget,
            })
            return declined(events)
        fused_bytes = (_chain_hbm_bytes(rows2, a0.k, a1.n, t2,
                                        live_a_blocks(a0, t2[3], t2[1]),
                                        itemsize)
                       + stage_hbm_bytes(a2, batch, itemsize))
        if fuse in (True, "pair") or fused_bytes < adj.hbm_bytes_staged:
            return AdjointChainPlan(
                depth=2, rec_fused=rec_fused,
                launches=(1 if rec_fused else 2) + 2 + 1,
                modes=modes, rec_modes=rec_modes, tiles=t2,
                rec_tiles=rec_tiles,
                vmem_bytes=chain_vmem_bytes(*t2, itemsize, accum),
                rec_vmem_bytes=rec_vmem,
                hbm_bytes_staged=adj.hbm_bytes_staged,
                hbm_bytes_fused=fused_bytes, events=tuple(events))
        events.append({
            "kind": "adjoint_fusion_degradation", "from": "pair",
            "reason": "byte_model", "hbm_bytes_fused": fused_bytes,
            "hbm_bytes_staged": adj.hbm_bytes_staged,
            "vmem_budget": vmem_budget,
        })
    return declined(events)


def build_plan(
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
    mesh=None,
    axes=None,
    batch_axis: AxisName = None,
    accum: str | None = None,  # accumulation mode (engine/numerics.py)
    error_budget: float | None = None,  # max a-priori plan rounding bound
) -> GemtPlan:
    """Plan a 3-stage GEMT for a tensor of ``x_shape`` (3D, or 4D batched).

    ``order=None`` searches all six parenthesizations and keeps the one with
    minimal (effective MACs, collective bytes, peak intermediate bytes);
    passing an explicit order pins it (the paper's reference chain is
    ``(3, 1, 2)``).

    ``fuse`` controls stage fusion (see ``FUSE_MODES``): ``None`` (default)
    picks the deepest fusion that models the fewest HBM bytes — the
    whole-transform triple megakernel when its tiles fit ``vmem_budget``
    and it beats the best pair schedule, else the consecutive pair with
    the largest modeled saving, else staged; ``True`` forces the deepest
    feasible fusion; ``False`` never fuses; ``"pair"`` / ``"triple"``
    restrict the search to that depth.  The per-stage plans are kept
    either way — they are the staged fallback the executor uses outside
    the fused stages.

    ``backend="einsum"`` pins every stage to the XLA einsum lowering and
    disables fusion — the bottom rung of the serving runtime's degradation
    ladder (``docs/serving.md``): no Pallas kernels, no fused VMEM
    residency, maximally conservative.  ``None`` (default) keeps the
    per-stage backend choice with the cost model.

    ``mesh``/``axes`` make the plan topology-aware: ``axes[s-1]`` names the
    mesh axis sharding mode ``s`` of the stationary tensor (None = local;
    tuple = a folded multi-axis shard).  ``x_shape`` stays **global**; the
    stages describe the per-shard schedule (see the module docstring) and
    every mode extent — and the matching ``K_s``, for the psum_scatter —
    must divide its axis size.  ``batch_axis`` optionally shards a leading
    batch dim (data parallelism; no collective, the rows just split).

    ``accum`` selects the accumulation mode every stage (and any fused
    kernel) runs under — see ``engine/numerics.py`` and
    ``docs/numerics.md``.  ``error_budget`` caps the plan's a-priori
    rounding bound (:func:`repro.engine.numerics.plan_error_bound`): when
    the bound at the requested mode blows the budget, the mode escalates
    ``plain`` → ``f32`` → ``compensated`` and each step is recorded as a
    ``numerics_degradation`` event.  The escalation runs *before* fusion
    planning — compensation's comp scratch inflates every fused VMEM
    footprint, so a tight ``(error_budget, vmem_budget)`` pair can
    legitimately demote triple → pair → staged.
    """
    if backend not in (None, "einsum"):
        raise ValueError(
            f"backend must be None (auto) or 'einsum', got {backend!r}")
    dims = tuple(int(d) for d in x_shape[-3:])
    if len(x_shape) not in (3, 4):
        raise ValueError(f"x must be 3D or 4D-batched, got shape {x_shape}")
    batch_global = int(x_shape[0]) if len(x_shape) == 4 else 1
    cs = {1: c1, 2: c2, 3: c3}
    for mode in (1, 2, 3):
        if cs[mode].ndim != 2 or cs[mode].shape[0] != dims[mode - 1]:
            raise ValueError(
                f"C{mode} shape {cs[mode].shape} incompatible with mode "
                f"extent {dims[mode - 1]}")

    axes = normalize_axes(axes) if mesh is not None else (None, None, None)
    shards = tuple(mesh_axis_size(mesh, a) for a in axes)
    batch_shards = mesh_axis_size(mesh, batch_axis) if mesh is not None else 1
    if mesh is None:
        batch_axis = None
    # A mesh axis can shard only one dim of the stationary tensor: a repeat
    # across modes (or with batch_axis) would build a duplicate-entry
    # PartitionSpec and fail far from the user's mistake.
    named = [n for a in (*axes, batch_axis) if a is not None
             for n in (a if isinstance(a, tuple) else (a,))]
    dupes = sorted({n for n in named if named.count(n) > 1})
    if dupes:
        raise ValueError(
            f"mesh axes {dupes} assigned to more than one of "
            f"axes={axes} / batch_axis={batch_axis!r}")
    for mode in (1, 2, 3):
        p = shards[mode - 1]
        if dims[mode - 1] % p:
            raise ValueError(
                f"mode-{mode} extent {dims[mode - 1]} not divisible by "
                f"axis {axes[mode - 1]!r} (size {p})")
        if int(cs[mode].shape[1]) % p:
            raise ValueError(
                f"C{mode} output extent {cs[mode].shape[1]} not divisible "
                f"by axis {axes[mode - 1]!r} (size {p}) — the psum_scatter "
                f"re-shards K{mode} over it")
    if batch_global % max(batch_shards, 1):
        raise ValueError(
            f"batch {batch_global} not divisible by batch_axis "
            f"{batch_axis!r} (size {batch_shards})")
    batch = batch_global // max(batch_shards, 1)
    local = tuple(d // p for d, p in zip(dims, shards))
    itemsize = jnp.dtype(x_dtype).itemsize * max(batch, 1)

    candidates = ([tuple(order)] if order is not None
                  else list(itertools.permutations((1, 2, 3))))
    best = None
    mask_cache: dict[int, np.ndarray] = {}
    for cand in candidates:
        if sorted(cand) != [1, 2, 3]:
            raise ValueError(f"order must be a permutation of (1,2,3), got {cand}")
        stages, macs, eff, peak, coll = _plan_for_order(
            local, cs, cand, batch=batch, itemsize=itemsize,
            esop_threshold=esop_threshold, block_sizes=block_sizes,
            mask_cache=mask_cache, axes=axes, shards=shards)
        # Collective bytes rank above peak bytes: ICI is the scarcer
        # resource, and the term is what pushes shard-local (especially
        # compressive) stages ahead of the sharded-mode scatter.
        score = (eff, coll, peak, cand)
        if best is None or score < best[0]:
            best = (score, cand, stages, macs, eff, peak, coll)
    _, chosen, stages, macs, eff, peak, coll = best

    # Guarded numerics: resolve the accumulation mode against the a-priori
    # error model BEFORE fusion planning — the comp scratch of a forced
    # compensation inflates every fused footprint below, so the budget can
    # demote fusion depth (docs/numerics.md).
    accum_requested = accum
    accum = normalize_accum(accum)
    if jnp.issubdtype(jnp.dtype(x_dtype), jnp.complexfloating):
        accum = "plain"  # DFT stages stay plain — kernels are real-valued
    if error_budget is not None:
        accum, error_bound, numerics_events = enforce_error_budget(
            stages, x_dtype, accum, error_budget)
    else:
        error_bound = plan_error_bound(stages, x_dtype, accum)
        numerics_events = []
    if accum != "plain":
        stages = tuple(dataclasses.replace(s, accum=accum) for s in stages)

    isz_raw = jnp.dtype(x_dtype).itemsize
    if block_sizes is None:
        # Row tiles from the VMEM model, before fusion planning: the
        # staged byte model counts C once per row block.  (bk, bn), and so
        # the ESOP masks and the sparsity signature, do not depend on bm.
        stages = tuple(dataclasses.replace(s, bm=stage_row_tile(
            s.rows, batch, s.bn, s.bk, isz_raw, accum, vmem_budget))
            for s in stages)
    fused = None
    fused3 = None
    fusion_events: list[dict] = []  # demotion records, filtered below
    if fuse not in FUSE_MODES:
        raise ValueError(f"fuse must be one of {FUSE_MODES}, got {fuse!r}")
    if backend is not None:
        # Pinned backend: every stage runs it dense (no block skipping) and
        # fusion is off — the pin exists to take Pallas out of the loop.
        stages = tuple(dataclasses.replace(s, backend=backend,
                                           macs_effective=s.macs)
                       for s in stages)
        eff = macs
        fuse = False
    if fuse in (None, True, "triple"):
        fused3 = _plan_fusion3(chosen, stages, cs, batch=batch,
                               itemsize=isz_raw, vmem_budget=vmem_budget,
                               force=fuse in (True, "triple"), axes=axes,
                               events=fusion_events, accum=accum)
    if fuse in (None, True, "pair") and not (fused3 and fuse is True):
        cands = []
        for first in (0, 1):
            fp = _plan_fusion(first, chosen, stages, local, cs, batch=batch,
                              itemsize=isz_raw, vmem_budget=vmem_budget,
                              force=(fuse is True), axes=axes, shards=shards,
                              events=fusion_events, accum=accum)
            if fp is not None:
                cands.append(fp)
        if cands:  # fuse the pair that saves the most modeled bytes
            fused = max(cands,
                        key=lambda f: f.hbm_bytes_staged - f.hbm_bytes_fused)
    # Graceful degradation triple → pair → staged: in auto mode (the only
    # way both candidates exist — fuse=True skips the pair search when the
    # triple is feasible) the deeper fusion must also *model* fewer bytes
    # than the best pair schedule — a budget-starved triple whose shrunken
    # bka re-streams X many times can lose to the pair kernel, and then
    # the pair runs.
    if fused3 is not None and fused is not None:
        if (fused3.hbm_bytes_fused
                <= plan_hbm_bytes(stages, fused, batch, isz_raw)):
            fused = None
        else:
            fusion_events.append({
                "kind": "fusion_degradation", "from": "triple",
                "reason": "byte_model_vs_pair",
                "hbm_bytes_fused": fused3.hbm_bytes_fused,
                "hbm_bytes_pair_plan": plan_hbm_bytes(stages, fused, batch,
                                                      isz_raw),
                "vmem_bytes": fused3.vmem_bytes,
                "vmem_budget": vmem_budget,
            })
            fused3 = None
    # Keep only genuine demotions: an event whose "from" tier still ended
    # up running (e.g. one pair candidate declined but the other fused, or
    # the triple engaged after a pair decline) is not a degradation.
    tier_rank = {"staged": 0, "pair": 1, "triple": 2}
    final_tier = ("triple" if fused3 is not None
                  else "pair" if fused is not None else "staged")
    events = tuple(
        dict(ev, to=final_tier) for ev in fusion_events
        if tier_rank[final_tier] < tier_rank[ev["from"]])
    # Numerics events bypass the tier filter: they record accumulation
    # escalations, not fusion demotions, and carry no "from" tier.
    events = tuple(numerics_events) + events

    out_shape = tuple(cs[m].shape[1] for m in (1, 2, 3))
    blocks = {s.mode: (s.bk, s.bn) for s in stages}
    key_parts = [
        f"x={tuple(x_shape)}", f"dt={jnp.dtype(x_dtype).name}",
        f"o={chosen}", f"th={esop_threshold}",
        f"bs={block_sizes}", f"fu={fuse}", f"vb={vmem_budget}",
        f"sig={sparsity_signature(cs, blocks)}",
    ]
    if backend is not None:  # unpinned keys stay byte-identical to PR 1–6
        key_parts.append(f"be={backend}")
    # default-numerics keys stay byte-identical to PR 1–8
    if accum_requested not in (None, "plain"):
        key_parts.append(f"ac={accum_requested}")
    if error_budget is not None:
        key_parts.append(f"eb={error_budget}")
    if mesh is not None:  # single-device keys stay byte-identical to PR 1–2
        key_parts.append(
            f"mesh={tuple(mesh.shape.items())};ax={axes};ba={batch_axis}")
    return GemtPlan(order=chosen, stages=stages, in_shape=dims,
                    out_shape=out_shape, macs=macs, macs_effective=eff,
                    peak_intermediate_bytes=peak, key="|".join(key_parts),
                    fused=fused, fused3=fused3,
                    hbm_bytes_staged=plan_hbm_bytes(stages, None, batch,
                                                    isz_raw),
                    hbm_bytes_moved=plan_hbm_bytes(stages, fused, batch,
                                                   isz_raw, fused3=fused3),
                    axes=axes, shards=shards, batch_axis=batch_axis,
                    batch_shards=batch_shards, collective_bytes=coll,
                    events=events, accum=accum, error_bound=error_bound,
                    error_budget=error_budget)

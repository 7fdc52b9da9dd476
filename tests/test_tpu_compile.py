"""Compile-only checks of the Pallas kernels for a described TPU v5e chip.

Nothing here runs on a device: each case lowers a kernel at the tiles the
planner (or the kernel's own caller) picks and compiles it with the TPU
compiler against a ``v5e:2x2`` topology *description*.  That catches what
interpret mode cannot — block shapes that break the (8, 128) tiling rule,
kernels that overflow VMEM, layouts Mosaic refuses — at no chip time.

The topology is described inside a module-scoped fixture, never at import
time: only one process may load the TPU library, and every test worker
imports every test file.
"""
import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core.transforms import (blocked_coefficient_matrix,
                                   coefficient_matrix)
from repro.engine.plan import (build_plan, derive_adjoint_plan,
                               lane_tile_ok, plan_adjoint_chain)
from repro.kernels.esop_gemm import esop_gemm_pallas, esop_plan
from repro.kernels.fused3_gemt import fused3_gemt_pallas
from repro.kernels.fused_chain import (chain3_gemt_pallas, chain_gemt_pallas,
                                       coeff_grad_batch_pallas)
from repro.kernels.fused_gemt import fused_gemt_pallas
from repro.kernels.sr_gemm import sr_gemm_pallas


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _pad(d: int, b: int) -> int:
    return -(-d // b) * b


def _compile(fn, sharding, *shapes):
    """Lower ``fn`` on abstract operands placed on ``sharding``; compile."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


def _dense_prefetch(n_blocks: int, t_steps: int):
    """Shapes of a dense ESOP schedule: counts (J,), idx (J, T)."""
    return ((n_blocks,), jnp.int32), ((n_blocks, t_steps), jnp.int32)


def compile_gemm(sharding, m, n, k, bm, bn, bk, dtype, accum="plain"):
    mp, np_, kp = _pad(m, bm), _pad(n, bn), _pad(k, bk)
    fn = functools.partial(sr_gemm_pallas, bm=bm, bn=bn, bk=bk,
                           interpret=False, accum=accum)
    return _compile(lambda x, c: fn(x, c), sharding,
                    ((mp, kp), dtype), ((kp, np_), dtype))


def compile_fused_pair(sharding, u, na, ka, nb, kb, tiles, dtype,
                       accum="plain"):
    bu, bka, bnb, bna, kbp = tiles
    up, nap, kap, nbp = _pad(u, bu), _pad(na, bna), _pad(ka, bka), _pad(nb, bnb)
    t_a, t_b = nap // bna, nbp // bnb
    counts, idx = _dense_prefetch(kap // bka, t_a)

    def fn(x3, ca, cb, counts_a, idx_a, idx_b):
        return fused_gemt_pallas(x3, ca, cb, bu=bu, bka=bka, bnb=bnb,
                                 bna=bna, plan=(counts_a, idx_a, t_a, idx_b,
                                                t_b), accum=accum)[0]

    return _compile(fn, sharding, ((up, nbp, nap), dtype),
                    ((nap, kap), dtype), ((nbp, kbp), dtype), counts, idx,
                    ((1, t_b), jnp.int32))


def compile_fused_triple(sharding, u, na, ka, nb, kb, nc, kc, tiles, dtype,
                         accum="plain"):
    bu, bka, bnb, bnc, bna, kbp, kcp = tiles
    up, nap, kap = _pad(u, bu), _pad(na, bna), _pad(ka, bka)
    nbp, ncp = _pad(nb, bnb), _pad(nc, bnc)
    t_a, t_b, t_c = nap // bna, nbp // bnb, ncp // bnc
    counts, idx = _dense_prefetch(kap // bka, t_a)

    def fn(x4, ca, cb, cc, counts_a, idx_a, idx_b, idx_c):
        return fused3_gemt_pallas(
            x4, ca, cb, cc, bu=bu, bka=bka, bnb=bnb, bnc=bnc, bna=bna,
            plan=(counts_a, idx_a, t_a, idx_b, t_b, idx_c, t_c),
            accum=accum)[0]

    return _compile(fn, sharding, ((up, ncp, nbp, nap), dtype),
                    ((nap, kap), dtype), ((nbp, kbp), dtype),
                    ((ncp, kcp), dtype), counts, idx,
                    ((1, t_b), jnp.int32), ((1, t_c), jnp.int32))


def compile_chain_pair(sharding, u, na, ka, nb, kb, tiles, dtype):
    bu, bka, bnb, bna, kbp = tiles
    up, nap, kap, nbp = _pad(u, bu), _pad(na, bna), _pad(ka, bka), _pad(nb, bnb)
    t_a = nap // bna
    counts, idx = _dense_prefetch(kap // bka, t_a)

    def fn(x3, ca, cb, counts_a, idx_a):
        return chain_gemt_pallas(x3, ca, cb, bu=bu, bka=bka, bnb=bnb,
                                 bna=bna, plan_a=(counts_a, idx_a, t_a))[:2]

    return _compile(fn, sharding, ((up, nbp, nap), dtype),
                    ((nap, kap), dtype), ((nbp, kbp), dtype), counts, idx)


def compile_chain_triple(sharding, u, na, ka, nb, kb, nc, kc, tiles, dtype):
    bu, bka, bnb, bnc, bna, kbp, kcp = tiles
    up, nap, kap = _pad(u, bu), _pad(na, bna), _pad(ka, bka)
    nbp, ncp = _pad(nb, bnb), _pad(nc, bnc)
    t_a = nap // bna
    counts, idx = _dense_prefetch(kap // bka, t_a)

    def fn(x4, ca, cb, cc, counts_a, idx_a):
        return chain3_gemt_pallas(x4, ca, cb, cc, bu=bu, bka=bka, bnb=bnb,
                                  bnc=bnc, bna=bna,
                                  plan_a=(counts_a, idx_a, t_a))[:3]

    return _compile(fn, sharding, ((up, ncp, nbp, nap), dtype),
                    ((nap, kap), dtype), ((nbp, kbp), dtype),
                    ((ncp, kcp), dtype), counts, idx)


def _dct(*dims):
    return [coefficient_matrix("dct", d) for d in dims]


def _assert_lane_rule(plan):
    """Every lane-dim tile the plan holds is 128-aligned or covers its
    whole (padded) extent — the rule the TPU compiler enforces."""
    for s in plan.stages:
        if s.backend == "einsum":
            continue
        assert lane_tile_ok(s.bn, s.k) and lane_tile_ok(s.bk, s.n), s
        assert lane_tile_ok(s.bm, s.rows), s  # a lane dim in the backward
    if plan.fused is not None:
        f = plan.fused
        assert lane_tile_ok(f.bna, f.na) and lane_tile_ok(f.bka, f.ka), f
        assert f.bnb % 8 == 0, f
    if plan.fused3 is not None:
        f = plan.fused3
        assert lane_tile_ok(f.bna, f.na) and lane_tile_ok(f.bka, f.ka), f
        assert f.bnb % 8 == 0 and f.bnc % 8 == 0, f


# Deployment widths of the engine's main path: a 128³ serving batch, a
# 256³ batch and the 512³ CT-sized volume (ROADMAP deployment 3).
PLANNED_SHAPES = [(4, 128, 128, 128), (2, 256, 256, 256), (512, 512, 512)]


@pytest.mark.parametrize("shape", PLANNED_SHAPES,
                         ids=lambda s: "x".join(map(str, s)))
def test_planned_kernels_compile(one_chip, shape):
    """Compile every kernel ``build_plan`` selects, at the tiles it picks."""
    cs = _dct(*shape[-3:])
    plan = build_plan(shape, jnp.float32, *cs)
    _assert_lane_rule(plan)
    batch = shape[0] if len(shape) == 4 else 1
    covered = set()
    if plan.fused3 is not None:
        f = plan.fused3
        compile_fused_triple(one_chip, batch, f.na, f.ka, f.nb, f.kb, f.nc,
                             f.kc, (f.bu, f.bka, f.bnb, f.bnc, f.bna, f.kbp,
                                    f.kcp), jnp.float32)
        covered = {0, 1, 2}
    if plan.fused is not None:
        f = plan.fused
        compile_fused_pair(one_chip, f.rows * batch, f.na, f.ka, f.nb, f.kb,
                           (f.bu, f.bka, f.bnb, f.bna, f.kbp), jnp.float32)
        covered = {f.first, f.first + 1}
    kernel_stages = 0
    for i, s in enumerate(plan.stages):
        if i in covered or s.backend == "einsum":
            continue
        assert _pad(s.rows * batch, s.bm) == s.rows * batch, s  # no row pad
        compile_gemm(one_chip, s.rows * batch, s.k, s.n, s.bm, s.bn, s.bk,
                     jnp.float32)
        kernel_stages += 1
    assert covered or kernel_stages, "plan selected no Pallas kernel"


@pytest.mark.parametrize("role", ["dx", "dc"])
def test_sr_gemm_vjp_compiles_at_planned_tiles(one_chip, monkeypatch, role):
    """The VJP of the 512³ plan's staged SR-GEMM at its row tile: dX
    runs the tiles as ``(bm, bk, bn)`` and dC as ``(bk, bn, bm)``, with
    ``bm`` the contraction and lane dim, so a row tile the VMEM model
    admits must compile in both roles."""
    from repro.kernels import ops

    n = 512
    plan = build_plan((n, n, n), jnp.float32, *_dct(n, n, n))
    (s,) = [s for i, s in enumerate(plan.stages)
            if i not in (plan.fused.first, plan.fused.first + 1)]
    assert s.backend == "sr_gemm" and s.bm > 128, s
    # Kernel dispatch asks on_tpu() for interpret mode; this is for a TPU.
    monkeypatch.setattr(ops, "on_tpu", lambda: True)

    def fn(x, c, g):
        f = lambda x, c: ops.sr_gemm(x, c, bm=s.bm, bn=s.bn, bk=s.bk,
                                     use_pallas=True)
        dx, dc = jax.vjp(f, x, c)[1](g)
        return dx if role == "dx" else dc

    hlo = _compile(fn, one_chip, ((s.rows, n), jnp.float32),
                   ((n, n), jnp.float32),
                   ((s.rows, n), jnp.float32)).as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("n", [32, 48])
def test_fused_triple_compiles(one_chip, n):
    """The megakernel at the planner's tiles: whatever the VMEM model
    admits under the default budget must compile under the chip's 16 MiB
    scoped-VMEM limit."""
    cs = _dct(n, n, n)
    plan = build_plan((4, n, n, n), jnp.float32, *cs, fuse="triple")
    assert plan.fused3 is not None, plan.events
    _assert_lane_rule(plan)
    f = plan.fused3
    compile_fused_triple(one_chip, 4, f.na, f.ka, f.nb, f.kb, f.nc, f.kc,
                         (f.bu, f.bka, f.bnb, f.bnc, f.bna, f.kbp, f.kcp),
                         jnp.float32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("accum", ["plain", "compensated"])
def test_sr_gemm_compiles(one_chip, dtype, accum):
    compile_gemm(one_chip, 65536, 256, 256, 128, 128, 128, dtype, accum)


def test_block_sparse_esop_gemm_compiles(one_chip):
    """One block-sparse stage at the planner's ESOP tiles (a banded C)."""
    n = 512
    c = np.zeros((n, n), np.float32)
    for i in range(n):
        c[i, max(0, i - 4):i + 5] = 1.0
    c = jnp.asarray(c)
    plan = build_plan((n, n, n), jnp.float32, c, c, c, fuse=False)
    _assert_lane_rule(plan)
    s = plan.stages[0]
    assert s.backend == "esop", s
    counts, idx, t_steps = esop_plan(c, s.bk, s.bn)
    m = s.rows

    def fn(x, cc, counts, idx):
        return esop_gemm_pallas(x, cc, None, bm=s.bm, bn=s.bn, bk=s.bk,
                                plan=(counts, idx, t_steps))[0]

    _compile(fn, one_chip, ((_pad(m, s.bm), n), jnp.float32),
             ((n, n), jnp.float32), (counts.shape, jnp.int32),
             (idx.shape, jnp.int32))


# The planes of one 4K 4:2:0 video buffer (32 frames), coded in 8x8x8
# blocks: luma and each chroma plane.
VIDEO_PLANES = [(32, 2160, 3840), (32, 1080, 1920)]


@pytest.mark.parametrize("shape", VIDEO_PLANES,
                         ids=lambda s: "x".join(map(str, s)))
def test_blocked_video_plane_compiles(one_chip, shape):
    """The blocked DCT of a video plane: the long modes' block-diagonal
    factors plan block-ESOP, whose kernels compile at the planner's tiles
    with the factors' own skip schedules; the 32-frame mode runs einsum
    in place, which keeps the plane's lane-dense layout (no padded copy)."""
    cs = [blocked_coefficient_matrix("dct", n, 8) for n in shape]
    plan = build_plan(shape, jnp.float32, *cs)
    _assert_lane_rule(plan)
    backend = {s.mode: s.backend for s in plan.stages}
    assert backend == {1: "einsum", 2: "esop", 3: "esop"}, plan.stages
    assert plan.fused is None and plan.fused3 is None, plan.events
    for s in plan.stages:
        if s.backend != "esop":
            continue
        assert _pad(s.rows, s.bm) == s.rows, s  # no row-padding copy
        cp = np.pad(np.asarray(cs[s.mode - 1]),
                    ((0, _pad(s.n, s.bk) - s.n), (0, _pad(s.k, s.bn) - s.k)))
        counts, idx, t_steps = esop_plan(jnp.asarray(cp), s.bk, s.bn)
        assert t_steps == 1  # one live 128-block per column: 8-blocks align

        def fn(x, c, counts, idx, s=s, t_steps=t_steps):
            return esop_gemm_pallas(x, c, None, bm=s.bm, bn=s.bn, bk=s.bk,
                                    plan=(counts, idx, t_steps))[0]

        _compile(fn, one_chip,
                 ((_pad(s.rows, s.bm), cp.shape[0]), jnp.float32),
                 (cp.shape, jnp.float32), (counts.shape, jnp.int32),
                 (idx.shape, jnp.int32))
    t = _compile(lambda x, c: jnp.einsum("abc,ax->xbc", x, c), one_chip,
                 (shape, jnp.float32), ((32, 32), jnp.float32))
    mem = t.memory_analysis()
    assert mem.output_size_in_bytes == 4 * math.prod(shape), mem


FIT_SHAPE = (8, 128, 128, 128)  # the fit step's batch of 8 128³ volumes


def test_fit_step_chain_kernels_compile(one_chip):
    """The backward walk's chain kernels and batched coefficient
    cotangents at the fit-step shape, at the tiles the chain plan picks."""
    cs = _dct(*FIT_SHAPE[-3:])
    plan = build_plan(FIT_SHAPE, jnp.float32, *cs)
    adj = derive_adjoint_plan(plan, FIT_SHAPE, jnp.float32,
                              *[c.T for c in cs])
    chain = plan_adjoint_chain(plan, adj, FIT_SHAPE, jnp.float32)
    assert chain.depth in (2, 3), chain.events
    batch = FIT_SHAPE[0]
    a0, a1, a2 = adj.stages
    if chain.depth == 3:
        bu, bka, bnb, bnc, bna = chain.tiles[:5]
        for t, d in ((bna, a0.n), (bka, a0.k)):
            assert lane_tile_ok(t, d), chain.tiles
        compile_chain_triple(one_chip, batch, a0.n, a0.k, a1.n, a1.k, a2.n,
                             a2.k, chain.tiles, jnp.float32)
    else:
        bu, bka, bnb, bna, kbp = chain.tiles
        assert lane_tile_ok(bna, a0.n) and lane_tile_ok(bka, a0.k)
        compile_chain_pair(one_chip, batch * a2.n, a0.n, a0.k, a1.n, a1.k,
                           chain.tiles, jnp.float32)
    if chain.rec_fused:
        s0, s1, s2 = plan.stages
        bu, bka, bnb, bna, kbp = chain.rec_tiles
        assert lane_tile_ok(bna, s0.n) and lane_tile_ok(bka, s0.k)
        compile_chain_pair(one_chip, batch * s2.n, s0.n, s0.k, s1.n, s1.k,
                           chain.rec_tiles, jnp.float32)
    # dC_s = A_sᵀ G_s for all three modes in one launch (ops pads to a
    # common (R, N, K) envelope with br = 128 row blocks)
    r = batch * FIT_SHAPE[1] * FIT_SHAPE[2]
    n = FIT_SHAPE[3]
    _compile(lambda a, g: coeff_grad_batch_pallas(a, g, br=128), one_chip,
             ((3, r, n), jnp.float32), ((3, r, n), jnp.float32))


def test_sharded_engine_program_compiles(topo, monkeypatch):
    """The engine's whole shard_map program for a 512³ DCT on the 2x2
    mesh (``axes=("data", "model", None)``): per-shard SR-GEMM kernels
    for the sharded modes, combined by reduce-scatters."""
    from repro.engine.executor import _sharded_callable
    from repro.kernels import ops

    # Planning and kernel dispatch ask on_tpu(); this trace is for a TPU.
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    mesh = jax.sharding.Mesh(np.asarray(topo.devices).reshape(2, 2),
                             ("data", "model"))
    n = 512
    cs = _dct(n, n, n)
    plan = build_plan((n, n, n), jnp.float32, *cs, mesh=mesh,
                      axes=("data", "model", None))
    _assert_lane_rule(plan)
    sharded = [s for s in plan.stages if s.axis is not None]
    assert sharded and all(s.backend == "sr_gemm" for s in sharded), plan
    assert plan.collective_bytes > 0
    fn, _ = _sharded_callable(plan, mesh, None, dict(enumerate(cs, 1)),
                              batched=False)
    spec = NamedSharding(mesh, P("data", "model", None))
    rep = NamedSharding(mesh, P())
    args = [jax.ShapeDtypeStruct((n, n, n), jnp.float32, sharding=spec)]
    args += [jax.ShapeDtypeStruct((n, n), jnp.float32, sharding=rep)] * 3
    hlo = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert "reduce-scatter" in hlo or "all-reduce" in hlo

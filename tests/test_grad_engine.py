"""Differentiable engine: gradcheck vs the einsum reference.

The engine's custom VJP (docs/engine.md, "Differentiation") must produce
the *same* four cotangents as ``jax.vjp`` of the plain einsum chain —
input and all three coefficient factors — to 1e-5 (relative to the
reference gradient's magnitude, fp32) across staged/pair/triple fusion,
sparse-ESOP coefficients, complex DFT stages, batching, the affine ``out``
seed, and the sharded mesh schedule.  ``info``'s ``grad_*`` fields and
``grad_stats()`` must prove the backward lowered through the engine, not
a silent einsum fallback.
"""
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from _hypothesis_compat import given, settings, st
from repro.core import (apply_dxt3d_layer, coefficient_matrix, dxt3d, gemt3,
                        init_dxt3d_layer)
from repro.engine import (AutotuneCache, derive_adjoint_plan, gemt3_planned,
                          grad_stats, plan_gemt3, reset_grad_stats)
from repro.kernels import ops
from repro.memo import ArrayMemo

RNG = np.random.default_rng(23)


def _rand(*shape, dtype=np.float32):
    if np.issubdtype(dtype, np.complexfloating):
        return jnp.asarray((RNG.normal(size=shape)
                            + 1j * RNG.normal(size=shape)).astype(dtype))
    return jnp.asarray(RNG.normal(size=shape).astype(dtype))


def _problem(dims, ranks=None, dtype=np.float32, batch=None, sparse=()):
    """Random GEMT problem; ``sparse`` lists modes made 50% block-zero."""
    ranks = ranks or dims
    shape = ((batch,) + tuple(dims)) if batch else tuple(dims)
    x = _rand(*shape, dtype=dtype)
    cs = []
    for mode, (n, k) in enumerate(zip(dims, ranks), 1):
        c = np.asarray(_rand(n, k, dtype=dtype))
        if mode in sparse:
            blk = 8
            keep = RNG.random((n // blk, k // blk)) >= 0.5
            c = c * np.kron(keep, np.ones((blk, blk)))
        cs.append(jnp.asarray(c.astype(dtype)))
    return x, tuple(cs)


def _ref(x, c1, c2, c3, out=None):
    y = jnp.einsum("...abc,ax,by,cz->...xyz", x, c1, c2, c3)
    return y if out is None else out + y


def _vjp_pair(x, cs, g, out=None, primal_tol=1e-4, **kwargs):
    """Engine and reference cotangent tuples for the same cotangent g."""
    args = (x,) + cs + ((out,) if out is not None else ())
    if out is not None:
        eng = lambda x, c1, c2, c3, o: gemt3_planned(
            x, c1, c2, c3, out=o, differentiable=True, **kwargs)
        ref = lambda x, c1, c2, c3, o: _ref(x, c1, c2, c3, o)
    else:
        eng = lambda x, c1, c2, c3: gemt3_planned(
            x, c1, c2, c3, differentiable=True, **kwargs)
        ref = _ref
    y_e, pull_e = jax.vjp(eng, *args)
    y_r, pull_r = jax.vjp(ref, *args)
    wide = jnp.complex64 if jnp.iscomplexobj(y_r) else jnp.float32
    y_en = np.asarray(jnp.asarray(y_e, wide))
    y_rn = np.asarray(jnp.asarray(y_r, wide))
    scale = max(float(np.max(np.abs(y_rn))), 1.0)
    np.testing.assert_allclose(y_en, y_rn, rtol=10 * primal_tol,
                               atol=primal_tol * scale)
    return pull_e(g), pull_r(g)


def assert_grads_close(got, want, tol=1e-5):
    """Each cotangent within ``tol`` of the reference, scaled to its
    magnitude (the acceptance bar: 1e-5/fp32)."""
    assert len(got) == len(want)
    for i, (g, w) in enumerate(zip(got, want)):
        w = np.asarray(w)
        scale = max(float(np.max(np.abs(w))), 1.0)
        np.testing.assert_allclose(
            np.asarray(g), w, rtol=10 * tol, atol=tol * scale,
            err_msg=f"cotangent {i} diverges from the einsum reference")


class TestGradMatchesReference:
    @pytest.mark.parametrize("fuse", [False, "pair", "triple", None])
    def test_fuse_tiers_square_batched(self, fuse):
        """All fusion depths backprop identically (4, 32, 32, 32) fp32."""
        x, cs = _problem((32, 32, 32), batch=4)
        g = _rand(4, 32, 32, 32)
        got, want = _vjp_pair(x, cs, g, fuse=fuse)
        assert_grads_close(got, want)

    @pytest.mark.parametrize("dims,ranks", [
        ((16, 12, 20), (8, 24, 10)),   # rectangular Tucker, mixed comp/exp
        ((64, 32, 16), (4, 16, 16)),   # strongly compressive mode 1
        ((24, 20, 28), (24, 20, 28)),  # square unbatched
    ])
    def test_rectangular_staged(self, dims, ranks):
        x, cs = _problem(dims, ranks)
        g = _rand(*ranks)
        got, want = _vjp_pair(x, cs, g, fuse=False)
        assert_grads_close(got, want)

    @pytest.mark.parametrize("fuse", ["pair", None])
    def test_fused_walk_with_staged_esop_stage(self, fuse):
        """The fused backward walk runs as one jit; an ESOP stage staged
        inside it (here the adjoint tail over the block-sparse C1ᵀ) must
        take a schedule built from the concrete matrix, not its tracer."""
        x = _rand(2, 16, 24, 8)
        keep = np.kron(np.eye(2), np.ones((8, 8)))  # 50% zero 8x8 blocks
        cs = (_rand(16, 16) * keep, _rand(24, 24), _rand(8, 8))
        g = _rand(2, 16, 24, 8)
        got, want = _vjp_pair(x, cs, g, fuse=fuse, block_sizes=(8, 8, 8))
        assert_grads_close(got, want)

    def test_sparse_esop_coefficients(self):
        """Block-sparse C engages ESOP forward *and* in the adjoint chain
        (transposed structure), with identical gradients."""
        x, cs = _problem((32, 32, 64), batch=2, sparse=(3,),
                         ranks=(32, 32, 64))
        blocks = (128, 8, 8)  # align stage blocks with the planted zeros
        _, info = gemt3_planned(x, *cs, fuse=False, block_sizes=blocks,
                                with_info=True, differentiable=True)
        assert "esop" in info["backends"]
        assert "esop" in info["grad_backends"]
        g = _rand(2, 32, 32, 64)
        got, want = _vjp_pair(x, cs, g, fuse=False, block_sizes=blocks)
        assert_grads_close(got, want)

    def test_complex_dft(self):
        """DFT stages (complex64, einsum backends) backprop via the same
        plain-transpose convention jax uses for dot_general."""
        n = 8
        c = coefficient_matrix("dft", n)
        x = _rand(n, n, n, dtype=np.complex64)
        g = _rand(n, n, n, dtype=np.complex64)
        got, want = _vjp_pair(x, (c, c, c), g)
        assert_grads_close(got, want, tol=1e-4)  # complex64 = 2x fp32 ulp

    def test_affine_out_seed(self):
        x, cs = _problem((16, 16, 16))
        out = _rand(16, 16, 16)
        g = _rand(16, 16, 16)
        got, want = _vjp_pair(x, cs, g, out=out)
        assert_grads_close(got, want)
        # d(out) = g exactly: the seed adds straight through
        np.testing.assert_allclose(np.asarray(got[-1]), np.asarray(g))

    def test_grad_of_scalar_loss(self):
        """jax.grad end-to-end (the training path) matches the reference."""
        x, cs = _problem((32, 32, 32), batch=2)
        eng = jax.grad(lambda *a: jnp.sum(
            gemt3_planned(*a, differentiable=True) ** 2), argnums=(0, 1, 2, 3))
        ref = jax.grad(lambda *a: jnp.sum(_ref(*a) ** 2),
                       argnums=(0, 1, 2, 3))
        assert_grads_close(eng(x, *cs), ref(x, *cs))

    def test_grad_under_jit(self):
        """Outer jit (tracer coefficients): planning degrades to dense but
        gradients stay exact."""
        x, cs = _problem((16, 12, 20), (8, 24, 10))
        eng = jax.jit(jax.grad(lambda *a: jnp.sum(
            gemt3_planned(*a, differentiable=True) ** 2), argnums=(0, 1)))
        ref = jax.grad(lambda *a: jnp.sum(_ref(*a) ** 2), argnums=(0, 1))
        assert_grads_close(eng(x, *cs), ref(x, *cs))

    def test_dxt3d_engine_differentiable(self):
        """dxt3d(engine=True, differentiable=True) is jax.grad-safe and
        matches the plain dxt3d gradient."""
        x = _rand(16, 16, 16)
        ge = jax.grad(lambda x: jnp.sum(
            dxt3d(x, "dct", engine=True, differentiable=True) ** 2))(x)
        gr = jax.grad(lambda x: jnp.sum(dxt3d(x, "dct") ** 2))(x)
        assert_grads_close((ge,), (gr,))

    def test_use_pallas_interpret_grads(self):
        """use_pallas=True (interpret mode off-TPU): pallas_calls never
        leak into jax.grad — the VJP-safe wrappers handle them."""
        x, cs = _problem((16, 16, 16))
        g = _rand(16, 16, 16)
        got, want = _vjp_pair(x, cs, g, fuse=False, use_pallas=True)
        assert_grads_close(got, want, tol=1e-4)

    @pytest.mark.grad_smoke
    def test_interpret_mode_fused_adjoint_drill(self):
        """CPU-only CI drives the TPU backward walk: use_pallas=True off
        TPU runs the chain kernels in interpret mode, so the fused walk —
        chain-pair recompute, chain-triple dX (g1, g2 emitted), batched
        dC — executes as real pallas_calls, with the launch accounting
        matching the forward-time prediction."""
        x, cs = _problem((16, 16, 16), batch=4)
        g = _rand(4, 16, 16, 16)
        _, info = gemt3_planned(x, *cs, with_info=True, differentiable=True,
                                use_pallas=True)
        assert info["grad_fused"] and info["grad_chain_depth"] == 3
        assert info["grad_rec_fused"]
        reset_grad_stats()
        got, want = _vjp_pair(x, cs, g, use_pallas=True)
        assert_grads_close(got, want, tol=1e-4)
        gs = grad_stats()
        assert gs["fused_launches"] == 2  # rec chain-pair + chain-triple
        total = (gs["kernel_stages"] + gs["einsum_stages"]
                 + gs["coeff_kernel"] + gs["coeff_einsum"])
        assert total == info["grad_launches"] == 3


_PROP_TOL = {"f32": 1e-5, "bf16": 2e-2, "c64": 1e-4}


class TestPropertyGradcheck:
    """Property-based differential gradcheck: real ``hypothesis`` when
    installed, the deterministic ``_hypothesis_compat`` example grid
    otherwise.  Every sampled combination of dims, rank compression,
    dtype, fusion knob, ESOP sparsity and batching must produce engine
    cotangents matching ``jax.vjp`` of the einsum reference within the
    per-dtype tolerance — exercising the fused-adjoint chain walks
    (depth 3/2), the staged walk (``fuse=False``), the einsum-pinned
    complex path, and the bf16 kernels."""

    @settings(max_examples=12, deadline=None)
    @given(st.sampled_from([16, 32, 48]),
           st.sampled_from([16, 24, 32]),
           st.sampled_from([8, 16, 32]),
           st.sampled_from([1.0, 0.5]),     # rank compression per mode
           st.sampled_from(["f32", "bf16", "c64"]),
           st.sampled_from([None, False, "pair", "triple"]),
           st.sampled_from([False, True]),  # 50% block-zero mode-1 factor
           st.sampled_from([None, 2]))      # leading batch axis
    def test_vjp_matches_reference(self, n1, n2, n3, rank_ratio, dt, fuse,
                                   sparse, batch):
        dims = (n1, n2, n3)
        # planted block-zeros need blk-8-aligned factors: pin ranks=dims
        ranks = (dims if sparse
                 else tuple(max(8, int(n * rank_ratio)) for n in dims))
        np_dt = np.complex64 if dt == "c64" else np.float32
        x, cs = _problem(dims, ranks, dtype=np_dt, batch=batch,
                         sparse=(1,) if sparse else ())
        g = _rand(*(((batch,) if batch else ()) + ranks), dtype=np_dt)
        if dt == "bf16":
            x, g = x.astype(jnp.bfloat16), g.astype(jnp.bfloat16)
            cs = tuple(c.astype(jnp.bfloat16) for c in cs)
        got, want = _vjp_pair(x, cs, g, fuse=fuse,
                              primal_tol=_PROP_TOL[dt])
        wide = jnp.complex64 if dt == "c64" else jnp.float32
        got = tuple(jnp.asarray(a, wide) for a in got)
        want = tuple(jnp.asarray(w, wide) for w in want)
        assert_grads_close(got, want, tol=_PROP_TOL[dt])

    def test_triple_to_pair_degradation_boundary(self):
        """N=64: the chain triple fits the default VMEM budget (depth 3,
        3 launches); a tightened budget degrades the walk to the chain
        pair + staged tail (depth 2, 4 launches), records the
        ``vmem_budget`` event, and still backprops exactly at the
        degraded depth."""
        x, cs = _problem((64, 64, 64), batch=8)
        _, info = gemt3_planned(x, *cs, with_info=True, differentiable=True)
        assert info["grad_chain_depth"] == 3 and info["grad_launches"] == 3
        tight = 2_000_000  # chain3 wants ~4.4 MB at N=64; the pair fits
        _, info_d = gemt3_planned(x, *cs, with_info=True,
                                  differentiable=True, vmem_budget=tight)
        assert info_d["grad_chain_depth"] == 2
        assert info_d["grad_launches"] == 4
        degr = [e for e in info_d["grad_events"]
                if e["kind"] == "adjoint_fusion_degradation"]
        assert degr and degr[0]["from"] == "triple"
        assert degr[0]["reason"] == "vmem_budget"
        assert degr[0]["vmem_bytes_min"] > tight == degr[0]["vmem_budget"]
        g = _rand(8, 64, 64, 64)
        got, want = _vjp_pair(x, cs, g, vmem_budget=tight)
        assert_grads_close(got, want)


class TestGradInfoAndCounters:
    def test_info_gains_grad_fields(self):
        x, cs = _problem((32, 32, 32), batch=4)
        _, info = gemt3_planned(x, *cs, with_info=True, differentiable=True)
        assert info["grad_order"] == info["order"][::-1]
        assert len(info["grad_backends"]) == 3
        assert len(info["grad_coeff_backends"]) == 3
        assert info["grad_macs"] > info["macs"]  # adjoint + 3 rank-k updates
        assert info["grad_hbm_bytes_moved"] > 0

    def test_no_silent_einsum_on_kernel_shapes(self):
        """Kernel-capable fp32 shapes: zero planned einsum stages in the
        backward, and zero executed einsum stages after a real grad."""
        x, cs = _problem((32, 32, 32), batch=4)
        _, info = gemt3_planned(x, *cs, with_info=True, differentiable=True)
        assert info["grad_einsum_stages"] == 0
        assert info["grad_kernel_stages"] > 0
        assert all(b != "einsum" for b in info["grad_coeff_backends"])
        reset_grad_stats()
        jax.grad(lambda x: jnp.sum(
            gemt3_planned(x, *cs, differentiable=True) ** 2))(x)
        gs = grad_stats()
        assert gs["backward_calls"] == 1
        assert gs["kernel_stages"] + gs["coeff_kernel"] > 0
        assert gs["einsum_stages"] == 0 and gs["coeff_einsum"] == 0

    def test_grad_stats_counts_backward_executions(self):
        x, cs = _problem((16, 16, 16))
        reset_grad_stats()
        f = jax.grad(lambda x: jnp.sum(
            gemt3_planned(x, *cs, differentiable=True) ** 2))
        f(x)
        f(x)
        assert grad_stats()["backward_calls"] == 2
        reset_grad_stats()
        assert grad_stats()["backward_calls"] == 0

    def test_adjoint_chain_depth_decided_by_byte_model(self):
        """The fused-adjoint chain depth follows the HBM byte model: the
        HBM-dominated square serving shape runs the full chain-triple
        walk (3 backward launches), while the compressive Tucker shape —
        whose emitted intermediates would *expand* HBM traffic — degrades
        to the chain pair + staged tail (4 launches) and records why."""
        x, cs = _problem((32, 32, 32), batch=8)
        _, info = gemt3_planned(x, *cs, with_info=True, differentiable=True)
        assert info["grad_fused"]  # chain triple ≈ 1/5 of staged bytes
        assert info["grad_chain_depth"] == 3
        assert info["grad_launches"] == 3
        assert len(info["grad_backends_executed"]) == 1
        assert info["grad_backends_executed"][0].startswith("fused(")
        xt, cst = _problem((64, 48, 32), (8, 24, 24))
        _, info_t = gemt3_planned(xt, *cst, with_info=True,
                                  differentiable=True)
        assert info_t["grad_fused"]
        assert info_t["grad_chain_depth"] == 2
        assert info_t["grad_launches"] == 4
        degr = [e for e in info_t["grad_events"]
                if e["kind"] == "adjoint_fusion_degradation"]
        assert degr and degr[0]["from"] == "triple"
        assert degr[0]["reason"] == "byte_model"
        assert degr[0]["hbm_bytes_fused"] > degr[0]["hbm_bytes_staged"]

    def test_triple_fusion_reused_by_adjoint(self):
        """A square DCT problem whose forward fuses the whole transform
        also fuses the adjoint (transposed problem is isomorphic)."""
        x, cs = _problem((32, 32, 32), batch=8)
        _, info = gemt3_planned(x, *cs, with_info=True, differentiable=True)
        if info["fused"] and len(info["fused"]["modes"]) == 3:
            assert info["grad_fused"]
            assert info["grad_backends_executed"][0].startswith("fused")

    def test_info_exposes_esop_memo_stats(self):
        x, cs = _problem((16, 16, 16))
        _, info = gemt3_planned(x, *cs, with_info=True)
        memo = info["esop_memo"]
        for key in ("entries", "maxsize", "hits", "misses", "evictions"):
            assert key in memo


class TestAdjointPlan:
    def test_derive_reverses_order_and_shapes(self):
        x, cs = _problem((16, 12, 20), (8, 24, 10))
        plan = plan_gemt3(x.shape, x.dtype, *cs)
        cts = tuple(ops.transposed_cached(c) for c in cs)
        adj = derive_adjoint_plan(plan, plan.out_shape, x.dtype, *cts)
        assert adj.order == plan.order[::-1]
        assert adj.in_shape == plan.out_shape
        assert adj.out_shape == plan.in_shape
        assert adj.key == plan.key + "|adjoint"

    def test_adjoint_plan_cached_across_backward_calls(self):
        from repro.engine.executor import _ADJ_PLAN_CACHE

        x, cs = _problem((16, 16, 16))
        f = jax.grad(lambda x: jnp.sum(
            gemt3_planned(x, *cs, differentiable=True) ** 2))
        f(x)
        n = len(_ADJ_PLAN_CACHE)
        assert n >= 1
        f(x)
        assert len(_ADJ_PLAN_CACHE) == n  # second backward reuses the plan

    def test_adjoint_never_replays_forward_tuned_tiles(self, tmp_path):
        """Tile-sharing regression: on square problems the adjoint GEMMs
        have the same shape+structure fingerprint as the forward ones, so
        shape-only keying silently replayed forward-tuned tiles for the
        adjoint (whose operand-transposed access pattern wants different
        tiles).  The cache key now carries an adj/fwd role: a
        forward-warmed cache must *miss* on every adjoint lookup and
        backward tuning must add its own role-separated entries."""
        cache = AutotuneCache(str(tmp_path / "autotune.json"))
        x, cs = _problem((32, 32, 32), batch=4)
        gemt3_planned(x, *cs, fuse=False, autotune=True,
                      autotune_cache=cache)
        n_fwd = len(cache)
        assert n_fwd > 0
        assert all("|fwd|" in k for k in cache._entries)
        jax.grad(lambda x: jnp.sum(gemt3_planned(
            x, *cs, fuse=False, autotune=True, autotune_cache=cache,
            differentiable=True) ** 2))(x)
        assert len(cache) > n_fwd  # adjoint missed the forward entries
        assert any("|adj|" in k for k in cache._entries)
        assert all(k.startswith("v4:") for k in cache._entries)


class TestEsopMemoLRU:
    def test_arraymemo_lru_eviction_and_stats(self):
        memo = ArrayMemo(maxsize=2)
        a, b, c = (jnp.arange(3), jnp.arange(4), jnp.arange(5))
        memo.get_or_compute(a, "k", lambda: 1)
        memo.get_or_compute(b, "k", lambda: 2)
        assert memo.get_or_compute(a, "k", lambda: -1) == 1  # hit refreshes
        memo.get_or_compute(c, "k", lambda: 3)  # evicts b (LRU)
        assert len(memo) == 2
        assert memo.get_or_compute(b, "k", lambda: 22) == 22  # recomputed
        assert memo.stats["hits"] == 1
        assert memo.stats["evictions"] >= 1
        assert memo.stats["misses"] == 4

    def test_arraymemo_set_maxsize_shrinks(self):
        memo = ArrayMemo()
        arrays = [jnp.arange(i + 1) for i in range(4)]
        for i, a in enumerate(arrays):
            memo.get_or_compute(a, "k", lambda i=i: i)
        assert len(memo) == 4
        memo.set_maxsize(2)
        assert len(memo) == 2
        assert memo.stats["evictions"] == 2

    def test_esop_memo_bounded_in_ops(self):
        stats0 = ops.esop_memo_stats()
        assert stats0["maxsize"] == int(os.environ.get(
            "REPRO_ESOP_MEMO_SIZE", "256"))
        try:
            ops.set_esop_memo_size(2)
            held = []  # keep arrays alive so only LRU (not GC) evicts
            for i in range(4):
                c = jnp.asarray(RNG.normal(size=(16, 16)).astype(np.float32))
                held.append(c)
                ops.esop_plan_cached(c, 8, 8)
            stats = ops.esop_memo_stats()
            assert stats["entries"] <= 2
            assert stats["evictions"] > stats0["evictions"]
        finally:
            ops.set_esop_memo_size(stats0["maxsize"])


class TestTrainingConsumers:
    def test_dxt3d_layer_fit_step_learns(self):
        """The engine-backed DXT layer trains: fitting the layer to a DCT
        target from a perturbed start drops the loss monotonically-ish."""
        from repro.optim import OptConfig
        from repro.train.step import build_dxt_fit_step, init_dxt_fit_state

        dims = (16, 16, 16)
        key = jax.random.PRNGKey(0)
        state = init_dxt_fit_state(dims, OptConfig(lr=3e-3, warmup_steps=1),
                                   key=key, init_scale=0.1)
        x = _rand(4, *dims)
        y = jnp.stack([dxt3d(xi, "dct") for xi in x])  # exact-transform target
        step = build_dxt_fit_step(OptConfig(lr=3e-3, warmup_steps=1))
        losses = []
        for _ in range(8):
            state, metrics = step(state, {"x": x, "y": y})
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0]
        assert "grad_norm" in metrics and "lr" in metrics

    def test_dft_layer_fits_complex_factors(self):
        """Complex kinds train end to end: the loss is real (|·|²), the
        factor init keeps the complex dtype (real dtype raises instead of
        silently dropping the imaginary part), and AdamW's second moment
        uses the gradient modulus."""
        from repro.optim import OptConfig
        from repro.train.step import build_dxt_fit_step, init_dxt_fit_state

        dims = (8, 8, 8)
        with pytest.raises(ValueError):
            init_dxt3d_layer(dims, kind="dft", dtype=jnp.float32)
        # init far enough from the optimum that the gradient signal beats
        # AdamW's weight decay; a 0.05 perturbation left an 8-step loss
        # decrease data-marginal (flipped with the suite's RNG history)
        state = init_dxt_fit_state(dims, OptConfig(lr=1e-3, warmup_steps=1),
                                   kind="dft", key=jax.random.PRNGKey(0),
                                   init_scale=0.3)
        assert jnp.iscomplexobj(state["params"]["c1"])
        x = jnp.asarray(np.random.default_rng(23)
                        .normal(size=(2, *dims)).astype(np.complex64))
        y = jnp.stack([dxt3d(xi, "dft") for xi in jnp.real(x)])
        step = build_dxt_fit_step(OptConfig(lr=1e-3, warmup_steps=1))
        losses = []
        for _ in range(8):
            state, m = step(state, {"x": x, "y": y})
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0]

    def test_dxt3d_layer_exact_transform_at_init(self):
        """Unperturbed init is the exact orthonormal transform."""
        dims = (8, 12, 16)
        params = init_dxt3d_layer(dims, kind="dct")
        x = _rand(2, *dims)
        y = apply_dxt3d_layer(params, x)
        want = jnp.stack([dxt3d(xi, "dct") for xi in x])
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)

    def test_dxt3d_layer_rank_truncation(self):
        params = init_dxt3d_layer((16, 16, 16), ranks=(4, 8, 16))
        x = _rand(16, 16, 16)
        y = apply_dxt3d_layer(params, x)
        assert y.shape == (4, 8, 16)
        with pytest.raises(ValueError):
            init_dxt3d_layer((8, 8, 8), ranks=(16, 8, 8))


class TestServeInverse:
    def test_session_roundtrip_via_per_call_inverse(self):
        """One session serves forward and inverse; the orthonormal round
        trip reproduces the input from the shared per-dims caches."""
        from repro.serve import DxtServeSession

        sess = DxtServeSession(kind="dct")
        batch = np.asarray(RNG.normal(size=(3, 16, 16, 16)), np.float32)
        y = sess.transform(batch)
        xr = sess.transform(y, inverse=True)
        np.testing.assert_allclose(np.asarray(xr), batch, rtol=1e-4,
                                   atol=1e-4)
        assert sess.requests_served == 6
        # both directions' coefficients live in the session cache
        assert {k[1] for k in sess._coeffs} == {False, True}

    def test_inverse_session_default(self):
        from repro.serve import DxtServeSession

        fwd = DxtServeSession(kind="dwht")
        inv = DxtServeSession(kind="dwht", inverse=True)
        batch = np.asarray(RNG.normal(size=(2, 8, 8, 8)), np.float32)
        np.testing.assert_allclose(np.asarray(inv.transform(fwd.transform(batch))),
                                   batch, rtol=1e-4, atol=1e-4)

    def test_forward_inverse_share_autotuned_tiles(self, tmp_path):
        """Dense orthonormal kinds: inverse serving adds no autotune-cache
        entries (same shapes, same zero-structure fingerprint)."""
        from repro.serve import DxtServeSession

        cache = AutotuneCache(str(tmp_path / "autotune.json"))
        sess = DxtServeSession(kind="dct", autotune=True,
                               autotune_cache=cache, fuse=False)
        batch = np.asarray(RNG.normal(size=(2, 16, 16, 16)), np.float32)
        sess.transform(batch)
        n_fwd = len(cache)
        assert n_fwd > 0
        sess.transform(batch, inverse=True)
        assert len(cache) == n_fwd


class TestShardedGrad:
    def test_sharded_grads_match_reference(self, virtual_devices):
        """Mesh-sharded differentiable engine vs the einsum reference on 8
        virtual devices (2x4 mesh, one sharded mode + one batch case)."""
        out = virtual_devices("""
            import numpy as np, jax, jax.numpy as jnp
            from jax.sharding import Mesh
            from repro.engine import gemt3_planned, grad_stats

            rng = np.random.default_rng(5)
            mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4),
                        ("data", "model"))
            x = jnp.asarray(rng.normal(size=(16, 8, 16)).astype(np.float32))
            cs = [jnp.asarray(rng.normal(size=(n, n)).astype(np.float32))
                  for n in (16, 8, 16)]

            def check(eng_fn, ref_fn, args):
                ge = jax.grad(eng_fn, argnums=tuple(range(len(args))))(*args)
                gr = jax.grad(ref_fn, argnums=tuple(range(len(args))))(*args)
                for a, b in zip(ge, gr):
                    scale = max(float(jnp.max(jnp.abs(b))), 1.0)
                    assert float(jnp.max(jnp.abs(a - b))) < 1e-4 * scale

            ref = lambda x, c1, c2, c3: jnp.sum(jnp.einsum(
                "abc,ax,by,cz->xyz", x, c1, c2, c3) ** 2)
            eng = lambda x, c1, c2, c3: jnp.sum(gemt3_planned(
                x, c1, c2, c3, mesh=mesh, axes=("data", "model", None),
                differentiable=True) ** 2)
            check(eng, ref, (x, *cs))

            xb = jnp.asarray(rng.normal(size=(4, 16, 8, 16))
                             .astype(np.float32))
            refb = lambda x: jnp.sum(jnp.einsum(
                "uabc,ax,by,cz->uxyz", x, *cs) ** 2)
            engb = lambda x: jnp.sum(gemt3_planned(
                x, *cs, mesh=mesh, axes=(None, "model", None),
                batch_axis="data", differentiable=True) ** 2)
            check(engb, refb, (xb,))
            gs = grad_stats()
            assert gs["backward_calls"] == 2
            print("SHARDED_GRAD_OK", gs["backward_calls"])
        """)
        assert "SHARDED_GRAD_OK" in out

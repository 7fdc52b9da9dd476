#!/usr/bin/env python3
"""Bring-up check: the TriADA engine's main path on a TPU, end to end.

Drives the system through the entry points its users call, at deployment
sizes, on seeded random data, and checks every result against a plain
float32 ``jnp.einsum`` reference run under
``jax.default_matmul_precision("highest")``:

  precision  the accuracy of an f32 product in a Pallas kernel and in an
             XLA dot at default precision (the tolerances assume one bf16
             pass, as measured on v5e)
  engine     ``dxt3d(engine=True)`` forward then inverse on a 512³ float32
             volume, then on a (2, 256, 256, 256) batch
  fit        3 steps of ``build_dxt_fit_step`` on 128³ factors, batch 8
  serve      ``ResilientDxtServer``: 16 coalesced (1, 128, 128, 128)
             requests, half forward and half inverse

``--mesh`` runs only the four-chip path: a 512³ DCT through
``gemt3_planned(mesh=)`` on a (2, 2) ("data", "model") mesh, against the
same reference.

It refuses to run unless JAX's default backend is a TPU, stops at the
first failure with a non-zero exit, and prints as its last stdout line
``{"ok": true, "device": {"platform", "kind", "count"}}``.

    python chip_smoke.py [--seed N] [--mesh]
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# Tolerances, as relative Frobenius error ||y - ref|| / ||ref|| against the
# float32 einsum reference at "highest" precision.
#
# Measured on TPU v5e (precision phase): an f32 product inside a Pallas
# kernel (``jnp.dot`` with no precision argument) and an XLA dot at default
# precision both run ONE bf16 pass — relative error 2.35e-3 on a 1024-deep
# random product, against 8.6e-8 at "highest".  The engine's f32
# transforms inherit that: each mode contraction rounds its operands to
# bf16, and three contractions land near sqrt(3) * 2.4e-3 ~= 4e-3 from the
# f32 reference.  The tolerances below are that model with a 2.5x margin;
# the precision phase fails if a single product is worse than one bf16
# pass, so the margin cannot hide a lower-precision path.
BF16_PASS = 4e-3       # rel. error bound of one single-pass bf16 product
TOL_TRANSFORM = 1e-2   # 3 contractions ~= 4e-3
TOL_ROUNDTRIP = 1.5e-2  # forward then inverse, 6 contractions ~= 5.8e-3
# The fit loss and factor gradients are functions of the residual
# pred - y: the residual carries the forward's error divided by its own
# relative size rho (measured on the reference; ~0.6 at the init noise
# used here), and the gradients add their own three backward
# contractions, so both are held to TOL_TRANSFORM * (1 + 1 / rho).


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def rel_err(y, ref) -> float:
    import jax.numpy as jnp

    return float(jnp.linalg.norm((y - ref).ravel())
                 / jnp.linalg.norm(ref.ravel()))


def reference_fn():
    """jitted ``X ×1 C1 ×2 C2 ×3 C3`` at highest precision (3D or batched)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def ref(x, c1, c2, c3):
        with jax.default_matmul_precision("highest"):
            return jnp.einsum("...abc,ai,bj,ck->...ijk", x, c1, c2, c3)

    return ref


def dct_mats(n: int, inverse: bool = False):
    from repro.core.transforms import (coefficient_matrix,
                                       inverse_coefficient_matrix)

    build = inverse_coefficient_matrix if inverse else coefficient_matrix
    return build("dct", n)


def phase_precision(key) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops

    ka, kb = jax.random.split(key)
    a = jax.random.normal(ka, (1024, 1024), jnp.float32)
    b = jax.random.normal(kb, (1024, 1024), jnp.float32)
    exact = np.asarray(a, np.float64) @ np.asarray(b, np.float64)

    def err(y):
        y = np.asarray(y, np.float64)
        return float(np.linalg.norm(y - exact) / np.linalg.norm(exact))

    with jax.default_matmul_precision("highest"):
        highest = jax.jit(jnp.dot)(a, b)
    out = {"pallas_sr_gemm": err(ops.sr_gemm(a, b)),
           "xla_default": err(jax.jit(jnp.dot)(a, b)),
           "xla_highest": err(highest)}
    for k, v in out.items():
        log(f"[precision] f32 product, {k}: rel_err={v!r}")
    check(out["xla_highest"] < 1e-6,
          "the highest-precision reference is not f32-accurate")
    check(out["pallas_sr_gemm"] <= BF16_PASS,
          f"Pallas f32 product below one bf16 pass: {out['pallas_sr_gemm']!r}")
    return out


def describe_plan(tag: str, x, info: dict, inverse: bool) -> None:
    """Log the plan the engine ran: order, backends, fusion depth, tiles."""
    from repro.engine import build_plan

    cs = [dct_mats(d, inverse) for d in x.shape[-3:]]
    plan = build_plan(x.shape, x.dtype, *cs)
    depth = 3 if plan.fused3 else 2 if plan.fused else 0
    tiles = {"stages": [(s.mode, s.backend, s.bm, s.bn, s.bk)
                        for s in plan.stages]}
    if plan.fused is not None:
        f = plan.fused
        tiles["pair"] = {"modes": (f.mode_a, f.mode_b), "bu": f.bu,
                         "bka": f.bka, "bnb": f.bnb, "bna": f.bna,
                         "kbp": f.kbp, "vmem_bytes": f.vmem_bytes}
    if plan.fused3 is not None:
        f = plan.fused3
        tiles["triple"] = {"bu": f.bu, "bka": f.bka, "bnb": f.bnb,
                           "bnc": f.bnc, "bna": f.bna,
                           "vmem_bytes": f.vmem_bytes}
    log(f"[{tag}] plan order={info['order']} backends={info['backends']} "
        f"executed={info['backends_executed']} fused_depth={depth} "
        f"tiles={json.dumps(tiles)}")
    log(f"[{tag}] events={json.dumps(info['events'], default=str)}")


def check_engine_info(tag: str, info: dict) -> None:
    kernels = [b for b in info["backends_executed"] if b != "einsum"]
    check(kernels, f"[{tag}] no stage ran a Pallas kernel: "
          f"{info['backends_executed']}")
    for ev in info["events"]:
        text = json.dumps(ev, default=str).lower()
        check("error" not in text and "compile" not in text,
              f"[{tag}] degradation names an error: {ev}")


def phase_engine(key, shape) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import dxt3d

    tag = "engine " + "x".join(map(str, shape))
    ref = reference_fn()
    x = jax.random.normal(key, shape, jnp.float32)
    t0 = time.perf_counter()
    y, info = dxt3d(x, "dct", engine=True, with_info=True)
    jax.block_until_ready(y)
    describe_plan(tag + " fwd", x, info, inverse=False)
    check_engine_info(tag + " fwd", info)
    fwd = [dct_mats(d) for d in shape[-3:]]
    e_fwd = rel_err(y, ref(x, *fwd))
    log(f"[{tag}] forward rel_err={e_fwd!r} (tol {TOL_TRANSFORM})")
    check(e_fwd <= TOL_TRANSFORM, f"[{tag}] forward rel_err {e_fwd!r}")

    xr, info_i = dxt3d(y, "dct", inverse=True, engine=True, with_info=True)
    jax.block_until_ready(xr)
    describe_plan(tag + " inv", y, info_i, inverse=True)
    check_engine_info(tag + " inv", info_i)
    inv = [dct_mats(d, inverse=True) for d in shape[-3:]]
    e_inv = rel_err(xr, ref(y, *inv))
    e_rt = rel_err(xr, x)
    log(f"[{tag}] inverse rel_err={e_inv!r} (tol {TOL_TRANSFORM}) "
        f"roundtrip rel_err={e_rt!r} (tol {TOL_ROUNDTRIP}) "
        f"wall_s={time.perf_counter() - t0:.1f} (incl. compile)")
    check(e_inv <= TOL_TRANSFORM, f"[{tag}] inverse rel_err {e_inv!r}")
    check(e_rt <= TOL_ROUNDTRIP, f"[{tag}] roundtrip rel_err {e_rt!r}")


def phase_fit(key, n: int = 128, batch: int = 8) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.layers import apply_dxt3d_layer
    from repro.engine import gemt3_planned, grad_stats, reset_grad_stats
    from repro.optim import OptConfig
    from repro.train.step import build_dxt_fit_step, init_dxt_fit_state

    dims = (n, n, n)
    kx, kp = jax.random.split(key)
    ocfg = OptConfig(lr=1e-3, warmup_steps=0, total_steps=3)
    state = init_dxt_fit_state(dims, ocfg, key=kp, init_scale=3e-2)
    ref = reference_fn()
    x = jax.random.normal(kx, (batch,) + dims, jnp.float32)
    y = ref(x, *[dct_mats(d) for d in dims])  # the exact DCT to fit
    p0 = state["params"]

    def ref_loss(p, x, y):
        with jax.default_matmul_precision("highest"):
            pred = jnp.einsum("...abc,ai,bj,ck->...ijk", x, p["c1"], p["c2"],
                              p["c3"])
        return jnp.mean((pred - y) ** 2)

    loss_ref, g_ref = jax.jit(jax.value_and_grad(ref_loss))(p0, x, y)
    rho = math.sqrt(float(loss_ref) / float(jnp.mean(y ** 2)))
    tol_fit = TOL_TRANSFORM * (1 + 1 / rho)
    loss_eng, g_eng = jax.value_and_grad(
        lambda p: jnp.mean((apply_dxt3d_layer(p, x) - y) ** 2))(p0)
    e_loss = abs(float(loss_eng) - float(loss_ref)) / float(loss_ref)
    e_grad = max(rel_err(g_eng[k], g_ref[k]) for k in ("c1", "c2", "c3"))
    log(f"[fit] loss0 engine={float(loss_eng)!r} ref={float(loss_ref)!r} "
        f"residual rho={rho!r} loss rel_err={e_loss!r} "
        f"grad rel_err={e_grad!r} (tol {tol_fit!r})")
    check(e_loss <= tol_fit, f"[fit] loss rel_err {e_loss!r}")
    check(e_grad <= tol_fit, f"[fit] gradient rel_err {e_grad!r}")

    _, info = gemt3_planned(x, p0["c1"], p0["c2"], p0["c3"],
                            differentiable=True, with_info=True)
    log(f"[fit] forward executed={info['backends_executed']} "
        f"grad_chain_depth={info.get('grad_chain_depth')} "
        f"grad_launches={info.get('grad_launches')} "
        f"grad_backends={info.get('grad_backends')}")

    fit_step = build_dxt_fit_step(ocfg)
    reset_grad_stats()
    t0 = time.perf_counter()
    losses = []
    for _ in range(3):
        state, metrics = fit_step(state, {"x": x, "y": y})
        losses.append(float(metrics["loss"]))
    stats = grad_stats()
    log(f"[fit] losses={losses} grad_stats={stats} "
        f"wall_s={time.perf_counter() - t0:.1f} (incl. compile)")
    check(all(math.isfinite(v) for v in losses),
          f"[fit] nonfinite loss {losses}")
    check(stats["einsum_stages"] == 0,
          f"[fit] backward ran einsum stages: {stats}")
    check(stats["backward_calls"] >= 3, f"[fit] no engine backward: {stats}")


def phase_serve(key, n: int = 128, count: int = 16) -> None:
    import jax
    import jax.numpy as jnp

    from repro.serve import ResilientDxtServer

    srv = ResilientDxtServer(max_coalesce=4, pipeline_depth=2,
                             finite_check_every=1)
    t0 = time.perf_counter()
    srv.warmup([(4, n, n, n)])
    srv.warmup([(4, n, n, n)], inverse=True)
    log(f"[serve] warmup wall_s={time.perf_counter() - t0:.1f}")
    xs = jax.random.normal(key, (count, 1, n, n, n), jnp.float32)
    reqs = [srv.submit(xs[i], inverse=bool(i % 2)) for i in range(count)]
    check(all(r is not None for r in reqs), "[serve] a request was shed")
    srv.drain()
    ref = reference_fn()
    fwd, inv = [dct_mats(n)] * 3, [dct_mats(n, inverse=True)] * 3
    worst = 0.0
    for i, r in enumerate(reqs):
        check(r.status == "done", f"[serve] request {r.id} {r.status}: "
              f"{r.error!r}")
        worst = max(worst, rel_err(r.result, ref(xs[i], *(inv if r.inverse
                                                           else fwd))))
    c = srv.counts
    log(f"[serve] done={sum(r.status == 'done' for r in reqs)}/{count} "
        f"batches={c['batches']} coalesced={c['coalesced']} "
        f"degraded={c['degraded']} retry={c['retries']} failed={c['failed']} "
        f"worst rel_err={worst!r} (tol {TOL_TRANSFORM})")
    check(c["degraded"] == 0 and c["retries"] == 0 and c["failed"] == 0,
          f"[serve] degraded/retried/failed requests: {c}")
    check(worst <= TOL_TRANSFORM, f"[serve] rel_err {worst!r}")


def phase_mesh(key, n: int = 512) -> None:
    import jax
    import jax.numpy as jnp

    from repro.engine import gemt3_planned

    check(len(jax.devices()) == 4, f"--mesh needs 4 chips, found "
          f"{len(jax.devices())}")
    mesh = jax.make_mesh((2, 2), ("data", "model"))
    cs = [dct_mats(n)] * 3
    x = jax.random.normal(key, (n, n, n), jnp.float32)
    t0 = time.perf_counter()
    y, info = gemt3_planned(x, *cs, mesh=mesh, axes=("data", "model", None),
                            with_info=True)
    jax.block_until_ready(y)
    sharded = [s for s in info["stages"] if s.get("axis") is not None]
    log(f"[mesh] order={info['order']} backends={info['backends']} "
        f"executed={info['backends_executed']} "
        f"collective_bytes={info['collective_bytes']} "
        f"sharded_stages={json.dumps(sharded, default=str)} "
        f"devices={len(y.sharding.device_set)} "
        f"wall_s={time.perf_counter() - t0:.1f} (incl. compile)")
    check(sharded and all(s["backend"] == "sr_gemm" for s in sharded),
          f"[mesh] sharded stages not on sr_gemm: {info['backends']}")
    check(info["collective_bytes"] > 0, "[mesh] no collective bytes")
    check(len(y.sharding.device_set) == 4, "[mesh] output not on 4 devices")
    e = rel_err(y, reference_fn()(x, *cs))
    log(f"[mesh] rel_err={e!r} (tol {TOL_TRANSFORM})")
    check(e <= TOL_TRANSFORM, f"[mesh] rel_err {e!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", action="store_true",
                    help="run only the 2x2 mesh path (needs 4 chips)")
    args = ap.parse_args(argv)

    import jax

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {jax.default_backend()!r}",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache(ROOT)}")
    dev = jax.devices()[0]
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 6)
    try:
        if args.mesh:
            phase_mesh(keys[0])
        else:
            phase_precision(keys[1])
            phase_engine(keys[2], (512, 512, 512))
            phase_engine(keys[3], (2, 256, 256, 256))
            phase_fit(keys[4])
            phase_serve(keys[5])
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

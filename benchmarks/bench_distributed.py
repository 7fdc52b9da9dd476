"""Distributed GEMT benchmarks: TriADA shard_map schedule vs GSPMD auto,
collective-byte comparison (dry-run artifacts), strong-scaling step model,
and the topology-aware engine vs the einsum schedule (D3).

Runs in a subprocess with 8 virtual devices (the only place outside
launch/dryrun.py that needs >1 device).
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import numpy as np

from repro.core import macs, time_steps

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run8(code: str) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    # Virtual CPU devices only: a child must never reach for the chip the
    # parent process may already hold.
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=600)


def bench_strong_scaling_model(rows):
    """TriADA strong-scaling (§5.1 tiling): each P³-cell tile streams the
    full contracted extent (N per stage, so N1+N2+N3 steps per output
    tile); with (N/P)³ tiles, total steps scale as 1/P³ — extreme strong
    scaling at a constant 100 % MACs/cell/step efficiency."""
    n = 64
    for p in (64, 32, 16, 8):
        tiles = (n // p) ** 3
        steps = tiles * time_steps(n, n, n)
        eff = macs(n, n, n) / (steps * p ** 3)  # MACs per cell-step
        rows.append((f"D1_strong_scaling_P{p}^3", 0.0,
                     f"steps={steps};cells={p**3};efficiency={eff:.2f}"))


def bench_shardmap_vs_auto(rows):
    """Collective bytes: hand-placed TriADA schedule vs GSPMD auto."""
    r = _run8("""
        import jax, jax.numpy as jnp
        from repro.core import gemt3_shardmap, gemt3_auto
        from repro.launch.roofline import analyze_hlo
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        sds = jax.ShapeDtypeStruct
        args = (sds((32, 32, 32), jnp.float32),) + (sds((32, 32), jnp.float32),) * 3
        for name, f in [("shardmap", jax.jit(gemt3_shardmap(mesh))),
                        ("auto", gemt3_auto(mesh))]:
            hlo = f.lower(*args).compile().as_text()
            c = analyze_hlo(hlo, 8)
            print(f"{name},{c.ici_bytes:.0f},{c.flops:.0f}")
    """)
    if r.returncode != 0:
        rows.append(("D2_shardmap_vs_auto", 0.0, f"FAILED:{r.stderr[-200:]}"))
        return
    vals = {}
    for line in r.stdout.strip().splitlines():
        name, ici, flops = line.split(",")
        vals[name] = float(ici)
        rows.append((f"D2_gemt_{name}", 0.0,
                     f"ici_bytes_per_dev={float(ici):.0f};flops={flops}"))
    if vals.get("auto"):
        rows.append(("D2_collective_ratio", 0.0,
                     f"shardmap_vs_auto={vals['shardmap'] / vals['auto']:.3f}"))


def bench_distributed_engine(rows):
    """D3: topology-aware engine inside shard_map vs the einsum schedule.

    Times the local stages both ways on an 8-virtual-device mesh (engine =
    planned Pallas dispatch per shard, einsum = the legacy ``engine=False``
    schedule), checks numerical agreement, and reports the planner's
    modeled per-shard local HBM bytes + per-device psum_scatter collective
    bytes.  ``python -m benchmarks.run --filter distributed_engine --json
    --out BENCH_distributed_engine.json`` writes the artifact.
    """
    r = _run8("""
        import time
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import gemt3_shardmap
        from repro.core.transforms import coefficient_matrix
        from repro.engine import gemt3_planned

        mesh = jax.make_mesh((2, 4), ("data", "model"))
        axes = ("data", "model", None)
        rng = np.random.default_rng(0)

        def tmin(fns, n=7):
            def once(fn):
                t0 = time.perf_counter()
                jax.block_until_ready(fn())
                return (time.perf_counter() - t0) * 1e6
            for fn in fns:
                once(fn)  # warmup/compile/trace
            best = [float("inf")] * len(fns)
            for r_ in range(n):  # interleaved: shared background noise
                idxs = range(len(fns)) if r_ % 2 == 0 else reversed(range(len(fns)))
                for i in idxs:
                    best[i] = min(best[i], once(fns[i]))
            return best

        def sparse_dct(n, zero_cols):
            c = np.asarray(coefficient_matrix("dct", n)).copy()
            c[:, n - zero_cols:] = 0.0
            return jnp.asarray(c)

        cases = [
            ("dense_32", (32, 32, 32),
             tuple(coefficient_matrix("dct", 32) for _ in range(3)), {}),
            ("dense_64", (64, 64, 64),
             tuple(coefficient_matrix("dct", 64) for _ in range(3)), {}),
            ("sparse_48", (48, 48, 48),
             (coefficient_matrix("dct", 48), sparse_dct(48, 24),
              sparse_dct(48, 24)), {"block_sizes": (8, 8, 8)}),
        ]
        for name, dims, cs, kw in cases:
            x = jnp.asarray(rng.normal(size=dims).astype(np.float32))
            f_eng = gemt3_shardmap(mesh, axes=axes, order=None, **kw)
            f_ein = jax.jit(gemt3_shardmap(mesh, axes=axes, engine=False))
            y_eng, y_ein = f_eng(x, *cs), f_ein(x, *cs)
            err = float(jnp.max(jnp.abs(y_eng - y_ein)))
            us_eng, us_ein = tmin([lambda: f_eng(x, *cs),
                                   lambda: f_ein(x, *cs)])
            info = gemt3_planned(x, *cs, mesh=mesh, axes=axes,
                                 with_info=True, **kw)[1]
            backends = "+".join(b.replace(", ", "-")
                                for b in info["backends_executed"])
            print(f"{name},{us_eng:.1f},{us_ein:.1f},{err:.1e},"
                  f"{''.join(map(str, info['order']))},{backends},"
                  f"{info['hbm_bytes_local']},{info['collective_bytes']},"
                  f"{info['fetch_savings']:.3f}")
    """)
    if r.returncode != 0:
        rows.append(("D3_distributed_engine", 0.0,
                     f"FAILED:{r.stderr[-200:]}"))
        return
    for line in r.stdout.strip().splitlines():
        (name, us_eng, us_ein, err, order, backends, local_b, coll_b,
         fetch) = line.split(",")
        rows.append((
            f"D3_engine_vs_einsum_{name}", float(us_eng),
            f"einsum_us={float(us_ein):.1f};"
            f"speedup={float(us_ein) / max(float(us_eng), 1e-9):.2f}x;"
            f"order={order};backends={backends};"
            f"hbm_bytes_local={local_b};collective_bytes={coll_b};"
            f"fetch_savings={fetch};max_abs_err={err}"))

"""Closed-loop spectral iteration, after the NAS Parallel Benchmarks' FT.

A field u0 is drawn from the seed and taken to its spectrum by one
forward 3-D transform through the system under test; the separable decay
factor ``exp(-4 alpha pi^2 (i^2 + j^2 + k^2))`` is kept as three vectors
(indices folded to the symmetric range).  Each iteration t then

1. ``evolve``: multiplies the running spectrum by the factor (a jitted
   program of the benchmark's own, ``ft_evolve``), so that it holds the
   spectrum times the factor to the power t;
2. applies the inverse transform through the system under test
   (``dxt3d(..., inverse=True, engine=True)``, with the mesh when the
   configuration has one);
3. computes the 1024-point checksum ``sum_j out[j s1 mod N1, j s2 mod N2,
   j s3 mod N3]`` (``ft_checksum``) and reads it to the host, where the
   iteration ends.

After ``niter`` iterations the loop restarts from the forward spectrum,
which set-up keeps on the device.  The traffic file sets alpha and the
checksum; the configuration sets the grid, niter, the transform and the
mesh.

The check runs once the window has closed and the program's state is
freed: a plain float32 reference at "highest" precision recomputes the
forward spectrum from the same u0, raises the factor to each power t
directly, and inverts.  It compares the 1024 points of every timed
iteration and the output field of one iteration, kept by a reservoir
sample drawn from the seed.  A configuration whose field leaves no room
for a second copy on the chip sets ``kept_field_parts``: the sample then
keeps a slab of 1/parts of the last mode (``ft_keep``), at an offset
drawn from the seed.
"""
from __future__ import annotations

import random
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

SPAN_NAMES = ("iteration", "evolve", "dxt3d_call", "checksum_wait")
# Iterations run before the window: the first builds the plan and compiles
# (or loads) every program; the second runs the loop as the window will.
WARMUP = 2
# Device time of these programs is the benchmark's own, not the transform's.
OWN_MODULES = ("ft_initial", "ft_evolve", "ft_checksum", "ft_keep")


def seed_key(seed: int):
    """A key that depends on all bits of a seed of up to 64 bits."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def decay_vectors(dims, alpha: float, power: int = 1) -> list:
    """``exp(-4 alpha pi^2 power ibar^2)`` per mode, ibar folded to
    [-N/2, N/2), in float32."""
    out = []
    for n in dims:
        ibar = (np.arange(n) + n // 2) % n - n // 2
        out.append(np.exp(-4.0 * alpha * np.pi ** 2 * power
                          * ibar.astype(np.float64) ** 2).astype(np.float32))
    return out


def checksum_indices(dims, count: int, strides) -> np.ndarray:
    """(3, count) int32 positions ``j * stride_m mod N_m``, j = 1..count."""
    j = np.arange(1, count + 1, dtype=np.int64)
    return np.stack([(j * s) % n for s, n in zip(strides, dims)]).astype(
        np.int32)


def ft_evolve(u, e1, e2, e3):
    return u * e1[:, None, None] * e2[None, :, None] * e3[None, None, :]


def ft_keep(out, start, width: int):
    return jax.lax.dynamic_slice_in_dim(out, start, width, axis=2)


def make_checksum(mesh, axes, dims, idx: np.ndarray):
    """``ft_checksum(out) -> (sum, points)``.  On a mesh each chip gathers
    the points that lie in its block and one ``psum`` adds them, so the
    field never moves."""
    idx = jnp.asarray(idx)

    if mesh is None:
        def ft_checksum(out):
            pts = out[idx[0], idx[1], idx[2]]
            return pts.sum(), pts
        return jax.jit(ft_checksum)

    names = tuple(a for a in axes if a is not None)

    def local(block):
        inside = jnp.ones(idx.shape[1], bool)
        pos = []
        for m in range(3):
            ext = block.shape[m]
            off = (0 if axes[m] is None
                   else jax.lax.axis_index(axes[m]) * ext)
            li = idx[m] - off
            inside &= (li >= 0) & (li < ext)
            pos.append(jnp.clip(li, 0, ext - 1))
        vals = jnp.where(inside, block[pos[0], pos[1], pos[2]], 0.0)
        return jax.lax.psum(vals, names)

    gather = jax.shard_map(local, mesh=mesh, in_specs=P(*axes),
                           out_specs=P(), check_vma=False)

    def ft_checksum(out):
        pts = gather(out)
        return pts.sum(), pts
    return jax.jit(ft_checksum)


class Workload:
    span_names = SPAN_NAMES
    own_modules = OWN_MODULES

    def __init__(self, config: dict, traffic: dict, devices, seed: int,
                 reference, transform=None):
        self.cfg, self.traffic, self.seed = config, traffic, seed
        self.ref = reference  # the configuration's plain reference module
        self.dims = tuple(config["grid"])
        self.niter = int(config["niter"])
        self.chips = len(devices)
        mcfg = config.get("mesh")
        if mcfg:
            shape = tuple(mcfg["shape"])
            self.mesh = Mesh(np.asarray(devices).reshape(shape),
                             tuple(mcfg["axis_names"]),
                             axis_types=(AxisType.Auto,) * len(shape))
            self.axes = tuple(mcfg["axes"])
            self.sharding = NamedSharding(self.mesh, P(*self.axes))
            self.replicated = NamedSharding(self.mesh, P())
        else:
            self.mesh, self.axes = None, None
            self.sharding = self.replicated = SingleDeviceSharding(devices[0])
        dims = self.dims

        def ft_initial(key):
            return jax.random.normal(key, dims, jnp.float32)
        self._initial = jax.jit(ft_initial, out_shardings=self.sharding)
        # The transform under test; the control passes its own.
        self.transform = transform or self.program_transform
        self.idx = checksum_indices(self.dims, traffic["checksum_points"],
                                    traffic["checksum_strides"])
        self._rng = random.Random(f"sample:{seed}")

    # -- the system under test ------------------------------------------
    def program_transform(self, u, inverse: bool):
        import repro.core as core

        kw = {} if self.mesh is None else {"mesh": self.mesh,
                                           "axes": self.axes}
        return core.dxt3d(u, self.cfg["transform"], inverse=inverse,
                          engine=True, **kw)

    # -- set-up -----------------------------------------------------------
    def initial_field(self):
        return self._initial(seed_key(self.seed))

    def setup(self) -> None:
        self.evolve = jax.jit(ft_evolve, out_shardings=self.sharding)
        self.keep_slab = jax.jit(ft_keep, static_argnums=(2,),
                                 out_shardings=self.sharding)
        self.checksum = make_checksum(self.mesh, self.axes, self.dims,
                                      self.idx)
        self.decay = [jax.device_put(v, self.replicated) for v in
                      decay_vectors(self.dims, self.traffic["evolve_alpha"])]
        self.spectrum = self.transform(self.initial_field(), False)
        jax.block_until_ready(self.spectrum)
        self.t, self.u = 0, None
        self.points, self.kept, self.timed = [], None, 0
        self.bad_dtype = 0
        for _ in range(WARMUP):
            self._iteration(record=False)
        self.points, self.t, self.u = [], 0, None

    # -- the loop -----------------------------------------------------------
    def _iteration(self, record: bool = True) -> float:
        with jax.profiler.TraceAnnotation("iteration"):
            src = self.spectrum if self.t == 0 else self.u
            with jax.profiler.TraceAnnotation("evolve"):
                self.u = self.evolve(src, *self.decay)
            self.t = self.t % self.niter + 1
            with jax.profiler.TraceAnnotation("dxt3d_call"):
                t0 = time.perf_counter()
                out = self.transform(self.u, True)
                host = time.perf_counter() - t0
            with jax.profiler.TraceAnnotation("checksum_wait"):
                total, pts = self.checksum(out)
                float(total)
        if not record:
            self._keep(out)  # warms ft_keep up
        else:
            self.timed += 1
            self.points.append((self.t, pts))
            if out.dtype != jnp.float32 or out.shape != self.dims:
                self.bad_dtype += 1
            if self._rng.random() < 1.0 / self.timed:
                self.kept = self._keep(out)
        if self.t == self.niter:
            self.t = 0
        return host

    def run(self, seconds: float | None = None,
            count: int | None = None) -> dict:
        """Iterate for ``seconds`` (finishing the iteration that crosses
        the end) or ``count`` iterations."""
        n, host = 0, 0.0
        t0 = time.perf_counter()
        while True:
            host += self._iteration()
            n += 1
            if count is not None and n >= count:
                break
            if seconds is not None and time.perf_counter() - t0 >= seconds:
                break
        return {"iterations": n, "elapsed_s": time.perf_counter() - t0,
                "host_call_s": host}

    def _keep(self, out) -> tuple:
        """``(t, start, slab)``: the whole output, or a slab of the last
        mode at an offset drawn from the seed."""
        parts = int(self.cfg.get("kept_field_parts", 1))
        if parts == 1:
            return self.t, 0, out
        width = self.dims[2] // parts
        start = self._rng.randrange(parts) * width
        return self.t, start, self.keep_slab(out, start, width)

    @property
    def trace_count(self) -> int:
        """A traced run traces one whole cycle of ``niter`` iterations."""
        return self.niter

    # -- the check ------------------------------------------------------------
    def release(self) -> None:
        """Drop the program's state; keep only what the check compares."""
        self.spectrum = self.u = None

    def check(self, limits: dict) -> tuple[dict, int]:
        """``({name: (value, limit)}, failed iterations)`` against the
        plain reference."""
        self.release()
        mats = [jax.device_put(self.ref.hartley(n), self.replicated)
                for n in self.dims]
        spec = self.ref.transform(self.initial_field(), mats)
        alpha = self.traffic["evolve_alpha"]
        scale = jax.jit(ft_evolve, out_shardings=self.sharding)
        stats = jax.jit(_field_stats)
        pts_ref, field = {}, {"rel": 0.0, "max": 0.0}
        kept_t, start, out = self.kept
        for t in sorted({t for t, _ in self.points}):
            dec = [jax.device_put(v, self.replicated)
                   for v in decay_vectors(self.dims, alpha, power=t)]
            y = self.ref.transform(scale(spec, *dec), mats)
            pts_ref[t] = np.asarray(self.checksum(y)[1], np.float64)
            if t == kept_t:
                part = y if out.shape == y.shape else self.keep_slab(
                    y, start, out.shape[2])
                field["rel"], field["max"] = (float(v)
                                              for v in stats(out, part))
            del y
        worst, failed = 0.0, 0
        for t, pts in self.points:
            ref = pts_ref[t]
            err = float(np.max(np.abs(np.asarray(pts, np.float64) - ref))
                        / np.sqrt(np.mean(ref ** 2)))
            worst = max(worst, err)
            failed += err > limits["checksum_err"]
        checks = {
            "checksum_err": (worst, limits["checksum_err"]),
            "field_rel_err": (field["rel"], limits["field_rel_err"]),
            "field_max_err": (field["max"], limits["field_max_err"]),
            "bad_outputs": (self.bad_dtype, 0),
        }
        return checks, failed


def _field_stats(out, ref):
    """Relative Frobenius error, and the largest error over the field's
    root mean square."""
    d = out.astype(jnp.float32) - ref
    rms = jnp.sqrt(jnp.mean(ref * ref))
    return (jnp.sqrt(jnp.sum(d * d) / jnp.sum(ref * ref)),
            jnp.max(jnp.abs(d)) / rms)

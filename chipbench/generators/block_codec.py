"""Closed-loop 3-D DCT block coding of a video buffer, one caller.

The configuration names the colour planes of one buffer (frames x rows x
columns each), the block and the pool.  Set-up draws ``pool`` buffers of
8-bit samples from the seed (level-shifted to [-128, 127], held as
float32, ``enc_frames``).  Iteration t then

1. takes every plane of buffer ``t mod pool`` to its blocked coefficients
   through the system under test, ``dxt3d(plane, kind, block=...,
   engine=True)``;
2. quantises and dequantises them at step ``q = steps[t mod len(steps)]``
   (the traffic's quantiser schedule), counting the nonzero levels and
   gathering the checksum points of the coefficients (``enc_quant``, a
   program of the benchmark's own), after which the coefficients are
   dropped;
3. reconstructs every plane from its dequantised coefficients with the
   inverse blocked transform, through the system under test;
4. sums the squared error of the reconstruction against the frames and
   the checksum points (``enc_measure``);
5. reads {nonzeros, squared error, checksum} to the host, where the
   iteration ends.

The check runs once the window has closed and the pool is freed.  A plain
float32 reference at "highest" precision compares each transform with the
input the program gave it: the checksum points of every timed iteration
against the reference's forward transform of the frames (each point from
its own block), and, for one timed iteration kept by a reservoir sample
drawn from the seed, one group of pictures (the block's frame extent) of
every plane: the program's coefficients against the reference's forward
transform of those frames, and the program's reconstruction against the
reference's inverse transform of the program's own dequantised
coefficients.  A flip of a rounding at a quantiser step therefore moves
no comparison.
"""
from __future__ import annotations

import functools
import importlib.util
import os
import random
import time

import jax
import jax.numpy as jnp
import numpy as np

_spec = importlib.util.spec_from_file_location(
    "chipbench_spectral_loop",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "spectral_loop.py"))
_spectral = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_spectral)
seed_key, checksum_indices = _spectral.seed_key, _spectral.checksum_indices

SPAN_NAMES = ("iteration", "forward", "quantise", "inverse", "measure_wait")
# Iterations run before the window: the first builds the plans and
# compiles (or loads) every program; the second runs the loop as the
# window will.
WARMUP = 2
# Device time of these programs is the benchmark's own, not the transform's.
OWN_MODULES = ("enc_frames", "enc_quant", "enc_measure", "enc_keep")
# Counters of the system under test read over the traced cycle.
COUNTERS = ("engine.macs", "engine.macs_effective")


@functools.partial(jax.jit, static_argnums=(1,))
def enc_frames(key, shape):
    """8-bit samples, level-shifted to [-128, 127], as float32."""
    return (jax.random.randint(key, shape, 0, 256, jnp.int32)
            - 128).astype(jnp.float32)


@jax.jit
def enc_quant(coeffs, q, idx):
    """Dequantised coefficients, the count of nonzero levels and the
    checksum points of the coefficients, over every plane."""
    deq, nonzeros, pts = [], jnp.int32(0), []
    for c, ix in zip(coeffs, idx):
        level = jnp.round(c / q)
        nonzeros += jnp.count_nonzero(level).astype(jnp.int32)
        deq.append(level * q)
        pts.append(c[ix[0], ix[1], ix[2]])
    return tuple(deq), nonzeros, jnp.concatenate(pts)


@jax.jit
def enc_measure(frames, recon, pts):
    """Squared error of the reconstruction, and the checksum."""
    sse = sum(jnp.sum(jnp.square(r - f)) for f, r in zip(frames, recon))
    return sse, pts.sum()


@functools.partial(jax.jit, static_argnums=(2,))
def enc_keep(planes, start, width: int):
    """``width`` frames of every plane from frame ``start``."""
    return tuple(jax.lax.dynamic_slice_in_dim(p, start, width, axis=0)
                 for p in planes)


class Workload:
    span_names = SPAN_NAMES
    own_modules = OWN_MODULES

    def __init__(self, config: dict, traffic: dict, devices, seed: int,
                 reference, transform=None):
        self.cfg, self.traffic, self.seed = config, traffic, seed
        self.ref = reference  # the configuration's plain reference module
        self.planes = {k: tuple(v) for k, v in config["planes"].items()}
        self.block = tuple(config["block"])
        self.pool = int(config["pool"])
        self.steps = tuple(float(q) for q in traffic["quantiser_steps"])
        self.device = devices[0]
        # The transform under test; the control passes its own.
        self.transform = transform or self.program_transform
        # Checksum point j lies in plane j mod (number of planes).
        names = list(self.planes)
        count = int(traffic["checksum_points"])
        self.idx = [checksum_indices(self.planes[p],
                                     len(range(i, count, len(names))),
                                     traffic["checksum_strides"])
                    for i, p in enumerate(names)]
        self._rng = random.Random(f"sample:{seed}")

    @property
    def transforms_per_iteration(self) -> int:
        """One forward and one inverse transform of every plane."""
        return 2 * len(self.planes)

    # -- the system under test ------------------------------------------
    def program_transform(self, plane, inverse: bool):
        import repro.core as core

        return core.dxt3d(plane, self.cfg["transform"], inverse=inverse,
                          engine=True, block=self.block)

    # -- set-up -----------------------------------------------------------
    def frames(self, buffer: int) -> tuple:
        """The planes of one buffer, drawn from the seed."""
        key = jax.random.fold_in(seed_key(self.seed), buffer)
        with jax.default_device(self.device):
            return tuple(enc_frames(jax.random.fold_in(key, i), shape)
                         for i, shape in enumerate(self.planes.values()))

    def setup(self) -> None:
        self.buffers = [self.frames(b) for b in range(self.pool)]
        self.idx_dev = tuple(jax.device_put(ix, self.device)
                             for ix in self.idx)
        jax.block_until_ready(self.buffers)
        self.t = 0
        self.records, self.kept, self.timed, self.bad = [], None, 0, 0
        for _ in range(WARMUP):
            self._iteration(record=False)
        self.records, self.t = [], 0

    # -- the loop -----------------------------------------------------------
    def _iteration(self, record: bool = True) -> float:
        b, q = self.t % self.pool, self.steps[self.t % len(self.steps)]
        frames = self.buffers[b]
        # Warm-up iterations keep (and discard) a sample, which compiles
        # enc_keep; timed ones keep one by a reservoir sample.
        keep = not record or self._rng.random() < 1.0 / (self.timed + 1)
        width = self.block[0]
        start = (self._rng.randrange(frames[0].shape[0] // width) * width
                 if keep else 0)
        host = 0.0
        with jax.profiler.TraceAnnotation("iteration"):
            with jax.profiler.TraceAnnotation("forward"):
                t0 = time.perf_counter()
                coeffs = tuple(self.transform(f, False) for f in frames)
                host += time.perf_counter() - t0
            with jax.profiler.TraceAnnotation("quantise"):
                deq, nonzeros, pts = enc_quant(coeffs, q, self.idx_dev)
                kept_coeffs = enc_keep(coeffs, start, width) if keep else None
                ok = _well_formed(coeffs, frames)
                del coeffs
            with jax.profiler.TraceAnnotation("inverse"):
                t0 = time.perf_counter()
                recon = tuple(self.transform(d, True) for d in deq)
                host += time.perf_counter() - t0
            with jax.profiler.TraceAnnotation("measure_wait"):
                sse, total = enc_measure(frames, recon, pts)
                nz, sse, total = jax.device_get((nonzeros, sse, total))
        if keep:
            kept = (b, start, kept_coeffs, enc_keep(deq, start, width),
                    enc_keep(recon, start, width))
        if record:
            self.timed += 1
            self.records.append((b, pts))
            if not (ok and _well_formed(recon, frames)
                    and np.isfinite(sse) and np.isfinite(total)
                    and 0 <= nz <= sum(f.size for f in frames)):
                self.bad += 1
            if keep:
                self.kept = kept
        self.t += 1
        return host

    def run(self, seconds: float | None = None,
            count: int | None = None) -> dict:
        """Iterate for ``seconds`` (finishing the iteration that crosses
        the end) or ``count`` iterations."""
        from repro import obs

        reg = obs.get_registry()
        before = [reg.value(k) for k in COUNTERS]
        n, host = 0, 0.0
        t0 = time.perf_counter()
        while True:
            host += self._iteration()
            n += 1
            if count is not None and n >= count:
                break
            if seconds is not None and time.perf_counter() - t0 >= seconds:
                break
        out = {"iterations": n, "elapsed_s": time.perf_counter() - t0,
               "host_call_s": host}
        out.update({k: reg.value(k) - v for k, v in zip(COUNTERS, before)})
        return out

    @property
    def trace_count(self) -> int:
        """A traced run traces two turns of the quantiser schedule."""
        return 2 * len(self.steps)

    # -- the check ------------------------------------------------------------
    def release(self) -> None:
        """Drop the program's state; keep only what the check compares."""
        self.buffers = None

    def check(self, limits: dict) -> tuple[dict, int]:
        """``({name: (value, limit)}, failed iterations)`` against the
        plain reference."""
        self.release()
        kept_b, start, coeffs, deq, recon = self.kept
        width = self.block[0]
        pts_ref, fwd, inv = {}, [0.0, 0.0], [0.0, 0.0]
        for b in sorted({b for b, _ in self.records} | {kept_b}):
            frames = self.frames(b)
            pts_ref[b] = np.concatenate([
                np.asarray(self._ref_points(f, ix), np.float64)
                for f, ix in zip(frames, self.idx)])
            if b != kept_b:
                continue
            for f, c, d, r in zip(frames, coeffs, deq, recon):
                slab = jax.lax.dynamic_slice_in_dim(f, start, width, axis=0)
                fwd = _worst(fwd, _field_stats(
                    c, self.ref.transform(slab, self.block)))
                inv = _worst(inv, _field_stats(
                    r, self.ref.transform(d, self.block, inverse=True)))
            del frames
        worst, failed = 0.0, 0
        for b, pts in self.records:
            ref = pts_ref[b]
            err = float(np.max(np.abs(np.asarray(pts, np.float64) - ref))
                        / np.sqrt(np.mean(ref ** 2)))
            worst = max(worst, err)
            failed += err > limits["checksum_err"]
        checks = {
            "checksum_err": (worst, limits["checksum_err"]),
            "forward_rel_err": (fwd[0], limits["forward_rel_err"]),
            "forward_max_err": (fwd[1], limits["forward_max_err"]),
            "inverse_rel_err": (inv[0], limits["inverse_rel_err"]),
            "inverse_max_err": (inv[1], limits["inverse_max_err"]),
            "bad_outputs": (self.bad, 0),
        }
        return checks, failed

    def _ref_points(self, frames, idx: np.ndarray):
        """The reference's coefficients at ``idx``, each from its block."""
        blk = np.asarray(self.block)[:, None]
        corner = jnp.asarray(idx // blk * blk)
        cubes = jax.vmap(lambda c: jax.lax.dynamic_slice(
            frames, tuple(c), self.block))(corner.T)
        n = cubes.shape[0]
        y = self.ref.transform(cubes.reshape(n * self.block[0],
                                             *self.block[1:]), self.block)
        within = idx % blk
        return y.reshape(n, *self.block)[np.arange(n), within[0], within[1],
                                         within[2]]


def _well_formed(outputs, frames) -> bool:
    """Every plane's output is float32 and of its frames' shape."""
    return all(o.dtype == jnp.float32 and o.shape == f.shape
               for o, f in zip(outputs, frames))


def _worst(acc, stats) -> list:
    return [max(a, float(s)) for a, s in zip(acc, stats)]


@jax.jit
def _field_stats(out, ref):
    """Relative Frobenius error, and the largest error over the field's
    root mean square."""
    d = out.astype(jnp.float32) - ref
    rms = jnp.sqrt(jnp.mean(ref * ref))
    return (jnp.sqrt(jnp.sum(d * d) / jnp.sum(ref * ref)),
            jnp.max(jnp.abs(d)) / rms)

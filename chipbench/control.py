#!/usr/bin/env python3
"""Read the numbers that decide ``correct`` for the program and for its
control, over many seeds in one process.

    python3 chipbench/control.py --workload npb_ft_c.iterate \\
        --program-seeds 1,2,3 --control-seeds 4,5,6 --seconds 2

The control is the plain reference put in the program's place: every
transform of the loop, the set-up's forward transform included, runs as
the reference's mode products computed in int8: both operands of every
product quantised to int8 by a per-tensor absmax scale, multiplied on the
MXU's int8 path with int32 accumulation, and rescaled (int8 is v5e's
other native MXU type, the step below the configured bfloat16 products).
Everything else about the run (set-up, window, check) is the benchmark's
own.  One JSON line per run on stdout: ``side``, ``seed``, ``correct``
and the checks.  The benchmark's runs never run the control.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def lower_mode(ref):
    """One of the reference's mode products, computed in int8."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=(2, 3))
    def mode_product(x, h, mode, sharding):
        sx = jnp.max(jnp.abs(x)) / 127.0
        sh = jnp.max(jnp.abs(h)) / 127.0
        xi = jnp.round(x / sx).astype(jnp.int8)
        hi = jnp.round(h / sh).astype(jnp.int8)
        y = jnp.einsum(ref.SPECS[mode], xi, hi,
                       preferred_element_type=jnp.int32)
        y = y.astype(jnp.float32) * (sx * sh)
        if sharding is not None:
            y = jax.lax.with_sharding_constraint(y, sharding)
        return y
    return mode_product


def control_transform(ref):
    """The reference in the program's place, in int8.  The DHT
    is its own inverse, so both directions use the same matrices, placed
    (replicated) beside the field at the first call."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    mode_product = lower_mode(ref)
    mats = []

    def transform(u, inverse):
        where = u.sharding
        sharding = where if isinstance(where, NamedSharding) else None
        if not mats:
            if sharding is not None:
                where = NamedSharding(where.mesh, P())
            mats.extend(jax.device_put(ref.hartley(n), where)
                        for n in u.shape)
        for mode, h in enumerate(mats):
            u = mode_product(u, h, mode, sharding)
        return u
    return transform


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--program-seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    import run

    spec = run.load_cell(args.workload)
    ref = run.load_module(os.path.join(HERE, "references",
                                       spec.config["reference"] + ".py"))
    runs = [("program", int(s)) for s in args.program_seeds.split(",") if s]
    runs += [("control", int(s)) for s in args.control_seeds.split(",") if s]
    for side, seed in runs:
        transform = control_transform(ref) if side == "control" else None
        rc, res = run.run_cell(spec, seed, args.seconds, trace=False,
                               transform=transform)
        if rc:
            return rc
        print(json.dumps({"side": side, "seed": seed,
                          "correct": res["correct"],
                          "iterations": res["attempted"],
                          "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

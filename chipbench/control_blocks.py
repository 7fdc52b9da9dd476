#!/usr/bin/env python3
"""Read the numbers that decide ``correct`` for a block-coding cell's
program and for its control, over many seeds in one process.

    python3 chipbench/control_blocks.py --workload uhd420_dct3d_b8.encode \\
        --program-seeds 1,2,3 --control-seeds 4,5,6 --seconds 2

The control is the plain reference put in the program's place, computed
in int8: each plane's forward and inverse blocked transform runs as three
mode products with the block-diagonal factors ``I ⊗ C_b`` of the
reference's b-point matrices, both operands of every product quantised to
int8 by a per-tensor absmax scale, multiplied with int32 accumulation and
rescaled (``control.lower_mode``; the zero blocks add nothing, so this is
the reference's in-block products at int8).  ``control.py`` cannot drive
these cells: its control is the dense Hartley transform.  Everything else
about the run (set-up, window, check) is the benchmark's own.  One JSON
line per run on stdout: ``side``, ``seed``, ``correct`` and the checks.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
SPECS = ("abc,ai->ibc", "abc,bj->ajc", "abc,ck->abk")


def control_transform(ref, block):
    """The reference's blocked transform in the program's place, in int8;
    the factors are placed beside the plane at their first use."""
    import jax
    import numpy as np

    import control

    mode_product = control.lower_mode(SimpleNamespace(SPECS=SPECS))
    mats = {}

    def factor(n, b, inverse):
        if (n, b, inverse) not in mats:
            c = ref.dct2(b)
            mats[n, b, inverse] = jax.device_put(
                np.kron(np.eye(n // b, dtype=np.float32),
                        c.T if inverse else c))
        return mats[n, b, inverse]

    def transform(u, inverse):
        for mode, (n, b) in enumerate(zip(u.shape, block)):
            u = mode_product(u, factor(n, b, inverse), mode, None)
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
        transform = (control_transform(ref, tuple(spec.config["block"]))
                     if side == "control" else None)
        rc, res = run.run_cell(spec, seed, args.seconds, trace=False,
                               transform=transform)
        if rc:
            return rc
        print(json.dumps({"side": side, "seed": seed,
                          "correct": res["correct"],
                          "iterations": res["attempted"],
                          "checks": res["checks"],
                          "memory_peak_bytes":
                              res["device"]["memory_peak_bytes"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Compile a mesh configuration's loop for a described TPU, with no chip.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse_mesh.py \
        [--config npb_ft_d] [--traffic ft_iterate]

Lowers and compiles, for a described ``v5e:2x2`` topology, every program a
run of the ``spectral_loop`` generator drives on the mesh: the initial
field, ``ft_evolve``, the engine's ``shard_map`` program for the inverse
transform (the planner's own plan at the configuration's grid, axes and
tiles), ``ft_checksum``, and the reference's three mode products.  Prints
``memory_analysis()`` per program, in bytes per device, and the loop's
largest live set: the transform's arguments (the running field and the
matrices), output and temporaries, the kept slab of an output, and the
spectrum kept for the restarts.  Run it before a four-chip call: what
does not compile or fit here fails there too.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def memory(compiled) -> dict:
    m = compiled.memory_analysis()
    return {k: int(getattr(m, k + "_in_bytes")) for k in
            ("argument_size", "output_size", "alias_size", "temp_size",
             "generated_code_size")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", default="npb_ft_d")
    ap.add_argument("--traffic", default="ft_iterate")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, HERE)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.transforms import inverse_coefficient_matrix
    from repro.engine.executor import _sharded_callable
    from repro.engine.plan import build_plan
    from repro.kernels import ops
    from run import load_module

    with open(os.path.join(HERE, "configs", args.config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(HERE, "traffic", args.traffic + ".json")) as f:
        traffic = json.load(f)
    gen = load_module(os.path.join(HERE, "generators",
                                   traffic["generator"] + ".py"))
    ref = load_module(os.path.join(HERE, "references",
                                   cfg["reference"] + ".py"))
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mcfg = cfg["mesh"]
    mesh = jax.sharding.Mesh(
        np.asarray(topo.devices).reshape(tuple(mcfg["shape"])),
        tuple(mcfg["axis_names"]))
    axes = tuple(mcfg["axes"])
    field = NamedSharding(mesh, P(*axes))
    rep = NamedSharding(mesh, P())
    dims = tuple(cfg["grid"])
    f32 = jnp.float32

    def sds(shape, sharding):
        return jax.ShapeDtypeStruct(shape, f32, sharding=sharding)

    x = sds(dims, field)
    mats = [sds((n, n), rep) for n in dims]
    vecs = [sds((n,), rep) for n in dims]
    report = {}

    # The engine's program, as gemt3_planned(mesh=) builds it on a TPU.
    ops.on_tpu = lambda: True  # planning and dispatch ask; this is for a TPU
    cs = [inverse_coefficient_matrix(cfg["transform"], n) for n in dims]
    plan = build_plan(dims, f32, *cs, mesh=mesh, axes=axes)
    fn, _ = _sharded_callable(plan, mesh, None, dict(enumerate(cs, 1)),
                              batched=False)
    engine = fn.lower(x, *mats).compile()
    hlo = engine.as_text()
    report["engine"] = memory(engine)
    report["engine"]["stages"] = [
        {"mode": s.mode, "backend": s.backend, "axis": s.axis,
         "tiles": [s.bm, s.bn, s.bk]} for s in plan.stages]
    report["engine"]["collectives"] = {
        k: hlo.count(k + "(") + hlo.count(k + "-start(")
        for k in ("reduce-scatter", "all-reduce", "all-gather")}
    report["engine"]["kernels"] = hlo.count("tpu_custom_call")

    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype)
    init = jax.jit(lambda k: jax.random.normal(k, dims, f32),
                   out_shardings=field)
    report["initial_field"] = memory(init.lower(key).compile())
    evolve = jax.jit(gen.ft_evolve, out_shardings=field)
    report["ft_evolve"] = memory(evolve.lower(x, *vecs).compile())
    idx = gen.checksum_indices(dims, traffic["checksum_points"],
                               traffic["checksum_strides"])
    checksum = gen.make_checksum(mesh, axes, dims, idx)
    report["ft_checksum"] = memory(checksum.lower(x).compile())
    for mode in range(3):
        c = ref._mode.lower(x, mats[mode], mode, field).compile()
        report[f"reference_mode{mode + 1}"] = memory(c)

    e = report["engine"]
    field_bytes = int(np.prod(dims)) * 4 // len(topo.devices)
    kept = field_bytes // int(cfg.get("kept_field_parts", 1))
    report["per_device"] = {
        "field_bytes": field_bytes,
        "loop_live_bytes": (e["argument_size"] + e["output_size"]
                            + e["temp_size"] + kept + field_bytes),
        "hbm_bytes": 16 * 1024 ** 3,
    }
    print(json.dumps(report, indent=1, default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main())

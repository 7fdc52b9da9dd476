import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ^ the two lines above MUST run before any jax import: jax locks the device
# count at first init.  512 placeholder CPU devices back both production
# meshes (multi-pod 2×16×16 = 512; single-pod 16×16 = 256 uses the first
# 256 devices).  The dry-run proves every (arch × shape × mesh) cell
# lowers, SPMD-partitions, and compiles; memory/cost/collective artifacts
# feed EXPERIMENTS.md §Dry-run and §Roofline.

import argparse
import dataclasses
import functools
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, LONG_CONTEXT_OK, SHAPES, input_specs, load_config
from repro.launch.mesh import (TP, act_rules, batch_specs, dp_axes,
                               param_rules, shardings_from_axes, specs_from_axes)
from repro.launch.flops import model_flops
from repro.launch.roofline import analyze_hlo, roofline_terms
from repro.models import ShardCtx, cache_axes_tree, init_cache, init_model, model_axes
from repro.optim import OptConfig
from repro.serve import build_decode_step, build_prefill_step
from repro.train import build_train_step, init_train_state, train_state_axes


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    if len(jax.devices()) == n:
        return jax.make_mesh(shape, axes,
                             axis_types=(AxisType.Auto,) * len(axes))
    # 512 placeholder devices back both meshes: single-pod = first 256.
    devs = np.asarray(jax.devices()[:n]).reshape(shape)
    return Mesh(devs, axes)


def _opt_cfg(cfg) -> OptConfig:
    # The 671B cell trades optimizer-state precision for HBM (DESIGN.md §4).
    state_dtype = jnp.bfloat16 if cfg.n_experts >= 256 else jnp.float32
    return OptConfig(state_dtype=state_dtype)


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               act_overrides: dict | None = None,
               param_overrides: dict | None = None,
               cfg_overrides: dict | None = None,
               microbatch: int = 1,
               fused_loss: bool = False,
               loss_chunk: int = 8192):
    """Lower + compile one (arch × shape × mesh) cell.  Returns artifacts."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_devices = mesh.size
    shape = SHAPES[shape_name]
    cfg = load_config(arch).finalize_for_mesh(TP)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    dp = int(np.prod([mesh.shape[a] for a in dp_axes(multi_pod)]))
    batch_shardable = shape.global_batch % dp == 0
    serve = shape.kind != "train"
    prules = param_rules(cfg, multi_pod, serve=serve, overrides=param_overrides)
    arules = act_rules(cfg, multi_pod, batch_shardable, overrides=act_overrides)
    ctx = ShardCtx(mesh=mesh, rules=arules)
    key = jax.random.PRNGKey(0)

    ins = input_specs(cfg, shape)
    bspecs = batch_specs(cfg, shape.kind, arules)
    batch_sh = {k: NamedSharding(mesh, bspecs.get(k) or P())
                for k in ins}

    t0 = time.time()
    if shape.kind == "train":
        ocfg = _opt_cfg(cfg)
        state_sds = jax.eval_shape(
            lambda k: init_train_state(k, cfg, ocfg), key)
        state_sh = shardings_from_axes(mesh, train_state_axes(cfg), prules)
        step = build_train_step(cfg, ctx, ocfg, microbatch=microbatch,
                                fused_loss=fused_loss, loss_chunk=loss_chunk)
        jf = jax.jit(step, in_shardings=(state_sh, batch_sh),
                     out_shardings=(state_sh, None), donate_argnums=(0,))
        lowered = jf.lower(state_sds, ins)
    else:
        params_sds = jax.eval_shape(lambda k: init_model(k, cfg), key)
        params_sh = shardings_from_axes(mesh, model_axes(cfg), prules)
        if shape.kind == "prefill":
            step = build_prefill_step(cfg, ctx)
            jf = jax.jit(step, in_shardings=(params_sh, batch_sh))
            lowered = jf.lower(params_sds, ins)
        else:  # decode: one token against a seq_len cache
            cache_sds = jax.eval_shape(
                lambda: init_cache(cfg, shape.global_batch, shape.seq_len))
            cache_sh = shardings_from_axes(mesh, cache_axes_tree(cfg), arules)
            step = build_decode_step(cfg, ctx)
            jf = jax.jit(step,
                         in_shardings=(params_sh, batch_sh, cache_sh, None),
                         out_shardings=(None, cache_sh),
                         donate_argnums=(2,))
            lowered = jf.lower(params_sds, ins, cache_sds,
                               jnp.int32(shape.seq_len - 1))
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    counts = analyze_hlo(hlo, n_devices)
    mf = model_flops(cfg, shape)
    terms = roofline_terms(counts, n_devices, mf["model_flops"])
    artifact = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": n_devices,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "per_device_total": (mem.argument_size_in_bytes
                                 + mem.output_size_in_bytes
                                 + mem.temp_size_in_bytes
                                 - mem.alias_size_in_bytes),
        },
        "cost_analysis": {k: cost.get(k) for k in
                          ("flops", "bytes accessed") if k in cost},
        "model_flops": mf,
        "roofline": terms,
    }
    return artifact, hlo


def run_cells(cells, out_dir: str, save_hlo: bool = True, **kw):
    os.makedirs(out_dir, exist_ok=True)
    ok, failed = [], []
    for arch, shape_name, multi_pod in cells:
        tag = f"{arch}__{shape_name}__{'multi' if multi_pod else 'single'}"
        path = os.path.join(out_dir, tag + ".json")
        if os.path.exists(path):
            print(f"[skip-cached] {tag}", flush=True)
            ok.append(tag)
            continue
        print(f"[lower+compile] {tag}", flush=True)
        try:
            artifact, hlo = lower_cell(arch, shape_name, multi_pod, **kw)
            with open(path, "w") as f:
                json.dump(artifact, f, indent=1)
            if save_hlo:
                import gzip
                with gzip.open(os.path.join(out_dir, tag + ".hlo.txt.gz"),
                               "wt") as f:
                    f.write(hlo)
            r = artifact["roofline"]
            print(f"  OK compile={artifact['compile_s']}s "
                  f"bound={r['bound']} "
                  f"compute={r['compute_s']:.2e}s mem={r['memory_s']:.2e}s "
                  f"coll={r['collective_s']:.2e}s "
                  f"bytes/dev={artifact['memory']['per_device_total']/2**30:.2f}GiB",
                  flush=True)
            ok.append(tag)
        except Exception as e:
            failed.append((tag, repr(e)))
            with open(os.path.join(out_dir, tag + ".FAILED.txt"), "w") as f:
                f.write(traceback.format_exc())
            print(f"  FAILED: {e!r}", flush=True)
    return ok, failed


def default_cells(mesh_filter: str | None = None):
    cells = []
    for arch in ARCH_IDS:
        cfg = load_config(arch)
        for shape_name in SHAPES:
            if (shape_name == "long_500k"
                    and cfg.name not in LONG_CONTEXT_OK):
                continue  # pure full-attention arch: skip documented in DESIGN.md
            for multi_pod in (False, True):
                if mesh_filter == "single" and multi_pod:
                    continue
                if mesh_filter == "multi" and not multi_pod:
                    continue
                cells.append((arch, shape_name, multi_pod))
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="arch id or 'all'")
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--no-save-hlo", dest="save_hlo", action="store_false")
    ap.add_argument("--microbatch", type=int, default=1)
    args = ap.parse_args()

    if args.arch and args.arch != "all":
        meshes = {"single": [False], "multi": [True],
                  "both": [False, True]}[args.mesh]
        shapes = [args.shape] if args.shape else list(SHAPES)
        cfg = load_config(args.arch)
        cells = [(args.arch, s, m) for s in shapes for m in meshes
                 if not (s == "long_500k" and cfg.name not in LONG_CONTEXT_OK)]
    else:
        cells = default_cells(None if args.mesh == "both" else args.mesh)

    ok, failed = run_cells(cells, args.out, save_hlo=args.save_hlo,
                           microbatch=args.microbatch)
    print(f"\n== dry-run summary: {len(ok)} ok, {len(failed)} failed ==")
    for tag, err in failed:
        print(f"  FAIL {tag}: {err}")
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()

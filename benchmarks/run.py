# One function per paper claim/table. Prints ``name,us_per_call,derived`` CSV;
# ``--json`` additionally writes the rows as a JSON artifact whose path comes
# from ``--out PATH`` (or ``--json PATH`` for backward compatibility), e.g.
#
#   python -m benchmarks.run --json BENCH_engine.json
#   python -m benchmarks.run --filter fused_gemt --json --out BENCH_fused_gemt.json
#   python -m benchmarks.run --filter fused3 --json --out BENCH_fused3_gemt.json
#
# ``--filter SUBSTR`` runs only the bench functions whose name contains the
# substring (cheap CI artifacts without paying for the whole sweep).
#
# ``--check-regression ARTIFACT.json`` re-runs exactly the bench functions
# that produced the artifact's rows and compares fresh results against the
# committed numbers: deterministic model metrics (byte counts, ratios,
# backends, error bounds) must reproduce, wall-clock numbers get a
# ``--tol-time`` tolerance band.  Exit code 1 on any regression, so CI fails
# loudly; the tier-2 ``bench_smoke`` pytest wires this against the committed
# artifacts.
from __future__ import annotations

import argparse
import json
import os
import sys


def _benches():
    from . import (bench_core, bench_distributed, bench_engine, bench_kernels,
                   bench_numerics, bench_roofline, bench_serve_throughput)

    return [
        bench_core.bench_linear_timesteps,
        bench_core.bench_esop_savings,
        bench_core.bench_esop_accuracy,
        bench_core.bench_staged_vs_elementwise,
        bench_core.bench_generality,
        bench_kernels.bench_sr_gemm_structure,
        bench_kernels.bench_esop_plan,
        bench_kernels.bench_xla_gemm_baseline,
        bench_distributed.bench_strong_scaling_model,
        bench_distributed.bench_shardmap_vs_auto,
        bench_distributed.bench_distributed_engine,
        bench_roofline.bench_roofline_summary,
        bench_engine.bench_planner_order,
        bench_engine.bench_esop_dispatch,
        bench_engine.bench_planned_vs_einsum,
        bench_engine.bench_autotune_cache,
        bench_engine.bench_fused_gemt,
        bench_engine.bench_fused3_gemt,
        bench_engine.bench_grad_engine,
        bench_engine.bench_serve_resilience,
        bench_serve_throughput.bench_serve_throughput,
        bench_numerics.bench_compensated_accum,
    ]


# Row-name prefix (up to the first "_") -> bench function name.  Artifacts
# only record row names, so --check-regression uses this to re-run just the
# functions that produced them.
_ROW_PREFIXES = {
    "B1": "bench_linear_timesteps", "B3": "bench_esop_savings",
    "B4": "bench_esop_accuracy", "B5": "bench_staged_vs_elementwise",
    "B6": "bench_generality",
    "K1": "bench_sr_gemm_structure", "K2": "bench_esop_plan",
    "K3": "bench_xla_gemm_baseline",
    "D1": "bench_strong_scaling_model", "D2": "bench_shardmap_vs_auto",
    "D3": "bench_distributed_engine",
    "R1": "bench_roofline_summary",
    "E1": "bench_planner_order", "E2": "bench_esop_dispatch",
    "E3": "bench_planned_vs_einsum", "E4": "bench_autotune_cache",
    "F1": "bench_fused_gemt", "F2": "bench_fused3_gemt",
    "G1": "bench_grad_engine",
    "S1": "bench_serve_resilience", "S2": "bench_serve_throughput",
    "N1": "bench_compensated_accum",
}

# Derived keys whose values are wall-clock measurements (or booleans derived
# from them): compared under the --tol-time band, never exactly.  Queueing-
# sensitive serving keys (requests/sec, SLO attainment) live here too — a
# loaded CI host shifts them without any code regression.
_NOISY_MARKERS = ("_us", "us_", "speedup", "wallclock", "no_worse", "warm",
                  "rps", "slo")

# Counter-snapshot keys that legitimately vary between the recording run and
# a fresh check (process-warm plan/memo/autotune caches shift hit/miss/build
# splits; degradations only fire at build time) — skipped entirely.  Keys
# carrying timing (histogram stats, *_us) get the --tol-time band; everything
# else (MAC/byte totals, stage/launch/request counts) must reproduce exactly.
_CACHE_COUNTER_MARKERS = ("hit", "miss", "evict", "load", "write", "build",
                          "degradation", "probe")
_TIMING_COUNTER_MARKERS = ("_us", "latency", ".mean", ".p50", ".p90", ".p99",
                           ".max", ".min", ".sum")


def _parse_derived(derived: str) -> dict[str, str]:
    out = {}
    for part in derived.split(";"):
        if "=" in part:
            k, v = part.split("=", 1)
            out[k] = v
    return out


def _as_float(v: str) -> float | None:
    try:
        return float(v[:-1] if v.endswith("x") else v)
    except ValueError:
        return None


def _is_noisy(key: str) -> bool:
    return any(m in key for m in _NOISY_MARKERS)


def compare_counters(recorded: dict, fresh: dict,
                     tol_time: float | None = 1.0) -> list[str]:
    """Compare a recorded registry counter snapshot against a fresh one.

    Cache-behaviour keys are skipped (warm-process hit/miss splits are not
    a contract), timing keys get the ``tol_time`` band, everything else —
    modeled MAC/byte totals, stage/launch/request counts — must reproduce
    exactly.
    """
    failures = []
    for key, rec_v in recorded.items():
        if any(m in key for m in _CACHE_COUNTER_MARKERS):
            continue
        if key not in fresh:
            failures.append(f"counters: {key} disappeared from fresh run")
            continue
        new_v = fresh[key]
        if any(m in key for m in _TIMING_COUNTER_MARKERS):
            if (tol_time is not None and float(rec_v) > 0
                    and float(new_v) > float(rec_v) * (1.0 + tol_time)):
                failures.append(
                    f"counters: {key} regressed {rec_v} -> {new_v} "
                    f"(band {tol_time:.0%})")
        elif float(new_v) != float(rec_v):
            failures.append(
                f"counters: {key} changed {rec_v} -> {new_v} (re-record "
                "the artifact if the model legitimately moved)")
    return failures


def _split_artifact(recorded):
    """A BENCH artifact is either the original bare row list or the
    counter-carrying ``{"rows": [...], "counters": {...}}`` form."""
    if isinstance(recorded, dict):
        return recorded.get("rows"), recorded.get("counters") or {}
    return recorded, {}


def check_regression(path: str, tol_time: float | None = 1.0,
                     rows: list[tuple[str, float, str]] | None = None,
                     counters: dict | None = None,
                     ) -> list[str]:
    """Compare a committed BENCH artifact against a fresh run.

    Returns a list of human-readable failure strings (empty = no
    regression).  ``tol_time`` is the relative band on wall-clock numbers
    (1.0 = fresh may be up to 2x the recorded value; speedups may shrink
    to recorded/(1+tol)); ``None`` skips wall-clock comparison entirely
    (deterministic model metrics only — useful where the committed
    artifact was recorded on different hardware).  ``rows`` injects
    pre-collected fresh rows (tests reuse one sweep for several checks);
    ``counters`` likewise injects a fresh registry snapshot for artifacts
    that embed one.
    """
    try:
        with open(path) as f:
            recorded = json.load(f)
    except (OSError, ValueError) as e:
        return [f"{path}: cannot read artifact ({e})"]
    recorded, rec_counters = _split_artifact(recorded)
    if not isinstance(recorded, list) or not recorded:
        return [f"{path}: not a BENCH artifact (expected a non-empty list)"]

    if rows is None:
        prefixes = {r["name"].split("_", 1)[0] for r in recorded}
        unknown = sorted(p for p in prefixes if p not in _ROW_PREFIXES)
        if unknown:
            return [f"{path}: unknown row prefixes {unknown} — update "
                    "_ROW_PREFIXES in benchmarks/run.py"]
        wanted = {_ROW_PREFIXES[p] for p in prefixes}
        from repro import obs

        # The fresh sweep runs inside its own registry so the snapshot
        # compares only what *these* benches recorded, not whatever else
        # ran in this process.
        with obs.session(name="bench-check", enable_tracing=False) as s:
            rows = []
            for fn in _benches():
                if fn.__name__ in wanted:
                    fn(rows)
            counters = s.registry.snapshot()
    fresh = {name: (us, _parse_derived(derived)) for name, us, derived in rows}

    failures = []
    if rec_counters:
        failures.extend(compare_counters(rec_counters, counters or {},
                                         tol_time=tol_time))
    for rec in recorded:
        name = rec["name"]
        if name not in fresh:
            failures.append(f"{name}: row missing from fresh run")
            continue
        fresh_us, fresh_kv = fresh[name]
        rec_us = float(rec.get("us_per_call", 0.0))
        if (tol_time is not None and rec_us > 0
                and fresh_us > rec_us * (1.0 + tol_time)):
            failures.append(
                f"{name}: us_per_call {fresh_us:.1f} exceeds recorded "
                f"{rec_us:.1f} by more than {tol_time:.0%}")
        for key, rec_v in _parse_derived(rec.get("derived", "")).items():
            if key not in fresh_kv:
                failures.append(f"{name}: derived key {key!r} disappeared")
                continue
            new_v = fresh_kv[key]
            rec_f, new_f = _as_float(rec_v), _as_float(new_v)
            if _is_noisy(key):
                if tol_time is None or rec_f is None or new_f is None:
                    continue  # timing-derived booleans flap with the host
                # direction: "us" keys = lower is better, speedup ratios =
                # higher is better; both get the same relative band
                if key.endswith("us") or key.endswith("_us"):
                    bad = rec_f > 0 and new_f > rec_f * (1.0 + tol_time)
                else:
                    bad = new_f < rec_f / (1.0 + tol_time)
                if bad:
                    failures.append(
                        f"{name}: {key} regressed {rec_v} -> {new_v} "
                        f"(band {tol_time:.0%})")
            elif key.startswith("max_abs_err"):
                # numerical-error keys (max_abs_err, max_abs_err_plain/
                # _comp): rounding detail may shift with XLA, but a 4x
                # growth (floored at 1e-5) is a real accuracy regression
                if (rec_f is not None and new_f is not None
                        and new_f > max(rec_f * 4, 1e-5)):
                    failures.append(
                        f"{name}: {key} grew {rec_v} -> {new_v}")
            elif rec_f is not None and new_f is not None:
                # deterministic model metric: must reproduce (tiny float
                # formatting slack only)
                if abs(new_f - rec_f) > max(1e-6, 1e-6 * abs(rec_f)):
                    failures.append(
                        f"{name}: model metric {key} changed "
                        f"{rec_v} -> {new_v} (re-record the artifact if "
                        "the model legitimately moved)")
            elif rec_v != new_v:
                failures.append(
                    f"{name}: {key} changed {rec_v!r} -> {new_v!r}")
    return failures


def collect_rows(name_filter: str | None = None) -> list[tuple[str, float, str]]:
    rows: list[tuple[str, float, str]] = []
    for fn in _benches():
        if name_filter and name_filter not in fn.__name__:
            continue
        fn(rows)
    return rows


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", metavar="OUT", nargs="?", const=True,
                    default=None,
                    help="also write rows as a JSON artifact (path from "
                         "--out, or given directly for compatibility)")
    ap.add_argument("--out", metavar="PATH", default=None,
                    help="JSON artifact path (implies --json; "
                         "e.g. BENCH_fused_gemt.json)")
    ap.add_argument("--filter", metavar="SUBSTR", default=None,
                    help="only run bench functions whose name contains this")
    ap.add_argument("--trace", metavar="TRACE_OUT", default=None,
                    help="record engine spans during the sweep and write a "
                         "Chrome-trace JSON (open in Perfetto / "
                         "chrome://tracing, or inspect with "
                         "`python -m repro.obs TRACE_OUT`)")
    ap.add_argument("--check-regression", metavar="ARTIFACT", default=None,
                    help="re-run the benches behind a committed BENCH "
                         "artifact and fail (exit 1) on regressions")
    ap.add_argument("--tol-time", type=float, default=1.0,
                    help="relative tolerance band on wall-clock numbers for "
                         "--check-regression (default 1.0 = 2x); negative "
                         "disables wall-clock comparison")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    if args.check_regression:
        tol = None if args.tol_time < 0 else args.tol_time
        failures = check_regression(args.check_regression, tol_time=tol)
        if failures:
            for f in failures:
                print(f"REGRESSION {f}")
            sys.exit(1)
        print(f"# {args.check_regression}: no regressions")
        return

    # Resolve the artifact path before the sweep runs — a bad flag combo
    # must not waste minutes of benchmarking before erroring out.
    path = None
    if args.json or args.out:  # --out alone implies the JSON artifact
        if isinstance(args.json, str) and args.out:
            ap.error("give the artifact path via --json PATH or --out PATH, "
                     "not both")
        path = args.out or (args.json if isinstance(args.json, str) else None)
        if path is None:
            ap.error("--json without a path requires --out PATH")

    from repro import obs

    # The sweep runs inside its own tracer/registry: the artifact's counter
    # snapshot reflects this sweep only, and --trace captures its spans.
    with obs.session(name="bench", enable_tracing=args.trace is not None) as s:
        rows = collect_rows(args.filter)
        counters = s.registry.snapshot()
        spans = s.tracer.spans() if args.trace else []
    if args.filter and not rows:
        ap.error(f"--filter {args.filter!r} matched no bench function "
                 "(artifact would be empty)")
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")

    if args.trace:
        obs.write_chrome_trace(args.trace, spans, s.registry)
        print(f"# wrote {len(spans)} spans to {args.trace}")

    if path:
        with open(path, "w") as f:
            json.dump({"rows": [{"name": n, "us_per_call": round(us, 1),
                                 "derived": d} for n, us, d in rows],
                       "counters": counters}, f, indent=1)
        print(f"# wrote {len(rows)} rows to {path}")


if __name__ == "__main__":
    main()

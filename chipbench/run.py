#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything the run needs is found by name from ``BENCHMARK.json`` at the
root of the checkout: the cell's configuration (``configs/<config>.json``,
which names its plain reference in ``references/``), its traffic
(``traffic/<traffic>.json``, which names its generator in
``generators/``) and one reader per metric (``metrics/<metric>.py``).

The run refuses to start without a TPU, or with fewer chips than the cell
asks for (exit 3, no result).  It builds its data on the device from the
seed, warms up the cell's own shapes, measures for ``--seconds``, and
checks what the timed path produced against a plain reference.  With
``--trace 1`` it then traces one more cycle of the loop and reports the
per-layer metrics instead of the end-to-end ones.  The last stdout line
is one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, ``breakdown`` (traced runs) and, last, ``checks``: every
number compared, beside its limit.  The same numbers are the last lines
of stderr.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import ctypes  # noqa: E402

# glibc's allocator moves its mmap and trim thresholds with the history of
# the process, so whether the transform's per-call numpy temporaries are
# faulted in anew differs from one process to the next (about 1 run in 12
# read 11% faster).  Pin both above every host allocation of a call, as a
# long-running process settles, before anything allocates.
_M_TRIM_THRESHOLD, _M_MMAP_THRESHOLD = -1, -3
try:
    _libc = ctypes.CDLL(None)
    for _opt in (_M_TRIM_THRESHOLD, _M_MMAP_THRESHOLD):
        _libc.mallopt(_opt, 1 << 30)
except (OSError, AttributeError):  # not glibc
    pass

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# Counters of the system under test that must not move in the window.
FROZEN_COUNTERS = ("plan.builds",)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: str):
    name = "chipbench_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, bench_path: str | None = None) -> SimpleNamespace:
    """The cell ``name`` with its configuration, traffic and metrics."""
    with open(bench_path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return SimpleNamespace(cell=cell, config=config, traffic=traffic,
                           end_to_end=mine(bench["end_to_end"]),
                           per_layer=mine(bench["per_layer"]))


class WindowGuard:
    """Counts compilations (JAX's monitoring events) and the frozen
    program counters between ``arm`` and ``disarm``."""

    def __init__(self):
        import jax.monitoring as mon

        self.compiles, self.armed = 0, False
        self._mon = mon
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if self.armed and event.startswith("/jax/core/compile/"):
            self.compiles += 1

    def _on_event(self, event, **kw):
        if self.armed and event == "/jax/compilation_cache/cache_hits":
            self.compiles += 1

    @staticmethod
    def _counters() -> dict:
        from repro import obs

        reg = obs.get_registry()
        return {k: reg.value(k) for k in FROZEN_COUNTERS}

    def arm(self) -> None:
        self.before = self._counters()
        self.armed = True

    def disarm(self) -> dict:
        self.armed = False
        after = self._counters()
        self._mon.unregister_event_duration_listener(self._on_duration)
        self._mon.unregister_event_listener(self._on_event)
        return {"window_compiles": self.compiles,
                "window_plan_builds": sum(after[k] - self.before[k]
                                          for k in FROZEN_COUNTERS)}


def traced_cycle(wl) -> tuple[dict, dict]:
    """Trace ``wl.trace_count`` more iterations; reduce the trace."""
    import jax

    tracefile = load_module(os.path.join(HERE, "tracefile.py"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory(prefix="chipbench-trace-") as tdir:
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            res = wl.run(count=wl.trace_count)
        finally:
            jax.profiler.stop_trace()
        ops, spans = tracefile.load_xplane(tdir, wl.span_names)
    its = [s for s in spans if s[0] == "iteration"]
    summary = {}
    if its and ops:
        summary = tracefile.reduce_trace(ops, spans, its[0][1], its[-1][2],
                                         wl.own_modules)
        summary["breakdown"] = tracefile.breakdown(summary)
    return res, summary


def run_cell(spec: SimpleNamespace, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, transform=None) -> tuple[int, dict]:
    """One run of a loaded cell.  Returns ``(exit code, result)``;
    ``require_tpu=False`` skips the look for a chip (tests only)."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    chips = int(spec.cell["chips"])
    if require_tpu:
        if jax.default_backend() != "tpu":
            log(f"chipbench: needs a TPU, JAX found {jax.default_backend()!r}")
            return 3, {}
        if jax.device_count() < chips:
            log(f"chipbench: {spec.cell['name']} needs {chips} chips, JAX "
                f"found {jax.device_count()}")
            return 3, {}
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache(ROOT)}")
    # Cache every program, however quick to compile, so that a second run
    # of a cell finds all of them.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()[:chips]
    dev = devices[0]
    work = load_module(os.path.join(HERE, "work.py"))
    peaks = work.load_peaks(dev.device_kind) if require_tpu else None
    gen = load_module(os.path.join(HERE, "generators",
                                   spec.traffic["generator"] + ".py"))
    ref = load_module(os.path.join(HERE, "references",
                                   spec.config["reference"] + ".py"))
    wl = gen.Workload(spec.config, spec.traffic, devices, seed, ref,
                      transform=transform)
    wl.setup()
    setup_s = time.perf_counter() - T_START
    log(f"setup_s={setup_s!r}")

    guard = WindowGuard()
    guard.arm()
    window = wl.run(seconds=seconds)
    log(f"window: {window}")
    traced, summary = (traced_cycle(wl) if trace else (None, {}))
    frozen = guard.disarm()

    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))

    checks, failed = wl.check(spec.config["limits"])
    checks.update({k: (v, 0) for k, v in frozen.items()})
    correct = all(v <= lim for v, lim in checks.values()) and failed == 0

    ctx = SimpleNamespace(setup_s=setup_s, window=window, traced=traced,
                          trace=summary, workload=wl, peaks=peaks, work=work)
    metrics = {}
    for m in (spec.per_layer if trace else spec.end_to_end):
        value = load_module(os.path.join(HERE, "metrics",
                                         m["name"] + ".py")).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if trace and summary:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
    result = {"correct": bool(correct),
              "attempted": window["iterations"] + (traced or {}).get(
                  "iterations", 0),
              "failed": failed, "metrics": metrics, "device": device}
    if summary:
        result["breakdown"] = summary["breakdown"]
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"check {k} = {v!r} limit {lim!r} "
            f"{'ok' if v <= lim else 'FAIL'}")
    return 0, result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_cell(args.workload)
    rc, result = run_cell(spec, args.seed, args.seconds, bool(args.trace))
    if rc == 0:
        print(json.dumps(result), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())

"""The trace reduction and the roofline's work, on synthetic traces."""
import importlib.util
import os
from types import SimpleNamespace

import pytest

import tracefile
import work

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def reader(name):
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_union_gaps_and_coverage():
    iv = [(0, 10), (5, 20), (30, 40), (35, 36), (50, 60)]
    assert tracefile.union(iv, 0, 100) == [(0, 20), (30, 40), (50, 60)]
    assert tracefile.covered(iv, 15, 55) == 5 + 10 + 5
    assert tracefile.gaps(iv, 0, 70) == [(20, 30), (40, 50), (60, 70)]
    assert tracefile.gaps([], 0, 5) == [(0, 5)]


def test_op_label_drops_hashes_and_shapes():
    name = ("%sr_gemm_pallas.1 = f32[262144,512]{1,0:T(8,128)} custom-call("
            "f32[262144,512]{1,0:T(8,128)} %x.1)")
    assert (tracefile.op_label(name, "jit_sr_gemm(123456)")
            == "jit_sr_gemm/%sr_gemm_pallas.1")


def test_assign_modules_by_containment():
    ops = [("a", 1, 2), ("b", 12, 13), ("c", 25, 26)]
    mods = [("jit_ft_evolve", 0, 10), ("jit_fused", 10, 20)]
    assert tracefile.assign_modules(ops, mods) == [
        ("a", "jit_ft_evolve", 1, 2), ("b", "jit_fused", 12, 13),
        ("c", "", 25, 26)]


def test_gap_attribution_to_innermost_span():
    spans = [("iteration", 0, 100), ("dxt3d_call", 10, 40),
             ("checksum_wait", 60, 100)]
    got = tracefile.attribute([(20, 30), (45, 55), (70, 80), (150, 160)],
                              spans)
    assert got == {"dxt3d_call": 10, "iteration": 10, "checksum_wait": 10,
                   "(no span)": 10}


def two_chip_trace():
    # Window [0, 1000] ns.  Chip 0: evolve 0-100, transform ops 100-500
    # (a reduce-scatter 300-400 among them), checksum 600-650.  Chip 1
    # the same, shifted by 50 ns.
    ops = {}
    for dev, d in (("/device:TPU:0", 0), ("/device:TPU:1", 50)):
        ops[dev] = [
            ("fusion.1", "jit_ft_evolve(1)", 0 + d, 100 + d),
            ("sr_gemm", "jit_fn(2)", 100 + d, 300 + d),
            ("reduce-scatter.3", "jit_fn(2)", 300 + d, 400 + d),
            ("sr_gemm", "jit_fn(2)", 400 + d, 500 + d),
            ("gather", "jit_ft_checksum(3)", 600 + d, 650 + d)]
    spans = [("iteration", 0, 1000), ("dxt3d_call", 500, 600)]
    return ops, spans


def test_reduce_trace_means_over_chips():
    ops, spans = two_chip_trace()
    s = tracefile.reduce_trace(ops, spans, 0, 1000,
                               ("ft_evolve", "ft_checksum"))
    assert s["chips"] == 2
    assert s["window_s"] == pytest.approx(1000e-9)
    # chip 0 busy 0-500 + 600-650; chip 1 busy 50-550 + 650-700 (clipped
    # to nothing beyond 1000): 550 ns each
    assert s["busy_s"] == pytest.approx(550e-9)
    assert s["work_s"] == pytest.approx(400e-9)
    assert s["collective_s"] == pytest.approx(100e-9)
    assert s["op_s"]["jit_fn/sr_gemm"] == pytest.approx(300e-9)
    # idle 450 ns per chip: chip 0 500-600 in dxt3d_call, 650-1000 in
    # iteration; chip 1 0-50 and 700-1000 in iteration, 550-650 (its
    # midpoint 600 ends dxt3d_call) in dxt3d_call
    assert sum(s["idle_by_span"].values()) == pytest.approx(450e-9)
    assert s["idle_by_span"]["dxt3d_call"] == pytest.approx(100e-9)
    assert s["idle_by_span"]["iteration"] == pytest.approx(350e-9)
    b = tracefile.breakdown(s, top=2)
    assert [k for k, _ in b["device_ops"]] == ["jit_fn/sr_gemm",
                                               "jit_ft_evolve/fusion.1"]
    assert b["idle_gaps"][0][0] == "iteration"


def test_metric_readers_on_a_reduced_trace():
    ops, spans = two_chip_trace()
    summary = tracefile.reduce_trace(ops, spans, 0, 1000,
                                     ("ft_evolve", "ft_checksum"))
    peaks = work.load_peaks("TPU v5 lite")
    wl = SimpleNamespace(dims=(512, 512, 512), chips=2)
    ctx = SimpleNamespace(trace=summary, traced={"iterations": 1,
                                                 "host_call_s": 0.02},
                          workload=wl, peaks=peaks, work=work)
    least, bound = work.least_time_s(wl.dims, peaks, 2)
    assert bound == "compute"
    assert reader("transform_roofline")(ctx) == pytest.approx(
        100 * least / 400e-9)
    assert reader("device_idle_pct.ft")(ctx) == pytest.approx(45.0)
    assert reader("host_call_ms.ft")(ctx) == pytest.approx(20.0)
    empty = SimpleNamespace(trace={}, traced=None, workload=wl,
                            peaks=peaks, work=work)
    for name in ("transform_roofline", "device_idle_pct.ft",
                 "host_call_ms.ft"):
        assert reader(name)(empty) is None


def test_work_of_npb_ft_class_c():
    dims = (512, 512, 512)
    assert work.dense_transform_flops(dims) == 2 * 512 ** 3 * 1536
    assert work.dense_transform_bytes(dims) == 4 * (2 * 512 ** 3
                                                    + 3 * 512 ** 2)
    peaks = work.load_peaks("TPU v5 lite")
    least, bound = work.least_time_s(dims, peaks)
    assert bound == "compute"
    assert least == pytest.approx(2.0929e-3, rel=1e-3)
    hbm = work.dense_transform_bytes(dims) / peaks["hbm_bytes_per_s"]
    assert hbm == pytest.approx(1.3148e-3, rel=1e-3)


def test_unknown_device_kind_has_no_peaks():
    with pytest.raises(KeyError):
        work.load_peaks("TPU v4")


def test_host_spans_from_a_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("iteration"):
            with jax.profiler.TraceAnnotation("dxt3d_call"):
                y = f(x)
            y.block_until_ready()
    jax.profiler.stop_trace()
    ops, spans = tracefile.load_xplane(str(tmp_path),
                                       ("iteration", "dxt3d_call"))
    assert ops == {}  # no TPU planes on the CPU
    names = [s[0] for s in spans]
    assert names.count("iteration") == 3 and names.count("dxt3d_call") == 3
    its = [s for s in spans if s[0] == "iteration"]
    assert all(a[2] <= b[1] for a, b in zip(its, its[1:]))

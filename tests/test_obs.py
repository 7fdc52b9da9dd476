"""Tier-2 obs_smoke: spans, metrics registry, exporters, engine telemetry.

Covers the observability contract end to end: span nesting/timing, the
disabled-mode zero-allocation fast path, Chrome-trace JSON schema
round-trip, serve latency percentiles, counter parity with the legacy
per-call ``info`` fields on the staged/pair/triple/sharded/backward
paths, fusion-degradation events, autotune-cache atomicity + corrupt
recovery, and the ``grad_stats`` shim.
"""
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.engine import (AutotuneCache, clear_plan_cache, gemt3_planned,
                          grad_stats, reset_grad_stats)
from repro.obs import trace as trace_mod

pytestmark = pytest.mark.obs_smoke

RNG = np.random.default_rng(7)


def _rand(*shape):
    return jnp.asarray(RNG.random(shape, dtype=np.float32))


def _problem(n=16):
    return (_rand(n, n, n), _rand(n, n), _rand(n, n), _rand(n, n))


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


def test_span_nesting_and_timing():
    with obs.session() as s:
        with obs.span("outer", {"k": 1}):
            time.sleep(0.002)
            with obs.span("inner"):
                time.sleep(0.001)
        spans = s.tracer.spans()
    assert [sp.name for sp in spans] == ["inner", "outer"]  # exit order
    inner, outer = spans
    assert outer.parent_id == 0 and inner.parent_id == outer.span_id
    assert inner.depth == 1 and outer.depth == 0
    assert outer.dur_ns >= inner.dur_ns > 0
    assert outer.t0_ns <= inner.t0_ns
    assert outer.attrs == {"k": 1}


def test_span_set_adds_attributes():
    with obs.session() as s:
        with obs.span("a") as sp:
            sp.set(extra=42)
        assert s.tracer.spans()[0].attrs["extra"] == 42


def test_ring_buffer_bounds_spans():
    with obs.session(capacity=4) as s:
        for i in range(10):
            with obs.span(f"s{i}"):
                pass
        names = [sp.name for sp in s.tracer.spans()]
    assert names == ["s6", "s7", "s8", "s9"]


def test_disabled_mode_is_zero_allocation():
    """span() must return the preallocated NULL_SPAN singleton (identity,
    not a fresh object) and never evaluate a callable attrs thunk."""
    with obs.session(enable_tracing=False) as s:
        assert trace_mod.span("x") is trace_mod.NULL_SPAN
        assert not trace_mod.enabled()
        called = []
        sp = trace_mod.span("x", lambda: called.append(1) or {})
        assert sp is trace_mod.NULL_SPAN and called == []
        with sp:
            pass
        assert s.tracer.spans() == []
    # enabled: the thunk *is* evaluated
    with obs.session() as s:
        with trace_mod.span("x", lambda: {"lazy": True}):
            pass
        assert s.tracer.spans()[0].attrs == {"lazy": True}


def test_untraced_engine_run_records_no_spans():
    x, c1, c2, c3 = _problem()
    with obs.session(enable_tracing=False) as s:
        gemt3_planned(x, c1, c2, c3)
        assert s.tracer.spans() == []
        # metrics are always on, even with tracing off
        assert s.registry.value("engine.executions") == 1


def test_root_id_shared_by_a_call_and_kept_past_a_dropped_parent():
    with obs.session(capacity=2) as s:
        with obs.span("outer") as outer:
            with obs.span("mid") as mid:
                with obs.span("leaf") as leaf:
                    pass
            with obs.span("sibling") as sib:
                pass
            in_flight = s.tracer.spans()
        with obs.span("next") as nxt:
            pass
    assert outer.root_id == outer.span_id and nxt.root_id == nxt.span_id
    assert mid.root_id == leaf.root_id == sib.root_id == outer.span_id
    assert leaf.parent_id == mid.span_id and sib.parent_id == outer.span_id
    # the buffer holds neither the open root nor the dropped leaf; the
    # spans it holds still name their call
    assert [sp.name for sp in in_flight] == ["mid", "sibling"]
    assert {sp.root_id for sp in in_flight} == {outer.span_id}
    doc = obs.chrome_trace([leaf, mid, sib, outer])
    assert {e["args"]["root_id"] for e in doc["traceEvents"]} == {
        outer.span_id}


def _host_events(log_dir, names):
    """``{name: [(start_ns, end_ns, stats), ...]}`` of the host plane's
    events named in ``names``, in start order."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in names:
                    out.setdefault(e.name, []).append(
                        (e.start_ns, e.end_ns, dict(e.stats)))
    return {k: sorted(v, key=lambda ev: ev[0]) for k, v in out.items()}


def test_profiler_session_records_engine_spans_on_its_clock(tmp_path):
    """With obs's tracer off, a recording profiler session switches the
    spans on: they land in obs's buffer and, under the same names and
    nesting, on the profile's host plane with their attributes."""
    from repro.core import dxt3d

    x = _rand(16, 16, 16)
    clear_plan_cache()  # also drops dxt3d's cached coefficient matrices
    with obs.session(enable_tracing=False) as s:
        assert not obs.enabled()
        jax.profiler.start_trace(str(tmp_path))
        try:
            assert obs.enabled()
            jax.block_until_ready(dxt3d(x, "dht", inverse=True, engine=True))
        finally:
            jax.profiler.stop_trace()
        assert not obs.enabled()
        assert obs.span("after") is obs.NULL_SPAN
        spans = sorted(s.tracer.spans(), key=lambda sp: sp.t0_ns)
    names = [sp.name for sp in spans]
    for want in ("dxt3d:dht", "dxt3d.coefficients", "plan.fingerprint",
                 "execute"):
        assert want in names
    # the three modes share one cached matrix: one fingerprint miss
    assert names.count("plan.fingerprint") == 1
    (root,) = [sp for sp in spans if sp.parent_id == 0]
    assert root.name == "dxt3d:dht"
    assert {sp.root_id for sp in spans} == {root.span_id}

    events = _host_events(str(tmp_path), set(names))
    assert {k: len(v) for k, v in events.items()} == {
        n: names.count(n) for n in set(names)}
    seen: dict = {}
    placed = {}
    for sp in spans:  # the k-th span of a name is its k-th event
        k = seen[sp.name] = seen.get(sp.name, -1) + 1
        placed[sp.span_id] = events[sp.name][k]
    for sp in spans:
        start, end, stats = placed[sp.span_id]
        assert end - start >= sp.dur_ns * 0.999  # encloses obs's interval
        if sp.parent_id:
            p_start, p_end, _ = placed[sp.parent_id]
            assert p_start <= start and end <= p_end, sp.name
        for key, val in sp.attrs.items():
            if isinstance(val, (bool, int, float, str)):
                assert stats[key] == val, (sp.name, key)
            elif isinstance(val, tuple):
                assert stats[key] == str(val), (sp.name, key)
    coeff = next(sp for sp in spans if sp.name == "dxt3d.coefficients")
    assert coeff.attrs == {"kind": "dht", "inverse": True,
                           "sizes": (16, 16, 16)}
    assert placed[coeff.span_id][2]["sizes"] == "(16, 16, 16)"


@pytest.mark.parametrize("tracer", [False, True])
@pytest.mark.parametrize("hook", [False, True])
@pytest.mark.parametrize("profiling", [False, True])
def test_enabled_and_span_follow_one_rule(monkeypatch, tracer, hook,
                                          profiling):
    """``enabled()`` is tracer OR fault hook OR profiler recording; a span
    records when the tracer is enabled or the profiler records; the hook
    fires whenever one is installed."""
    monkeypatch.setattr(trace_mod, "_profiling", lambda: profiling)
    fired = []
    prev = obs.set_fault_hook(fired.append if hook else None)
    try:
        with obs.session(enable_tracing=tracer) as s:
            assert obs.enabled() == (tracer or hook or profiling)
            sp = obs.span("x", {"a": 1})
            with sp:
                pass
            recorded = s.tracer.spans()
    finally:
        obs.set_fault_hook(prev)
    assert fired == (["x"] if hook else [])
    if tracer or profiling:
        assert [r.name for r in recorded] == ["x"]
    else:
        assert sp is obs.NULL_SPAN and recorded == []


def test_fingerprint_span_only_on_memo_miss():
    """Fingerprints are memoized on array identity: a second call with the
    same matrices records no ``plan.fingerprint``; ``dxt3d`` hands the
    engine its cached matrices, so after its first call it misses on none."""
    from repro.core import clear_coefficient_cache, dxt3d

    x, c1, c2, c3 = _problem()
    clear_coefficient_cache()
    with obs.session() as s:
        gemt3_planned(x, c1, c2, c3)
        first = [sp for sp in s.tracer.spans()
                 if sp.name == "plan.fingerprint"]
        s.tracer.clear()
        gemt3_planned(x, c1, c2, c3)
        assert [sp.name for sp in s.tracer.spans()
                if sp.name == "plan.fingerprint"] == []
        fps = []
        for _ in range(2):
            s.tracer.clear()
            dxt3d(x, "dht", inverse=True, engine=True)
            fps.append(sum(1 for sp in s.tracer.spans()
                           if sp.name == "plan.fingerprint"))
        assert fps == [1, 0]  # one matrix serves all three 16-wide modes
    assert len(first) == 3
    assert first[0].attrs == {"shape": (16, 16), "dtype": "float32"}


def test_dxt3d_second_call_hits_the_coefficient_cache():
    """A second eager ``dxt3d`` reuses its three coefficient matrices: the
    cache counts three hits, and no ``plan.fingerprint`` or ``esop.plan``
    span fires because the identity-keyed memos hit too."""
    from repro.core import clear_coefficient_cache, dxt3d

    x = _rand(16, 8, 24)  # three distinct keys
    clear_coefficient_cache()
    with obs.session() as s:
        reg = s.registry
        first = dxt3d(x, "dht", inverse=True, engine=True)
        assert reg.value("dxt3d.coeff_cache.misses") == 3
        assert reg.value("dxt3d.coeff_cache.hits") == 0
        first_names = [sp.name for sp in s.tracer.spans()]
        s.tracer.clear()
        second = dxt3d(x, "dht", inverse=True, engine=True)
        assert reg.value("dxt3d.coeff_cache.misses") == 3
        assert reg.value("dxt3d.coeff_cache.hits") == 3
        names = [sp.name for sp in s.tracer.spans()]
    assert first_names.count("plan.fingerprint") == 3
    assert "esop.plan" in first_names
    assert "dxt3d.coefficients" in names
    assert "plan.fingerprint" not in names
    assert "esop.plan" not in names
    np.testing.assert_array_equal(np.asarray(first), np.asarray(second))


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_registry_counters_gauges_histograms():
    r = obs.MetricsRegistry("t")
    r.inc("a.b", 3)
    r.inc("a.b")
    r.set_gauge("g", 2.5)
    for v in range(1, 101):
        r.observe("h", float(v))
    assert r.value("a.b") == 4
    assert r.value("nonexistent") == 0
    snap = r.snapshot()
    assert snap["a.b"] == 4 and snap["g"] == 2.5
    assert snap["h.count"] == 100
    # sum/mean are exact running totals (not window-bounded), so
    # throughput math over a snapshot needs no percentile estimate
    assert snap["h.sum"] == 5050.0
    assert snap["h.mean"] == 50.5
    h = r.histogram("h")
    assert h.percentile(50) <= h.percentile(90) <= h.percentile(99)
    assert h.summary()["max"] == 100.0
    assert h.summary()["sum"] == 5050.0
    empty = obs.Histogram()
    assert empty.summary()["sum"] == 0.0
    r.reset("a.")
    assert r.value("a.b") == 0 and r.gauge("g").value == 2.5


def test_session_isolation():
    obs.inc("iso.test", 5)
    before = obs.get_registry().value("iso.test")
    with obs.session() as s:
        obs.inc("iso.test", 100)
        assert s.registry.value("iso.test") == 100
    assert obs.get_registry().value("iso.test") == before


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------


def test_chrome_trace_schema_roundtrip(tmp_path):
    path = str(tmp_path / "trace.json")
    with obs.session() as s:
        with obs.span("root", {"shape": (4, 4, 4)}):
            with obs.span("child", {"macs": 64}):
                pass
        obs.inc("engine.macs", 64)
        doc = obs.write_chrome_trace(path, s.tracer.spans(), s.registry)
    loaded = json.loads(open(path).read())
    assert loaded == json.loads(json.dumps(doc))
    events = loaded["traceEvents"]
    assert len(events) == 2
    for ev in events:
        assert ev["ph"] == "X"
        assert set(ev) >= {"name", "cat", "ts", "dur", "pid", "tid", "args"}
        assert ev["dur"] >= 0 and ev["ts"] >= 0
    by_name = {e["name"]: e for e in events}
    assert (by_name["child"]["args"]["parent_id"]
            == by_name["root"]["args"]["span_id"])
    assert by_name["root"]["args"]["shape"] == [4, 4, 4]
    assert loaded["counters"]["engine.macs"] == 64
    assert loaded["displayTimeUnit"] == "ms"


def test_report_and_cli(tmp_path, capsys):
    from repro.obs.export import main as obs_main

    path = str(tmp_path / "trace.json")
    with obs.session() as s:
        with obs.span("stage:m1:sr_gemm"):
            pass
        obs.write_chrome_trace(path, s.tracer.spans(), s.registry)
        text = obs.format_report(s.tracer.spans(), s.registry)
    assert "stage:m1:sr_gemm" in text
    assert obs_main([path]) == 0
    assert "stage:m1:sr_gemm" in capsys.readouterr().out
    assert obs_main([path, "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["spans"]["stage:m1:sr_gemm"]["count"] == 1


def test_span_tree_lines_indent_children():
    with obs.session() as s:
        with obs.span("parent"):
            with obs.span("kid"):
                pass
        lines = obs.span_tree_lines(s.tracer.spans())
    assert lines[0].startswith("parent") and lines[1].startswith("  kid")


# ---------------------------------------------------------------------------
# engine counter parity with legacy info fields
# ---------------------------------------------------------------------------


def _assert_grid_steps_parity(reg, infos):
    """``engine.grid_steps`` is the stages' ``info["grid_steps"]`` summed
    over the executions, and every kernel launch counts some."""
    per_call = [sum(si["grid_steps"] for si in i["stages"]) for i in infos]
    assert per_call == [i["grid_steps"] for i in infos]
    assert reg.value("engine.grid_steps") == sum(per_call) > 0
    for i in infos:
        for si in i["stages"]:
            assert (si["grid_steps"] > 0) == (si["backend"] != "einsum"), si


def _run_and_compare(fuse, n=24):
    x, c1, c2, c3 = _problem(n)
    clear_plan_cache()
    with obs.session() as s:
        infos = []
        for _ in range(3):
            _, info = gemt3_planned(x, c1, c2, c3, with_info=True, fuse=fuse)
            infos.append(info)
        reg = s.registry
        assert reg.value("engine.executions") == len(infos)
        assert reg.value("engine.macs") == sum(i["macs"] for i in infos)
        _assert_grid_steps_parity(reg, infos)
        assert (reg.value("engine.hbm_bytes_moved")
                == sum(i["hbm_bytes_moved"] for i in infos))
        assert (reg.value("engine.hbm_bytes_staged")
                == sum(i["hbm_bytes_staged"] for i in infos))
        fused = sum(1 for i in infos
                    if i["fused"] and len(i["fused"]["modes"]) == 2)
        fused3 = sum(1 for i in infos
                     if i["fused"] and len(i["fused"]["modes"]) == 3)
        assert reg.value("engine.fused_launches") == fused
        assert reg.value("engine.fused3_launches") == fused3
        assert reg.value("plan.builds") == 1
        assert reg.value("plan.cache_hits") == len(infos) - 1
    return infos[0]


def test_counter_parity_staged():
    info = _run_and_compare(fuse=False)
    assert info["fused"] is None


def test_counter_parity_pair():
    info = _run_and_compare(fuse="pair")
    assert info["fused"] and len(info["fused"]["modes"]) == 2


def test_counter_parity_triple():
    info = _run_and_compare(fuse="triple")
    assert info["fused"] and len(info["fused"]["modes"]) == 3


def test_counter_parity_grid_steps_esop():
    """Staged block-ESOP stages count the grid they launch: row blocks ×
    column blocks × the schedule's live steps, not the dense depth."""
    from repro.core.transforms import blocked_coefficient_matrix

    from repro.engine.plan import build_plan

    x = _rand(16, 256, 256)
    c1 = _rand(16, 16)
    c = blocked_coefficient_matrix("dct", 256, 8)
    clear_plan_cache()
    with obs.session() as s:
        infos = [gemt3_planned(x, c1, c, c, fuse=False, with_info=True)[1]
                 for _ in range(2)]
        _assert_grid_steps_parity(s.registry, infos)
    plan = build_plan(x.shape, x.dtype, c1, c, c, fuse=False)
    tiles = {st.mode: st for st in plan.stages}
    esop = [si for si in infos[0]["stages"] if si["backend"] == "esop"]
    assert esop
    for si in esop:
        st = tiles[si["mode"]]
        assert si["t_steps"] == 1 < si["t_steps_dense"]
        assert st.bm > 128 and si["rows"] % st.bm == 0, st
        assert si["grid_steps"] == (si["rows"] // st.bm) * (256 // st.bn), si


def test_counter_parity_sharded():
    from jax.sharding import Mesh

    x, c1, c2, c3 = _problem(16)
    mesh = Mesh(np.array(jax.devices()[:1]), ("d",))
    clear_plan_cache()
    with obs.session() as s:
        _, info = gemt3_planned(x, c1, c2, c3, with_info=True, mesh=mesh,
                                axes=("d", None, None))
        reg = s.registry
        assert reg.value("engine.executions") == 1
        assert reg.value("engine.macs") == info["macs"]
        assert (reg.value("engine.collective_bytes")
                == info["collective_bytes"])


def test_counter_parity_backward():
    x, c1, c2, c3 = _problem(16)
    clear_plan_cache()
    with obs.session() as s:
        _, info = gemt3_planned(x, c1, c2, c3, with_info=True,
                                differentiable=True)
        loss = lambda *a: jnp.sum(jnp.abs(
            gemt3_planned(*a, differentiable=True)))
        jax.grad(loss, argnums=(0, 1, 2, 3))(x, c1, c2, c3)
        gs = grad_stats()
        assert gs["backward_calls"] == 1
        # shim parity: grad_stats() IS the grad.* namespace
        for k, v in gs.items():
            assert s.registry.value("grad." + k) == v
        # executed counters in exact parity with the predicted info
        # fields — the fused-adjoint walk dispatches what it planned
        for k in ("kernel_stages", "einsum_stages", "coeff_kernel",
                  "coeff_einsum", "fused_launches"):
            assert gs[k] == info["grad_" + k], k
        total = (gs["kernel_stages"] + gs["einsum_stages"]
                 + gs["coeff_kernel"] + gs["coeff_einsum"])
        assert total == info["grad_launches"] <= 4  # fused walk, was 8
        reset_grad_stats()
        assert grad_stats()["backward_calls"] == 0
        assert s.registry.value("grad.backward_calls") == 0


# ---------------------------------------------------------------------------
# acceptance: traced forward+backward exports a Chrome trace whose
# vjp.backward span lists all 8 backward launches by name
# ---------------------------------------------------------------------------


def test_traced_backward_exports_eight_attributed_launches(tmp_path):
    x, c1, c2, c3 = _problem(16)
    clear_plan_cache()
    path = str(tmp_path / "bwd_trace.json")
    with obs.session() as s:
        # fuse=False pins the adjoint to the staged chain: exactly
        # 2 recompute + 3 grad_x + 3 coeff_grad = 8 attributed launches
        loss = lambda *a: jnp.sum(jnp.abs(
            gemt3_planned(*a, differentiable=True, fuse=False)))
        jax.grad(loss, argnums=(0, 1, 2, 3))(x, c1, c2, c3)
        doc = obs.write_chrome_trace(path, s.tracer.spans(), s.registry)
    loaded = json.loads(open(path).read())
    assert loaded == json.loads(json.dumps(doc))
    events = loaded["traceEvents"]
    (vjp,) = [e for e in events if e["name"] == "vjp.backward"]
    pieces = vjp["args"]["pieces"]
    assert len(pieces) == 8, pieces
    kinds = [p.split(":")[0] for p in pieces]
    assert kinds == ["grad_recompute"] * 2 + ["grad_x"] * 3 + [
        "coeff_grad"] * 3
    # the per-launch grad.* spans are retired: the pieces are the record
    assert not [e for e in events if e["name"].startswith("grad.")]
    # the backward's lowered stages nest under the vjp.backward span
    vjp_id = vjp["args"]["span_id"]
    stage_like = [e for e in events
                  if e["name"].startswith(("stage:", "coeff_grad:"))
                  and e["args"]["parent_id"] == vjp_id]
    assert len(stage_like) == 8
    assert loaded["counters"]["grad.backward_calls"] == 1
    assert loaded["counters"]["grad.kernel_stages"] + loaded["counters"][
        "grad.einsum_stages"] == 5


def test_fused_backward_spans_attributed_like_forward():
    """The fused-adjoint walk's launches carry the same attribution
    contract as the staged one: the ``vjp.backward`` span lists one piece
    per planned launch, ``info["grad_launches"]`` of them."""
    x, c1, c2, c3 = _problem(16)
    clear_plan_cache()
    with obs.session() as s:
        _, info = gemt3_planned(x, c1, c2, c3, with_info=True,
                                differentiable=True)
        assert info["grad_fused"] and info["grad_chain_depth"] >= 2
        loss = lambda *a: jnp.sum(jnp.abs(
            gemt3_planned(*a, differentiable=True)))
        jax.grad(loss, argnums=(0, 1, 2, 3))(x, c1, c2, c3)
        spans = s.tracer.spans()
    (vjp,) = [sp for sp in spans if sp.name == "vjp.backward"]
    pieces = vjp.attrs["pieces"]
    assert len(pieces) == info["grad_launches"]
    assert pieces[0].startswith("grad_recompute:")
    assert sum(1 for p in pieces if p.startswith("grad_x:fused:")) == 1
    assert pieces[-1].startswith("coeff_grad:fused:")
    tails = [p for p in pieces if p.startswith("grad_chain:")]
    assert len(tails) == (1 if info["grad_chain_depth"] == 2 else 0)
    assert not [sp for sp in spans if sp.name.startswith("grad.")]


@pytest.mark.parametrize("watcher", ["tracer", "fault_hook", "profiler"])
def test_profiler_session_keeps_the_composed_backward(tmp_path, watcher):
    """Nothing that watches selects the backward: with obs's tracer on, a
    fault injector installed (no spec matches) or a profiler session
    recording, the custom-VJP backward builds the same cached programs
    and moves the same counters as with everything off."""
    from repro.engine import executor
    from repro.runtime.faults import FaultInjector, FaultSpec

    x, c1, c2, c3 = _problem(16)
    loss = lambda *a: jnp.sum(jnp.abs(
        gemt3_planned(*a, differentiable=True)))

    def backward(watch):
        clear_plan_cache()
        with obs.session(enable_tracing=watch == "tracer") as s:
            hook = (FaultInjector(FaultSpec("no-such-span")).install()
                    if watch == "fault_hook" else None)
            if watch == "profiler":
                jax.profiler.start_trace(str(tmp_path))
            try:
                jax.block_until_ready(
                    jax.grad(loss, argnums=(0, 1, 2, 3))(x, c1, c2, c3))
            finally:
                if watch == "profiler":
                    jax.profiler.stop_trace()
                if hook is not None:
                    hook.uninstall()
            keys = set(executor._SHARDED_FN_CACHE)
            return grad_stats(), keys, [sp.name for sp in s.tracer.spans()]

    stats, keys, names = backward(None)
    w_stats, w_keys, w_names = backward(watcher)
    assert w_stats == stats and stats["backward_calls"] == 1
    assert w_keys == keys and {k[0] for k in keys} == {"vjp_walk"}
    assert names == []
    if watcher != "fault_hook":
        assert "vjp.backward" in w_names


# ---------------------------------------------------------------------------
# fusion-degradation events
# ---------------------------------------------------------------------------


def test_fusion_degradation_events_surface_in_info():
    x, c1, c2, c3 = _problem(32)
    clear_plan_cache()
    with obs.session() as s:
        _, info = gemt3_planned(x, c1, c2, c3, with_info=True,
                                vmem_budget=20_000)
        events = info["events"]
        assert events, "tiny budget must demote fusion and record why"
        for ev in events:
            assert ev["kind"] == "fusion_degradation"
            assert ev["from"] in ("triple", "pair")
            assert ev["to"] == "staged"
            assert ev["reason"] == "vmem_budget"
            assert ev["vmem_bytes_min"] > ev["vmem_budget"] == 20_000
        assert (s.registry.value("plan.fusion_degradations") == len(events))
        # cache hit replays the same events without re-counting
        _, info2 = gemt3_planned(x, c1, c2, c3, with_info=True,
                                 vmem_budget=20_000)
        assert info2["events"] == events
        assert (s.registry.value("plan.fusion_degradations") == len(events))


def test_no_degradation_events_on_roomy_budget():
    x, c1, c2, c3 = _problem(16)
    clear_plan_cache()
    _, info = gemt3_planned(x, c1, c2, c3, with_info=True, fuse=False)
    # forced staging is a user choice, not a degradation
    assert info["events"] == []


# ---------------------------------------------------------------------------
# serve latency histogram
# ---------------------------------------------------------------------------


def test_serve_stats_latency_percentiles():
    from repro.serve.decode import DxtServeSession

    sess = DxtServeSession(kind="dct")
    batch = RNG.random((2, 8, 8, 8)).astype(np.float32)
    with obs.session() as s:
        for _ in range(5):
            sess.transform(batch)
        stats = sess.stats()
        assert s.registry.value("serve.requests") == 5
    assert stats["requests_served"] == 10  # 5 calls x batch 2
    lat = stats["latency_us"]
    assert lat["count"] == 5
    assert lat["min"] > 0
    assert lat["min"] <= lat["p50"] <= lat["p90"] <= lat["p99"] <= lat["max"]
    assert lat["mean"] > 0
    assert stats["hbm_bytes_moved"] > 0


# ---------------------------------------------------------------------------
# autotune cache: atomic writes + corrupt recovery
# ---------------------------------------------------------------------------


def test_autotune_cache_atomic_save_roundtrip(tmp_path):
    path = str(tmp_path / "autotune.json")
    with obs.session() as s:
        cache = AutotuneCache(path)
        # a live-schema key: load() prunes unrecognized (stale-version) keys
        key = "v4:16x16x16|float32|dense|fwd|plain|s"
        cache.put(key, {"bm": 64, "bn": 64, "bk": 64, "us": 1.0})
        cache.save()
        assert s.registry.value("autotune.cache.writes") == 1
        # no temp litter, and the file is complete valid JSON
        assert [f for f in os.listdir(tmp_path)] == ["autotune.json"]
        assert json.loads(open(path).read())[key]["bm"] == 64
        fresh = AutotuneCache(path)
        assert fresh.get(key)["bn"] == 64
        assert s.registry.value("autotune.cache.loads") == 1
        assert s.registry.value("autotune.cache.hits") == 1
        assert fresh.get("absent") is None
        assert s.registry.value("autotune.cache.misses") == 1


def test_autotune_cache_corrupt_recovery(tmp_path):
    path = str(tmp_path / "autotune.json")
    with open(path, "w") as f:
        f.write('{"torn": ')  # torn write
    with obs.session() as s:
        cache = AutotuneCache(path)
        assert len(cache) == 0
        assert s.registry.value("autotune.cache.corrupt_recovered") == 1
        # non-dict JSON counts as corrupt too
        with open(path, "w") as f:
            json.dump([1, 2, 3], f)
        cache.load()
        assert len(cache) == 0
        assert s.registry.value("autotune.cache.corrupt_recovered") == 2
        # recovery is silent for runs: put/save works over the rubble
        key = "v4:8x8x8|float32|dense|fwd|plain|s"
        cache.put(key, {"bm": 8, "bn": 8, "bk": 8})
        cache.save()
        assert AutotuneCache(path).get(key)["bm"] == 8


# ---------------------------------------------------------------------------
# memo counters
# ---------------------------------------------------------------------------


def test_esop_memo_counters_mirror_stats():
    from repro.kernels import ops

    c = jnp.asarray((RNG.random((16, 16)) > 0.5).astype(np.float32))
    with obs.session() as s:
        before = ops.esop_memo_stats()
        ops.esop_plan_cached(c, 8, 8)   # miss
        ops.esop_plan_cached(c, 8, 8)   # hit
        after = ops.esop_memo_stats()
        assert (s.registry.value("memo.esop.misses")
                == after["misses"] - before["misses"] == 1)
        assert (s.registry.value("memo.esop.hits")
                == after["hits"] - before["hits"] == 1)

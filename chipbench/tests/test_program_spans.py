"""The readers of the program's own spans, on synthetic obs spans."""
import importlib.util
import os
from types import SimpleNamespace

import pytest

from repro import obs

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
READERS = ("entry_ms.ft", "coeff_build_ms.ft", "fingerprint_ms.ft",
           "dispatch_ms.ft")


def reader(name):
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def record_calls(n, fingerprints=3):
    """``n`` entry calls shaped like ``dxt3d``'s, with set durations (ms):
    root 36, coefficients 30, each fingerprint 1, execute 2."""
    for _ in range(n):
        with obs.span("dxt3d:dht") as root:
            with obs.span("dxt3d.coefficients") as c:
                pass
            fps = []
            for _ in range(fingerprints):
                with obs.span("plan.fingerprint") as f:
                    fps.append(f)
            with obs.span("execute") as e:
                with obs.span("fused_pair:m12") as k:
                    pass
        for sp, ms in [(root, 36), (c, 30), (e, 2), (k, 1.5)] + [
                (f, 1) for f in fps]:
            sp.dur_ns = int(ms * 1e6)


@pytest.mark.parametrize("name,want", [
    ("entry_ms.ft", 36.0), ("coeff_build_ms.ft", 30.0),
    ("fingerprint_ms.ft", 3.0), ("dispatch_ms.ft", 2.0)])
def test_reader_sums_its_spans_per_iteration(name, want):
    with obs.session():
        record_calls(4)
        got = reader(name)(SimpleNamespace(traced={"iterations": 4}))
    assert got == pytest.approx(want)


def test_fingerprint_reads_zero_on_memo_hits():
    with obs.session():
        record_calls(5, fingerprints=0)
        got = reader("fingerprint_ms.ft")(
            SimpleNamespace(traced={"iterations": 5}))
    assert got == 0.0


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_nothing_unless_one_root_per_iteration(name):
    read = reader(name)
    with obs.session():
        record_calls(3)
        assert read(SimpleNamespace(traced={"iterations": 4})) is None
        assert read(SimpleNamespace(traced={"iterations": 2})) is None
        assert read(SimpleNamespace(traced=None)) is None
    # a program that records no spans, as one without the profiler bridge
    with obs.session():
        assert read(SimpleNamespace(traced={"iterations": 20})) is None

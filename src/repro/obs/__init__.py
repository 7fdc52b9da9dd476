"""Engine observability: structured spans, a metrics registry, exporters.

Three pieces (see ``docs/observability.md``):

* :mod:`repro.obs.trace` — nested timed spans over the engine's host-side
  control flow (plan → autotune → lower → kernel launches → collectives →
  VJP chain → serve requests), ring-buffered, near-zero cost when disabled;
* :mod:`repro.obs.metrics` — counters/gauges/histograms absorbing the
  engine's scattered accounting (``info`` byte/MAC fields, grad stats,
  memo + autotune-cache hit/miss, fusion-degradation events, serve
  latency percentiles);
* :mod:`repro.obs.export` — Chrome-trace (Perfetto) JSON export, text/JSON
  reports, and the ``python -m repro.obs`` CLI.

Typical use::

    from repro import obs

    obs.enable()                       # start recording spans
    y, info = gemt3_planned(x, c1, c2, c3, with_info=True,
                            differentiable=True)
    jax.grad(lambda x: gemt3_planned(x, c1, c2, c3,
                                     differentiable=True).sum())(x)
    obs.write_chrome_trace("trace.json")   # open in ui.perfetto.dev
    print(obs.format_report())

``obs.session()`` scopes both the tracer and the metrics registry for
isolated measurements (e.g. one serve session, one bench run).
"""
from __future__ import annotations

import contextlib
from types import SimpleNamespace

from . import export, metrics, trace
from .export import (chrome_trace, format_report, report_dict,
                     span_tree_lines, write_chrome_trace)
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      get_registry, inc, observe, set_gauge, set_registry)
from .trace import (NULL_SPAN, Span, Tracer, clear, disable, enable,
                    enabled, get_fault_hook, get_tracer, set_fault_hook,
                    set_tracer, span, spans)

__all__ = [
    # spans
    "Span", "Tracer", "NULL_SPAN", "span", "enable", "disable",
    "enabled", "spans", "clear", "get_tracer", "set_tracer",
    "set_fault_hook", "get_fault_hook",
    # metrics
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "get_registry",
    "set_registry", "inc", "observe", "set_gauge",
    # exporters
    "chrome_trace", "write_chrome_trace", "span_tree_lines",
    "format_report", "report_dict",
    # scoping
    "session",
    # submodules
    "trace", "metrics", "export",
]


@contextlib.contextmanager
def session(name: str = "session", capacity: int | None = None,
            enable_tracing: bool = True):
    """Scope a fresh tracer + metrics registry for the ``with`` body.

    Everything the engine records inside the block lands in the session's
    own objects (per-session isolation of the formerly process-global
    counters); the previous tracer/registry are restored on exit, so
    nothing leaks either way.  Yields a namespace with ``.tracer`` and
    ``.registry``.
    """
    tracer = Tracer(capacity or trace.DEFAULT_CAPACITY)
    tracer.enabled = bool(enable_tracing)
    registry = MetricsRegistry(name)
    prev_tracer = set_tracer(tracer)
    prev_registry = set_registry(registry)
    try:
        yield SimpleNamespace(tracer=tracer, registry=registry)
    finally:
        set_tracer(prev_tracer)
        set_registry(prev_registry)

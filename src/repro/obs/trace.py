"""Structured spans: nested, timed, attribute-carrying trace records.

The engine's host-side control flow (plan, autotune probe, lower, each
kernel/einsum stage launch, the psum_scatter collective, the VJP's
recompute + adjoint chain, serve requests) is instrumented with spans —
``with span("stage:m2:sr_gemm", {...}):`` regions that record wall time,
nesting and structured attributes (plan key, fuse tier, backend, modeled
MACs/HBM/collective bytes, shapes).  Completed spans land in a per-tracer
ring buffer (:class:`Tracer`, bounded by ``capacity``) and export to
Chrome-trace JSON via :mod:`repro.obs.export`.

Timing semantics under jax: spans measure the *host* — dispatch plus any
compile — not device execution (jax dispatch is asynchronous).  Inside a
``jit``/``shard_map`` body the span records trace time, once per
compilation; the span *structure* (which stages lower, in what nesting)
is exact either way.

Profiler bridge: a span also records while a JAX profiler session is
recording (``jax.profiler.start_trace`` … ``stop_trace``), tracer enabled
or not.  It then enters a ``jax.profiler.TraceAnnotation`` of the same
name, with its attributes as event stats, so the engine's spans
appear on the profile's host plane on the device trace's clock.  Each span
carries ``root_id``, the id of the outermost span open on its thread when
it started (its own id for a root): every span of one call shares it.
The check binds ``jax.profiler`` at import, so importing ``repro.obs``
loads jax, as the rest of the package does.  A profiler session records
spans and changes no code path: engine code that runs launches one by
one so that spans time them asks the tracer (``get_tracer().enabled``),
not :func:`enabled`.

Disabled-mode cost is the contract: :func:`span` returns the preallocated
:data:`NULL_SPAN` singleton without allocating, and hot call sites guard
attribute construction behind :func:`enabled`, so an untraced serve hot
path pays one global load + attribute check and the profiler's
is-recording check per site.

Fault hook: the chaos layer (:mod:`repro.runtime.faults`) registers a
callable via :func:`set_fault_hook` that receives every span *name* at the
moment the span would start — before any work the span guards.  While a
hook is installed :func:`enabled` reports True so the guarded call sites
actually reach :func:`span` (tracing itself may stay off; :func:`span`
still returns :data:`NULL_SPAN` then).  The hook may raise (injected
kernel/collective failure) or sleep (injected delay); with no hook
installed the hot path is unchanged — one extra global load.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque

from jax.profiler import TraceAnnotation

__all__ = [
    "Span",
    "Tracer",
    "NULL_SPAN",
    "get_tracer",
    "set_tracer",
    "enabled",
    "enable",
    "disable",
    "span",
    "spans",
    "clear",
    "set_fault_hook",
    "get_fault_hook",
    "DEFAULT_CAPACITY",
]

DEFAULT_CAPACITY = 8192

# True only while a JAX profiler session records.  Bound once: every
# guarded span site calls it.
_profiling = TraceAnnotation.is_enabled


class _NullSpan:
    """Preallocated no-op span: the disabled-mode zero-allocation fast path.

    ``span()`` returns this singleton whenever tracing is off; entering,
    exiting and ``set()`` do nothing, and it is falsy so call sites can
    skip attribute construction with ``if sp:``.
    """

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self

    def __bool__(self):
        return False

    def __repr__(self):
        return "<NULL_SPAN>"


NULL_SPAN = _NullSpan()


class Span:
    """One timed region.  Use as a context manager; ``set(**attrs)`` adds
    attributes (before, during or right after the region — the record is
    buffered at ``__exit__``; the profiler's copy takes those set before
    ``__enter__``)."""

    __slots__ = ("name", "attrs", "span_id", "parent_id", "root_id", "depth",
                 "t0_ns", "dur_ns", "_tracer", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict | None):
        self._tracer = tracer
        self.name = name
        self.attrs = dict(attrs) if attrs else {}
        self.span_id = next(tracer._ids)
        self.parent_id = 0
        self.root_id = self.span_id
        self.depth = 0
        self.t0_ns = 0
        self.dur_ns = 0
        self._annotation = None

    def set(self, **attrs):
        self.attrs.update(attrs)
        return self

    def __enter__(self):
        stack = self._tracer._stack()
        if stack:
            self.parent_id = stack[-1].span_id
            self.root_id = stack[0].span_id
            self.depth = len(stack)
        stack.append(self)
        if _profiling():
            self._annotation = TraceAnnotation(self.name, **self.attrs)
            self._annotation.__enter__()
        self.t0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.dur_ns = time.perf_counter_ns() - self.t0_ns
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        stack = self._tracer._stack()
        if stack and stack[-1] is self:
            stack.pop()
        self._tracer._buf.append(self)
        return False

    def __repr__(self):
        return (f"Span({self.name!r}, {self.dur_ns / 1e3:.1f}us, "
                f"id={self.span_id}, parent={self.parent_id})")


class Tracer:
    """Ring-buffered span recorder (one per session; thread-safe nesting
    via a per-thread active-span stack)."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self.enabled = False
        self._buf: deque[Span] = deque(maxlen=int(capacity))
        self._ids = itertools.count(1)
        self._tls = threading.local()

    @property
    def capacity(self) -> int:
        return self._buf.maxlen

    def _stack(self) -> list:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def start(self, name: str, attrs: dict | None = None) -> Span:
        return Span(self, name, attrs)

    def spans(self) -> list[Span]:
        return list(self._buf)

    def clear(self) -> None:
        self._buf.clear()

    def resize(self, capacity: int) -> None:
        if capacity != self._buf.maxlen:
            self._buf = deque(self._buf, maxlen=int(capacity))


_TRACER = Tracer()

# Chaos hook (see module docstring): callable(name) invoked at span start.
_FAULT_HOOK = None


def set_fault_hook(hook):
    """Install ``hook(span_name)`` on the span hot path; returns the
    previous hook (``None`` if none) so injectors can nest/restore."""
    global _FAULT_HOOK
    prev = _FAULT_HOOK
    _FAULT_HOOK = hook
    return prev


def get_fault_hook():
    return _FAULT_HOOK


def get_tracer() -> Tracer:
    return _TRACER


def set_tracer(tracer: Tracer) -> Tracer:
    """Install ``tracer`` as the process-current tracer; returns the
    previous one (``obs.session()`` uses this for per-session isolation)."""
    global _TRACER
    prev = _TRACER
    _TRACER = tracer
    return prev


def enabled() -> bool:
    """Cheap guard for hot call sites: build span names/attrs only when
    this returns True, else use :data:`NULL_SPAN` directly.  True while
    the tracer is enabled or a profiler session records, and whenever a
    fault hook is installed, so chaos drills reach :func:`span` (and the
    hook) even with tracing off."""
    return _TRACER.enabled or _FAULT_HOOK is not None or _profiling()


def span(name: str, attrs: dict | None = None):
    """Start a span on the current tracer; :data:`NULL_SPAN` unless the
    tracer is enabled or a profiler session records.

    ``attrs`` may be a zero-arg callable, evaluated only when the span
    records (lazy construction for attribute dicts that cost something).
    An installed fault hook fires first — it may raise or delay, standing
    in for the kernel/collective failure the span would have timed.
    """
    hook = _FAULT_HOOK
    if hook is not None:
        hook(name)
    t = _TRACER
    if not (t.enabled or _profiling()):
        return NULL_SPAN
    if callable(attrs):
        attrs = attrs()
    return Span(t, name, attrs)


def enable(capacity: int | None = None) -> Tracer:
    if capacity is not None:
        _TRACER.resize(capacity)
    _TRACER.enabled = True
    return _TRACER


def disable() -> None:
    _TRACER.enabled = False


def spans() -> list[Span]:
    return _TRACER.spans()


def clear() -> None:
    _TRACER.clear()

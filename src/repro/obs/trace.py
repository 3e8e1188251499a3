"""Structured phase-level tracing with a zero-overhead no-op default.

A :class:`Tracer` collects :class:`Span` records — name, wall-clock
interval, and free-form attributes (phase, strategy, device count, bytes
on wire, …) — and exports them as Chrome-trace JSON (the ``traceEvents``
array format), loadable in ``chrome://tracing`` and https://ui.perfetto.dev.
A live span also writes itself into the JAX profiler's own trace whenever
a profiler session is active, so the same spans read beside the device's
ops on the profiler's clock.

Design constraints, in order:

1. **Disabled is free.**  With no tracer installed and no profiler
   session (the default) every instrumentation site costs one
   module-global ``None`` check plus ``TraceAnnotation.is_enabled()``;
   the module-level :func:`span` helper returns the shared
   :data:`NULL_SPAN` identity context manager — the same object every
   call, zero allocations (asserted in tests/test_obs.py).  Hot paths
   that would otherwise build a kwargs dict per element should fetch
   :func:`active` once and branch on ``None`` (the phase closures do).
2. **Live spans share the device's clock.**  A live span enters a
   ``jax.profiler.TraceAnnotation(name, **attrs)`` whenever a profiler
   session is active, and records into the installed :class:`Tracer`
   (if any) as before.  Its attributes become the annotation's stats
   (as given at entry; ``set()`` reaches only the Tracer).  Served-path
   spans add no host sync: the device's time is read from the device
   trace, which shares their clock, not from span lengths.  The
   per-phase closures of :mod:`repro.core.distributed` are the one
   exception: with a Tracer installed they ``block_until_ready`` inside
   their span, so per-phase span sums compare with wall time and the
   cost model (benchmarks/phases.py's accounting); values never change
   (benchmarks/phase_trace.py asserts traced ≡ untraced).
3. **Spans are data.**  A span is (name, t0, t1, attrs); retrospective
   intervals (e.g. a request's enqueue wait, known only at flush time)
   are first-class via :meth:`Tracer.add_span` — in the Tracer's memory
   only, since nothing enters the profiler's trace after the fact.
4. **Stitching is ambient.**  :meth:`Tracer.context` opens a
   thread-local block of ambient attributes: every span recorded on
   that thread while the block is open — from any instrumentation site,
   however deep in the call stack — inherits them (explicit attrs win).
   The serving layer uses it to stamp ``window_id``/``request_ids``
   onto the phase/pipeline spans a window's flush emits, stitching one
   request lifecycle from ``serve/submit`` down to the kernels without
   threading ids through every call signature.  Thread-local, so
   concurrent tenant flushes never cross-contaminate; nothing changes
   while tracing is disabled (ambient merging happens inside
   ``_record``, which only runs with a tracer installed).

Install/uninstall is explicit and process-global (:func:`install` /
:func:`uninstall`, or the :func:`tracing` context manager); thread-safe
recording via one lock per tracer.
"""
from __future__ import annotations

import dataclasses
import json
import threading
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

# Whether a JAX profiler session is active (a C++ call, about 0.1 us).
_profiling = TraceAnnotation.is_enabled


@dataclasses.dataclass
class Span:
    """One recorded interval. Times are ``time.perf_counter()`` seconds."""

    name: str
    t0: float
    t1: float
    attrs: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def duration(self) -> float:
        return self.t1 - self.t0


class _NullSpan:
    """The shared identity context manager returned while tracing is
    disabled: entering/exiting does nothing, ``set()`` swallows attrs.
    One module-level instance exists (:data:`NULL_SPAN`); no call path
    allocates a new one."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> "_NullSpan":
        return self


NULL_SPAN = _NullSpan()


class _LiveSpan:
    """An in-flight span: context-manager entry stamps t0 (and enters a
    profiler ``TraceAnnotation`` when a session is active), exit stamps
    t1 and hands the record to the tracer, if there is one.
    ``set(**attrs)`` adds attributes mid-flight (e.g. bytes known only
    after the phase ran); they reach the Tracer and the annotation."""

    __slots__ = ("_tracer", "name", "attrs", "t0", "t1", "_annotation")

    def __init__(self, tracer: Optional["Tracer"], name: str,
                 attrs: Dict[str, Any]):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs
        self.t0 = 0.0
        self.t1 = 0.0
        self._annotation: Optional[TraceAnnotation] = None

    def __enter__(self) -> "_LiveSpan":
        if _profiling():
            self._annotation = TraceAnnotation(self.name, **self.attrs)
            self._annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = time.perf_counter()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
            self._annotation = None
        if self._tracer is not None:
            self._tracer._record(Span(self.name, self.t0, self.t1,
                                      self.attrs))
        return False

    def set(self, **attrs) -> "_LiveSpan":
        self.attrs.update(attrs)
        if self._annotation is not None:
            self._annotation.set_metadata(**attrs)
        return self


class _AmbientContext:
    """One entry on a tracer's thread-local ambient-attrs stack (see
    :meth:`Tracer.context`)."""

    __slots__ = ("_tracer", "_attrs")

    def __init__(self, tracer: "Tracer", attrs: Dict[str, Any]):
        self._tracer = tracer
        self._attrs = attrs

    def __enter__(self) -> "_AmbientContext":
        tl = self._tracer._ambient
        stack = getattr(tl, "stack", None)
        if stack is None:
            stack = tl.stack = []
        stack.append(self._attrs)
        return self

    def __exit__(self, *exc) -> bool:
        self._tracer._ambient.stack.pop()
        return False


class Tracer:
    """A process-local span collector with a Chrome-trace exporter."""

    def __init__(self):
        self.spans: List[Span] = []
        self._lock = threading.Lock()
        self._ambient = threading.local()
        self.epoch = time.perf_counter()   # ts origin for the export

    # -- recording ------------------------------------------------------
    def span(self, name: str, **attrs) -> _LiveSpan:
        """A context manager recording one interval around its body."""
        return _LiveSpan(self, name, attrs)

    def add_span(self, name: str, t0: float, t1: float, **attrs) -> Span:
        """Record a retrospective interval from explicit perf_counter
        stamps (e.g. enqueue wait: submit time → flush time). It stays
        in this Tracer's memory: nothing can enter the profiler's trace
        after the fact, so what a profiled run needs of such intervals
        is kept as counters instead (e.g. ``SLOAccount.queue_wait_s``)."""
        s = Span(name, t0, t1, attrs)
        self._record(s)
        return s

    def context(self, **attrs) -> _AmbientContext:
        """Thread-local ambient attributes for a block: every span this
        thread records while the block is open inherits ``attrs``
        (explicit span attrs win on clashes; nested contexts merge,
        inner-most winning).  Other threads are unaffected — concurrent
        tenant flushes each stitch their own ``window_id``."""
        return _AmbientContext(self, attrs)

    def _ambient_attrs(self) -> Optional[Dict[str, Any]]:
        stack = getattr(self._ambient, "stack", None)
        if not stack:
            return None
        merged: Dict[str, Any] = {}
        for frame in stack:
            merged.update(frame)
        return merged

    def _record(self, span: Span) -> None:
        ambient = self._ambient_attrs()
        if ambient:
            for k, v in ambient.items():
                span.attrs.setdefault(k, v)
        with self._lock:
            self.spans.append(span)

    def clear(self) -> None:
        with self._lock:
            self.spans = []

    # -- queries --------------------------------------------------------
    def by_name(self) -> Dict[str, List[Span]]:
        out: Dict[str, List[Span]] = {}
        for s in list(self.spans):
            out.setdefault(s.name, []).append(s)
        return out

    def total(self, prefix: str = "") -> float:
        """Summed duration (seconds) of every span whose name starts with
        ``prefix`` (empty prefix: all spans)."""
        return sum(s.duration for s in list(self.spans)
                   if s.name.startswith(prefix))

    def filter(self, prefix: str = "", **attrs) -> List[Span]:
        """Spans matching a name prefix and (exact-equality) attrs."""
        out = []
        for s in list(self.spans):
            if not s.name.startswith(prefix):
                continue
            if all(s.attrs.get(k) == v for k, v in attrs.items()):
                out.append(s)
        return out

    # -- export ---------------------------------------------------------
    def chrome_trace(self) -> Dict[str, Any]:
        """The Chrome-trace JSON object (``traceEvents`` complete events,
        microsecond timestamps relative to the tracer's epoch). Loads in
        chrome://tracing and ui.perfetto.dev unchanged."""
        events = []
        for s in list(self.spans):
            events.append({
                "name": s.name,
                "cat": str(s.attrs.get("phase", s.name.split("/", 1)[0])),
                "ph": "X",
                "ts": (s.t0 - self.epoch) * 1e6,
                "dur": s.duration * 1e6,
                "pid": 0,
                "tid": 0,
                "args": {k: (v if isinstance(v, (int, float, str, bool))
                             or v is None else str(v))
                         for k, v in s.attrs.items()},
            })
        events.sort(key=lambda e: e["ts"])
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def export_chrome_trace(self, path) -> int:
        """Write the Chrome-trace JSON to ``path``; returns event count."""
        doc = self.chrome_trace()
        with open(path, "w") as fh:
            json.dump(doc, fh, indent=1, default=float)
        return len(doc["traceEvents"])


# ---------------------------------------------------------------------------
# The process-global active tracer (None = tracing disabled, the default)
# ---------------------------------------------------------------------------

_active: Optional[Tracer] = None


def active() -> Optional[Tracer]:
    """The installed tracer, or None when tracing is disabled. Hot paths
    fetch this once and branch — the disabled branch is one comparison."""
    return _active


def enabled() -> bool:
    return _active is not None


def install(tracer: Tracer) -> Tracer:
    """Make ``tracer`` the process-global active tracer."""
    global _active
    _active = tracer
    return tracer


def uninstall() -> None:
    global _active
    _active = None


class tracing:
    """``with tracing(tracer):`` installs the tracer for the block and
    restores the previous one (usually None) on exit, exceptions included."""

    def __init__(self, tracer: Optional[Tracer] = None):
        self.tracer = tracer if tracer is not None else Tracer()
        self._prev: Optional[Tracer] = None

    def __enter__(self) -> Tracer:
        global _active
        self._prev = _active
        _active = self.tracer
        return self.tracer

    def __exit__(self, *exc) -> bool:
        global _active
        _active = self._prev
        return False


def span(name: str, **attrs):
    """Module-level convenience: a span on the active tracer; with no
    tracer but a profiler session active, a span that writes only the
    profiler's ``TraceAnnotation``; with neither, the shared
    :data:`NULL_SPAN` identity context manager.

    Note the kwargs dict is built before the enabled check — per-element
    hot loops should use ``t = active()`` + an explicit ``None`` branch
    instead (the phase closures do)."""
    t = _active
    if t is not None:
        return t.span(name, **attrs)
    if _profiling():
        return _LiveSpan(None, name, attrs)
    return NULL_SPAN

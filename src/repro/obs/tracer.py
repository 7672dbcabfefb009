"""Hierarchical span tracer + typed counters — the `repro.obs` core.

Dependency-free (stdlib only) structured instrumentation for the whole
compile path: analysis passes, the SMT tightening loop, and the lowered
execution backends all emit into one event stream so "where did the 30s
stage budget go?" has an answer (docs/observability.md).

Three primitives:

  * **spans** — `with span("smt.stage", stage="det") as sp:` records a
    monotonic `[t0, t1)` interval with nested parent ids (per-thread span
    stacks, so concurrent threads trace independently).  `sp.set(k=v)`
    attaches attributes mid-flight; attributes land in both exporters.
  * **events** — `event("smt.budget_exhausted", stage=...)` is an instant
    marker attached to the current span.
  * **counters** — `CounterGroup` is a *dict subclass* with a
    lock, `add()` and `reset()`: the three legacy module-global stat dicts
    (`analysis.driver.MEMO_STATS` / `DISK_CACHE_STATS`,
    `smt.solver.STATS`) are byte-compatible shims over it — existing
    `STATS["hits"]`-style reads keep working while mutation is now locked
    and resettable.

Tracing is **off by default and free when off**: the module-level `span`
/ `event` helpers check one global and return a shared no-op object, so
the instrumented hot paths cost a pointer compare per call.  Enable with
`enable()` / `tracing()`; export with `repro.obs.exporters` (JSONL +
Chrome trace-event JSON, perfetto-loadable).

**One clock with the device.**  While a tracer is active, every span
also enters a `jax.profiler.TraceAnnotation` of its own name, so a
running JAX profiler records it on its ``/host:`` plane beside the
device's ``XLA Ops``.  JAX is looked up once, when the tracer is
installed; without it (or with the profiler idle) spans are unchanged.
"""
from __future__ import annotations

import itertools
import threading
import time
from typing import Any, Dict, List, Optional

__all__ = [
    "CounterGroup", "Span", "Tracer", "active_tracer", "all_counters",
    "disable", "enable", "event", "is_enabled",
    "runtime_ranges_enabled", "span", "tracing",
]


# ---------------------------------------------------------------------------
# typed counters (the legacy-stat-dict mechanism)
# ---------------------------------------------------------------------------

_COUNTER_REGISTRY: Dict[str, "CounterGroup"] = {}
_REGISTRY_LOCK = threading.Lock()


class CounterGroup(dict):
    """A named group of monotonic counters: a locked, resettable dict.

    Subclassing `dict` keeps every legacy consumer byte-compatible
    (`MEMO_STATS["hits"]`, `dict(STATS)`, `.update(...)` all still work)
    while adding what the ad-hoc globals lacked: `add()` mutates under a
    lock (safe for multi-threaded solver use), `reset()` restores the
    declared initial values, and the group registers itself so exporters
    can snapshot every counter in the process (`all_counters()`).

    Values are ints or floats (e.g. `smt.solver.STATS["secs"]`).
    """

    def __init__(self, name: str, **initial):
        super().__init__(**initial)
        self.name = name
        self._initial = dict(initial)
        self._lock = threading.Lock()
        with _REGISTRY_LOCK:
            _COUNTER_REGISTRY[name] = self

    def add(self, key: str, n=1):
        """Locked increment; returns the new value."""
        with self._lock:
            v = self.get(key, 0) + n
            super().__setitem__(key, v)
            return v

    def set(self, key: str, value):
        """Locked assignment."""
        with self._lock:
            super().__setitem__(key, value)

    def reset(self) -> None:
        """Restore the declared initial values (drop any extra keys)."""
        with self._lock:
            super().clear()
            super().update(self._initial)

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return dict(self)


def all_counters() -> Dict[str, Dict[str, Any]]:
    """{group name: {counter: value}} over every registered group."""
    with _REGISTRY_LOCK:
        groups = list(_COUNTER_REGISTRY.values())
    return {g.name: g.snapshot() for g in groups}


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

class Span:
    """One finished (or in-flight) span.  Context-manager protocol; use
    through `Tracer.span` / the module-level `span` helper."""

    __slots__ = ("tracer", "name", "attrs", "span_id", "parent_id",
                 "t0", "t1", "thread_id", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, attrs: Dict[str, Any]):
        self.tracer = tracer
        self.name = name
        self.attrs = attrs
        self.span_id = -1
        self.parent_id: Optional[int] = None
        self.t0 = 0.0
        self.t1 = 0.0
        self.thread_id = 0
        self._annotation = None

    def set(self, **attrs) -> "Span":
        """Attach/overwrite attributes (any time before export)."""
        self.attrs.update(attrs)
        return self

    def __enter__(self) -> "Span":
        tr = self.tracer
        self.span_id = next(tr._ids)
        self.thread_id = threading.get_ident()
        stack = tr._stack()
        self.parent_id = stack[-1].span_id if stack else None
        stack.append(self)
        if tr.annotation is not None:
            self._annotation = tr.annotation(self.name)
            self._annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.t1 = time.perf_counter()
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        if exc_type is not None:
            self.attrs.setdefault("error", exc_type.__name__)
        stack = self.tracer._stack()
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:                 # tolerate mis-nested exits
            stack.remove(self)
        self.tracer._record_span(self)
        return False


class _NullSpan:
    """Shared no-op stand-in when tracing is disabled (zero allocation)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False

    def set(self, **attrs):
        return self


_NULL = _NullSpan()


class Tracer:
    """Thread-safe collector of spans and instant events.

    `runtime_ranges=True` opts the execution backends into per-stage
    observed-range / saturation / alpha-headroom telemetry
    (`repro.obs.runtime`); plain tracing never touches pixel data.
    Each span also enters `annotation` (`jax.profiler.TraceAnnotation`,
    None without JAX) of its name for its life.  A tracer is built only
    by `enable()` / `tracing()`, so JAX is looked up only when tracing is
    installed.
    """

    def __init__(self, runtime_ranges: bool = False):
        self.runtime_ranges = runtime_ranges
        self.annotation = _profiler_annotation()
        self.t0 = time.perf_counter()
        self.wall_t0 = time.time()
        self._ids = itertools.count(1)     # .__next__ is atomic under the GIL
        self._lock = threading.Lock()
        self._spans: List[Span] = []
        self._events: List[dict] = []
        self._tls = threading.local()

    # -- collection ---------------------------------------------------------
    def _stack(self) -> List[Span]:
        s = getattr(self._tls, "stack", None)
        if s is None:
            s = self._tls.stack = []
        return s

    def _record_span(self, sp: Span) -> None:
        with self._lock:
            self._spans.append(sp)

    def span(self, name: str, **attrs) -> Span:
        return Span(self, name, attrs)

    def current_span(self) -> Optional[Span]:
        stack = self._stack()
        return stack[-1] if stack else None

    def event(self, name: str, **attrs) -> None:
        parent = self.current_span()
        rec = {"kind": "event", "name": name,
               "ts": time.perf_counter(),
               "parent": parent.span_id if parent else None,
               "thread": threading.get_ident(), "attrs": attrs}
        with self._lock:
            self._events.append(rec)

    # -- queries (exporters + tests) ----------------------------------------
    def spans(self, name: Optional[str] = None) -> List[Span]:
        with self._lock:
            out = list(self._spans)
        if name is not None:
            out = [s for s in out if s.name == name]
        return sorted(out, key=lambda s: (s.t0, s.span_id))

    def events(self, name: Optional[str] = None) -> List[dict]:
        with self._lock:
            out = list(self._events)
        if name is not None:
            out = [e for e in out if e["name"] == name]
        return sorted(out, key=lambda e: e["ts"])

    def us(self, t: float) -> float:
        """Monotonic seconds -> microseconds since this tracer's origin."""
        return (t - self.t0) * 1e6


# ---------------------------------------------------------------------------
# module-level active tracer (the instrumentation surface)
# ---------------------------------------------------------------------------

_ACTIVE: Optional[Tracer] = None


def _profiler_annotation():
    """`jax.profiler.TraceAnnotation`, or None where JAX is not installed."""
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:
        return None
    return TraceAnnotation


def enable(runtime_ranges: bool = False) -> Tracer:
    """Install (and return) a fresh process-wide tracer."""
    global _ACTIVE
    _ACTIVE = Tracer(runtime_ranges=runtime_ranges)
    return _ACTIVE


def disable() -> Optional[Tracer]:
    """Remove the active tracer; returns it (for export)."""
    global _ACTIVE
    t, _ACTIVE = _ACTIVE, None
    return t


def active_tracer() -> Optional[Tracer]:
    return _ACTIVE


def is_enabled() -> bool:
    return _ACTIVE is not None


def runtime_ranges_enabled() -> bool:
    t = _ACTIVE
    return t is not None and t.runtime_ranges


class tracing:
    """`with tracing() as tr:` — scoped enable/restore (tests, harnesses)."""

    def __init__(self, runtime_ranges: bool = False):
        self.runtime_ranges = runtime_ranges
        self._prev: Optional[Tracer] = None

    def __enter__(self) -> Tracer:
        global _ACTIVE
        self._prev = _ACTIVE
        _ACTIVE = Tracer(runtime_ranges=self.runtime_ranges)
        return _ACTIVE

    def __exit__(self, *a):
        global _ACTIVE
        _ACTIVE = self._prev
        return False


def span(name: str, **attrs):
    """Span on the active tracer, or a shared no-op when tracing is off.

    The disabled path is one global load + `is None` test — cheap enough
    for per-stage instrumentation on production hot loops.
    """
    t = _ACTIVE
    if t is None:
        return _NULL
    return t.span(name, **attrs)


def event(name: str, **attrs) -> None:
    t = _ACTIVE
    if t is not None:
        t.event(name, **attrs)

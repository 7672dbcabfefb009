"""`repro.obs` — unified tracing/metrics for analysis, solver, and backends.

Quick start::

    from repro import obs

    with obs.tracing(runtime_ranges=True) as tr:
        plan = run_plan(pipe, ["interval", "smt"])
        run_fixed(pipe, imgs, plan, backend="lowered")
    obs.write_chrome_trace(tr, "trace.json")     # perfetto-loadable
    obs.write_jsonl(tr, "trace.jsonl")           # repro.obs.report input

Submodules: `tracer` (spans/counters core), `exporters` (JSONL + Chrome
trace-event JSON), `runtime` (per-stage range/saturation/headroom
telemetry), `report` (per-stage summary tables, also a CLI:
``python -m repro.obs.report trace.jsonl``).  See docs/observability.md.
"""
from .tracer import (            # noqa: F401
    CounterGroup, Span, Tracer, active_tracer, all_counters, disable,
    enable, event, is_enabled, runtime_ranges_enabled, span,
    tracing,
)
from .exporters import (         # noqa: F401
    load_jsonl, to_chrome_trace, to_jsonl_records, write_chrome_trace,
    write_jsonl,
)
from .warnonce import reset_warn_once, warn_once   # noqa: F401
from . import runtime            # noqa: F401

__all__ = [
    "CounterGroup", "Span", "Tracer", "active_tracer", "all_counters",
    "disable", "enable", "event", "is_enabled", "load_jsonl",
    "reset_warn_once", "runtime", "runtime_ranges_enabled", "span",
    "to_chrome_trace", "to_jsonl_records", "tracing", "warn_once",
    "write_chrome_trace", "write_jsonl",
]

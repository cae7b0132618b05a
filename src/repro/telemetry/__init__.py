"""repro.telemetry — unified tracing, counters, and trace export.

One subsystem answers "where did the step's time and bytes go" across
the balancer, the adaptive FEM loop, and the serving engine:

* ``Tracer`` — nestable spans (``span(name, **attrs)`` context manager,
  ``@traced`` decorator) with an explicit ``block=`` option that calls
  ``jax.block_until_ready`` on designated outputs *before* the clock
  stops, so timings measure device work rather than async dispatch.
* ``Counter``/``Gauge`` registry (``tracer.metrics``) for the paper's
  quality metrics — ``imbalance``, ``cut``, ``migration_total_v``,
  ``migration_retained``, ``comm_halo_bytes``, ``comm_psum_bytes``,
  ``moved_kv_bytes`` — with per-step ``tick`` snapshots.
* Exporters: ``export_chrome_trace`` (Perfetto-loadable JSON) and
  ``export_jsonl`` (line-delimited event log), both schema-validated.
* ``NullTracer`` — the process default; instrumented hot paths cost
  nothing when telemetry is off.
* Under a real tracer every span also lands on the JAX profiler's host
  plane (``jax.profiler.TraceAnnotation``, the device trace's clock), and
  ``tracing()`` records each program JAX traces, lowers, compiles or
  loads from its compile cache as a ``JitEvent`` (``tr.jit_events``,
  totals in ``tr.jit_summary()``), attributed to the innermost span.

Usage::

    from repro import telemetry
    with telemetry.tracing() as tr:
        session.run()                      # library spans land in tr
    telemetry.export_chrome_trace(tr, "trace.json")
    telemetry.export_jsonl(tr, "counters.jsonl")
    print(tr.metrics.summary()["totals"])

``python -m repro.telemetry.smoke --out DIR`` runs an adaptive session
plus a serve trace under one tracer and writes/validates both artifacts.
"""
from .metrics import (Counter, Gauge, MetricsRegistry,  # noqa: F401
                      NullMetricsRegistry)
from .tracer import (JIT_KINDS, JitEvent, NullTracer, Span,  # noqa: F401
                     SpanEvent, Tracer, get_tracer, set_tracer, span,
                     stopwatch, traced, tracing)
from .export import (SchemaError, chrome_trace,  # noqa: F401
                     export_chrome_trace, export_jsonl, jsonl_events,
                     validate_chrome_trace, validate_jsonl)

__all__ = [
    "Counter", "Gauge", "MetricsRegistry", "NullMetricsRegistry",
    "JIT_KINDS", "JitEvent", "NullTracer", "Span", "SpanEvent", "Tracer",
    "get_tracer", "set_tracer", "span", "stopwatch", "traced", "tracing",
    "SchemaError", "chrome_trace", "export_chrome_trace", "export_jsonl",
    "jsonl_events", "validate_chrome_trace", "validate_jsonl",
    "capture",
]


def capture(fn, *args, **kwargs):
    """Run ``fn(*args, **kwargs)`` under a fresh tracer; return
    ``(result, summary)`` where ``summary`` is the metrics summary dict.

    The one-liner benchmarks use to attach counter totals to their JSON
    records without managing tracer scope themselves."""
    with tracing() as tr:
        result = fn(*args, **kwargs)
    return result, tr.metrics.summary()

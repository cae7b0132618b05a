"""Span tracer: nestable timed regions with async-dispatch-safe clocks.

JAX dispatch is asynchronous: ``fn(x)`` returns as soon as the work is
*enqueued*, so a naive ``perf_counter`` pair around a jitted call times
the dispatch, not the device work.  Every span here therefore carries an
explicit ``block`` option: outputs designated via ``Span.block_on`` (or
the return value, for the ``@traced`` decorator) are passed through
``jax.block_until_ready`` *before* the clock stops, so a span's duration
covers the device work it launched.

Two entry points with different off-switch semantics:

* ``Tracer.span`` / module-level ``repro.telemetry.span`` -- records a
  ``SpanEvent`` into the active tracer.  When the active tracer is the
  ``NullTracer`` (telemetry off) this returns a shared no-op handle:
  no clock reads, no blocking, no allocation -- instrumented hot paths
  cost nothing.
* ``stopwatch`` -- for call sites whose *callers* consume the duration
  (``Balancer.balance_timed``, the adaptive session's ``StepStats``
  timings): always times and always honors ``block``, recording into
  the tracer only when one is active.  Timing correctness is therefore
  independent of whether telemetry is on.

While a real ``Tracer`` is active, every span (``stopwatch`` spans
included) also opens a ``jax.profiler.TraceAnnotation`` of its own name,
so program spans sit on the host plane of a profiler trace, on the
device's clock; and ``tracing()`` listens to JAX's compile events
(``jax.monitoring``), recording each top-level trace, lowering, compile
and persistent-cache load as a ``JitEvent`` attributed to the innermost
open span.

Single-threaded by design (the control planes it instruments are); the
span stack is per-tracer, depth/nesting come from ``with`` discipline.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Dict, List, Optional

import jax

from .metrics import MetricsRegistry, NullMetricsRegistry

__all__ = ["JIT_KINDS", "JitEvent", "NullTracer", "Span", "SpanEvent",
           "Tracer", "get_tracer", "set_tracer", "span", "stopwatch",
           "traced", "tracing"]

# jax.monitoring duration events -> JitEvent kinds
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
JIT_KINDS = ("jit_traces", "jit_lowerings", "jit_compiles", "jit_cache_hits")


@dataclasses.dataclass
class SpanEvent:
    """One completed span, times in microseconds since the tracer epoch;
    ``parent`` is the name of the span that enclosed it (None at top)."""
    name: str
    ts_us: float
    dur_us: float
    depth: int
    attrs: Dict[str, Any]
    parent: Optional[str] = None


@dataclasses.dataclass
class JitEvent:
    """One program JAX traced, lowered, compiled or loaded from its
    persistent compile cache while a tracer was active.

    ``kind`` is one of ``JIT_KINDS`` (``jit_compiles`` counts programs
    compiled afresh, ``jit_cache_hits`` those loaded from the cache);
    ``program`` is the name the device trace gives the program
    (``jit_<function>``); ``span`` is the innermost open span and
    ``step`` the nearest ``step`` attribute on the span stack."""
    kind: str
    program: str
    dur_s: float
    span: Optional[str]
    step: Optional[int]


def _program_name(fun_name: str) -> str:
    """``solve`` / ``jit(solve)`` -> ``jit_solve``, the name of the
    program in the device trace."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        fun_name = fun_name[4:-1]
    return "jit_" + fun_name


class Span:
    """Context-manager handle of one timed region.

    ``block_on(x)`` designates ``x`` (any pytree) as an output the span
    must wait for; on exit, designated outputs go through
    ``jax.block_until_ready`` before the clock stops iff the span was
    created with ``block=True``.  ``set(**attrs)`` attaches attributes;
    ``dur_s`` is available after exit.
    """

    __slots__ = ("_tracer", "name", "attrs", "_block", "_outs",
                 "_t0", "_t1", "depth", "parent", "_annotation")

    def __init__(self, tracer: Optional["Tracer"], name: str, block: bool,
                 attrs: Dict[str, Any]):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs
        self._block = block
        self._outs: List[Any] = []
        self._t0 = self._t1 = 0.0
        self.depth = 0
        self.parent: Optional[str] = None
        self._annotation = None

    def block_on(self, value):
        """Designate ``value`` as an output to sync on before the clock
        stops (returns it unchanged, so it composes inline)."""
        self._outs.append(value)
        return value

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    def __enter__(self) -> "Span":
        if self._tracer is not None:
            self.depth = self._tracer._enter(self)
            # the same span on the profiler's host plane (device clock)
            self._annotation = jax.profiler.TraceAnnotation(self.name)
            self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._block and self._outs:
            # the whole point: device work launched inside the span is
            # billed to the span, not to whoever syncs next
            jax.block_until_ready(self._outs)
        self._t1 = time.perf_counter()
        if self._tracer is not None:
            self._annotation.__exit__(exc_type, exc, tb)
            self._annotation = None
            self._tracer._exit(self)
        return False

    @property
    def dur_s(self) -> float:
        """Blocking wall-clock duration in seconds (after exit)."""
        return self._t1 - self._t0


class _NullSpan:
    """Shared no-op span handle: the telemetry-off fast path."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False

    def block_on(self, value):
        return value

    def set(self, **attrs) -> "_NullSpan":
        return self

    @property
    def dur_s(self) -> float:
        return 0.0


_NULL_SPAN = _NullSpan()


class NullTracer:
    """Telemetry off: same surface as ``Tracer``, does nothing.

    ``span`` hands back one shared handle (no allocation, no clock read,
    no blocking); ``metrics`` swallows updates.  This is the process
    default so instrumented code never pays for unused telemetry.
    """

    enabled = False

    def __init__(self):
        self.metrics = NullMetricsRegistry()
        self.events: List[SpanEvent] = []

    def span(self, name: str, *, block: bool = False, **attrs) -> _NullSpan:
        return _NULL_SPAN

    def tick(self, step: int, **attrs) -> None:
        pass

    def traced(self, name: Optional[str] = None, *, block: bool = False,
               **attrs) -> Callable:
        return traced(name, block=block, **attrs)


class Tracer:
    """Collects ``SpanEvent``s and a ``MetricsRegistry`` for one run.

    Times are relative to the tracer's construction (``perf_counter``
    epoch), in microseconds -- the unit Chrome-trace wants.  Spans nest
    via the ``with`` stack; ``tick(step)`` snapshots every registered
    counter/gauge with a timestamp so exporters can emit per-step
    counter tracks.  ``jit_events`` collects the ``JitEvent``s of every
    ``tracing()`` scope the tracer was installed by; ``jit_summary()``
    totals them per kind and program.
    """

    enabled = True

    def __init__(self):
        self._epoch = time.perf_counter()
        self.events: List[SpanEvent] = []
        self._stack: List[Span] = []
        self.metrics = MetricsRegistry()
        self.jit_events: List[JitEvent] = []
        self._listening = 0             # nested tracing() scopes
        self._trace_depth = 0           # open jaxpr traces (nested jits)
        self._compile_hit = False       # cache hit inside the open compile

    # -- span lifecycle (driven by Span.__enter__/__exit__) -----------------
    def _enter(self, sp: Span) -> int:
        depth = len(self._stack)
        sp.parent = self._stack[-1].name if self._stack else None
        self._stack.append(sp)
        return depth

    def _exit(self, sp: Span) -> None:
        if self._stack and self._stack[-1] is sp:
            self._stack.pop()
        self.events.append(SpanEvent(
            name=sp.name,
            ts_us=(sp._t0 - self._epoch) * 1e6,
            dur_us=sp.dur_s * 1e6,
            depth=sp.depth,
            attrs=sp.attrs,
            parent=sp.parent))

    # -- jax.monitoring listeners (registered by tracing()) -----------------
    def _listen(self) -> None:
        self._listening += 1
        if self._listening == 1:
            self._trace_depth = 0
            jax.monitoring.register_scalar_listener(self._on_start)
            jax.monitoring.register_event_duration_secs_listener(
                self._on_duration)
            jax.monitoring.register_event_listener(self._on_event)

    def _unlisten(self) -> None:
        self._listening -= 1
        if self._listening == 0:
            jax.monitoring.unregister_scalar_listener(self._on_start)
            jax.monitoring.unregister_event_duration_listener(
                self._on_duration)
            jax.monitoring.unregister_event_listener(self._on_event)

    def _on_start(self, event: str, value, **kw) -> None:
        # JAX records each timed compile phase's start as a scalar
        if event == _TRACE_EVENT:
            self._trace_depth += 1
        elif event == _COMPILE_EVENT:
            self._compile_hit = False

    def _on_event(self, event: str, **kw) -> None:
        if event == _CACHE_HIT_EVENT:
            self._compile_hit = True

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event == _TRACE_EVENT:
            self._trace_depth = max(self._trace_depth - 1, 0)
            if self._trace_depth > 0:
                return        # a jitted helper traced inside a program
            kind = "jit_traces"
        elif event == _LOWER_EVENT:
            kind = "jit_lowerings"
        elif event == _COMPILE_EVENT:
            kind = "jit_cache_hits" if self._compile_hit else "jit_compiles"
            self._compile_hit = False
        else:
            return
        step = next((sp.attrs["step"] for sp in reversed(self._stack)
                     if "step" in sp.attrs), None)
        self.jit_events.append(JitEvent(
            kind=kind, program=_program_name(str(kw.get("fun_name", "?"))),
            dur_s=float(duration),
            span=self._stack[-1].name if self._stack else None, step=step))

    # -- public API ---------------------------------------------------------
    def span(self, name: str, *, block: bool = False, **attrs) -> Span:
        return Span(self, name, block, attrs)

    def now_us(self) -> float:
        return (time.perf_counter() - self._epoch) * 1e6

    def tick(self, step: int, **attrs) -> None:
        """Per-step counter snapshot (timestamped for counter tracks)."""
        self.metrics.tick(step, ts_us=self.now_us(), **attrs)

    def traced(self, name: Optional[str] = None, *, block: bool = False,
               **attrs) -> Callable:
        """Decorator twin of ``span`` bound to THIS tracer."""
        return traced(name, block=block, tracer=self, **attrs)

    def jit_summary(self) -> Dict[str, Dict[str, Dict[str, float]]]:
        """``{kind: {program: {"n": count, "s": seconds}}}`` over
        ``jit_events``, every kind of ``JIT_KINDS`` present."""
        out: Dict[str, Dict[str, Dict[str, float]]] = {
            k: {} for k in JIT_KINDS}
        for e in self.jit_events:
            row = out[e.kind].setdefault(e.program, {"n": 0, "s": 0.0})
            row["n"] += 1
            row["s"] += e.dur_s
        return out


# ---------------------------------------------------------------------------
# Active-tracer plumbing
# ---------------------------------------------------------------------------

_ACTIVE: Any = NullTracer()


def get_tracer():
    """The process-wide active tracer (a ``NullTracer`` unless installed)."""
    return _ACTIVE


def set_tracer(tracer):
    """Install ``tracer`` as the active one; returns the previous."""
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = tracer if tracer is not None else NullTracer()
    return prev


class tracing:
    """``with tracing() as tr:`` -- install a (new) tracer for a scope and
    record JAX's compile events into it until the scope exits."""

    def __init__(self, tracer: Optional[Tracer] = None):
        self.tracer = tracer if tracer is not None else Tracer()
        self._prev = None

    def __enter__(self) -> Tracer:
        self._prev = set_tracer(self.tracer)
        if self.tracer.enabled:
            self.tracer._listen()
        return self.tracer

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self.tracer.enabled:
            self.tracer._unlisten()
        set_tracer(self._prev)
        return False


def span(name: str, *, block: bool = False, **attrs):
    """Span on the active tracer (shared no-op handle when telemetry is
    off -- safe in hot paths)."""
    return _ACTIVE.span(name, block=block, **attrs)


def stopwatch(name: str, *, block: bool = True, tracer=None, **attrs) -> Span:
    """Always-timing span: records into ``tracer`` (default: the active
    one) when enabled, but times -- and honors ``block`` -- regardless.

    Use where the caller consumes ``dur_s`` (``balance_timed``,
    ``StepStats`` stage timings): the measurement contract must not
    depend on whether telemetry is on.
    """
    tr = tracer if tracer is not None else _ACTIVE
    return Span(tr if tr.enabled else None, name, block, attrs)


def traced(name: Optional[str] = None, *, block: bool = False, tracer=None,
           **attrs) -> Callable:
    """Decorator: wrap a function in a span on the active tracer.

    ``block=True`` designates the return value, so the span's clock stops
    only after the returned arrays are device-ready.  The tracer is
    resolved per *call* (late binding), so decorated library code follows
    ``tracing()`` scopes."""

    def deco(fn: Callable) -> Callable:
        label = name or fn.__qualname__

        @functools.wraps(fn)
        def wrapper(*args, **kw):
            tr = tracer if tracer is not None else _ACTIVE
            with tr.span(label, block=block, **attrs) as sp:
                out = fn(*args, **kw)
                if block:
                    sp.block_on(out)
            return out
        return wrapper
    return deco

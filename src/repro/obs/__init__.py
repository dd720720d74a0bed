"""repro.obs — unified metrics/span telemetry for plan → exchange →
kernel → serve.

One process-wide :class:`Obs` instance (``default_obs()``) owns a
:class:`~repro.obs.metrics.MetricsRegistry` and a
:class:`~repro.obs.spans.SpanRecorder`.  Everything is **off by
default**: until ``enable()`` is called, ``span()`` returns the shared
:data:`~repro.obs.spans.NULL_SPAN` and every counter mutator early-outs
on one boolean — instrumented hot paths (decode steps, cache lookups)
cost one attribute read + one branch.

Usage::

    from repro.obs import default_obs

    obs = default_obs()
    obs.enable()
    ...  # run instrumented code: solves, decode steps, resizes
    print(obs.report())                  # rollup table
    obs.export_perfetto("trace.json")    # load in ui.perfetto.dev

**What enabling adds.**  Every span is also a JAX profiler annotation
(see :mod:`repro.obs.spans`), so a ``jax.profiler`` trace shows the
program's spans beside the device's operations.  Two counters, labelled
by JAX's compile event and function name, say which function was traced,
lowered or compiled, and for how long: ``jax/compiles`` and
``jax/compile_seconds`` (``backend_compile`` includes a load from the
persistent compile cache).  Python's garbage collections become
``py/gc`` spans with their generation and the objects they collected, so
a host stall that a collection caused is named in the trace.

**TraceRecorder bridge** (the online-calibration pipe): attach a
``repro.profile.TraceRecorder`` via ``enable(tracer=...)`` and every
closing span whose attributes carry ``plan=<CommPlan>`` and
``pure_exchange=True`` is forwarded to ``tracer.record_plan`` — the same
samples ``fit_trace`` consumes.  ``ServeEngine(observe=True)`` uses
exactly this path to refit ``MachineParams`` from production decode
steps (see ``docs/OPERATIONS.md`` § Observability).

The blessed wall clock is :func:`now` (``time.perf_counter``); rule R4
of ``tools/lint_repro.py`` keeps ad-hoc ``perf_counter`` calls out of
``src/repro`` so all timing flows through here or ``repro.profile``.
"""
from __future__ import annotations

import gc
import weakref
from typing import Dict, List, Optional

from .export import report as _report
from .export import save_perfetto, to_perfetto
from .metrics import (  # noqa: F401  (re-exported API)
    Counter,
    DEFAULT_TIME_BUCKETS,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from .spans import (  # noqa: F401
    DEFAULT_RING_SIZE,
    NULL_SPAN,
    Span,
    SpanEvent,
    SpanRecorder,
    now,
)

__all__ = [
    "Obs", "default_obs", "now", "NULL_SPAN", "SpanEvent",
    "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "DEFAULT_TIME_BUCKETS", "DEFAULT_RING_SIZE",
]

#: JAX's compile-time monitoring events, by the label they are counted under
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jaxpr_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jaxpr_to_mlir",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}


class Obs:
    """Metrics registry + span ring + optional TraceRecorder bridge."""

    def __init__(self, ring_size: int = DEFAULT_RING_SIZE):
        self._enabled_ref: List[bool] = [False]
        self.metrics = MetricsRegistry(self._enabled_ref)
        self.spans = SpanRecorder(ring_size=ring_size)
        self.spans.on_close = self._on_span_close
        self._tracer = None     # Optional[repro.profile.TraceRecorder]
        self._gc_span = None    # the open py/gc span, between its callbacks

    # ------------------------------------------------------------ state
    @property
    def enabled(self) -> bool:
        return self._enabled_ref[0]

    @property
    def tracer(self):
        """The attached TraceRecorder, or None (always None when
        disabled — callers may use this to gate bridge-only work)."""
        return self._tracer if self.enabled else None

    def enable(self, tracer=None, ring_size: Optional[int] = None) -> "Obs":
        if ring_size is not None and ring_size != self.spans.ring.maxlen:
            self.spans = SpanRecorder(ring_size=ring_size)
            self.spans.on_close = self._on_span_close
        if tracer is not None:
            self._tracer = tracer
        _hook(self)
        self._enabled_ref[0] = True
        return self

    def disable(self) -> "Obs":
        self._enabled_ref[0] = False
        return self

    def attach_tracer(self, tracer) -> "Obs":
        self._tracer = tracer
        return self

    def reset(self) -> "Obs":
        """Drop all recorded data (registry declarations survive)."""
        self.metrics.clear()
        self.spans.clear()
        return self

    # ------------------------------------------------------- recording
    def span(self, name: str, **attrs):
        """Open a span; ``with obs.span("amg/solve", levels=3): ...``.
        Disabled fast path: returns the shared NULL_SPAN singleton."""
        if not self._enabled_ref[0]:
            return NULL_SPAN
        return self.spans.span(name, **attrs)

    def event(self, name: str, **attrs) -> None:
        """Record an instant event (replan, resize, refit, ...)."""
        if self._enabled_ref[0]:
            self.spans.event(name, **attrs)

    def counter(self, name: str, help: str = "") -> Counter:
        return self.metrics.counter(name, help)

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self.metrics.gauge(name, help)

    def histogram(self, name: str, help: str = "", **kw) -> Histogram:
        return self.metrics.histogram(name, help, **kw)

    # ------------------------------------------------ JAX and gc hooks
    def _on_jax_event(self, event: str, duration: float, **kw) -> None:
        label = COMPILE_EVENTS.get(event)
        if label is None or not self._enabled_ref[0]:
            return
        fun = kw.get("fun_name", "")
        self.metrics.counter("jax/compiles").inc(event=label, fun=fun)
        self.metrics.counter("jax/compile_seconds").inc(
            duration, event=label, fun=fun)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            if self._enabled_ref[0]:
                self._gc_span = self.spans.span(
                    "py/gc", generation=info["generation"])
                self._gc_span.__enter__()
        elif self._gc_span is not None:
            sp, self._gc_span = self._gc_span, None
            sp.set(collected=info["collected"])
            sp.__exit__(None, None, None)

    # --------------------------------------------------------- bridge
    def _on_span_close(self, ev: SpanEvent) -> None:
        # pure-exchange spans feed the calibration trace: same samples
        # fit_trace consumes, so production steps calibrate like benches.
        if self._tracer is not None and ev.attrs.get("pure_exchange"):
            plan = ev.attrs.get("plan")
            if plan is not None:
                self._tracer.record_plan(
                    plan,
                    float(ev.attrs.get("seconds", ev.duration)),
                    label=ev.name,
                    pure_exchange=True,
                    fingerprint=ev.attrs.get("fingerprint"),
                )
        # top-level span close = natural counter-track sample point (a
        # collection is no program boundary, and comes too often)
        if ev.depth == 0 and ev.name != "py/gc":
            for name, c in sorted(self.metrics._counters.items()):
                if c._series:
                    self.spans.counter_sample(name, c.total())

    # --------------------------------------------------------- export
    def snapshot(self) -> Dict:
        return self.metrics.snapshot()

    def delta(self, before: Dict) -> Dict:
        return MetricsRegistry.delta(before, self.metrics.snapshot())

    def report(self) -> str:
        return _report(self.spans.events(), self.metrics.snapshot())

    def span_tree(self) -> str:
        return self.spans.tree()

    def to_perfetto(self, process_name: str = "repro") -> Dict:
        return to_perfetto(self.spans.events(), process_name=process_name)

    def export_perfetto(self, path, process_name: str = "repro") -> None:
        save_perfetto(self.spans.events(), path, process_name=process_name)


# Every instance ever enabled hears JAX's compile events and Python's
# collections through one listener and one callback, registered once; an
# instance no longer referenced drops out of the set.
_HOOKED: "weakref.WeakSet[Obs]" = weakref.WeakSet()


def _hook(obs: Obs) -> None:
    if _on_gc not in gc.callbacks:
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_on_jax_event)
        gc.callbacks.append(_on_gc)
    _HOOKED.add(obs)


def _on_jax_event(event: str, duration: float, **kw) -> None:
    for obs in list(_HOOKED):
        obs._on_jax_event(event, duration, **kw)


def _on_gc(phase: str, info: dict) -> None:
    for obs in list(_HOOKED):
        obs._on_gc(phase, info)


_DEFAULT: Obs = Obs()


def default_obs() -> Obs:
    """The process-wide instance every instrumented module reports to."""
    return _DEFAULT

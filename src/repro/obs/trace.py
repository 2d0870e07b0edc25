"""Lightweight span tracer with a Chrome/Perfetto trace-event exporter.

The observability layer's data plane: every layer of the read/write stack
(``FileReader``/``DatasetReader``/``DatasetWriter`` at the top, the
``IOScheduler``'s open -> coalesce -> classify -> dispatch -> drain pipeline,
``FlushPolicy`` drains, the kernel decode route) opens spans on the tracer
threaded through the :class:`~repro.store.IOScheduler`.  Three event kinds:

* **spans** (``ph: "X"`` complete events) — timed regions, context-manager
  API, nestable;
* **instants** (``ph: "i"``) — structured point events: admission-policy
  flips, flush-on-evict writes, and the *pallas fallback-reason* telemetry
  (a ``pallas_fallback`` event whenever ``decode="pallas"`` silently routes
  to numpy, with the reason — float values, variable-width leaf, >31-bit
  packing, opaque codec — in ``args``);
* **counters** (``ph: "C"``) — counter tracks sampled at batch close: queue
  depth, per-tier hit rate, resident/dirty bytes.

Zero-cost when disabled: the default tracer is the module singleton
:data:`NULL_TRACER` (``enabled=False``); its ``span()`` returns the shared
:data:`NULL_SPAN` singleton, so a disabled trace allocates **no span
objects** and appends nothing.  Instrumented code never needs an ``if``:
``with tracer.span(...)`` is safe and free either way.  The hard contract
(tested): logical IOPS/bytes and every priced time are bit-identical whether
tracing is on or off — the tracer observes the pipeline, it never steers it.

Two sinks.  The default, ``sink="memory"``, keeps events in memory:
timestamps are host-wall microseconds since tracer construction
(``time.perf_counter``), and :meth:`Tracer.export` writes the standard
``{"traceEvents": [...]}`` JSON object form — open it at
https://ui.perfetto.dev or ``chrome://tracing``.  The modelled device time
lives in span ``args`` where the instrumentation site provides it.
``sink="profiler"`` writes each span as a ``jax.profiler.TraceAnnotation``
of its name (no args), so program spans land on the profiler's clock beside
the device's operations while a ``jax.profiler`` trace is running; it keeps
no events, and its counters (``metrics``) still count.
"""

from __future__ import annotations

import json
import time
from typing import Dict, List, Optional

from .metrics import MetricsRegistry

__all__ = ["Tracer", "NullTracer", "NULL_TRACER", "NULL_SPAN", "SINKS"]


class _NullSpan:
    """Shared no-op span: entering/exiting does nothing, setting args is
    swallowed.  A module singleton — disabled tracing allocates nothing."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **args) -> None:
        pass


NULL_SPAN = _NullSpan()


class _Span:
    """One open span; appended to the tracer's event list on exit."""

    __slots__ = ("_tracer", "name", "cat", "args", "_ts", "tid")

    def __init__(self, tracer: "Tracer", name: str, cat: str, args: Dict,
                 tid: Optional[int] = None):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self.tid = tid
        self._ts = tracer._now_us()

    def set(self, **args) -> None:
        """Attach/overwrite span args from inside the region."""
        self.args.update(args)

    def __enter__(self) -> "_Span":
        return self

    def __exit__(self, *exc) -> bool:
        tr = self._tracer
        tr.events.append({
            "name": self.name, "cat": self.cat, "ph": "X",
            "ts": self._ts, "dur": tr._now_us() - self._ts,
            "pid": tr.pid,
            "tid": self.tid if self.tid is not None else tr.tid,
            "args": self.args,
        })
        return False


class _ProfilerSpan:
    """One open span of the profiler sink: a ``TraceAnnotation`` of the
    span's name that swallows ``set()`` calls."""

    __slots__ = ("_ann",)

    def __init__(self, annotation, name: str):
        self._ann = annotation(name)

    def __enter__(self) -> "_ProfilerSpan":
        self._ann.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        self._ann.__exit__(*exc)
        return False

    def set(self, **args) -> None:
        pass


SINKS = ("memory", "profiler")


class Tracer:
    """Collects Chrome-trace events; ``enabled=False`` is a strict no-op.

    One tracer per IO path: pass it to ``FileReader``/``DatasetReader``/
    ``DatasetWriter`` (or directly to ``IOScheduler``) and every layer below
    shares it.  ``metrics`` is a :class:`~repro.obs.metrics.MetricsRegistry`
    fed alongside the event list (fallback-reason counters, span-less
    counts) so tests can query aggregates without parsing the trace.
    ``sink`` picks where spans go (see the module docstring): ``"memory"``
    or ``"profiler"``.
    """

    def __init__(self, enabled: bool = True, pid: int = 1, tid: int = 1,
                 sink: str = "memory"):
        if sink not in SINKS:
            raise ValueError(f"sink must be one of {SINKS}, got {sink!r}")
        self.enabled = bool(enabled)
        self.sink = sink
        self.pid = pid
        self.tid = tid
        self.events: List[Dict] = []
        self.metrics = MetricsRegistry()
        self._tracks: Dict[str, int] = {}
        self._t0 = time.perf_counter()
        self._annotation = None
        if sink == "profiler" and self.enabled:
            import jax  # lazy: obs imports no accelerator at module level

            self._annotation = jax.profiler.TraceAnnotation

    def _now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    # -- event API -----------------------------------------------------------
    def span(self, name: str, cat: str = "io", tid: Optional[int] = None,
             **args):
        """Open a timed span (context manager).  ``tid`` overrides the
        tracer's default track — the scheduler uses one track per request so
        concurrent takers render as separate Perfetto lanes.  Returns the
        shared :data:`NULL_SPAN` when disabled — no allocation, no
        recording.  The profiler sink records the name only."""
        if not self.enabled:
            return NULL_SPAN
        if self._annotation is not None:
            return _ProfilerSpan(self._annotation, name)
        return _Span(self, name, cat, args, tid=tid)

    def track(self, key: Optional[str]) -> int:
        """Intern ``key`` as a stable per-request track id (tid).

        The first time a key is seen a Chrome ``thread_name`` metadata event
        is emitted so Perfetto labels the lane with the request id; repeat
        calls return the same tid.  ``None`` (or disabled, or the profiler
        sink) falls back to the tracer's default track."""
        if not self.enabled or key is None or self._annotation is not None:
            return self.tid
        tid = self._tracks.get(key)
        if tid is None:
            tid = self.tid + 1 + len(self._tracks)
            self._tracks[key] = tid
            self.events.append({
                "name": "thread_name", "ph": "M", "ts": self._now_us(),
                "pid": self.pid, "tid": tid, "args": {"name": str(key)},
            })
        return tid

    def instant(self, name: str, cat: str = "event", **args) -> None:
        """A structured point event (thread-scoped instant); the profiler
        sink drops it."""
        if not self.enabled or self._annotation is not None:
            return
        self.events.append({
            "name": name, "cat": cat, "ph": "i", "s": "t",
            "ts": self._now_us(), "pid": self.pid, "tid": self.tid,
            "args": args,
        })

    def counter(self, name: str, values: Dict[str, float],
                cat: str = "counter", ts: Optional[float] = None) -> None:
        """One sample on a counter track (Perfetto renders each key as a
        series under the track ``name``).  ``ts`` overrides the host-clock
        timestamp with an explicit microsecond value — the metrics plane
        uses this to replay virtual-clock gauge series as counter tracks
        (``MetricsPlane.to_trace``) so they line up with the simulated
        timeline rather than orchestration wall time.  The profiler sink
        drops it."""
        if not self.enabled or self._annotation is not None:
            return
        self.events.append({
            "name": name, "cat": cat, "ph": "C",
            "ts": self._now_us() if ts is None else float(ts),
            "pid": self.pid, "tid": self.tid,
            "args": dict(values),
        })

    def fallback(self, encoding: str, reason: str, **args) -> None:
        """The structured *fallback-reason* event: ``decode="pallas"`` routed
        (part of) a decode to numpy.  ``encoding`` is the route
        (``miniblock``/``fullzip``), ``reason`` a stable slug
        (``float-values``, ``variable-width-leaf``, ``>31-bit``,
        ``opaque-codec:<name>``, ...).  Counted in ``metrics`` under
        ``decode.fallback.<encoding>.<reason>`` for test/CI queries (by
        either sink)."""
        if not self.enabled:
            return
        self.metrics.counter(f"decode.fallback.{encoding}.{reason}").inc()
        self.instant("pallas_fallback", cat="decode",
                     encoding=encoding, reason=reason, **args)

    def count(self, name: str, n: int = 1) -> None:
        """Add ``n`` to the ``metrics`` counter ``name`` (either sink);
        nothing when disabled."""
        if self.enabled:
            self.metrics.counter(name).inc(n)

    # -- export --------------------------------------------------------------
    def trace_events(self) -> Dict:
        """The Chrome trace-event JSON object form."""
        return {"traceEvents": list(self.events), "displayTimeUnit": "ms"}

    def export(self, path: str) -> int:
        """Write the trace to ``path``; returns the number of events.
        ``allow_nan=False`` — a NaN in any event is a bug, not an artifact
        feature (see the bench NaN-leak fix)."""
        with open(path, "w") as f:
            json.dump(self.trace_events(), f, allow_nan=False)
        return len(self.events)

    def reset(self) -> None:
        self.events = []
        self.metrics = MetricsRegistry()
        self._tracks = {}
        self._t0 = time.perf_counter()


class NullTracer(Tracer):
    """The always-disabled tracer; :data:`NULL_TRACER` is the one instance
    instrumented objects default to."""

    def __init__(self):
        super().__init__(enabled=False)


NULL_TRACER = NullTracer()

"""Observability: unified metrics + structured event tracing.

The telemetry subsystem every layer records through (docs/
observability.md): trainers (``StepTimer`` phases as spans, loss/
timing gauges), serving (queue depth, rejects, deadline misses, lane
occupancy, speculative accept rate, request latency histograms),
resilience (chaos faults, Supervisor attempts/backoff, checkpoint
durations), and the data path (prefetch occupancy, h2d bytes).

Usage::

    from distkeras_tpu import obs

    with obs.session(trace_path="run.jsonl") as sess:
        trainer.train(tokens)
        engine.step()
    print(sess.registry.render_text())          # Prometheus text
    # python scripts/obs_report.py run.jsonl    # offline run report

**Disabled is the default and costs (almost) nothing.**  Every hook in
the production code calls a module function here (``obs.count`` /
``obs.gauge`` / ``obs.observe`` / ``obs.event`` / ``obs.span``) whose
first statement is ``if _ACTIVE is None: return`` — one module-attr
load and an ``is`` check, the same idiom as ``resilience.chaos.probe``.
No registry, no trace file, no background thread exists until
:func:`enable` runs.  Nothing here ever reaches inside a jitted
program (no host callbacks — pinned by the graph lint's
``host-callback`` rule over the real step programs, tests/test_obs.py),
so enabling telemetry cannot change compile counts or comm budgets.

One session is active at a time (like a chaos ``FaultPlan``: a
telemetry stream must be read off one sink, not two interleaved ones).
"""

from __future__ import annotations

import atexit
import contextlib
import sys

from distkeras_tpu.obs.metrics import (Counter, Gauge, Histogram,
                                        MetricsRegistry,
                                        DEFAULT_TIME_BUCKETS,
                                        percentile_from_buckets)
from distkeras_tpu.obs.trace import EventTrace, read_trace
from distkeras_tpu.obs.slo import SloEngine, SloRule
from distkeras_tpu.obs.live import HeartbeatHealth, TelemetryServer

_ACTIVE = None


class ObsSession:
    """One enabled telemetry window: a :class:`MetricsRegistry` plus an
    optional :class:`EventTrace` (``trace_path=None`` = metrics only).

    On close the registry snapshot is appended to the trace as its
    final ``metrics`` record, so the JSONL file alone is enough for
    ``scripts/obs_report.py`` (latency percentiles included).  Trace
    records are buffered in memory and written at close, at
    ``sess.trace.flush()`` and past a bound of size or age
    (:class:`EventTrace`); in a process that has imported jax every
    span is also a ``jax.profiler.TraceAnnotation`` of the same name.

    **Live telemetry plane** (round 11): ``serve_port=`` starts a
    :class:`~distkeras_tpu.obs.live.TelemetryServer` on the session's
    registry (``/metrics``, ``/snapshot.json``, ``/healthz``,
    ``/trace/tail``, ``/metrics/cluster`` — port 0 = ephemeral, read
    ``sess.server.port``); ``slo_rules=`` starts the rolling-window
    :class:`~distkeras_tpu.obs.slo.SloEngine` ticker (also started,
    rule-less, whenever the server runs, so ``/metrics`` always
    carries the ``slo_windowed`` gauges).  Both are stdlib daemon
    threads that only READ the registry: enabling them cannot touch
    compile counts (the ``obs_live`` compile session pins it).
    """

    def __init__(self, trace_path: str | None = None,
                 run_id: str | None = None,
                 serve_port: int | None = None,
                 serve_host: str = "127.0.0.1", health=None,
                 slo_rules=None, slo_tick_s: float = 1.0,
                 residency=None):
        self.registry = MetricsRegistry()
        self.trace = (EventTrace(trace_path, run_id=run_id)
                      if trace_path else None)
        if self.trace is not None and "jax" in sys.modules:
            # One clock: every span is also a TraceAnnotation of the
            # same name, so a jax.profiler trace taken meanwhile holds
            # the spans on its host plane, beside the device's
            # operations.  A process that never imported jax (a
            # router) has no profiler to annotate for.
            self.trace.annotation = \
                sys.modules["jax"].profiler.TraceAnnotation
        self.run_id = self.trace.run_id if self.trace else run_id
        self.slo = None
        self.server = None
        try:
            if slo_rules is not None or serve_port is not None:
                self.slo = SloEngine(
                    self.registry, slo_rules or (), tick_s=slo_tick_s,
                    emit=self.trace.event if self.trace else None
                ).start()
            if serve_port is not None:
                self.server = TelemetryServer(
                    self.registry, port=serve_port, bind=serve_host,
                    trace_path=trace_path, health=health,
                    residency=residency).start()
        except BaseException:
            # A failed live-plane start (e.g. the fixed serve_port is
            # already bound) must not leak the already-running ticker
            # thread or the open trace file: enable() re-raises with
            # _ACTIVE still None, so nothing else could clean up.
            self.close()
            raise

    def close(self) -> None:
        if self.server is not None:
            self.server.stop()
        if self.slo is not None:
            self.slo.stop()
        if self.trace is not None:
            self.trace.metrics(self.registry.snapshot())
            self.trace.close()


def enable(trace_path: str | None = None, run_id: str | None = None,
           **live_kw) -> ObsSession:
    """Activate telemetry; returns the session.  Pair with
    :func:`disable`, or use :func:`session` for scoped enablement.
    ``live_kw`` (``serve_port=`` / ``serve_host=`` / ``health=`` /
    ``slo_rules=`` / ``slo_tick_s=``) opt into the live telemetry
    plane — see :class:`ObsSession`."""
    global _ACTIVE
    if _ACTIVE is not None:
        raise RuntimeError(
            "an obs session is already active; telemetry sessions do "
            "not nest (disable() the current one first)")
    _ACTIVE = ObsSession(trace_path=trace_path, run_id=run_id,
                         **live_kw)
    return _ACTIVE


def disable() -> None:
    """Deactivate and close the current session (no-op when none)."""
    global _ACTIVE
    sess, _ACTIVE = _ACTIVE, None
    if sess is not None:
        sess.close()


# Trace records are buffered in memory: a process that enabled a
# session and simply returns from main must still leave its last
# buffer in the file.
atexit.register(disable)


@contextlib.contextmanager
def session(trace_path: str | None = None, run_id: str | None = None,
            **live_kw):
    """``with obs.session("run.jsonl") as sess: ...`` (pass
    ``serve_port=``/``slo_rules=`` for the live telemetry plane)."""
    sess = enable(trace_path=trace_path, run_id=run_id, **live_kw)
    try:
        yield sess
    finally:
        disable()


def active() -> ObsSession | None:
    """The enabled session, or None — production hooks use the module
    functions below instead of checking this directly."""
    return _ACTIVE


# --------------------------------------------------------------- hooks
#
# The functions the instrumented layers call.  Each one is a no-op
# (one attribute load + `is` check) when telemetry is disabled.


# Each hook binds _ACTIVE to a local ONCE: a concurrent disable()
# (bench_suite's per-config teardown, while a daemon Prefetcher thread
# is mid-record) must find a hook working on the session it sampled,
# never a half-observed None.


def count(name: str, n: float = 1.0, **labels) -> None:
    """Increment a counter (created on first use)."""
    sess = _ACTIVE
    if sess is None:
        return
    sess.registry.counter(name).inc(n, **labels)


def gauge(name: str, value: float, **labels) -> None:
    """Set a gauge (created on first use)."""
    sess = _ACTIVE
    if sess is None:
        return
    sess.registry.gauge(name).set(value, **labels)


def observe(name: str, value: float, buckets=None, **labels) -> None:
    """Record one histogram observation (default latency buckets)."""
    sess = _ACTIVE
    if sess is None:
        return
    h = (sess.registry.histogram(name) if buckets is None
         else sess.registry.histogram(name, buckets=buckets))
    h.observe(value, **labels)


def event(name: str, **fields) -> None:
    """Append a point event to the trace (no-op without a trace)."""
    sess = _ACTIVE
    if sess is None or sess.trace is None:
        return
    sess.trace.event(name, **fields)


_NULL = contextlib.nullcontext()


def span(name: str, **fields):
    """Span context manager; a shared null context when disabled (no
    allocation on the disabled path)."""
    sess = _ACTIVE
    if sess is None or sess.trace is None:
        return _NULL
    return sess.trace.span(name, **fields)


__all__ = ["ObsSession", "MetricsRegistry", "Counter", "Gauge",
           "Histogram", "EventTrace", "read_trace",
           "percentile_from_buckets", "DEFAULT_TIME_BUCKETS",
           "SloRule", "SloEngine", "TelemetryServer", "HeartbeatHealth",
           "enable", "disable", "session", "active",
           "count", "gauge", "observe", "event", "span"]

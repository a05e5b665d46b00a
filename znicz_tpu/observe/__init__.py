"""Unified telemetry: metrics registry + host-span tracing.

One observability layer shared by the training engine and the serving
engine (the modern equivalent of the reference's live workflow
introspection — plotters and ``veles/web_status.py``):

- :mod:`znicz_tpu.observe.metrics` — a thread-safe process-local
  registry of counters/gauges/histograms with JSON and Prometheus
  text exposition.  ``WebStatusServer`` serves it at ``/metrics``.
- :mod:`znicz_tpu.observe.tracing` — a host-side span tracer (unit
  fires, epochs, region dispatches, compiles, blocking device→host
  reads, SDC votes, serving dispatches) and of the process's own
  start-up (``initialize:``, ``param_fill``, ``upload:``, JAX's
  ``jax:trace`` / ``jax:lower`` / ``jax:backend_compile``; beside
  them ``znicz_setup_seconds{phase}``), every span naming its parent,
  exporting Chrome-trace/Perfetto JSON, served live at
  ``/trace.json``.  Its clock is ``perf_counter``: a span rides the
  profiler's own clock (a ``TraceAnnotation``) only inside a
  :func:`profile_window`; ``znbench/run.py`` shifts the ring onto a
  device trace and ``znbench/trace_reduce.py`` is the reduction.
- :func:`profile_window` — capture a ``jax.profiler`` device trace
  (Python tracer off) + the window's host spans around any region,
  and ``op_scopes.json`` beside them.
- :func:`op_scopes` (:mod:`znicz_tpu.observe.scopes`) — which unit,
  and which phase of it (forward, backward, ``update``,
  ``fingerprint``, a phase the unit's class declares: ``PHASES``),
  every HLO instruction of the compiled region programs belongs to:
  the key to a profile's ``fusion.362``.
- :mod:`znicz_tpu.observe.recorder` (round 24) — the ops flight
  recorder: a bounded crash-safe JSONL journal of consequential ops
  events (swaps, canary verdicts, restarts, quarantines, breaker
  transitions), served at ``/flightrecord``.
- :mod:`znicz_tpu.observe.federation` (round 24) — gang-level
  metrics federation: supervisor/fleet scrape loops fold child
  ``/metrics`` pages, in-process child registries and the heartbeat
  channel into ``znicz_fed_*`` series with process/pool labels.
- :class:`RequestTrace` (round 24) — the request-scoped trace
  context minted at ``submit()`` that rides a request through every
  hop and renders its life as a parented span tree in /trace.json.

Master gate: ``root.common.engine.telemetry`` (default on;
near-zero overhead — hot sites check :func:`enabled` first).
"""

from znicz_tpu.observe.metrics import (  # noqa: F401
    DEFAULT_BUCKETS,
    REGISTRY,
    MetricsRegistry,
    enabled,
    window_p99,
)
from znicz_tpu.observe.scopes import op_scopes  # noqa: F401
from znicz_tpu.observe.tracing import (  # noqa: F401
    NULL_TRACE,
    TRACER,
    RequestTrace,
    SpanTracer,
    adopt_pending_trace,
    new_request_trace,
    now_us,
    profile_window,
    set_pending_trace,
)
from znicz_tpu.observe.recorder import (  # noqa: F401
    FlightRecorder,
    get_recorder,
    record,
    set_recorder,
)
from znicz_tpu.observe.federation import (  # noqa: F401
    Federator,
)

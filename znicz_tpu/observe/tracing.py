"""Host-side span tracer: Dapper-style spans over the control plane.

The device's time is observable through ``jax.profiler`` traces
(reduced by ``znbench/trace_reduce.py``: busy time as a union of
intervals, self time per operation, idle gaps); this module records the
HOST half — which unit, epoch, blocking read or serving request the
host was in while the device worked or waited.  Spans (unit fires,
workflow runs, epochs, region dispatches, compiles, blocking
device→host reads, host→device uploads, SDC votes, serving phases) go
into ONE bounded ring buffer on ``perf_counter`` and are exported as
Chrome-trace/Perfetto JSON (``ph: "X"`` complete events):
``chrome://tracing`` / Perfetto show them, ``WebStatusServer`` serves
them live at ``/trace.json``.

The process's own start-up is on the same ring, counted from the OS's
start of the process (:func:`process_start_us`, negative: before the
tracer's epoch): ``initialize:<workflow>`` with one
``initialize:<unit>`` child per call of a unit's ``initialize`` (cat
``setup``; ``deferred`` where the unit asked for a later pass),
``param_fill`` (cat ``setup``: the host drawing random parameters),
``upload:<vector>`` (cat ``transfer``: the host's time in the copy up,
the mirror of ``host_read:<vector>``) and, made from JAX's own stamps
(:func:`watch_startup`), ``jax:trace``, ``jax:lower``,
``jax:backend_compile`` and ``jax:cache_load`` (cat ``compile``) under
whatever was open on the thread — ``compile:<region>`` for a step
program, whose remainder is then the first execution.  Each adds its
seconds to ``znicz_setup_seconds{phase}`` as it closes.

Every span names the span that caused it: ``args`` carries
``span_id`` (unique in the process), ``parent_span_id`` (the span
open on the same thread when this one began, 0 at the root) and
``depth``.  A span's self time is its duration minus its children's.
Request spans (``cat="request"``) keep ids of their own, scoped by
``trace_id`` (see :class:`RequestTrace`).

Lining spans up with the device: the ring's clock is not the
profiler's.  Only while a :func:`profile_window` device trace is open
does a span also enter a ``jax.profiler.TraceAnnotation`` of the same
name, which puts a copy on the profiler's host lane; the benchmark
instead shifts the ring's spans onto the profiler's clock by the
offset of one annotation both clocks saw (``znbench/run.py``
``host_spans_on_trace_clock``; ``tests/test_observe_spans.py`` pins
the two copies to within 200 µs).  With no trace open a span costs
two clock reads and one ring append.  (``jax.named_scope`` is the
tracing-time cousin: the jit-region builder enters it per member unit,
always, so device-op metadata carries the unit — see
``JitRegion.build_callable``; ``observe.op_scopes()`` reads it back
from the compiled program.)

:func:`profile_window` is the capture helper: a context manager that
opens a ``jax.profiler`` trace (Python tracer off) around any region
and drops the window's host spans beside it as
``host_spans.trace.json``, and the region programs' op → unit map as
``op_scopes.json``.

All recording is gated on :func:`znicz_tpu.observe.metrics.enabled`
(``root.common.engine.telemetry``); a disabled tracer costs one dict
lookup per span.
"""

from __future__ import annotations

import functools
import itertools
import json
import os
import threading
import time
from collections import deque
from contextlib import contextmanager

from znicz_tpu.observe import metrics as _metrics

#: trace time zero (module import); spans report microseconds since
_EPOCH = time.perf_counter()


def now_us() -> float:
    """Microseconds since the tracer epoch (the Chrome-trace ``ts``
    time base)."""
    return (time.perf_counter() - _EPOCH) * 1e6


@functools.cache
def process_start_us() -> float:
    """The OS's start time of this process on the tracer's time base
    (negative: the interpreter started before this module was
    imported) — the zero every start-up reading counts from.  Linux
    keeps it in ``/proc/self/stat`` (field 22, clock ticks since boot);
    the boot time is taken as now less ``/proc/uptime``, which is good
    to a tick where ``btime`` of ``/proc/stat`` is whole seconds.
    Elsewhere the tracer's own epoch stands in."""
    try:
        with open("/proc/self/stat") as fh:
            # field 2, the command, may hold spaces and parentheses
            fields = fh.read().rpartition(")")[2].split()
        with open("/proc/uptime") as fh:
            uptime_s = float(fh.read().split()[0])
        age_s = uptime_s - int(fields[19]) / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return 0.0
    return now_us() - max(age_s, 0.0) * 1e6


#: True while a :func:`profile_window` device trace is open — the ONLY
#: time a host span pays for a ``jax.profiler.TraceAnnotation`` (there
#: is nobody to see the annotation otherwise, and the decode token
#: loop opens a span per step, so the idle cost is a hot-path tax)
_DEVICE_TRACE_OPEN = False


def _trace_annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` for ``name`` when jax is
    importable (it always is in this framework; the guard keeps the
    tracer usable standalone)."""
    try:
        import jax
        return jax.profiler.TraceAnnotation(name)
    except Exception:  # noqa: BLE001 — tracer must never break the host loop
        return None


#: process-unique span ids (0 is "no parent"); ``next`` on a count is
#: atomic under the GIL
_SPAN_SEQ = itertools.count(1)


class _NullSpan:
    """The span handed out when telemetry is off — a shared, stateless
    no-op context manager."""

    __slots__ = ()
    dur_us = 0.0
    self_us = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> None:
        pass


_NULL_SPAN = _NullSpan()


class _Span:
    """One open host span (class-based: the generator-frame cost of
    ``@contextmanager`` is measurable at decode-step cadence)."""

    __slots__ = ("_tracer", "_name", "_cat", "_args", "_ann", "_t0",
                 "_depth", "_id", "_parent", "_child_us", "dur_us")

    def __init__(self, tracer, name, cat, args) -> None:
        self._tracer = tracer
        self._name = name
        self._cat = cat
        self._args = args
        self._child_us = 0.0

    def set(self, **args) -> None:
        """Arguments known only inside the with-body."""
        self._args.update(args)

    @property
    def self_us(self) -> float:
        """The duration less the children's, readable where
        ``dur_us`` is."""
        return self.dur_us - self._child_us

    def __enter__(self):
        stack = self._tracer._stack()
        self._depth = len(stack)
        self._parent = stack[-1]._id if stack else 0
        self._id = next(_SPAN_SEQ)
        stack.append(self)
        self._ann = (_trace_annotation(self._name)
                     if _DEVICE_TRACE_OPEN else None)
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = now_us()
        return self

    def __exit__(self, *exc):
        t1 = now_us()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        stack = self._tracer._stack()
        stack.pop()
        #: readable after the with-body: the counter beside a span
        #: sums the very duration the span recorded
        self.dur_us = t1 - self._t0
        if stack:
            stack[-1]._child_us += self.dur_us
        self._tracer._append({
            "ph": "X", "name": self._name, "cat": self._cat,
            "pid": self._tracer._pid,
            "tid": threading.get_native_id(),
            "ts": self._t0, "dur": self.dur_us,
            "args": {**self._args, "depth": self._depth,
                     "span_id": self._id,
                     "parent_span_id": self._parent}})
        return False


class SpanTracer:
    """Bounded ring buffer of completed host spans."""

    def __init__(self, max_events: int = 65536) -> None:
        self._events: deque = deque(maxlen=max_events)
        self._lock = threading.Lock()
        self._local = threading.local()
        self._seq = 0
        self._dropped = 0
        self._pid = os.getpid()

    # ------------------------------------------------------------------
    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _append(self, event: dict) -> None:
        with self._lock:
            self._seq += 1
            event["_seq"] = self._seq
            if len(self._events) == self._events.maxlen:
                self._dropped += 1
            self._events.append(event)

    def dropped(self) -> int:
        """Events the full ring has pushed out, oldest first, since
        the process began: a reader that needs the ring whole (the
        start-up's partition) refuses one that has wrapped."""
        with self._lock:
            return self._dropped

    def mark(self) -> int:
        """A position marker; pass to :meth:`to_chrome_trace` /
        :meth:`export` as ``since`` to keep only later events."""
        with self._lock:
            return self._seq

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()

    # ------------------------------------------------------------------
    def span(self, name: str, cat: str = "host", **args):
        """Record a span around the with-body.  Nesting is tracked per
        thread: the event's ``args`` carry ``span_id``, the
        ``parent_span_id`` of the span open on this thread when it
        began (0 at the root) and ``depth``; while a
        :func:`profile_window` device trace is open a
        ``jax.profiler.TraceAnnotation`` rides the span so the
        captured device trace carries it on its host lane.  This is
        the decode loop's per-step hot path: a class-based context
        manager (no generator frame) and the annotation gated on an
        open device trace keep the always-on cost to two clock reads
        and one ring append."""
        if not _metrics.enabled():
            return _NULL_SPAN
        return _Span(self, name, cat, args)

    def complete(self, name: str, t0_us: float, t1_us: float,
                 cat: str = "host", nested: bool = False,
                 **args) -> int:
        """Record a retroactive span from explicit timestamps (epoch
        boundaries are only known at the END of the epoch) and return
        its id (0 with telemetry off).  It began before whatever is
        open now, so it is a root (parent 0) unless the caller passes
        ids of its own, as :class:`RequestTrace` does, or says that it
        is ``nested``: it began and ended inside the span open on this
        thread (a phase JAX stamped while the caller's span was open),
        which is then its parent and counts it among its children."""
        if not _metrics.enabled():
            return 0
        dur = max(0.0, t1_us - t0_us)
        depth = 0
        if "span_id" not in args:
            stack = self._stack() if nested else ()
            if stack:
                depth = len(stack)
                stack[-1]._child_us += dur
            args = {**args, "span_id": next(_SPAN_SEQ),
                    "parent_span_id": stack[-1]._id if stack else 0}
        self._append({
            "ph": "X", "name": name, "cat": cat,
            "pid": self._pid, "tid": threading.get_native_id(),
            "ts": t0_us, "dur": dur,
            "args": {"depth": depth, **args}})
        return args["span_id"]

    def instant(self, name: str, cat: str = "host", **args) -> None:
        if not _metrics.enabled():
            return
        self._append({
            "ph": "i", "s": "t", "name": name, "cat": cat,
            "pid": self._pid, "tid": threading.get_native_id(),
            "ts": now_us(), "args": dict(args)})

    # ------------------------------------------------------------------
    def to_chrome_trace(self, since: int = 0) -> dict:
        """The Chrome-trace/Perfetto JSON object (``traceEvents``,
        and ``dropped``: what the full ring has pushed out)."""
        with self._lock:
            events = [ev for ev in self._events if ev["_seq"] > since]
        out_events = [{"ph": "M", "name": "process_name",
                       "pid": self._pid, "tid": 0,
                       "args": {"name": "znicz_tpu host spans"}}]
        for ev in events:
            ev = dict(ev)
            ev.pop("_seq", None)
            out_events.append(ev)
        return {"traceEvents": out_events, "displayTimeUnit": "ms",
                "dropped": self.dropped()}

    def export(self, path: str, since: int = 0) -> str:
        with open(path, "w") as fh:
            json.dump(self.to_chrome_trace(since=since), fh)
        return path


#: the process-global tracer every instrumentation site records on
TRACER = SpanTracer()


# ----------------------------------------------------------------------
# the process's own start-up: JAX's stamps of a program's making
# ----------------------------------------------------------------------
#: what ``jax.monitoring`` reports as a program is made (jax 0.9.0
#: ``_src/dispatch.py`` ``log_elapsed_time``: a scalar as the phase
#: opens, its duration as it ends) → the span it becomes and its
#: phase of ``znicz_setup_seconds``
_JAX_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": ("jax:trace", "trace"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        ("jax:lower", "lower"),
    "/jax/core/compile/backend_compile_duration":
        ("jax:backend_compile", "backend_compile"),
}
#: a load from JAX's persistent cache (``_src/compiler.py``): a
#: duration and no opening, reported inside the backend-compile phase
#: that it ends
_JAX_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_watching = False
_watch_lock = threading.Lock()


class _JaxPhases(threading.local):
    #: phases JAX has open on this thread: a jitted function met
    #: inside a trace is traced too, a lowering rule may trace, and
    #: each reports its own event inside the outer one's time
    open = 0
    #: ``(t0, t1)`` of the cache loads since the last backend compile
    loads: tuple = ()


_jax_phases = _JaxPhases()


def _on_jax_scalar(event: str, _value, **_kw) -> None:
    if event in _JAX_PHASES:
        _jax_phases.open += 1


def _on_jax_duration(event: str, secs: float, **kw) -> None:
    """One completed span per phase JAX reports, under the span open
    on this thread.  The listener runs as the phase ends, so the span
    ends now on the tracer's clock and began ``secs`` earlier: JAX
    stamps ``time.time()``, which an offset would carry over only
    until the wall clock is next slewed.  Of phases inside phases the
    outermost is recorded — its time holds the others', and no instant
    is then in two spans — but for a cache load, which comes out as
    the child of the backend compile it ended."""
    if event == _JAX_CACHE_LOAD:
        t1 = now_us()
        _jax_phases.loads += ((t1 - secs * 1e6, t1),)
        return
    if event not in _JAX_PHASES:
        return
    name, phase = _JAX_PHASES[event]
    _jax_phases.open = inside = max(_jax_phases.open - 1, 0)
    loads = ()
    if phase == "backend_compile":
        loads, _jax_phases.loads = _jax_phases.loads, ()
    if inside or not _metrics.enabled():
        return
    t1 = now_us()
    args = {"fun_name": kw["fun_name"]} if "fun_name" in kw else {}
    span_id = TRACER.complete(name, t1 - secs * 1e6, t1, cat="compile",
                              nested=True, **args)
    _metrics.setup_seconds(phase).inc(secs)
    for t0, t1 in loads:
        TRACER.complete("jax:cache_load", t0, t1, cat="compile",
                        span_id=next(_SPAN_SEQ), parent_span_id=span_id,
                        depth=len(TRACER._stack()) + 1)
        _metrics.setup_seconds("cache_load").inc((t1 - t0) / 1e6)


def watch_startup() -> None:
    """With telemetry on: say on ``/metrics`` when the process began
    and, once per process however many workflows and regions ask,
    start turning JAX's stamps into spans (``jax.monitoring`` listeners
    cannot be removed; this is the one place they are registered)."""
    if not _metrics.enabled():
        return
    _metrics.process_start_time_seconds().set(
        time.time() - (now_us() - process_start_us()) / 1e6)
    global _watching
    with _watch_lock:
        if _watching:
            return
        _watching = True
    import jax
    jax.monitoring.register_scalar_listener(_on_jax_scalar)
    jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


# ----------------------------------------------------------------------
# round 24: request-scoped trace context
# ----------------------------------------------------------------------
#: process-unique trace-id sequence (pid-prefixed so merged traces
#: from a gang of processes never collide)
_TRACE_SEQ = itertools.count(1)


class RequestTrace:
    """Trace context minted at ``submit()`` and riding the REQUEST
    object (not a thread-local) through every hop it takes — batcher
    queue, prefill dispatch, the disagg handoff payload, the decode
    token loop — because a request crosses threads and pools while a
    single logical trace must survive all of them.

    Phases are begun/ended from whatever thread owns the request at
    that moment; each closed phase lands in the process tracer as a
    ``cat="request"`` complete span parented under the request's root
    span (``trace_id``/``span_id``/``parent_span_id`` in ``args``), so
    ``/trace.json`` renders one request's life as a span tree and
    ``trace_top.py --requests`` can aggregate per-phase percentiles.
    :meth:`phase_end` returns the phase duration in seconds so the
    engine can feed its windowed-p99 gauges from the same clock.
    """

    __slots__ = ("trace_id", "name", "args", "t0_us", "_phase_t0",
                 "_span_seq", "phases", "events", "_finished")

    def __init__(self, name: str = "request", **args) -> None:
        self.trace_id = f"{os.getpid():x}-{next(_TRACE_SEQ):06x}"
        self.name = name
        self.args = dict(args)
        self.t0_us = now_us()
        self._phase_t0: dict[str, float] = {}
        #: root span is 1; child spans/events count up from 2
        self._span_seq = itertools.count(2)
        self.phases: dict[str, float] = {}
        self.events: list[str] = []
        self._finished = False

    def phase_begin(self, phase: str) -> None:
        """Open ``phase`` (idempotent: a retry re-entering the same
        phase keeps the FIRST begin, so retried work is charged to the
        phase that absorbed it)."""
        self._phase_t0.setdefault(phase, now_us())

    def phase_end(self, phase: str, **args) -> float:
        """Close ``phase`` and record it as a child span; returns the
        phase duration in seconds (0.0 when the phase never began)."""
        t0 = self._phase_t0.pop(phase, None)
        if t0 is None:
            return 0.0
        t1 = now_us()
        dur_s = (t1 - t0) / 1e6
        self.phases[phase] = self.phases.get(phase, 0.0) + dur_s
        TRACER.complete(f"req.{phase}", t0, t1, cat="request",
                        trace_id=self.trace_id,
                        span_id=next(self._span_seq),
                        parent_span_id=1, phase=phase, **args)
        return dur_s

    def event(self, name: str, **args) -> None:
        """An instant under the request's root span (breaker shed,
        deadline eviction, handoff drop, swap pause, routing choice)."""
        self.events.append(name)
        TRACER.instant(f"req.{name}", cat="request",
                       trace_id=self.trace_id,
                       span_id=next(self._span_seq),
                       parent_span_id=1, **args)

    def finish(self, outcome: str = "ok", **args) -> None:
        """Close the root span (idempotent — the first outcome
        wins)."""
        if self._finished:
            return
        self._finished = True
        for phase in list(self._phase_t0):  # close any dangling phase
            self.phase_end(phase)
        TRACER.complete(self.name, self.t0_us, now_us(), cat="request",
                        trace_id=self.trace_id, span_id=1,
                        parent_span_id=0, outcome=outcome,
                        **{**self.args, **args})


class _NullTrace:
    """The no-op trace every call site holds when telemetry is off —
    keeps the instrumentation unconditional at one attribute call."""

    __slots__ = ()
    trace_id = "-"
    phases: dict = {}
    events: list = []

    def phase_begin(self, phase: str) -> None:
        pass

    def phase_end(self, phase: str, **args) -> float:
        return 0.0

    def event(self, name: str, **args) -> None:
        pass

    def finish(self, outcome: str = "ok", **args) -> None:
        pass


NULL_TRACE = _NullTrace()


def new_request_trace(name: str = "request", **args):
    """Mint a request trace (:class:`NULL_TRACE` when telemetry is
    off, so call sites never branch)."""
    if not _metrics.enabled():
        return NULL_TRACE
    return RequestTrace(name, **args)


#: fleet→engine adoption channel: FleetEngine mints the trace (so the
#: routing decision is on it), parks it here, and the engine's
#: synchronous same-thread submit() adopts it instead of minting a new
#: one — no API change on every submit signature in between
_PENDING = threading.local()


def set_pending_trace(trace) -> None:
    _PENDING.trace = trace


def adopt_pending_trace():
    """Pop the thread's parked trace (None when nothing was parked)."""
    trace = getattr(_PENDING, "trace", None)
    _PENDING.trace = None
    return trace


@contextmanager
def profile_window(outdir: str, n_steps: int | None = None,
                   device: bool = True, tracer: SpanTracer | None = None):
    """Capture a ``jax.profiler`` device trace plus the window's host
    spans around the with-body.

    ``outdir`` receives the profiler's trace directory (the
    ``.xplane.pb`` that ``znbench/trace_reduce.py`` reads),
    ``host_spans.trace.json`` (Chrome-trace JSON of the host spans
    recorded during the window) and, beside a device trace,
    ``op_scopes.json`` (:func:`znicz_tpu.observe.op_scopes`: the unit
    and phase behind every operation name of the region programs, so
    ``fusion.362`` can be looked up).  The profiler runs with its Python
    tracer off: a per-call tracer on a host-bound loop measures
    itself.
    ``n_steps`` is recorded on the window span so per-step math in the
    post-processors has its divisor.  ``device=False`` skips the jax
    profiler (host spans only — cheap enough for always-on use); with
    ``device=True`` a profiler that will not start raises — a device
    trace that was asked for and is missing must not read as success.

    Usage mid-training::

        with observe.profile_window("profiles/r09", n_steps=32):
            for _ in range(32):
                step()
    """
    if tracer is None:  # NOT `or`: an empty SpanTracer is falsy
        tracer = TRACER
    os.makedirs(outdir, exist_ok=True)
    global _DEVICE_TRACE_OPEN
    if device:
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(outdir, profiler_options=options)
        _DEVICE_TRACE_OPEN = True
    mark = tracer.mark()
    try:
        with tracer.span("profile_window", cat="profile",
                         n_steps=n_steps or 0):
            yield outdir
    finally:
        if device:
            import jax
            _DEVICE_TRACE_OPEN = False
            try:
                jax.profiler.stop_trace()
            except Exception:  # noqa: BLE001 — already stopped elsewhere
                pass
        tracer.export(os.path.join(outdir, "host_spans.trace.json"),
                      since=mark)
        if device:
            from znicz_tpu.observe import scopes
            with open(os.path.join(outdir, "op_scopes.json"), "w") as fh:
                json.dump(scopes.op_scopes(), fh)

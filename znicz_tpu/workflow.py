"""Workflow: a container of units with a scheduler and lifecycle.

Rebuilds the reference's workflow engine (reference:
``veles/workflow.py``).  Differences that are deliberate TPU-first
design, not omissions:

- The reference scheduled unit callbacks on a thread pool
  (``veles/thread_pool.py``) because GPU kernel launches overlapped
  under the GIL.  On TPU the device pipeline parallelism comes from
  XLA's async dispatch and from jit regions compiling whole chains into
  one program, so the host scheduler is a deterministic worklist — no
  threads, no races, reproducible unit ordering.
- ``generate_graph`` emits Graphviz DOT like the reference.
"""

from __future__ import annotations

from collections import deque

from znicz_tpu.mutable import Bool
from znicz_tpu.observe import metrics as _metrics
from znicz_tpu.observe import tracing as _tracing
from znicz_tpu.units import Container, EndPoint, StartPoint, Unit


class Workflow(Container):
    """A directed graph of units executed from ``start_point``.

    Lifecycle: construct units and wire links in ``__init__`` (or
    after), then :meth:`initialize` (multi-pass, resolves deferred
    attribute links), then :meth:`run` — the scheduler fires units
    until :attr:`end_point` runs or :meth:`stop` is called.
    """

    def __init__(self, workflow: "Workflow | None" = None,
                 name: str | None = None, **kwargs) -> None:
        super().__init__(workflow, name=name, **kwargs)
        self.start_point = StartPoint(self, name="start_point")
        self.end_point = EndPoint(self, name="end_point")
        from znicz_tpu.parallel.partition import PartitionTable
        #: the workflow's ONE ordered partition-rule table — units
        #: declare placement overrides into it (TP, ring, ZeRO-1,
        #: population member axis) and every Vector binds against it
        #: at init_vectors time (parallel.partition)
        self.partition = PartitionTable(name=self.name)
        self.stopped = Bool(False)
        self._finished = False
        self._max_fires: int | None = None  # safety valve for tests
        #: step-boundary hooks (round 18): fired by the Decision unit
        #: once per training step (per chunk under run_chunked) — the
        #: elastic WorkerSupervisor beats its heartbeat and services
        #: preemption requests here.  Exceptions propagate (Preempted
        #: is a SystemExit and must unwind the run loop).
        self._step_hooks: list = []

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------
    def initialize(self, **kwargs) -> None:
        """Initialize all units, retrying ones whose linked attributes
        are produced by units initialized later (reference behavior:
        multi-pass dependency resolution).

        Start-up is spanned here, for every workflow:
        ``initialize:<workflow>`` (cat ``setup``) around the passes and
        ``initialize:<unit>`` around each call of a unit's
        ``initialize`` (``deferred`` where it asked for a later pass);
        their self time adds to ``znicz_setup_seconds{initialize}``.  A
        workflow nested as a unit comes out as a child of its unit
        span."""
        _tracing.watch_startup()
        with _tracing.TRACER.span(f"initialize:{self.name}",
                                  cat="setup") as span:
            self._initialize_units(**kwargs)
        self._count_initialize(span)
        self._initialized = True

    @staticmethod
    def _count_initialize(span) -> None:
        # JAX stamps its phases on another clock than the span's: a
        # span that is all children may come out a hair under them
        if span.self_us > 0:
            _metrics.setup_seconds("initialize").inc(span.self_us / 1e6)

    def _initialize_units(self, **kwargs) -> None:
        pending = list(self.units)
        passes = 0
        while pending:
            passes += 1
            deferred: list[tuple[Unit, AttributeError]] = []
            progress = False
            for unit in pending:
                if unit.is_initialized:
                    continue
                with _tracing.TRACER.span(
                        f"initialize:{unit.name}", cat="setup",
                        kind=type(unit).__name__) as span:
                    try:
                        unit.initialize(**kwargs)
                        unit._initialized = True
                        progress = True
                    except AttributeError as exc:
                        # a base-class initialize may have set the flag
                        # before the subclass raised — the workflow loop
                        # is authoritative about who still needs a pass
                        unit._initialized = False
                        deferred.append((unit, exc))
                        span.set(deferred=True)
                self._count_initialize(span)
            if not deferred:
                break
            if not progress:
                unit, exc = deferred[0]
                raise RuntimeError(
                    f"workflow '{self.name}' initialize deadlock after "
                    f"{passes} passes; first stuck unit: {unit} "
                    f"({exc})") from exc
            pending = [u for u, _ in deferred]

    def run(self) -> None:
        """Fire units from ``start_point`` until completion.

        Deterministic worklist scheduler: a unit is enqueued when its
        gate opens; ``gate_block`` drops the control signal,
        ``gate_skip`` propagates without running.
        """
        if not self.is_initialized:
            raise RuntimeError(f"workflow '{self.name}' not initialized")
        import time as _time
        self.run_started_at = _time.time()  # consumers (Publisher)
        #                       use it to tell this run's artifacts apart
        self._finished = False
        self.stopped.value = False
        queue: deque[Unit] = deque([self.start_point])
        self.start_point.reset_links()
        fires = 0
        with self._run_span():
            while queue and not self._finished and not self.stopped:
                unit = queue.popleft()
                if unit.gate_block:
                    continue
                if not unit.gate_skip:
                    unit._fire()
                    if self._finished or self.stopped:
                        break
                for dst in list(unit.links_to):
                    if dst.open_gate(unit):
                        dst.reset_links()
                        queue.append(dst)
                fires += 1
                if self._max_fires is not None and fires > self._max_fires:
                    raise RuntimeError(
                        f"workflow '{self.name}' exceeded max_fires="
                        f"{self._max_fires} (runaway loop?)")
        self.on_workflow_finished()

    def _run_span(self):
        """The root span every driver of this workflow opens around
        its loop (``run`` here; ``run_chunked`` and its kin in
        ``StandardWorkflow``)."""
        return _tracing.TRACER.span(f"workflow:{self.name}",
                                    cat="workflow")

    def on_end_point(self) -> None:
        self._finished = True

    def stop(self) -> None:
        self.stopped.value = True
        for unit in self.units:
            unit.stop()

    # ------------------------------------------------------------------
    # step-boundary hooks (round 18: elastic supervision)
    # ------------------------------------------------------------------
    def add_step_hook(self, fn) -> None:
        if fn not in self._step_hooks:
            self._step_hooks.append(fn)

    def remove_step_hook(self, fn) -> None:
        if fn in self._step_hooks:
            self._step_hooks.remove(fn)

    def on_step_boundary(self) -> None:
        """Called by the Decision unit after every step's bookkeeping —
        the one safe point to heartbeat, poll preemption flags and
        take a barriered checkpoint (the whole gang reaches the same
        boundary in lockstep)."""
        for fn in list(self._step_hooks):
            fn()

    def on_workflow_finished(self) -> None:
        """Hook: after the scheduler drains.  Logs the slowest units
        (reference behavior: per-unit timing table at workflow end)."""
        rows = sorted((u for u in self.units if u.run_count),
                      key=lambda u: u.run_time_total, reverse=True)[:5]
        if rows:
            table = ", ".join(
                f"{u.name}: {u.run_time_total:.3f}s/{u.run_count}x"
                for u in rows)
            self.debug("slowest units: %s", table)

    # ------------------------------------------------------------------
    # snapshot protocol
    # ------------------------------------------------------------------
    def state_dict(self, allow_collective: bool = False) -> dict:
        """Pure-data state tree: per-unit Vectors + declared scalars +
        the PRNG streams (so resume continues the exact trajectory).

        ``allow_collective``: see :meth:`Unit.state_dict` — True only
        from lockstep snapshot points (the Snapshotter unit)."""
        from znicz_tpu.utils import prng
        state: dict = {"__units__": {}, "__prng__": prng.get().get_state()}
        for unit in self.units:
            unit_state = unit.state_dict(allow_collective=allow_collective)
            if unit_state:
                state["__units__"][unit.name] = unit_state
        return state

    def load_state(self, state: dict) -> None:
        from znicz_tpu.utils import prng
        by_name = state.get("__units__", {})
        for unit in self.units:
            unit_state = by_name.get(unit.name)
            if unit_state:
                unit.load_state(unit_state)
        if "__prng__" in state:
            prng.get().set_state(state["__prng__"])

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------
    def generate_graph(self) -> str:
        """Graphviz DOT of the control-flow graph (reference:
        ``veles/workflow.py`` ``generate_graph``)."""
        lines = [f'digraph "{self.name}" {{', "  rankdir=TB;"]
        ids = {unit: f"u{i}" for i, unit in enumerate(self.units)}
        for unit, uid in ids.items():
            lines.append(
                f'  {uid} [label="{unit.name}\\n{type(unit).__name__}"];')
        for unit, uid in ids.items():
            for dst in unit.links_to:
                if dst in ids:
                    lines.append(f"  {uid} -> {ids[dst]};")
        lines.append("}")
        return "\n".join(lines)

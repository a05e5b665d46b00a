"""Dataflow units: nodes of the workflow graph.

Rebuilds the reference's unit model (reference: ``veles/units.py``):

- **control links** (``b.link_from(a)``): b becomes runnable when a
  finishes; a unit with several incoming links waits for *all* of them
  (:class:`Repeater` waits for *any* — that is what closes training
  loops);
- **attribute links** (``b.link_attrs(a, ("input", "output"))``):
  ``b.input`` is a live alias of ``a.output`` — the data plane;
- **gates**: ``gate_block`` (don't run, don't propagate — control flow
  stops here while the gate holds) and ``gate_skip`` (don't run, but
  propagate), both :class:`~znicz_tpu.mutable.Bool` so other units flip
  them live.

TPU-first note: this graph is the *host control plane* executed between
device steps.  The per-minibatch compute chain is compiled out of the
graph into a single XLA program by the jit-region engine
(:mod:`znicz_tpu.accelerated_units`); gates that flip per-epoch stay
here, gates that flip per-minibatch become static region keys.
"""

from __future__ import annotations

import time
from typing import TYPE_CHECKING, Iterable

from znicz_tpu.mutable import Bool, LinkableAttribute
from znicz_tpu.observe import metrics as _metrics
from znicz_tpu.observe import tracing as _tracing
from znicz_tpu.utils.logger import Logger

if TYPE_CHECKING:  # pragma: no cover
    from znicz_tpu.workflow import Workflow


class Unit(Logger):
    """A node in the dataflow graph.

    Subclasses override :meth:`initialize` (allocate state once the
    graph is wired) and :meth:`run` (one firing).  ``initialize`` may
    raise :class:`AttributeError` if a linked attribute is not yet
    available; the workflow retries in dependency order
    (reference behavior: ``veles/workflow.py`` multi-pass initialize).
    """

    def __init__(self, workflow: "Workflow | None", name: str | None = None,
                 **kwargs) -> None:
        # _linked_attrs must exist before any attribute writes resolve.
        object.__setattr__(self, "_linked_attrs", {})
        super().__init__(**kwargs)
        self.name = name or type(self).__name__
        self.links_from: dict[Unit, bool] = {}
        self.links_to: dict[Unit, bool] = {}
        self.gate_block = Bool(False)
        self.gate_skip = Bool(False)
        self._initialized = False
        self.run_count = 0
        self.run_time_total = 0.0
        self._workflow: "Workflow | None" = None
        if workflow is not None:
            workflow.add_ref(self)

    # ------------------------------------------------------------------
    # attribute linking (data plane)
    # ------------------------------------------------------------------
    def __setattr__(self, name: str, value) -> None:
        link = self._linked_attrs.get(name)
        if link is not None:
            link.set(value)
            return
        object.__setattr__(self, name, value)

    def __getattr__(self, name: str):
        # Called only when normal lookup fails.
        if name == "_linked_attrs":
            raise AttributeError(name)
        link = self._linked_attrs.get(name)
        if link is not None:
            return link.get()
        raise AttributeError(
            f"{type(self).__name__} '{self.__dict__.get('name', '?')}' "
            f"has no attribute '{name}'")

    def link_attrs(self, other: "Unit",
                   *pairs: "str | tuple[str, str]",
                   two_way: bool = True) -> "Unit":
        """Alias attributes of ``other`` into this unit.

        Each pair is either a name (same on both sides) or
        ``(dst_name, src_name)``: ``self.dst_name`` aliases
        ``other.src_name``.
        """
        for pair in pairs:
            dst, src = (pair, pair) if isinstance(pair, str) else pair
            self.__dict__.pop(dst, None)  # the alias must win lookups
            self._linked_attrs[dst] = LinkableAttribute(other, src, two_way)
        return self

    def unlink_attrs(self, *names: str) -> None:
        for name in names:
            self._linked_attrs.pop(name, None)

    # ------------------------------------------------------------------
    # control linking
    # ------------------------------------------------------------------
    def link_from(self, *units: "Unit") -> "Unit":
        for unit in units:
            self.links_from[unit] = False
            unit.links_to[self] = False
        return self

    def unlink_from(self, *units: "Unit") -> None:
        for unit in units:
            self.links_from.pop(unit, None)
            unit.links_to.pop(self, None)

    def unlink_all(self) -> None:
        for unit in list(self.links_from):
            self.unlink_from(unit)
        for unit in list(self.links_to):
            unit.unlink_from(self)

    def open_gate(self, src: "Unit") -> bool:
        """Record that ``src`` finished; True when this unit may fire.

        Default: all incoming links must have fired (barrier join).
        """
        if src in self.links_from:
            self.links_from[src] = True
        return all(self.links_from.values())

    def reset_links(self) -> None:
        for unit in self.links_from:
            self.links_from[unit] = False

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------
    @property
    def workflow(self) -> "Workflow | None":
        return self._workflow

    @property
    def is_initialized(self) -> bool:
        return self._initialized

    def initialize(self, **kwargs) -> None:
        """Allocate state.  May raise AttributeError to defer."""
        self._initialized = True

    def run(self) -> None:
        """One firing of the unit."""

    def stop(self) -> None:
        """Called when the workflow is stopping; release resources."""

    # ------------------------------------------------------------------
    # snapshot protocol (reference: whole-graph pickle in
    # ``veles/snapshotter.py``; here state is a pure data tree split
    # from code — SURVEY.md §5.4)
    # ------------------------------------------------------------------
    #: extra scalar/ndarray attributes to persist beside owned Vectors
    SNAPSHOT_ATTRS: tuple = ()
    #: owned Vectors that must NOT be snapshotted (e.g. the loader's
    #: device-resident dataset — large, immutable, rebuilt on resume)
    SNAPSHOT_EXCLUDE: tuple = ()

    def state_dict(self, allow_collective: bool = False) -> dict:
        """``allow_collective=True`` when EVERY process reaches this
        call in lockstep (the in-graph Snapshotter unit: SPMD runs it
        on all processes) — model-sharded persistent state is then
        gathered via the collective read.  Solo callers (the master's
        emergency snapshot) must leave it False."""
        from znicz_tpu.memory import Vector  # local: avoid import cycle
        import numpy as _np
        out: dict = {}
        for name, val in self.__dict__.items():
            if name in self.SNAPSHOT_EXCLUDE:
                continue
            if isinstance(val, Vector) and val:
                if val.needs_collective_read:
                    if val.batch_major:
                        # Batch-sharded buffers are per-minibatch
                        # transients (loader/forward/err chains refill
                        # them before any consumer on resume); never
                        # worth a cross-process all-gather.
                        continue
                    if not allow_collective:
                        # Persistent sharded state (tensor-parallel
                        # weights/momentum) cannot be silently skipped
                        # — resuming would restore fresh random init
                        # for just these layers.  Reading it here
                        # would all-gather, which deadlocks on a solo
                        # snapshot path, so fail loudly instead.
                        raise NotImplementedError(
                            f"{self}: snapshotting model-sharded "
                            f"Vector '{val.name}' outside a lockstep "
                            f"snapshot point — use the Snapshotter "
                            f"unit (all processes) for tensor-"
                            f"parallel state")
                    # lockstep: map_read → device.get →
                    # process_allgather reassembles the full array
                val.map_read()
                # ZeRO-1 state is stored data-axis-sharded and possibly
                # zero-padded; the read above gathered the full array —
                # slice the padding so the checkpoint holds the LOGICAL
                # tensor, independent of the mesh size that wrote it
                out[name] = _np.array(val.strip_data_pad(val.mem),
                                      copy=True)
        for name in self.SNAPSHOT_ATTRS:
            out[name] = getattr(self, name)
        return out

    def load_state(self, state: dict) -> None:
        from znicz_tpu.memory import Vector
        import numpy as _np
        for name, val in state.items():
            cur = self.__dict__.get(name)
            if isinstance(cur, Vector):
                arr = _np.array(val, copy=True)
                if cur and cur.data_shard_dim is not None:
                    # re-shard for the CURRENT mesh: the live Vector's
                    # padding (computed at initialize for this run's
                    # data-axis size) may differ from the writer's
                    arr = cur.apply_data_pad(arr)
                cur.reset(arr)
            else:
                setattr(self, name, val)

    #: a constant of the class, not a knob for unit authors:
    #: ``TrivialUnit`` alone clears it, because its fire does nothing
    #: (counted and timed still, but no span and no histogram sample)
    TRACE_FIRES = True

    # engine hook — called by the workflow scheduler
    def _fire(self) -> None:
        self._record_fire(self.run)

    def _record_fire(self, body):
        """One fire of this unit around ``body()``; returns what it
        returns.  The scheduler's ``_fire`` and the drivers that call
        units outside it (``run_chunked`` and its kin: one fire of the
        loader around a whole chunk's bookkeeping) record the same
        thing through here."""
        start = time.perf_counter()
        if self.TRACE_FIRES and _metrics.enabled():
            # telemetry on: the fire becomes a host span (lined up
            # with XLA device lanes when a profiler window is open)
            # and a sample in the per-unit run-time histogram
            with _tracing.TRACER.span(self.name, cat="unit",
                                      kind=type(self).__name__):
                result = body()
            elapsed = time.perf_counter() - start
            _metrics.unit_run_seconds(self.name).observe(elapsed)
        else:
            result = body()
            elapsed = time.perf_counter() - start
        self.run_time_total += elapsed
        self.run_count += 1
        return result

    def __repr__(self) -> str:
        return f"<{type(self).__name__} '{self.name}'>"


class TrivialUnit(Unit):
    """A no-op unit (useful as a join/fan-out point).  Its fires
    record no span: a ``Repeater`` fires once per step and does
    nothing."""

    TRACE_FIRES = False

    def initialize(self, **kwargs) -> None:
        super().initialize(**kwargs)


class Repeater(TrivialUnit):
    """Opens its gate on ANY incoming link — the loop-closing unit.

    Reference: ``veles/workflow.py`` ``Repeater``; without any-semantics
    a training loop (start_point → repeater ← last backward unit) would
    deadlock waiting for both predecessors every iteration.
    """

    def open_gate(self, src: Unit) -> bool:
        if src in self.links_from:
            self.links_from[src] = True
        return any(self.links_from.values())


class StartPoint(TrivialUnit):
    """The workflow's entry node (reference: ``veles/workflow.py``)."""


class EndPoint(TrivialUnit):
    """The workflow's exit node; firing it completes the run."""

    def run(self) -> None:
        wf = self.workflow
        if wf is not None:
            wf.on_end_point()


class Container(Unit):
    """A unit that owns other units (reference: ``veles/units.py``)."""

    def __init__(self, workflow: "Workflow | None", name: str | None = None,
                 **kwargs) -> None:
        # before super().__init__: _linked_attrs does not exist yet
        object.__setattr__(self, "units", [])
        super().__init__(workflow, name=name, **kwargs)

    def add_ref(self, unit: Unit) -> None:
        if unit is self:
            raise ValueError("a container cannot contain itself")
        taken = {u.name for u in self.units}
        if unit.name in taken:  # unique names (snapshot state keys)
            i = 2
            while f"{unit.name}_{i}" in taken:
                i += 1
            unit.name = f"{unit.name}_{i}"
        self.units.append(unit)
        unit._workflow = self  # type: ignore[assignment]

    def del_ref(self, unit: Unit) -> None:
        self.units.remove(unit)
        unit._workflow = None

    def __iter__(self) -> "Iterable[Unit]":
        return iter(self.units)

    def __len__(self) -> int:
        return len(self.units)

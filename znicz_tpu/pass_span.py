"""A span of the layer table run R times on shared weights — the
looped ("universal", shared-depth) transformer's stack (Ouro, PR 35).

A table entry with ``"passes": R`` belongs to a looped span; adjacent
entries with the key form ONE span (their counts must agree).
``StandardWorkflow`` still builds one forward and one GD unit per
table entry — ``wf.forwards[i]`` / ``wf.gds[i]`` stay one per entry —
and one :class:`PassSpan` per span, which the fused step
(``JitRegion._trace_members``) hands the members to:

.. code-block:: text

    u⁰ = the input of the first member           (the unit before the span)
    for r = 0 … R−1:   u^(r+1) = member_N(… member_1(u^r))
    states = [u¹ … u^R]                          (B, R, …), sequence leading

- **forward**: every member is applied R times inside ONE traced step,
  pass r under the scope ``<unit>/pass<r>``; after each application
  the member's input, output and stashed pullback (``_traced_vjp``)
  go on the span's tape.  Afterwards a member's ``output`` holds its
  LAST application's value and the first member's input is the
  span's again.  The unit after the span reads the last pass's state
  (its ``input`` is the last member's ``output``, as in a chain), or —
  where its class sets ``TAKES_PASSES`` (``ops/loop_exits.py``) — all
  R of them, :attr:`PassSpan.states`;
- **backward**: the passes are walked back from the last to the first.
  Before a member's GD unit fires for pass r, the tape puts that
  application's input, output and pullback back, so the unit's own
  ``xla_run`` is the backward of THAT application: R single-use
  pullbacks a member, consumed in reverse.  The cotangent of u^(r+1)
  is the JOIN of what reaches it from after the span (an exit of pass
  r, or the chain's error for the last pass) and pass r + 1's
  cotangent of its input (:attr:`PassSpan.err_last`, which the last
  member's GD unit reads as its ``err_output``);
- **one update**: a parameter's gradient is the SUM over the passes.
  While a pass other than pass 0 is walked back the phase is
  ``("partial", span)``: ``GradientDescentBase._whole_gradient`` — the
  one home of gradients that arrive in parts — adds the gradient to
  :attr:`PassSpan.partial` (trace-local, f32, under the scope
  ``pass_sum``) and the update does not run; in pass 0, the last
  walked, the phase is ``("whole", span)`` and momentum, decay, clip,
  the anomaly gate and the SDC folds see the sum, once.

Unrolled, not ``lax.scan`` over the passes: the units write their
results into ``Vector``\\ s as they trace, and R single-use pullbacks
are R closures — a scan would need every member rewritten as a pure
function of a carry.  (Not tried; PERF.md §6, PR 35, has the unrolled
program's cold and warm set-up.)

Only members whose backward needs nothing of the forward but its
input, output and pullback may loop (``PASS_SAFE`` on the forward
class: attention, the gated MLP, the RMS norm); a dropout mask or an
expert layer's routing totals would be the LAST pass's in every pass,
so such a member is refused by name.  The numpy eager chain does not
run the passes and refuses a looped table by name at ``initialize``.
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from znicz_tpu.accelerated_units import AcceleratedUnit, set_pass_phase
from znicz_tpu.memory import Vector

#: the layer-table key of a looped span's members
TABLE_KEY = "passes"


def spans_of(layers) -> list[tuple[int, int, int]]:
    """``(first index, one past the last, R)`` of every looped span of
    a layer table: maximal runs of adjacent entries with
    :data:`TABLE_KEY`, whose counts must agree."""
    spans, start = [], None
    for i, spec in enumerate([*layers, {}]):
        passes = spec.get(TABLE_KEY)
        if start is not None and passes != layers[start][TABLE_KEY]:
            if passes is not None:
                raise ValueError(
                    f"layer {i}: '{TABLE_KEY}': {passes} beside layer "
                    f"{i - 1}'s {layers[start][TABLE_KEY]} — adjacent "
                    f"members of a looped span run the same number of "
                    f"passes")
            spans.append((start, i, int(layers[start][TABLE_KEY])))
            start = None
        if start is None and passes is not None:
            start = i
    return spans


class PassSpan(AcceleratedUnit):
    """One looped span (module docstring).  A unit, so that the
    workflow initializes it in its place — after its last member,
    before the unit that reads :attr:`states` — but no node of the
    control graph: the region traces it through its members."""

    def __init__(self, workflow, passes: int, name: str | None = None,
                 **kwargs) -> None:
        super().__init__(workflow, name=name or "pass_span", **kwargs)
        self.passes = int(passes)
        if self.passes < 1:
            raise ValueError(f"{self}: '{TABLE_KEY}' must be ≥ 1, got "
                             f"{passes}")
        #: the members, one forward and one GD unit per table entry,
        #: both in the table's order
        self.forwards: list = []
        self.gds: list = []
        #: every pass's output of the last member, (B, R, …) — only
        #: where the unit after the span takes all passes
        self.states = Vector(name=f"{self.name}.states", batch_major=True)
        self.takes_passes = False
        #: the cotangent of the last member's output in the pass being
        #: walked back (the join; module docstring)
        self.err_last = Vector(name=f"{self.name}.err_last",
                               batch_major=True)
        #: the GD unit after the span: its ``err_input`` is what reaches
        #: the span from there
        self.consumer_gd = None
        #: [member applications run, steps], counted on the device and
        #: read once per epoch (:meth:`on_epoch_ended`)
        self.applications = Vector(name=f"{self.name}.applications")
        #: trace-local: per pass, per member (input, output, pullback);
        #: the passes' partial gradient sums by parameter identity
        self._tape: list = []
        self.partial: dict = {}

    def unserved(self) -> str:
        """What every member says of itself to
        ``export.refuse_unserved`` (``Forward.unserved``)."""
        return (f"is a member of a looped span (table key '{TABLE_KEY}': "
                f"{self.passes} passes over {len(self.forwards)} layers "
                f"on shared weights); serving runs a chain once — a pass "
                f"has no K/V cache of its own and no early exit yet "
                f"(ROADMAP R7, serving half)")

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device=device, **kwargs)
        first, last = self.forwards[0], self.forwards[-1]
        if first.input is None or not first.input or not last.output:
            raise AttributeError(f"{self}: members not allocated yet")
        if self.device.is_host_only:
            raise NotImplementedError(
                f"{self}: the numpy eager chain does not run a looped "
                f"span (table key '{TABLE_KEY}': {self.passes} passes "
                f"over {len(self.forwards)} layers); the passes exist on "
                f"the XLA path only")
        for unit in self.forwards:
            if not getattr(type(unit), "PASS_SAFE", False):
                raise NotImplementedError(
                    f"{self}: {unit} ({type(unit).__name__}) cannot be "
                    f"a member of a looped span ('{TABLE_KEY}'): its "
                    f"backward reads forward state the span does not "
                    f"keep per pass")
        shape = tuple(last.output.shape)
        if self.passes > 1 and tuple(first.input.shape) != shape:
            raise ValueError(
                f"{self}: the last member's output {shape} does not "
                f"return to the first member's input "
                f"{tuple(first.input.shape)}")
        if self.takes_passes and not self.states:
            self.states.reset(np.zeros(
                (shape[0], self.passes) + shape[1:],
                dtype=last.output.mem.dtype))
        if not self.err_last:
            self.err_last.reset(np.zeros(shape, dtype=self.act_store_dtype))
        if not self.applications:
            self.applications.reset(np.zeros(2, np.float32))
        from znicz_tpu.parallel import partition
        for slot in ("states", "err_last"):
            vec = getattr(self, slot)
            if vec:
                partition.declare(self, vec, partition.like(
                    last.output, batch_major=True), slot=slot)
        partition.declare(self, self.applications, partition.REPLICATED,
                          slot="applications")
        self.init_vectors(self.states, self.err_last, self.applications)
        from znicz_tpu.observe import metrics as obs_metrics
        n = len(self.forwards)
        for stat, value in (("passes", self.passes), ("layers", n),
                            ("applications", self.passes * n)):
            obs_metrics.loop(self.name, stat).set(value)
        self.info("%s: %d layers (%s … %s) run %d times on shared "
                  "weights: %d applications a step, one update a "
                  "parameter%s", self.name, n, first.name, last.name,
                  self.passes, self.passes * n,
                  ", every pass's state kept for the exits"
                  if self.takes_passes else "")

    def on_epoch_ended(self) -> None:
        """Read the device's count once (the ``Decision`` calls this
        with the other epoch-end reads), publish it, start over."""
        from znicz_tpu.observe import metrics as obs_metrics
        self.applications.map_read()
        run, steps = (float(v) for v in self.applications.mem)
        #: applications per step over the last epoch
        self.applications_per_step = run / steps if steps else None
        if steps and obs_metrics.enabled():
            obs_metrics.loop(self.name, "applications_per_step").set(
                run / steps)
        self.applications.map_invalidate()
        self.applications.mem[...] = 0.0   # uploaded on the next fire

    def run(self) -> None:      # no node of the control graph
        raise RuntimeError(f"{self} is traced by its region, not fired")

    # -- the traced step (JitRegion._trace_members) ---------------------
    def trace_forward(self, trace) -> None:
        """Apply the forward members ``passes`` times;
        ``trace(unit, r)`` traces one application under its scopes."""
        first, last = self.forwards[0], self.forwards[-1]
        entry = first.input.devmem
        self._tape, states = [], []
        for r in range(self.passes):
            if r:
                first.input.devmem = last.output.devmem
            frame = []
            for unit in self.forwards:
                trace(unit, r)
                frame.append((unit.input.devmem, unit.output.devmem,
                              unit.__dict__.get("_traced_vjp")))
            self._tape.append(frame)
            states.append(last.output.devmem)
        first.input.devmem = entry
        if self.states:
            self.states.devmem = jnp.stack(states, axis=1)
        self.applications.devmem = self.applications.devmem + np.asarray(
            [len(self._tape) * len(self.forwards), 1], np.float32)

    def trace_backward(self, trace) -> None:
        """Walk the passes back, last to first (module docstring)."""
        first = self.forwards[0]
        entry = first.input.devmem
        final = [unit.output.devmem for unit in self.forwards]
        reach = self.consumer_gd.err_input.devmem.astype(jnp.float32)
        members = list(zip(self.forwards, self.gds))
        previous = set_pass_phase(None)
        try:
            for r in reversed(range(self.passes)):
                if self.takes_passes:
                    err = reach[:, r]
                else:
                    err = reach if r == self.passes - 1 else None
                if r < self.passes - 1:
                    # what pass r + 1 sends back to its input joins
                    # what reaches this pass's state from its exit
                    back = self.gds[0].err_input.devmem.astype(
                        jnp.float32)
                    err = back if err is None else err + back
                self.err_last.devmem = err
                set_pass_phase(("partial" if r else "whole", self))
                for (unit, gd), (x, y, vjp) in zip(
                        reversed(members), reversed(self._tape[r])):
                    unit.input.devmem, unit.output.devmem = x, y
                    if vjp is not None:
                        unit._traced_vjp = vjp
                    trace(gd, r)
        finally:
            set_pass_phase(previous)
        first.input.devmem = entry
        for unit, y in zip(self.forwards, final):
            unit.output.devmem = y

    def forget_trace(self) -> None:
        """Drop what a trace left (escaped tracers must not outlive
        it)."""
        self._tape, self.partial = [], {}

"""Accelerated units and the jit-region engine.

Rebuilds the reference's ``AcceleratedUnit`` (reference:
``veles/accelerated_units.py`` — base class whose ``run`` dispatches to
``ocl_run``/``cuda_run``/``numpy_run`` and which builds/caches device
kernels), redesigned for XLA's compilation model:

- every compute unit provides ``numpy_run`` (host oracle — the spec)
  and ``xla_run`` (pure jax ops over its Vectors' ``devmem``);
- there is **no kernel-build machinery** — instead, the hot
  per-minibatch chain of units is compiled into a **jit region**: one
  ``jax.jit``'ed, donated-buffer XLA program produced by tracing each
  member unit's ``xla_run`` in control order.  This replaces the
  reference's per-unit Python dispatch around kernel launches
  (SURVEY.md §3.1 "the whole minibatch step must be ONE jitted
  function").

Unit contract for region membership:

- ``xla_run`` must be *pure device compute*: read ``vector.devmem``,
  write ``vector.devmem``, no ``map_*`` calls, no host branches on
  data values (host branches on *static* flags are fine if the flag is
  part of :meth:`AcceleratedUnit.region_key` — the region recompiles
  per key, e.g. dropout train vs test);
- per-step host bookkeeping goes in ``host_run`` (runs outside the
  region, before it fires);
- random state lives in a Vector of PRNG key data so it is a region
  leaf (see :meth:`AcceleratedUnit.init_rng`).
"""

from __future__ import annotations

import re
import weakref
from typing import Sequence

import numpy as np

import jax

from znicz_tpu.backends import Device, NumpyDevice
from znicz_tpu.memory import Vector
from znicz_tpu.observe import metrics as _metrics
from znicz_tpu.observe import scopes as _scopes
from znicz_tpu.observe import tracing as _tracing
from znicz_tpu.units import Unit
from znicz_tpu.utils import prng
from znicz_tpu.utils.logger import Logger
from znicz_tpu.workflow import Workflow

#: the gradient-accumulation phase active for the CURRENT region trace
#: (round 20).  ``None`` outside accumulation (the historical single
#: fused-batch step); ``("accum", M)`` while tracing a microbatch body
#: whose gradients must be summed into the micro-accumulation buffers
#: without touching parameters; ``("apply", M)`` while tracing the
#: final microbatch, whose body folds the buffered sum into one
#: optimizer step.  Tracing is synchronous and single-threaded inside
#: ``JitRegion.build_callable``, so a module global (set/reset in the
#: traced function body, i.e. AT TRACE TIME) is sufficient — the value
#: never needs to survive into the compiled program, it only steers
#: which ops get traced (``GradientDescentBase._apply_param_xla``,
#: the evaluator's flag seeding, the anomaly guard's commit).
_ACCUM_PHASE: "tuple[str, int] | None" = None


def current_accum_phase() -> "tuple[str, int] | None":
    """The accumulation phase of the region body currently being
    traced (``None`` / ``("accum", M)`` / ``("apply", M)``)."""
    return _ACCUM_PHASE


#: the pass of a looped span whose backward is being traced
#: (``znicz_tpu.pass_span``): ``None`` outside a span; ``("partial",
#: span)`` while walking back a pass that is not the last to be walked
#: (its parameter gradients are parts of a sum), ``("whole", span)`` in
#: the last one walked (pass 0), where the sum is complete.  Set at
#: trace time like :data:`_ACCUM_PHASE` and read at the same place,
#: ``GradientDescentBase._whole_gradient``.
_PASS_PHASE: "tuple | None" = None


def current_pass_phase() -> "tuple | None":
    return _PASS_PHASE


def set_pass_phase(phase: "tuple | None") -> "tuple | None":
    """Install ``phase``; returns the one it replaces."""
    global _PASS_PHASE
    previous, _PASS_PHASE = _PASS_PHASE, phase
    return previous


class AcceleratedUnit(Unit):
    """Base class for compute units with oracle + XLA paths."""

    #: the scopes a unit opens inside its own (``jax.named_scope``),
    #: which ``observe.op_scopes()`` reads as phases of it and of its
    #: backward unit: ``{scope: how}`` in the order they are tested,
    #: ``how`` one of ``observe.scopes.ALL`` / ``PRODUCTS`` — declared
    #: where the scope is opened, and nowhere else
    PHASES: dict = {}

    def __init_subclass__(cls, **kwargs) -> None:
        super().__init_subclass__(**kwargs)
        _scopes.declare(cls.__dict__.get("PHASES", {}))

    def __init__(self, workflow, name: str | None = None, **kwargs) -> None:
        super().__init__(workflow, name=name, **kwargs)
        self.device: Device | None = None
        self._in_region = False
        self.rng_state = Vector(name=f"{self.name}.rng_state")

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------
    def initialize(self, device: Device | None = None, **kwargs) -> None:
        if device is None and isinstance(self.workflow, AcceleratedWorkflow):
            device = self.workflow.device
        if device is None:
            raise ValueError(f"{self}: no device supplied")
        self.device = device
        super().initialize(**kwargs)

    @property
    def compute_dtype(self) -> np.dtype:
        assert self.device is not None
        return self.device.compute_dtype

    @property
    def mxu_dtype(self):
        """Matmul/conv INPUT dtype for the XLA path, from
        ``root.common.precision_type``: ``jnp.bfloat16`` in bf16 mode
        (native MXU dtype — inputs cast down, accumulation and
        parameters stay float32: standard TPU mixed precision), else
        None (full-precision math)."""
        if self.device is not None \
                and self.device.compute_dtype == np.dtype("bfloat16"):
            import jax.numpy as jnp
            return jnp.bfloat16
        return None

    @property
    def act_store_dtype(self) -> np.dtype:
        """STORAGE dtype for activation / error tensors (the big
        batch-major intermediates): ``bfloat16`` in bf16 mode on XLA
        devices, else ``float32``.

        Profiling the AlexNet step (profiles/r03_b256_xla_lrn) showed
        ~60% of device time in bandwidth-bound work over f32
        activations; storing them bf16 halves that traffic.  Math
        still runs in f32 where it matters (GEMM/conv accumulation via
        ``preferred_element_type``, LRN denominators, evaluator loss)
        — this is storage precision, not compute precision.  Params,
        weight gradients, and loss accumulators stay f32.  Opt out:
        ``root.common.engine.bf16_activations = False``.  The numpy
        oracle path (host-only devices) always stores f32.
        """
        from znicz_tpu.utils.config import root
        assert self.device is not None, \
            f"{self}: act_store_dtype before initialize resolved a device"
        if (not self.device.is_host_only
                and self.device.compute_dtype == np.dtype("bfloat16")
                and bool(root.common.engine.get("bf16_activations",
                                                True))):
            import jax.numpy as jnp
            return np.dtype(jnp.bfloat16)
        return np.dtype(np.float32)

    @property
    def fp8_dtype(self):
        """Matmul INPUT dtype under the ``engine.fp8_matmul`` lever
        (round 21, default OFF): ``jnp.float8_e4m3fn`` when the lever
        is on and this jax build carries the dtype, else ``None``.
        Accumulation stays f32 (``preferred_element_type``) and
        parameters stay f32 — fp8 is input precision only, the same
        convergence-gated shape as ``bf16_grad_comms`` (the lever
        stays off until the QUANT_BENCH fp8 A/B and the FP8_TPU chip
        arm clear it)."""
        from znicz_tpu.utils.config import root
        if not bool(root.common.engine.get("fp8_matmul", False)):
            return None
        import jax.numpy as jnp
        return getattr(jnp, "float8_e4m3fn", None)

    def mxu_dot(self, xp, a, b):
        """``a @ b`` routed through the MXU at the configured input
        precision (f32 accumulation); numpy path untouched (oracle).
        Precision ladder: fp8 (``engine.fp8_matmul``) over bf16
        (``precision_type``) over f32."""
        import jax.numpy as jnp
        if xp is jnp:
            dt = self.fp8_dtype or self.mxu_dtype
            if dt is not None:
                return jnp.dot(a.astype(dt), b.astype(dt),
                               preferred_element_type=jnp.float32)
        return xp.dot(a, b)

    def init_vectors(self, *vectors: Vector) -> None:
        """Attach vectors to the device (reference:
        ``AcceleratedUnit.init_vectors``).

        On XLA devices every Vector first BINDS against the owning
        workflow's partition-rule table (``parallel.partition``): its
        canonical ``unit.name/slot`` path resolves to a PartitionSpec
        (first match wins, unmatched = hard error) and the legacy
        slot attributes are stamped FROM that resolution, so
        ``Device.sharding_for`` becomes a table lookup."""
        assert self.device is not None
        from znicz_tpu.parallel import partition
        table = (None if self.device.is_host_only
                 else partition.table_for(self.workflow))
        framework_unit = type(self).__module__.startswith("znicz_tpu")
        for vec in vectors:
            if vec:
                if table is not None:
                    try:
                        partition.bind(table, vec, self.name,
                                       self.device)
                    except partition.UnmatchedLeafError:
                        # the hard-error contract covers the
                        # framework's slot vocabulary; user/test units
                        # with ad-hoc names keep the legacy attribute
                        # path unless they declare rules
                        if framework_unit:
                            raise
                vec.initialize(self.device)

    def partition_leaf(self, slot: str, placement, vec: Vector | None = None,
                       logical_shape=None):
        """Declare this unit's ``slot`` placement in the workflow's
        partition table (an exact-path override rule).  Under
        ``engine.partition_rules=False`` the same decision is applied
        as the legacy slot attributes instead — one call site, two
        arms, pinned bitwise-equal by the golden-table test."""
        from znicz_tpu.parallel import partition
        vec = vec if vec is not None else getattr(self, slot)
        return partition.declare(self, vec, placement, slot=slot,
                                 logical_shape=logical_shape)

    def unmap_vectors(self, *vectors: Vector) -> None:
        for vec in vectors:
            if vec:
                vec.unmap()

    def init_rng(self, gen: "prng.RandomGenerator | None" = None) -> None:
        """Give this unit a device-resident PRNG key chain (a region
        leaf, so stochastic units stay inside jit regions)."""
        gen = gen or prng.get()
        key = gen.key()
        self.rng_state.reset(np.asarray(jax.random.key_data(key)))
        self.init_vectors(self.rng_state)

    def take_key(self):
        """Inside ``xla_run``: split a fresh subkey, advancing the
        device-side chain functionally."""
        key = jax.random.wrap_key_data(self.rng_state.devmem)
        key, sub = jax.random.split(key)
        self.rng_state.devmem = jax.random.key_data(key)
        return sub

    # ------------------------------------------------------------------
    # execution
    # ------------------------------------------------------------------
    def host_run(self) -> None:
        """Per-step host bookkeeping (runs even when the device compute
        is owned by a jit region)."""

    def run(self) -> None:
        self.host_run()
        if self._in_region:
            return  # device compute happens inside the region program
        if self.device is None or self.device.is_host_only:
            self.numpy_run()
        else:
            self.xla_run()
        self.join_beside()

    def join_beside(self) -> None:
        """What reaches this unit beside the chain, joined to what its
        run made — after every run, eager or traced.  Nothing, but for
        a backward unit whose input a unit further on read too
        (``GradientDescentBase.join_beside``)."""

    def numpy_run(self) -> None:
        raise NotImplementedError(f"{type(self).__name__}.numpy_run")

    def xla_run(self) -> None:
        raise NotImplementedError(f"{type(self).__name__}.xla_run")

    # ------------------------------------------------------------------
    # region protocol
    # ------------------------------------------------------------------
    def region_vectors(self) -> list[Vector]:
        """Vectors this unit touches in ``xla_run`` — region leaves.

        Default: every Vector in ``__dict__`` (own state) plus every
        linked attribute resolving to a Vector (inputs from other
        units).  Deterministic order by attribute name.
        """
        found: dict[int, Vector] = {}
        for name in sorted(self.__dict__):
            val = self.__dict__[name]
            if isinstance(val, Vector) and val:
                found.setdefault(id(val), val)
        for name in sorted(self._linked_attrs):
            val = self._linked_attrs[name].get()
            if isinstance(val, Vector) and val:
                found.setdefault(id(val), val)
        return list(found.values())

    def region_key(self) -> tuple:
        """Hashable static flags; region recompiles when they change."""
        return ()


class JitRegion(Logger):
    """Compiles an ordered chain of AcceleratedUnits into one donated
    XLA program per static-key combination."""

    def __init__(self, name: str, units: Sequence[AcceleratedUnit],
                 device: Device, pass_spans: Sequence = ()) -> None:
        super().__init__()
        self.name = name
        self.units = list(units)
        self.device = device
        for unit in self.units:
            unit._in_region = True
        #: looped spans among the members (``znicz_tpu.pass_span``):
        #: the members of one are traced R times, forward in order and
        #: backward in reverse, by the span (:meth:`build_callable`)
        self.pass_spans = list(pass_spans)
        self._vectors: list[Vector] | None = None
        self._cache: dict[tuple, object] = {}

    def _collect_vectors(self) -> list[Vector]:
        seen: dict[int, Vector] = {}
        for unit in (*self.units, *self.pass_spans):
            for vec in unit.region_vectors():
                seen.setdefault(id(vec), vec)
        return list(seen.values())

    @property
    def debug_checks(self) -> bool:
        """``root.common.engine.debug_checks``: compile the region
        through ``checkify`` (NaN / inf / div-by-zero / OOB-index
        checks on every primitive) and raise a located error from
        ``run`` — the debug-mode equivalent of the Vector state
        machine for *inside*-the-program faults (SURVEY.md §5.2).
        Costs a host sync per step and disables buffer donation; for
        debugging, not production."""
        from znicz_tpu.utils.config import root
        return bool(root.common.engine.get("debug_checks", False))

    @staticmethod
    def _jit(body, donate: bool, n_leaves: int):
        """``jax.jit`` of a region body over its leaves.

        A donated program keeps in its signature the leaves a step only
        WRITES (unit outputs, errors, a slab's copy in its readers'
        dtype): jit drops an argument the body never reads, donated or
        not, so the old value's buffer would outlive the dispatch
        beside the new value's — and the compiler plan its temporaries
        against memory that is not free (a program that compiles and
        does not load: PERF.md §6, PR 35, PR 37 and PR 47).  Kept, a
        donated leaf lends its buffer to its successor, and the
        compiler's count of the chip is the runtime's."""
        return jax.jit(
            body, donate_argnums=tuple(range(n_leaves)) if donate else (),
            keep_unused=donate)

    def _dispatch(self, variant: tuple, build, span: str,
                  count: int = 1, donate: bool = True,
                  **span_args) -> None:
        """The ONE dispatch protocol of a region; :meth:`run`,
        :meth:`run_chunk`, :meth:`run_accum` and :meth:`run_undonated`
        pass what differs between their programs and nothing else:

        - ``variant``: the program's tag — ``("step",)``,
          ``("chunk", n)``, ``("accum", n)``, ``("nodonate", phase)``
          — part of the in-memory key and of the persisted one;
        - ``build(skips, leaves)``: the un-jitted, named function;
        - ``span`` / ``span_args``: the warmed call's span is
          ``<span>:<region>`` (cat ``region``) with these arguments,
          and the ``compile:<region>`` span carries the same ones;
        - ``count``: the steps one dispatch adds to
          ``znicz_region_steps_total``;
        - ``donate``: whether the program may reuse its input buffers.

        The protocol: vectors collected once and unmapped, the gate
        skips and the units' static keys make the key, the program is
        looked up; on a miss it comes from the persisted store
        (:meth:`_persisted_program`, which counts and spans its own
        eager compile) or from a lazy ``jax.jit`` whose first call is
        the compile — counted on ``znicz_xla_compiles_total`` and
        spanned ``compile:<region>`` — and is remembered for
        ``observe.op_scopes()`` (:meth:`_remember`: nothing is lowered
        for it unless someone asks); a hit is the cat-``region``
        span alone.  Then the step count, then the leaves go back
        into their Vectors.

        ``engine.debug_checks`` is decided here, once:

        ========  ====================================================
        step      compiled through ``checkify``: no donation (the
                  error pytree breaks input→output aliasing), never
                  persisted, the located error raised after each call
        chunk     ``n`` per-step dispatches of the checked step
                  program (checkify's error pytree does not thread
                  through the scan harness)
        accum     refused: the accumulation scan cannot carry it
        nodonate  refused likewise: the phase programs of the
                  pipeline executor are built bare
        ========  ====================================================
        """
        if self._vectors is None:
            self._vectors = self._collect_vectors()
        vectors = self._vectors
        for vec in vectors:
            vec.unmap()
        skips = tuple(bool(unit.gate_skip) for unit in self.units)
        checks = self.debug_checks
        kind = variant[0]
        if checks and kind == "chunk":
            for _ in range(count):
                self.run()
            return
        if checks and kind != "step":
            raise NotImplementedError(
                f"engine.debug_checks does not compose with the "
                f"'{kind}' program of region '{self.name}' "
                f"(checkify's error state threads through neither the "
                f"accumulation scan nor the pipeline's phase "
                f"programs); disable one of them")
        # the step program's key carries the checkify flag where the
        # others carry their tag: only it is ever built under checkify
        key = tuple(unit.region_key() for unit in self.units) \
            + (skips,) + ((checks,) if kind == "step" else variant)
        fn = self._cache.get(key)
        leaves = [vec._devmem for vec in vectors]
        if fn is None:
            self.debug("region '%s': compiling %s for key %s "
                       "(%d units, %d leaves)", self.name, variant, key,
                       len(self.units), len(vectors))
            body = build(skips, leaves)
            # taken before the call: a donated leaf is gone after it
            structs = [jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype,
                sharding=getattr(leaf, "sharding", None))
                for leaf in leaves]
            jitted = None
            if not checks:  # checkify programs are never persisted
                fn = self._persisted_program(variant + key, body,
                                             leaves, donate=donate)
            if fn is not None:
                self._cache[key] = fn
                out = fn(*leaves)
            else:
                if checks:
                    from jax.experimental import checkify
                    body = checkify.checkify(
                        body, errors=checkify.all_checks)
                    donate = False
                # compile/retrace counter: the steady-state retrace
                # guard asserts this stays flat once every variant is
                # warmed.  jit compiles lazily, so the first dispatch
                # rides inside the compile span — that is where the
                # trace+compile cost actually lands.  JAX's own stamps
                # come out as its children (jax:trace, jax:lower,
                # jax:backend_compile; observe.tracing.watch_startup):
                # what is left of the span is the first execution.
                _metrics.xla_compiles(f"region:{self.name}").inc()
                with _tracing.TRACER.span(f"compile:{self.name}",
                                          cat="compile", **span_args):
                    fn = self._cache[key] = jitted = self._jit(
                        body, donate, len(leaves))
                    out = fn(*leaves)
            self._remember(body, jitted, structs, donate)
        else:
            # the warmed program's call is a span of its own (under
            # wf.run() a child of the region unit's fire): past the few
            # programs the runtime admits in flight the call BLOCKS
            # until one finishes, and that wait is not the unit's work
            with _tracing.TRACER.span(f"{span}:{self.name}",
                                      cat="region", **span_args):
                out = fn(*leaves)
        if checks:
            err, out = out
            err.throw()  # located NaN/inf/OOB report, e.g. "nan
            #              generated by primitive: log" + traceback
        _metrics.region_steps(self.name).inc(count)
        for vec, leaf in zip(vectors, out):
            vec.devmem = leaf
        if checks:
            # a unit that wrote a Vector on the device and left the
            # cast kept of it behind is named here
            for vec in vectors:
                vec.check_cast()

    def _remember(self, body, jitted, structs, donate: bool) -> None:
        """Hand ``observe.op_scopes()`` what it needs to read this
        program's compiled text, should anyone ask: the jitted function
        (a program of the persisted store has none: one is made when
        asked), the leaves' shapes, dtypes and shardings, donation, and
        the member units by name, class, family and the phases their
        classes declare (``PHASES``).  Nothing is
        lowered here.  When asked, the lowering of the SAME jitted
        function finds JAX's own record of the running executable; a
        re-trace (JAX dropped that record, or the store's program)
        runs the body, which leaves tracers in the Vectors — they are
        put back."""
        from znicz_tpu.ops.nn_units import family_of, phases_of
        ref = weakref.ref(self)

        def text() -> str:
            region = ref()
            held = [] if region is None else \
                [(vec, vec._devmem) for vec in region._vectors]
            try:
                fn = jitted if jitted is not None else JitRegion._jit(
                    body, donate, len(structs))
                return fn.lower(*structs).compile().as_text()
            finally:
                for vec, leaf in held:
                    vec._devmem = leaf

        _scopes.remember(
            body.__name__,
            tuple((unit.name, type(unit).__name__, *family_of(unit),
                   phases_of(unit)) for unit in self.units),
            text)

    def run(self) -> None:
        """One region step: the donated ``znicz_step__<region>``
        program."""
        self._dispatch(("step",),
                       lambda skips, leaves: self.build_callable(skips),
                       "dispatch")

    def program_name(self, variant: str) -> str:
        """``znicz_<variant>__<region>``: the name a jitted program's
        function carries, so a profile's module line
        (``jit_znicz_step__train_region``) and an HLO dump say which
        program of which region they show."""
        region = re.sub(r"\W", "_", self.name)
        return f"znicz_{variant}__{region}"

    def _named(self, fn, variant: str):
        fn.__name__ = fn.__qualname__ = self.program_name(variant)
        return fn

    def build_callable(self, skips: tuple[bool, ...],
                       accum_phase: "tuple[str, int] | None" = None):
        """The pure (un-jitted) region function ``leaves -> leaves``,
        wrapping member ``xla_run``s in the Vector tracing harness.
        Single home of the tracing invariant — external jittable entry
        points (``__graft_entry__.entry``) reuse it instead of
        re-threading ``Vector._tracing`` by hand.

        ``accum_phase`` (round 20) selects the gradient-accumulation
        variant of the body: ``("accum", M)`` traces a
        buffer-the-gradients microbatch, ``("apply", M)`` the final
        microbatch that commits one optimizer step from the buffered
        sum.  The phase is installed while the body traces (see
        :data:`_ACCUM_PHASE`), so phase-aware units branch statically
        — each phase is its own compiled program variant.

        The members are traced by :meth:`_trace_members`, which hands
        a looped span (``znicz_tpu.pass_span``) its members; what a
        trace leaves on the units and the spans — single-use pullbacks,
        the passes' tape and partial sums — is dropped in ``finally``.

        The function is named for what it is (:meth:`program_name`:
        ``step``, or ``accum_micro`` / ``apply_micro`` in a phase)."""
        if self._vectors is None:
            self._vectors = self._collect_vectors()
        precision = getattr(self.device, "matmul_precision", "default")
        # the body reaches its region through a weak reference: a
        # jitted body that outlives the workflow (``observe.scopes``
        # keeps one per program until its text is read) pins no unit
        # and no Vector
        ref = weakref.ref(self)

        def fn(*leaves):
            global _ACCUM_PHASE
            region = ref()
            if region is None:
                raise RuntimeError("the region of this body is gone")
            vectors, units = region._vectors, region.units
            prev_phase = _ACCUM_PHASE
            _ACCUM_PHASE = accum_phase
            for vec, leaf in zip(vectors, leaves):
                vec._tracing = True
                vec._devmem = leaf
            try:
                with jax.default_matmul_precision(precision):
                    region._trace_members(units, skips)
                return tuple(vec._devmem for vec in vectors)
            finally:
                _ACCUM_PHASE = prev_phase
                for vec in vectors:
                    vec._tracing = False
                for unit in units:
                    # drop any intra-trace pullback stash a forward
                    # left for a (possibly gate-skipped) GD pair —
                    # escaped tracers must not outlive the trace; a
                    # looped span keeps R of them per member and the
                    # passes' partial gradient sums, dropped likewise
                    if getattr(unit, "_traced_vjp", None) is not None:
                        unit._traced_vjp = None
                    forget = getattr(unit, "forget_trace", None)
                    if forget is not None:   # the stream units' hand-overs
                        forget()
                for span in region.pass_spans:
                    span.forget_trace()

        return self._named(
            fn, f"{accum_phase[0]}_micro" if accum_phase else "step")

    def _trace_members(self, units, skips) -> None:
        """Trace every member that is not skipped, in order, each under
        ``jax.named_scope(unit.name)``, always: the compiled program's
        op metadata says which unit an instruction belongs to
        (``observe.op_scopes()`` reads it).  A scope costs nothing at
        run time and is not part of JAX's cache key.

        The members of a looped span are handed to the span where its
        first forward member and where its first backward member (in
        this order: the LAST layer's) stand: it applies the forward
        members R times and walks the backward members back R times,
        each application under ``<unit>/pass<r>``."""
        def trace(unit, pass_index: int | None = None) -> None:
            with jax.named_scope(unit.name):
                if pass_index is None:
                    unit.xla_run()
                else:
                    with jax.named_scope(f"pass{pass_index}"):
                        unit.xla_run()
                unit.join_beside()

        heads = {}
        for span in self.pass_spans:
            heads[id(span.forwards[0])] = (span, span.forwards,
                                           span.trace_forward)
            heads[id(span.gds[-1])] = (span, span.gds[::-1],
                                       span.trace_backward)
        i = 0
        while i < len(units):
            head = heads.get(id(units[i]))
            if head is None:
                if not skips[i]:
                    trace(units[i])
                i += 1
                continue
            span, members, walk = head
            n = len(members)
            if [id(u) for u in units[i:i + n]] != [id(u) for u in members]:
                raise RuntimeError(
                    f"region '{self.name}': the members of looped span "
                    f"'{span.name}' are not contiguous in the region")
            skipped = set(skips[i:i + n])
            if len(skipped) != 1:
                raise RuntimeError(
                    f"region '{self.name}': looped span '{span.name}' "
                    f"is skipped in part ({skips[i:i + n]})")
            if not skipped.pop():
                walk(trace)
            i += n

    def run_chunk(self, n_steps: int) -> None:
        """Execute ``n_steps`` region steps in ONE dispatch:
        ``lax.scan`` over the region body (the idiomatic JAX training
        loop).  Amortizes per-step dispatch/RPC cost — the difference
        between one host round trip per minibatch and one per chunk.

        Caller contract: every per-step input the device program needs
        must be device-resident and self-advancing across the chunk —
        i.e. the loader runs a device schedule
        (``FullBatchLoader.device_schedule``), PRNG chains / LR state /
        error accumulators are already region leaves — and the static
        key (gate skips, unit modes) must not change within the chunk.
        The caller advances host-side bookkeeping (epoch counters)
        separately; ``StandardWorkflow.run_chunked`` does both.
        """
        if n_steps == 1:
            return self.run()

        def build(skips, leaves):
            body, invariant = self._analyzed_body(
                self.build_callable(skips), leaves)

            def chunk_fn(*leaves):
                scanned = JitRegion._scan_body(body, invariant, leaves,
                                               n_steps)
                return tuple(scanned)

            return self._named(chunk_fn, f"chunk{n_steps}")

        # one span per chunk, not per step
        self._dispatch(("chunk", n_steps), build, "chunk",
                       count=n_steps, steps=n_steps)

    # -- shared scan machinery (run_chunk / run_accum) ------------------
    def _analyzed_body(self, body, leaves):
        """Trace ``body`` once and split its leaves into loop-carried
        vs loop-invariant.

        Loop-invariant analysis: leaves the body never writes
        (datasets, schedule tables) must NOT ride a scan carry — XLA
        copies carries it cannot alias across iterations, which for a
        device-resident dataset means re-copying the whole table every
        step (measured 3.1 ms/step on a 1 GB table — PERF.md round 5).
        A jaxpr outvar that IS the corresponding invar was passed
        through untouched; such leaves become closed-over scan-body
        inputs instead.  Returns ``(body_fn, invariant)`` where the
        probe jaxpr IS the step body — the region is traced once, not
        once per analysis + once per jit."""
        jaxpr = jax.make_jaxpr(body)(*leaves)
        invariant = tuple(
            ov is iv for ov, iv in zip(jaxpr.jaxpr.outvars,
                                       jaxpr.jaxpr.invars))
        from jax.extend import core as jex_core
        return jex_core.jaxpr_as_fun(jaxpr), invariant

    @staticmethod
    def _scan_body(body, invariant, leaves, length: int) -> list:
        """``lax.scan`` the analyzed ``body`` ``length`` times over
        ``leaves``: invariant leaves close over the scan, the rest ride
        the carry; returns the full merged leaf list."""
        ro = [l for l, inv in zip(leaves, invariant) if inv]

        def step(carry, _):
            full, it_c, it_r = [], iter(carry), iter(ro)
            for inv in invariant:
                full.append(next(it_r) if inv else next(it_c))
            out = body(*full)
            return tuple(o for o, inv in zip(out, invariant)
                         if not inv), None

        carry0 = tuple(l for l, inv in zip(leaves, invariant)
                       if not inv)
        out_rw, _ = jax.lax.scan(step, carry0, xs=None, length=length)
        merged, it_w, it_r = [], iter(out_rw), iter(ro)
        for inv in invariant:
            merged.append(next(it_r) if inv else next(it_w))
        return merged

    def run_accum(self, n_micro: int) -> None:
        """One ACCUMULATED optimizer step in ONE dispatch (round 20):
        ``n_micro`` consecutive microbatches from the device-resident
        loader schedule run accumulate-then-apply —

        - microbatches ``0 .. n_micro-2`` trace in ``("accum", M)``
          phase: forwards + backward gradients only, each weighted
          GD summing its gradient into a float32 micro-accumulation
          buffer (``acc_micro_*``) while parameters, momentum and the
          anomaly/SDC state stay untouched;
        - microbatch ``n_micro-1`` traces in ``("apply", M)`` phase:
          its gradient joins the buffered sum, the mean
          ``(Σ grads)/M`` flows through the UNCHANGED update path
          (ZeRO-1, bf16 opt-state, anomaly gate, SDC fingerprints all
          compose), and the buffers are zeroed for the next step.

        The accum microbatches ride a ``lax.scan`` with the same
        loop-invariance analysis as :meth:`run_chunk` (weights,
        momentum and dataset tables close over the scan — they are
        read-only in accum phase), so the whole accumulated step is
        one donated-buffer program: per-chip batch/activation memory
        stays at MICRObatch scale while the effective (optimizer)
        batch is ``n_micro`` times larger.

        Caller contract matches :meth:`run_chunk`, plus: all
        ``n_micro`` schedule entries must be same-class (TRAIN) FULL
        minibatches — ``StandardWorkflow.run_accumulated`` validates
        divisibility and advances the host-side loader mirror.
        """
        if n_micro == 1:
            return self.run()

        def build(skips, leaves):
            accum_body, invariant = self._analyzed_body(
                self.build_callable(skips,
                                    accum_phase=("accum", n_micro)),
                leaves)
            apply_body = self.build_callable(
                skips, accum_phase=("apply", n_micro))

            def accum_fn(*leaves):
                merged = JitRegion._scan_body(accum_body, invariant,
                                              leaves, n_micro - 1)
                return apply_body(*merged)

            # what the persisted key hashes is the jaxpr of this FULL
            # composed accum+apply function — the accum body alone is
            # blind to apply-only constants (lr, momentum), which
            # would let a wrong optimizer step load
            return self._named(accum_fn, f"accum{n_micro}")

        self._dispatch(("accum", n_micro), build, "accum",
                       count=n_micro, micro=n_micro)

    def run_undonated(self,
                      accum_phase: "tuple[str, int] | None" = None,
                      ) -> None:
        """One region step compiled WITHOUT buffer donation, optionally
        in a gradient-accumulation phase — the pipeline executor's
        dispatch primitive (``parallel.pipeline``): its per-microbatch
        activation store holds references to leaf buffers across
        dispatches, which donation would invalidate.  Programs cache
        alongside the donated variants under a distinct key."""
        self._dispatch(
            ("nodonate", accum_phase),
            lambda skips, leaves: self.build_callable(
                skips, accum_phase=accum_phase),
            "dispatch", donate=False)

    def _persisted_program(self, variant: tuple, fn, leaves,
                           donate: bool):
        """Resolve one region program variant through the persisted
        AOT cache (round 23): a deserialized executable on a hit, an
        eagerly-compiled-and-stored one on a miss.  Returns ``None``
        when the cache is disabled or the program is not safely
        keyable — the caller then takes the lazy ``jax.jit`` path,
        bit-identical to the pre-cache behavior.

        Region bodies bake unit hyperparameters into the trace, so
        the key is the **jaxpr hash** of the exact function being
        jitted (plus operand avals, donation, platform, build): the
        hit path still traces — that is what computes the key — but
        skips the XLA compile, which is where nearly all cold-start
        wall-clock lives.  A deserialized load never touches the
        ``region:<name>`` compile counter."""
        from znicz_tpu.serving import aot_cache as _aot
        cache = _aot.active_cache()
        if cache is None:
            return None
        site = f"region:{self.name}"
        # a donated program has every leaf for a parameter, each
        # aliased to an output: not the program a store of before
        # PR 47 holds under the bare key
        kept = ("keep_written_leaves",) if donate else ()
        key = _aot.jaxpr_key(fn, leaves,
                             extra=(site, donate) + tuple(variant) + kept)
        if key is None:
            return None
        donate_argnums = tuple(range(len(leaves))) if donate else ()
        prog = cache.get(key, site)
        if prog is not None:
            prog = _aot.guard_donated(prog, donate_argnums)
        else:
            _metrics.xla_compiles(site).inc()
            with _tracing.TRACER.span(f"compile:{self.name}",
                                      cat="compile"):
                prog = self._jit(fn, donate, len(leaves)).lower(
                    *leaves).compile()
            cache.put(key, prog, site,
                      meta={"family": site,
                            "variant": [str(v) for v in variant[:2]]})
        return self._respecialize_guard(prog, fn, donate_argnums, site)

    @staticmethod
    def _respecialize_guard(prog, fn, donate_argnums, site):
        """An AOT ``Compiled`` is pinned to the exact input shardings
        and devices it was lowered with; lazy ``jax.jit`` transparently
        respecializes when they change between fires (on a mesh the
        compiler assigns shardings to a step's outputs, which become
        the next fire's inputs).  Dispatch the fixed program until it
        rejects its operands, then hand the variant to a lazy jit —
        bit-identical to the pre-cache behavior, and counted as a real
        compile."""
        fallback = None

        def call(*leaves):
            nonlocal fallback
            if fallback is None:
                try:
                    return prog(*leaves)
                except ValueError:
                    _metrics.xla_compiles(site).inc()
                    fallback = jax.jit(fn,
                                       donate_argnums=donate_argnums,
                                       keep_unused=bool(donate_argnums))
            return fallback(*leaves)

        return call


class RegionUnit(AcceleratedUnit):
    """Workflow node that fires a :class:`JitRegion` as one step.

    Wiring pattern (see ``StandardWorkflow``): member units keep their
    ``host_run`` in the control graph *before* this unit; their device
    compute runs here, fused.
    """

    def __init__(self, workflow, units: Sequence[AcceleratedUnit],
                 name: str | None = None, pass_spans: Sequence = (),
                 **kwargs) -> None:
        super().__init__(workflow, name=name or "jit_region", **kwargs)
        self._member_units = list(units)
        self._pass_spans = list(pass_spans)
        self.region: JitRegion | None = None

    def initialize(self, device: Device | None = None, **kwargs) -> None:
        super().initialize(device=device, **kwargs)
        if isinstance(self.device, NumpyDevice):
            # Oracle backend: no compilation; members run themselves.
            for unit in self._member_units:
                unit._in_region = False
            self.gate_skip.value = True
            return
        for unit in self._member_units:
            if not unit.is_initialized:
                raise AttributeError(f"region member {unit} not initialized")
        assert self.device is not None
        self.region = JitRegion(self.name, self._member_units, self.device,
                                pass_spans=self._pass_spans)

    def run(self) -> None:
        assert self.region is not None
        self.region.run()


class AcceleratedWorkflow(Workflow):
    """Workflow owning a device (reference:
    ``veles/accelerated_units.py`` ``AcceleratedWorkflow``)."""

    def __init__(self, workflow=None, name: str | None = None,
                 **kwargs) -> None:
        super().__init__(workflow, name=name, **kwargs)
        self.device: Device | None = None

    def initialize(self, device: Device | None = None, **kwargs) -> None:
        self.device = device if device is not None else Device.create()
        super().initialize(device=self.device, **kwargs)

"""Population engine: K model replicas trained in ONE jit region.

Rebuilds ROADMAP item 5 — Veles's genetics/ensemble plugins trained one
candidate per cluster node; here a *population axis* of K replicas of
one :class:`~znicz_tpu.models.standard_workflow.StandardWorkflow`
architecture trains simultaneously on the mesh:

- the template workflow's hot chain (loader gather → forwards →
  evaluator → backwards → anomaly guard) is extracted through the SAME
  :meth:`JitRegion.build_callable` tracing harness the per-step and
  scan-chunk paths compile — then ``jax.vmap``'ed over a leading
  member axis and jitted once per static region key (zero compiles per
  warmed step; pinned by the retrace guard's population case);
- region leaves split into **shared** (the dataset tables and the
  minibatch schedule — read-only inside a step, decided by the same
  jaxpr ``outvar is invar`` invariance analysis ``run_chunk`` uses)
  and **member-stacked** (parameters, momentum, activations, PRNG key
  chains, each member's epoch shuffle order, each member's
  ``lr_state`` hyperparameters) — stacked leaves live in
  ``member_axis`` Vectors sharded over the mesh's DATA axis, so small
  nets train K-per-chip while an indivisible K stays replicated and
  XLA time-slices;
- every member reproduces its independent sequential run BITWISE: the
  member axis carries each member's own weight init, its own device
  PRNG chain (dropout/stochastic pooling), and its own counter-based
  epoch permutation (``loader.base.epoch_permutation`` over the
  member's snapshotted shuffle seed), so the vmapped step is the K
  sequential trajectories, not an approximation of them
  (``tests/test_population.py`` pins it);
- evolution (tournament selection, arithmetic weight crossover,
  hyperparameter mutation, PBT exploit/explore truncation) runs at
  epoch boundaries as jitted gathers/blends over the stacked tree
  (:mod:`znicz_tpu.population.evolution`) — when the member axis is
  sharded those gathers ARE the cross-chip collectives.

Notes vs the ordinary training stack: ZeRO-1 stays disengaged here by
construction (the template initializes on a mesh-free device — the
member axis owns the data axis, and member-sharding already stores
each member's optimizer state on 1/K of the chips, which is the same
HBM effect); ``engine.debug_checks`` (checkify) is not supported
inside the vmapped program.
"""

from __future__ import annotations

from typing import Callable, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from znicz_tpu.backends import NumpyDevice, XLADevice
from znicz_tpu.loader.base import TRAIN, VALID, epoch_permutation
from znicz_tpu.memory import Vector
from znicz_tpu.observe import metrics as _metrics
from znicz_tpu.observe import tracing as _tracing
from znicz_tpu.population import evolution as _evo
from znicz_tpu.utils import prng
from znicz_tpu.utils.logger import Logger


def leaf_keys(units) -> dict[int, tuple[str, str]]:
    """Stable identity for every Vector a unit chain owns:
    ``id(vector) -> (unit_name, attribute)``, first owner wins in
    deterministic (unit order, sorted attr) order.  Two workflows
    built from the same layers list produce the same key set, which is
    what lets per-member harvested state line up with the template's
    region leaves."""
    out: dict[int, tuple[str, str]] = {}
    for unit in units:
        for attr in sorted(unit.__dict__):
            val = unit.__dict__[attr]
            if isinstance(val, Vector) and val:
                out.setdefault(id(val), (unit.name, attr))
    return out


def harvest_state(workflow) -> dict:
    """Snapshot one freshly-initialized member's population-relevant
    state: every owned Vector's host value by ``(unit, attr)`` key
    plus the loader's counter-based shuffle seed.  Called on throwaway
    builds (one per distinct member seed) — the host PRNG stream is
    device-independent, so a NumpyDevice build harvests the exact
    init an XLA run would start from."""
    out = {}
    for unit in workflow.hot_chain_units():
        for attr in sorted(unit.__dict__):
            val = unit.__dict__[attr]
            if isinstance(val, Vector) and val:
                out.setdefault((unit.name, attr),
                               np.array(np.asarray(val), copy=True))
    return {"vectors": out,
            "shuffle_seed": int(workflow.loader._shuffle_seed)}


class PopulationRegion(Logger):
    """The vmapped K-member step over a template workflow's hot chain.

    Owns the stacked leaves (``member_axis`` Vectors placed through
    ``Device.sharding_for``), the per-static-key program cache, and
    the per-member schedule synchronization.  Drive it like a
    JitRegion: :meth:`step` per minibatch (host bookkeeping rides the
    template loader), read/write leaves via :meth:`read_leaf` /
    :meth:`write_leaf`.
    """

    def __init__(self, template, member_states: Sequence[dict],
                 pop_device=None, name: str = "population") -> None:
        super().__init__()
        self.name = name
        self.template = template
        self.n_members = len(member_states)
        if self.n_members < 1:
            raise ValueError("population needs at least 1 member")
        if template._region_unit is None:
            raise ValueError(
                "population needs an XLA-initialized template "
                "(numpy backend has no jit region to vmap)")
        self.device = template.device
        self.pop_device = pop_device if pop_device is not None \
            else template.device
        self.loader = template.loader
        self.region = template._region_unit.region
        self.units = self.region.units
        self._shuffle_seeds = [int(s["shuffle_seed"])
                               for s in member_states]
        self._programs: dict[tuple, object] = {}
        self._synced_epoch = 0
        self._keyof = leaf_keys(self.units)
        self._lr_vecs = [g.lr_state for g in template.gds
                         if g.lr_state]
        self._build(member_states)

    # ------------------------------------------------------------------
    # construction
    # ------------------------------------------------------------------
    def _train_skips(self) -> tuple:
        """The train-variant gate skips without touching the schedule:
        gate_skip Bools derive from ``loader.minibatch_class``, so
        flipping it to TRAIN momentarily selects the full
        fwd+bwd+update variant (the superset of every variant's
        writes — the right one for invariance analysis)."""
        loader = self.loader
        saved = loader.minibatch_class
        loader.minibatch_class = TRAIN
        try:
            return tuple(bool(u.gate_skip) for u in self.units)
        finally:
            loader.minibatch_class = saved

    def _build(self, member_states: Sequence[dict]) -> None:
        region = self.region
        loader = self.loader
        body = region.build_callable(self._train_skips())
        vectors = region._vectors
        assert vectors is not None
        self.vectors = vectors
        self._index = {id(v): i for i, v in enumerate(vectors)}
        for vec in vectors:
            vec.unmap()
        leaves0 = [vec._devmem for vec in vectors]
        # which leaves does a step WRITE?  (same outvar-is-invar
        # analysis run_chunk uses to keep the dataset off the carry)
        jaxpr = jax.make_jaxpr(body)(*leaves0)
        invariant = [ov is iv for ov, iv in zip(jaxpr.jaxpr.outvars,
                                                jaxpr.jaxpr.invars)]
        for vec, leaf in zip(vectors, leaves0):
            vec._devmem = leaf  # tracing left tracers behind
        lr_ids = {id(v) for v in self._lr_vecs}
        sched_perm = getattr(loader, "sched_perm", None)
        self.member_mask = [
            (not inv) or (vec is sched_perm) or (id(vec) in lr_ids)
            for vec, inv in zip(vectors, invariant)]
        self.in_axes = tuple(0 if m else None for m in self.member_mask)
        # leaves evolution may touch: member state minus each member's
        # identity (its PRNG chain and its own shuffle stream)
        rng_ids = {id(u.rng_state) for u in self.units
                   if getattr(u, "rng_state", None) is not None
                   and u.rng_state}
        self.evolvable = [
            m and vec is not sched_perm and id(vec) not in rng_ids
            for vec, m in zip(vectors, self.member_mask)]

        mesh = getattr(self.pop_device, "mesh", None)
        n_data = getattr(self.pop_device, "n_data_shards", 1)
        if mesh is not None and self.n_members % n_data:
            self.warning(
                "population of %d does not divide the %d-way data "
                "axis — member axis stays replicated (time-sliced)",
                self.n_members, n_data)

        # stack: one member_axis Vector per region leaf.  Placement is
        # DECLARATIVE: each stacked leaf gets a Member rule in the
        # template workflow's partition table (member-axis placement
        # and its divisibility fallback are rule consequences), shared
        # leaves get an explicit replicated rule; the rules-off arm
        # applies the equivalent legacy attributes.
        from znicz_tpu.parallel import partition
        table = partition.table_for(self.template)
        self.svecs: list[Vector] = []
        for vec, member in zip(vectors, self.member_mask):
            key = self._keyof.get(id(vec), (vec.name, ""))
            sname = f"{self.name}.{key[0]}.{key[1] or vec.name}"
            if not member:
                svec = Vector(name=sname)
                placement = partition.REPLICATED
                svec.reset(np.asarray(vec))
            else:
                svec = Vector(name=sname, member_axis=True)
                md = (vec.model_shard_dim + 1
                      if vec.model_shard_dim is not None else None)
                placement = partition.Member(md)
                svec.reset(self._stacked_init(vec, member_states))
            path = partition.path_of(svec)
            if table is not None:
                table.declare_leaf(path, placement)
                table.bind(svec, path, self.pop_device)
            else:
                partition.apply_legacy(svec, partition.materialize(
                    placement, path, tuple(svec.shape),
                    getattr(self.pop_device, "n_data_shards", 1)))
            svec.initialize(self.pop_device)
            self.svecs.append(svec)
        # a leaf that keeps a cast of itself (``Vector.keep_cast``)
        # keeps it stacked: an upload of the stacked leaf makes the
        # stacked copy again, as the template's made its own
        for vec, svec in zip(vectors, self.svecs):
            if vec.cast_copy is not None:
                svec.keep_cast(self.svec(vec.cast_copy),
                               vec.cast_copy.dtype)
        # template device copies are dead weight now — the stacked
        # leaves are the live state; keep only the host mirrors (the
        # export path and schedule bookkeeping read those)
        for vec in vectors:
            vec.map_read()
            vec.reset(vec.mem)
        # pin in/out shardings so host re-uploads (schedule sync,
        # accumulator zeroing) and compiler-chosen output layouts can
        # never disagree — the zero-recompile contract on a mesh
        if mesh is not None:
            self._shardings = tuple(
                self.pop_device.sharding_for(sv) for sv in self.svecs)
        else:
            self._shardings = None
        _metrics.population_members(self.name).set(self.n_members)

    def _stacked_init(self, vec: Vector,
                      member_states: Sequence[dict]) -> np.ndarray:
        loader = self.loader
        if vec is getattr(loader, "sched_perm", None):
            return self.stacked_epoch_orders(0)
        key = self._keyof.get(id(vec))
        base = np.asarray(vec)
        vals = [np.asarray(s["vectors"].get(key, base))
                for s in member_states]
        return np.stack(vals)

    # ------------------------------------------------------------------
    # per-member schedule
    # ------------------------------------------------------------------
    def stacked_epoch_orders(self, epoch: int) -> np.ndarray:
        """(K, total_samples) — every member's sample order for
        ``epoch``, each from its own counter-based shuffle stream
        (test/validation segments ride natural order, identical
        across members; the TRAIN segment is each member's own Philox
        permutation — exactly what K independent loaders would use)."""
        loader = self.loader
        total = loader.total_samples
        lo, hi = loader.class_index_range(TRAIN)
        out = np.tile(np.arange(total, dtype=np.int32),
                      (self.n_members, 1))
        n = hi - lo
        if n > 0 and loader.shuffle_limit > 0:
            eff = min(int(epoch), int(loader.shuffle_limit) - 1)
            for i, seed in enumerate(self._shuffle_seeds):
                out[i, lo:hi] = lo + epoch_permutation(seed, eff, n)
        return out

    def _sync_schedule(self) -> None:
        epoch = int(self.loader.epoch_number)
        if epoch == self._synced_epoch:
            return
        self._synced_epoch = epoch
        sched_perm = getattr(self.loader, "sched_perm", None)
        if sched_perm is None:
            return
        sv = self.svec(sched_perm)
        sv.map_invalidate()
        sv.mem[...] = self.stacked_epoch_orders(epoch)
        # the upload rides the next dispatch's unmap sweep

    # ------------------------------------------------------------------
    # leaf access
    # ------------------------------------------------------------------
    def svec(self, vec: Vector) -> Vector:
        return self.svecs[self._index[id(vec)]]

    def is_member(self, vec: Vector) -> bool:
        return self.member_mask[self._index[id(vec)]]

    def read_leaf(self, vec: Vector) -> np.ndarray:
        """Host copy of a leaf's current value ((K, ...) when
        member-stacked)."""
        sv = self.svec(vec)
        sv.map_read()
        return sv.mem

    def write_leaf(self, vec: Vector, arr: np.ndarray) -> None:
        sv = self.svec(vec)
        sv.map_invalidate()
        sv.mem[...] = arr

    def set_member_lrs(self, lrs: Sequence[float]) -> None:
        """Assign each member its own learning rate (both the weight
        and bias slots — the ``build(learning_rate=…)`` semantic every
        sample uses).  Members then train — and evolution mutates —
        K different rates inside the one compiled program."""
        if len(lrs) != self.n_members:
            raise ValueError(f"{len(lrs)} rates for "
                             f"{self.n_members} members")
        if not self._lr_vecs:
            raise ValueError(
                "template has no promoted lr leaves — call "
                "StandardWorkflow.promote_lr_leaves() before building "
                "the population")
        stacked = np.asarray([[lr, lr] for lr in lrs], dtype=np.float32)
        for vec in self._lr_vecs:
            self.write_leaf(vec, stacked)

    def member_lrs(self) -> np.ndarray:
        """(K,) current per-member learning rates (first promoted GD
        unit's weight-lr slot)."""
        if not self._lr_vecs:
            raise ValueError("no promoted lr leaves")
        return np.array(self.read_leaf(self._lr_vecs[0])[:, 0],
                        dtype=np.float64)

    # ------------------------------------------------------------------
    # dispatch
    # ------------------------------------------------------------------
    def _program(self, key: tuple, skips: tuple):
        fn = self._programs.get(key)
        if fn is None:
            self.debug("population '%s': compiling for key %s "
                       "(%d members, %d leaves)", self.name, key,
                       self.n_members, len(self.svecs))
            _metrics.xla_compiles(f"population:{self.name}").inc()
            body = self.region.build_callable(skips)
            vfn = jax.vmap(body, in_axes=self.in_axes,
                           out_axes=self.in_axes)
            donate = tuple(range(len(self.svecs)))
            if self._shardings is not None:
                fn = jax.jit(vfn, donate_argnums=donate,
                             in_shardings=self._shardings,
                             out_shardings=self._shardings)
            else:
                fn = jax.jit(vfn, donate_argnums=donate)
            self._programs[key] = fn
        return fn

    def _dispatch(self) -> None:
        skips = tuple(bool(u.gate_skip) for u in self.units)
        key = tuple(u.region_key() for u in self.units) + (skips,)
        fn = self._program(key, skips)
        for sv in self.svecs:
            sv.unmap()
        leaves = [sv._devmem for sv in self.svecs]
        with _tracing.TRACER.span(f"population:{self.name}",
                                  cat="region"):
            out = fn(*leaves)
        for sv, leaf in zip(self.svecs, out):
            sv.devmem = leaf
        _metrics.region_steps(f"population:{self.name}").inc()

    def step(self) -> None:
        """One population minibatch step: template-loader host
        bookkeeping (cursor/epoch/flags — shared across members by
        construction: every member has the same schedule geometry),
        per-member schedule sync at epoch boundaries, then ONE device
        dispatch training all K members."""
        self.loader.run()
        self._sync_schedule()
        self._dispatch()

    def run_schedule_entry(self, position: int) -> None:
        """Dispatch the step for one explicit schedule entry (the
        stacked-ensemble aggregate pass): points the device cursor —
        and the template loader's host state — at ``position`` and
        fires the matching variant.  Leaves the training cursor moved;
        use after training only."""
        loader = self.loader
        cls, lo, hi = loader._schedule[position]
        loader.minibatch_class = cls
        loader.minibatch_size = hi - lo
        loader.minibatch_offset = lo
        cursor = getattr(loader, "sched_cursor", None)
        if cursor is None or not cursor:
            raise ValueError("population eval pass needs the "
                             "device-resident schedule")
        self.write_leaf(cursor, np.full((self.n_members,), position,
                                        dtype=np.int32))
        self._dispatch()

    # ------------------------------------------------------------------
    # member readout / install
    # ------------------------------------------------------------------
    def install_member(self, member: int) -> None:
        """Copy member ``member``'s slice of every stacked leaf back
        into the template workflow's Vectors, making the template THE
        member — the bridge to every single-model surface (export,
        ``publish_bundle``, the serving canary/promote pipeline)."""
        if not 0 <= member < self.n_members:
            raise ValueError(f"member {member} out of range")
        for vec, sv, m in zip(self.vectors, self.svecs,
                              self.member_mask):
            if not m:
                continue
            sv.map_read()
            vec.reset(np.array(sv.mem[member], copy=True))

    # ------------------------------------------------------------------
    # evolution
    # ------------------------------------------------------------------
    def evolvable_slots(self) -> list[int]:
        return [i for i, e in enumerate(self.evolvable) if e]

    def lr_slots_within(self, slots: Sequence[int]) -> list[int]:
        lr_ids = {id(v) for v in self._lr_vecs}
        return [j for j, i in enumerate(slots)
                if id(self.vectors[i]) in lr_ids]

    def apply_evolution(self, fn, fitness: np.ndarray, key) -> None:
        """Run a jitted evolution step over the evolvable stacked
        leaves in place."""
        slots = self.evolvable_slots()
        for i in slots:
            self.svecs[i].unmap()
        leaves = [self.svecs[i]._devmem for i in slots]
        out = fn(jnp.asarray(fitness, dtype=jnp.float32), key, *leaves)
        for i, leaf in zip(slots, out):
            self.svecs[i].devmem = leaf
        for i in slots:
            # (a blend of two members' casts is not their blend's cast)
            self.svecs[i].recast()


class PopulationTrainer(Logger):
    """High-level driver: build K members of one sample architecture,
    train them simultaneously through a :class:`PopulationRegion`,
    evolve at epoch boundaries, track per-member fitness.

    Parameters
    ----------
    build_fn:
        ``callable(**build_kwargs) -> StandardWorkflow`` (a sample's
        ``build``).
    n_members / base_seed / member_seeds:
        member *i* is the workflow ``build_fn`` produces after
        ``prng.seed_all(member_seeds[i])`` (default
        ``base_seed + i``) — its weight init, device PRNG chain and
        epoch shuffle stream all follow that seed, exactly as an
        independent run's would.  Repeated seeds share one harvest
        (the genetics mesh path seeds every member identically and
        varies only the learning rate).
    mesh:
        optional ``(data, model)`` mesh; the member axis shards over
        its data axis.  ``None`` = single device.
    member_lrs / lr_bounds:
        optional per-member learning rates (requires promoted lr
        leaves, done automatically) and the clip range evolution
        respects.
    evolve:
        ``"pbt"`` (exploit/explore truncation), ``"ga"`` (tournament +
        arithmetic crossover + lr mutation) or ``None`` (pure stacked
        training — the ensemble/genetics evaluation mode).
    """

    def __init__(self, build_fn: Callable, n_members: int,
                 base_seed: int | None = None,
                 member_seeds: Sequence[int] | None = None,
                 build_kwargs: dict | None = None,
                 mesh=None,
                 member_lrs: Sequence[float] | None = None,
                 lr_bounds: tuple[float, float] | None = None,
                 evolve: str | None = "pbt",
                 evolve_every: int = 1,
                 truncation: float = 0.25,
                 elite: int = 1,
                 mutation_sigma: float = 0.2,
                 explore_factors: tuple[float, float] = (0.8, 1.25),
                 seed: int = 777,
                 name: str = "population") -> None:
        super().__init__()
        from znicz_tpu.utils.config import root
        if n_members < 1:
            raise ValueError("n_members must be >= 1")
        if evolve not in (None, "pbt", "ga"):
            raise ValueError(f"unknown evolve strategy '{evolve}'")
        self.build_fn = build_fn
        self.n_members = int(n_members)
        if member_seeds is not None:
            if len(member_seeds) != n_members:
                raise ValueError("member_seeds length mismatch")
            self.member_seeds = [int(s) for s in member_seeds]
        else:
            base = int(root.common.seed if base_seed is None
                       else base_seed)
            self.member_seeds = [base + i for i in range(n_members)]
        self.build_kwargs = dict(build_kwargs or {})
        self.mesh = mesh
        self.member_lrs = (None if member_lrs is None
                           else [float(x) for x in member_lrs])
        self.lr_bounds = lr_bounds
        self.evolve = evolve
        self.evolve_every = max(1, int(evolve_every))
        self.truncation = float(truncation)
        self.elite = int(elite)
        self.mutation_sigma = float(mutation_sigma)
        self.explore_factors = explore_factors
        self.seed = int(seed)
        self.name = name
        self.template = None
        self.region: PopulationRegion | None = None
        self.history: list[dict] = []
        self.generations = 0
        #: best fitness each member has reached so far (the
        #: min-validation-error tracking a Decision unit would do)
        self.member_best_fitness = np.full(n_members, -np.inf)
        self.best_fitness = -np.inf
        self.best_member: int | None = None
        self._evolve_fn = None
        self._evolve_meta = (None, 0)
        self._base_key = None

    # ------------------------------------------------------------------
    def initialize(self) -> "PopulationTrainer":
        if self.region is not None:
            return self
        if self.mesh is None:
            template_device = XLADevice()
            pop_device = template_device
        else:
            # template traces mesh-free (per-member semantics); the
            # stacked leaves place over the mesh
            template_device = XLADevice(
                device=self.mesh.devices.flat[0])
            pop_device = XLADevice(mesh=self.mesh)
        states: list[dict] = []
        by_seed: dict[int, dict] = {}
        for i, s in enumerate(self.member_seeds):
            if i > 0 and s in by_seed:
                states.append(by_seed[s])
                continue
            prng.seed_all(s)
            wf = self.build_fn(**self.build_kwargs)
            wf._max_fires = None
            if i == 0:
                wf.initialize(device=template_device)
                wf.promote_lr_leaves()
                self.template = wf
            else:
                wf.initialize(device=NumpyDevice())
            state = harvest_state(wf)
            by_seed[s] = state
            states.append(state)
        self.region = PopulationRegion(self.template, states,
                                       pop_device=pop_device,
                                       name=self.name)
        if self.member_lrs is not None:
            self.region.set_member_lrs(self.member_lrs)
        self._base_key = jax.random.key(self.seed)
        return self

    # ------------------------------------------------------------------
    # fitness
    # ------------------------------------------------------------------
    @property
    def _metric_class(self) -> int:
        loader = self.template.loader
        return VALID if loader.class_lengths[VALID] > 0 else TRAIN

    def _read_epoch_fitness(self) -> np.ndarray:
        """(K,) fitness of the epoch that just ended (higher=better):
        ``-validation_err_pt`` for classification,
        ``-validation_mse`` for regression — read from the stacked
        evaluator accumulators, then zeroed exactly as a Decision
        unit zeroes its per-epoch device accumulators."""
        region = self.region
        wf = self.template
        ev = wf.evaluator
        loader = wf.loader
        cls = self._metric_class
        length = max(1, loader.class_lengths[cls])
        if wf.loss == "softmax":
            errs = np.array(region.read_leaf(ev.epoch_n_err),
                            dtype=np.int64)          # (K, 3)
            fitness = -100.0 * errs[:, cls] / length
            region.write_leaf(ev.epoch_n_err, 0)
            if ev.epoch_loss:
                region.write_leaf(ev.epoch_loss, 0.0)
            if getattr(ev, "compute_confusion", False) \
                    and ev.confusion_matrix:
                region.write_leaf(ev.confusion_matrix, 0)
        else:
            sse = np.array(region.read_leaf(ev.epoch_sse),
                           dtype=np.float64)
            fitness = -sse[:, cls] / length
            region.write_leaf(ev.epoch_sse, 0.0)
        return fitness

    def _record_fitness(self, fitness: np.ndarray) -> None:
        self.member_best_fitness = np.maximum(
            self.member_best_fitness, fitness)
        best = int(np.argmax(fitness))
        if fitness[best] > self.best_fitness:
            self.best_fitness = float(fitness[best])
        self.best_member = best
        if _metrics.enabled():
            for i, f in enumerate(fitness):
                _metrics.population_fitness(self.name, i).set(float(f))
            _metrics.population_best_fitness(self.name).set(
                self.best_fitness)

    # ------------------------------------------------------------------
    # evolution
    # ------------------------------------------------------------------
    def _evolution_program(self):
        if self._evolve_fn is not None:
            return self._evolve_fn
        region = self.region
        slots = region.evolvable_slots()
        lr_slots = region.lr_slots_within(slots)
        if self.evolve == "pbt":
            fn, n_cut = _evo.build_pbt_step(
                self.n_members, lr_slots, truncation=self.truncation,
                factors=self.explore_factors, lr_bounds=self.lr_bounds)
            self._evolve_meta = ("pbt", n_cut)
        else:
            blendable = [
                np.issubdtype(region.svecs[i].dtype, np.floating)
                for i in slots]
            fn, n_elite = _evo.build_ga_step(
                self.n_members, blendable, lr_slots, elite=self.elite,
                mutation_sigma=self.mutation_sigma,
                lr_bounds=self.lr_bounds)
            self._evolve_meta = ("ga", n_elite)
        _metrics.xla_compiles(f"population-evolve:{self.name}").inc()
        donate = tuple(range(2, 2 + len(slots)))
        if region._shardings is not None:
            # pin leaf shardings through the evolution program too —
            # a compiler-chosen (replicated) output here would break
            # the step program's pinned input shardings next dispatch
            from znicz_tpu.parallel import replicated_sharding
            rep = replicated_sharding(self.mesh)
            leaf_sh = tuple(region._shardings[i] for i in slots)
            self._evolve_fn = jax.jit(
                fn, donate_argnums=donate,
                in_shardings=(rep, rep) + leaf_sh,
                out_shardings=leaf_sh)
        else:
            self._evolve_fn = jax.jit(fn, donate_argnums=donate)
        return self._evolve_fn

    def evolve_generation(self, fitness: np.ndarray) -> None:
        """Apply one evolution generation to the stacked tree (called
        at epoch boundaries by :meth:`run`; callable directly)."""
        if self.evolve is None or self.n_members < 2:
            return
        fn = self._evolution_program()
        key = jax.random.fold_in(self._base_key, self.generations)
        self.region.apply_evolution(fn, fitness, key)
        self.generations += 1
        strategy, n = self._evolve_meta
        if _metrics.enabled():
            _metrics.population_generations(self.name).inc()
            if strategy == "pbt":
                _metrics.population_evolution(self.name,
                                              "exploit").inc(n)
                _metrics.population_evolution(self.name,
                                              "explore").inc(n)
            else:
                refilled = self.n_members - n
                _metrics.population_evolution(self.name,
                                              "crossover").inc(refilled)
                _metrics.population_evolution(self.name,
                                              "mutate").inc(refilled)

    # ------------------------------------------------------------------
    # driving
    # ------------------------------------------------------------------
    def run_epoch(self) -> np.ndarray:
        """One full epoch over the schedule for all K members; returns
        the (K,) epoch fitness."""
        region = self.region
        loader = self.template.loader
        while True:
            region.step()
            if loader.epoch_ended:
                break
        fitness = self._read_epoch_fitness()
        self._record_fitness(fitness)
        return fitness

    def run(self, max_epochs: int | None = None) -> list[dict]:
        """Train the population for ``max_epochs`` (default: the
        template Decision's budget), evolving every ``evolve_every``
        epochs (never after the final one — there is nothing left to
        train the mutated members on)."""
        if self.region is None:
            self.initialize()
        if max_epochs is None:
            max_epochs = self.template.decision.max_epochs
        if not max_epochs:
            raise ValueError("max_epochs undecided: pass it here or "
                             "in the template's decision_config")
        for epoch in range(int(max_epochs)):
            fitness = self.run_epoch()
            entry = {
                "epoch": epoch,
                "fitness": [float(f) for f in fitness],
                "best": float(np.max(fitness)),
                "mean": float(np.mean(fitness)),
                "best_member": int(np.argmax(fitness)),
            }
            if self.region._lr_vecs:
                entry["lrs"] = [float(x)
                                for x in self.region.member_lrs()]
            self.history.append(entry)
            self.info("epoch %d: best %.4f mean %.4f (member %d)",
                      epoch, entry["best"], entry["mean"],
                      entry["best_member"])
            if epoch + 1 < max_epochs \
                    and (epoch + 1) % self.evolve_every == 0:
                self.evolve_generation(fitness)
        return self.history

    # ------------------------------------------------------------------
    # best-member egress (the PBT -> serving loop)
    # ------------------------------------------------------------------
    def install_best(self) -> int:
        """Write the current best member's state into the template
        workflow; returns the member index."""
        if self.best_member is None:
            raise RuntimeError("run() first")
        self.region.install_member(self.best_member)
        return self.best_member

    def export_best(self, path: str) -> str:
        self.install_best()
        return self.template.export_forward(path)

    def publish_best(self, directory: str,
                     prefix: str = "model") -> tuple[int, str]:
        """Publish the best member as the next monotonic
        sha256-sidecar bundle in ``directory`` — the handoff the
        round-13 canary/promote pipeline picks up, closing the
        PBT→serving loop."""
        from znicz_tpu.resilience.publisher import publish_bundle
        self.install_best()
        return publish_bundle(self.template, directory, prefix=prefix)


def train_drafter(build_fn: Callable, n_members: int = 4, *,
                  publish_dir: str, prefix: str = "drafter",
                  mesh=None, base_seed: int = 211,
                  lr_bounds: tuple[float, float] = (0.01, 0.4),
                  evolve: str = "pbt", evolve_every: int = 2,
                  seed: int = 97, name: str = "drafter",
                  **trainer_kwargs) -> tuple[int, str,
                                             "PopulationTrainer"]:
    """The speculative-decoding drafter hook (round 15): train a
    SMALL causal-LM population with the round-14 engine, publish the
    best member through the round-13 pipeline, and hand the bundle
    path to the decode engine's draft/verify loop.

    ``build_fn`` must produce the drafter architecture (a tiny
    token-first chain — embedding → causal attention → last_token →
    softmax); the population varies seeds and learning rates, trains
    every member in ONE vmapped jit region, and the fittest member
    becomes the drafter.  A drafter is pure throughput machinery —
    the big model's verification forward decides every token, so a
    mediocre drafter costs acceptance rate, never correctness.

    Returns ``(version, bundle_path, trainer)`` — the bundle carries
    the usual sha256 sidecar, so a
    :class:`~znicz_tpu.resilience.publisher.PublicationWatcher` can
    also hot-refresh drafters later."""
    trainer = PopulationTrainer(
        build_fn, n_members, base_seed=base_seed, mesh=mesh,
        lr_bounds=lr_bounds, evolve=evolve,
        evolve_every=evolve_every, seed=seed, name=name,
        **trainer_kwargs)
    trainer.initialize()
    trainer.run()
    version, path = trainer.publish_best(publish_dir, prefix=prefix)
    return version, path, trainer

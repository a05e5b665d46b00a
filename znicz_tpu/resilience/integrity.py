"""Silent-data-corruption sentinel: fingerprints, audits, quarantine.

Every defense the resilience stack shipped before this round catches
*loud* failures — non-finite losses (guard), crashed processes
(elastic supervisor), corrupt files (sha256 sidecars).  A defective
accelerator that computes *plausible-but-wrong* values trips none of
them: the numbers are finite, the process is alive, the bytes on disk
digest cleanly — and the bad chip silently poisons weights or logits.
Fleet experience (Hochschild et al., "Cores that don't count",
HotOS'21; Dixit et al., "Silent Data Corruptions at Scale", 2021) puts
such chips at ~1/1000 machines.  This module is the detection layer:

- **Step fingerprints** — every weighted GD unit folds a cheap
  sub-sampled checksum of its post-update parameters (and its folded
  gradient) into a shared device vector hosted by the
  :class:`~znicz_tpu.resilience.guard.AnomalyGuard`
  (``sdc_fingerprint``, seeded by the evaluator each train step).
  The fold rides the existing ``_apply_param_xla`` path inside the
  SAME jit region — zero extra compiles, zero extra per-step d2h
  (the fingerprint is read at the sentinel's vote cadence, like the
  guard's anomaly state).  The samples are taken WHERE THEY LIE: the
  positions come from the static shape on the host
  (:func:`sample_positions`) and one gather at those constant
  multi-dimensional indices reads them; only the ~64 samples are cast
  to f32.  Not through a flat view: a TPU keeps a tensor tiled
  (``T(8,128)``), so ``ravel`` is no bitcast there and XLA rewrote
  the WHOLE tensor in linear order to pick 64 elements of it — three
  folds per parameter per step, each a ``copy`` of
  ``f32[16384,8,8,128]`` (1.63 ms) for an OLMoE expert slab: 18
  copies, 29.4 ms of a 165 ms step, 1.19 x the momentum update they
  guard (PERF.md §5/§6, PR 25/26).  On the host the cast-first form
  copied every tensor a vote read back.

- **Cross-replica vote** — post-update parameters are definitionally
  identical across data replicas, so per-replica fingerprints must
  agree.  At ``engine.sdc_vote_interval`` the sentinel all-gathers
  ``(claimed device fp, host-recomputed fp, sticky self-check)``
  triples and :func:`vote_verdict` localizes a diverging chip/host.
  The HOST recompute (this process's local param copy) is the replica
  comparison — an in-program fold can be GSPMD-homogenized (sharded
  reduction reads each row from its owner) and must not be trusted
  for cross-host divergence.  Localization is self-evident either
  way GSPMD compiles the fold: a homogenized claimed fp disagrees
  with the corrupt host's local recompute, while per-device folds
  trip the guard's sticky temporal self-check — so even a 2-process
  gang names the culprit; ≥3 processes also majority-vote on the
  host fingerprints.  Scope: the vote sees divergence in state that
  replicas maintain INDEPENDENTLY (pure-DP parameters).  Under
  ZeRO-1 the per-step reduce-scatter/all-gather re-derives params
  from shared collectives, so per-host corruption becomes globally
  CONSISTENT corruption within one step — invisible to any replica
  compare and exactly what the redundant-compute audit exists for.

- **Redundant-compute audit** — at ``engine.sdc_audit_interval`` the
  last microbatch's step is replayed on a SHADOW oracle (the numpy
  backend — a genuinely different compute substrate on CPU meshes,
  and always a different chip than the suspect accelerator): the
  sentinel captures pre-step state, lets the device run the step, then
  replays it through a numpy-backend clone of the workflow and
  compares per-tensor post-update parameter fingerprints within
  ``engine.sdc_audit_rtol``.  A confirmed mismatch attributes
  ``znicz_sdc_suspect_total{process,device}`` and escalates.

- **Quarantine** — under an elastic gang (round 18), a confirmed
  culprit annotates the heartbeat channel (culprit ids + the
  last-known-good PRE-divergence snapshot recorded at the last clean
  vote) and exits :data:`~znicz_tpu.resilience.supervisor.EXIT_SDC`;
  healthy peers exit ``EXIT_PEER_LOST`` after annotating, and the
  :class:`~znicz_tpu.resilience.supervisor.ElasticSupervisor`
  restarts the survivors from the pre-divergence snapshot with the
  culprit blocklisted (``znicz_host_losses_total{kind=sdc}``).
  Unsupervised runs roll back to the last-known-good snapshot
  in-process.  Serving-side quarantine lives in
  :mod:`znicz_tpu.serving.engine` (sampled shadow audit) +
  :class:`~znicz_tpu.serving.fleet.ReplicaGroup` (replica removal).

Drillable fault sites: ``sdc.flip_param`` / ``sdc.flip_grad`` (an
exponent-scale multiplier applied to one element on one process —
rides a device leaf like the guard's NaN injection, so injecting never
recompiles) and ``sdc.serving_bitflip`` (a serving replica's replies
corrupted post-program).

Gate: ``root.common.engine.sdc_fingerprints`` (default on whenever the
anomaly guard is on).  Knobs: ``sdc_vote_interval`` (50),
``sdc_audit_interval`` (0 = off), ``sdc_suspect_threshold`` (1),
``sdc_fp_rtol`` / ``sdc_audit_rtol`` (1e-3).
"""

from __future__ import annotations

import math
import os

import numpy as np

from znicz_tpu.observe import metrics as _metrics
from znicz_tpu.observe import tracing as _tracing
from znicz_tpu.resilience import faults as _faults
from znicz_tpu.utils.config import root
from znicz_tpu.utils.logger import Logger

#: the sdc injection sites the guard's device leaf hosts
SDC_TRAIN_SITES = ("sdc.flip_param", "sdc.flip_grad")

#: elements sampled per tensor by the fingerprint (static stride from
#: the static shape — the fold compiles into the existing region)
FP_SAMPLES = 64


def enabled() -> bool:
    """The sentinel gate: ``engine.sdc_fingerprints`` (default on).
    The fold itself only engages where the guard wired the fingerprint
    vector, so this is a build-time decision like the guard's."""
    return bool(root.common.engine.get("sdc_fingerprints", True))


def sample_positions(shape) -> tuple[np.ndarray, ...]:
    """THE definition of which elements a fingerprint reads: flat
    (row-major) positions ``0, stride, 2·stride, …`` with ``stride =
    max(1, n // FP_SAMPLES)``, returned as one index array per
    dimension of ``shape``.  Computed on the host from the static
    shape, so inside a jit region they are constants."""
    n = math.prod(shape)
    stride = max(1, n // FP_SAMPLES)
    return np.unravel_index(np.arange(0, n, stride), shape)


def tensor_fingerprint(xp, arr):
    """Position-weighted sub-sampled checksum of one tensor.

    Samples ``~FP_SAMPLES`` elements at a static stride (element 0
    always included — deterministic coverage of the drill's flip
    target) and folds them with position weights so swapped values
    cannot cancel.  Works identically for ``xp`` = numpy (host/oracle
    recompute) and jax.numpy (the in-region fold); all math in f32 so
    a healthy device fold and the same fold re-traced later are
    bitwise-stable.

    The samples are read WHERE THEY LIE — one gather at the constant
    multi-dimensional indices of :func:`sample_positions` — and cast
    to f32 after sampling.  Never through ``ravel(arr)[::stride]``:
    that reads the same elements, but on a TPU a flat view of a tiled
    tensor is a whole-tensor relayout, and a cast before sampling is a
    whole-tensor pass on either backend (module docstring).
    """
    positions = sample_positions(arr.shape)
    sample = arr[positions].astype(xp.float32)
    weights = 1.0 + (xp.arange(len(positions[0]), dtype=xp.float32)
                     % 31.0)
    return xp.sum(sample * weights)


def host_param_fingerprint(workflow) -> float:
    """Recompute the parameter fingerprint ON THE HOST from the same
    tensors the device fold covered (each GD unit records the exact
    Vector set it folded — see ``GradientDescentBase._fp_folded``),
    in the same order.  f64 accumulation: the comparison against the
    device's f32 fold is tolerance-based (``engine.sdc_fp_rtol``)."""
    total = 0.0
    for gd_unit in getattr(workflow, "gds", ()):
        for vec in getattr(gd_unit, "_fp_folded", {}).values():
            vec.map_read()
            total += float(tensor_fingerprint(np, np.asarray(vec.mem)))
    return total


def audit_fingerprints(workflow) -> list[tuple[str, float]]:
    """Per-tensor host fingerprints ``[(vector name, fp)]`` over every
    parameter the device fold covers — the audit compares these
    between the device run and the shadow oracle so a mismatch is
    attributable to a named tensor."""
    out = []
    for gd_unit in getattr(workflow, "gds", ()):
        for vec in getattr(gd_unit, "_fp_folded", {}).values():
            vec.map_read()
            out.append((vec.name,
                        float(tensor_fingerprint(np,
                                                 np.asarray(vec.mem)))))
    return out


def _rel_diff(a: float, b: float) -> float:
    return abs(a - b) / max(abs(a), abs(b), 1e-12)


def vote_verdict(device_fps, host_fps, rtol: float,
                 self_flags=None) -> dict:
    """Pure verdict over the all-gathered fingerprint evidence.

    ``device_fps[p]`` is process p's on-device claimed param
    fingerprint; ``host_fps[p]`` its host recompute over the same
    buffers; ``self_flags[p]`` its guard's sticky self-check mismatch
    count (a param that mutated between one step's post-update fold
    and the next step's pre-update refold).  Returns
    ``{"divergent": bool, "culprits": [p...], "self_bad": [p...]}``:

    - all device fingerprints agree (within ``rtol``) and nothing
      self-flagged → clean;
    - a process that self-flagged (sticky on-device check) or whose
      claimed fold disagrees with its own host recompute saw its
      parameters mutate outside any computation — a self-evident
      culprit, localizable even in a 2-process gang
      (``sdc.flip_param``'s exact signature);
    - otherwise the minority cluster of device fingerprints is the
      culprit set (majority vote, needs ≥3 voters); a tie (2-process
      gang, divergence through the compute path) marks every
      divergent member suspect — the redundant-compute audit is the
      tiebreaker.
    """
    device_fps = [float(v) for v in device_fps]
    host_fps = [float(v) for v in host_fps]
    n = len(device_fps)
    flags = ([float(v) for v in self_flags]
             if self_flags is not None else [0.0] * n)
    # the HOST fingerprints are the replica-state comparison: each is
    # computed from that process's LOCAL copy of the parameters, which
    # GSPMD cannot homogenize (an in-program fold is free to be
    # computed as a sharded reduction + all-reduce, which reads each
    # row from its OWNER's copy and hides per-host divergence)
    divergent = any(_rel_diff(host_fps[0], v) > rtol
                    for v in host_fps[1:])
    self_bad = [p for p in range(n)
                if flags[p] > 0.0
                or _rel_diff(device_fps[p], host_fps[p]) > rtol]
    if not divergent and not self_bad:
        return {"divergent": False, "culprits": [], "self_bad": []}
    if self_bad:
        return {"divergent": True, "culprits": sorted(self_bad),
                "self_bad": sorted(self_bad)}
    # cluster host fingerprints; minority cluster(s) are the culprits
    clusters: list[list[int]] = []
    for p, v in enumerate(host_fps):
        for cluster in clusters:
            if _rel_diff(host_fps[cluster[0]], v) <= rtol:
                cluster.append(p)
                break
        else:
            clusters.append([p])
    biggest = max(len(c) for c in clusters)
    majority = [c for c in clusters if len(c) == biggest]
    if len(majority) == 1 and biggest > n - biggest:
        culprits = sorted(p for c in clusters if c is not majority[0]
                          for p in c)
    else:  # tie: every divergent member is suspect
        culprits = list(range(n))
    return {"divergent": True, "culprits": culprits, "self_bad": []}


class IntegritySentinel(Logger):
    """Host-side driver of the SDC detectors for one training
    workflow.  Ticked by the Decision unit every step boundary
    (:meth:`on_step`) — all processes tick in lockstep, so the vote's
    all-gather is a legal collective."""

    def __init__(self, workflow, **overrides) -> None:
        super().__init__()
        engine = root.common.engine
        self.workflow = workflow
        self.vote_interval = int(overrides.get(
            "vote_interval", engine.get("sdc_vote_interval", 50)))
        self.audit_interval = int(overrides.get(
            "audit_interval", engine.get("sdc_audit_interval", 0)))
        self.suspect_threshold = int(overrides.get(
            "suspect_threshold", engine.get("sdc_suspect_threshold", 1)))
        self.fp_rtol = float(overrides.get(
            "fp_rtol", engine.get("sdc_fp_rtol", 1e-3)))
        self.audit_rtol = float(overrides.get(
            "audit_rtol", engine.get("sdc_audit_rtol", 1e-3)))
        self._tick = 0
        self._suspect_streak: dict[int, int] = {}
        self._audit_streak = 0
        #: newest snapshot known to PREDATE any divergence — recorded
        #: at every clean vote; the quarantine resume target
        self.last_good_snapshot: str | None = None
        self._pending_audit_state: dict | None = None
        self._shadow = None
        self.quarantined = False

    # ------------------------------------------------------------------
    def read_device_fingerprint(self) -> np.ndarray | None:
        """The guard-hosted f32[5] fingerprint state (one tiny d2h at
        vote/audit cadence only) — [claimed param fp, grad fp,
        pre-update refold, sticky self-check mismatches, previous
        claimed fp]; None when absent (guard off, population-stacked
        state)."""
        guard = getattr(self.workflow, "anomaly_guard", None)
        if guard is None:
            return None
        return guard.read_sdc_fingerprint()

    # ------------------------------------------------------------------
    # the per-step tick (Decision._resilience_tick)
    # ------------------------------------------------------------------
    def on_step(self) -> None:
        if self.quarantined:
            return
        self._tick += 1
        if self.audit_interval > 0:
            if self._pending_audit_state is not None:
                with _tracing.TRACER.span("sdc_audit", cat="resilience",
                                          tick=self._tick):
                    self._run_audit()
            elif (self._tick + 1) % self.audit_interval == 0:
                # the NEXT step is the audit target: capture its
                # pre-state now (we are at the boundary before it)
                self._capture_audit_state()
        if self.vote_interval > 0 and self._tick % self.vote_interval == 0:
            # the vote reads every parameter back (a host_read span
            # each) and fingerprints it on the host: the device idles
            with _tracing.TRACER.span("sdc_vote", cat="resilience",
                                      tick=self._tick):
                self._vote()

    # ------------------------------------------------------------------
    # cross-replica vote
    # ------------------------------------------------------------------
    def _vote(self) -> None:
        wf = self.workflow
        fp = self.read_device_fingerprint()
        if fp is None or fp[0] == 0.0:
            return  # no train step folded yet (or stacked state)
        from znicz_tpu.parallel.process_shard import (_exact_allgather,
                                                      process_info)
        pidx, pcount = process_info()
        host_fp = host_param_fingerprint(wf)
        triple = [fp[0], host_fp, fp[3]]  # claimed, recomputed, sticky
        if pcount == 1:
            # single process: the self-checks alone (sticky on-device
            # count + claimed-vs-host-recompute) — catch a post-fold
            # buffer mutation without any peer to compare against
            verdict = vote_verdict([triple[0]], [triple[1]],
                                   self.fp_rtol,
                                   self_flags=[triple[2]])
        else:
            gathered = _exact_allgather(
                np.asarray(triple, dtype=np.float64))  # (P, 3)
            verdict = vote_verdict(gathered[:, 0], gathered[:, 1],
                                   self.fp_rtol,
                                   self_flags=gathered[:, 2])
        if not verdict["divergent"]:
            _metrics.sdc_votes(wf.name, "clean").inc()
            self._suspect_streak.clear()
            snap = getattr(wf, "snapshotter", None)
            dest = getattr(snap, "destination", None)
            if dest and os.path.exists(dest):
                self.last_good_snapshot = dest
            return
        _metrics.sdc_votes(wf.name, "divergent").inc()
        _metrics.sdc_detected("vote").inc()
        for p in verdict["culprits"]:
            _metrics.sdc_suspects(p, "-").inc()
            self._suspect_streak[p] = self._suspect_streak.get(p, 0) + 1
        self.warning(
            "SDC vote DIVERGENT at tick %d: culprits=%s (self-evident="
            "%s, last_good=%s)", self._tick, verdict["culprits"],
            verdict["self_bad"], self.last_good_snapshot)
        confirmed = [p for p, s in self._suspect_streak.items()
                     if s >= self.suspect_threshold]
        if confirmed:
            self._quarantine(confirmed, detector="vote")

    # ------------------------------------------------------------------
    # redundant-compute audit
    # ------------------------------------------------------------------
    def _shadow_workflow(self):
        if self._shadow is None:
            self._shadow = self.workflow.build_shadow()
        return self._shadow

    def _capture_audit_state(self) -> None:
        wf = self.workflow
        from znicz_tpu.parallel.process_shard import process_info
        if process_info()[1] > 1:
            # multi-process audits would need per-process 1/N replay;
            # the cross-replica vote is the multi-host detector
            return
        try:
            self._pending_audit_state = wf.state_dict()
        except Exception as exc:  # noqa: BLE001 — audit must not kill
            self.warning("audit state capture failed: %s", exc)
            self._pending_audit_state = None

    def _run_audit(self) -> None:
        """Replay the step that JUST ran on the device (pre-state was
        captured at the previous boundary) through the numpy-backend
        shadow and compare per-tensor post-update fingerprints."""
        wf = self.workflow
        state = self._pending_audit_state
        self._pending_audit_state = None
        from znicz_tpu.utils import prng as _prng
        saved_prng = _prng.get().get_state()
        try:
            # the shadow's load_state/step must not perturb the LIVE
            # process's global PRNG stream (bit-identical trajectory
            # with and without audits — test-pinned)
            shadow = self._shadow_workflow()
            shadow.load_state(state)
            shadow.loader.run()
            for unit in shadow.hot_chain_units()[1:]:
                if not unit.gate_block and not unit.gate_skip:
                    unit.run()
            # same declarative config → same construction order →
            # identical unit/vector names, so names key the comparison
            shadow_fps = dict(audit_fingerprints(shadow))
        except Exception as exc:  # noqa: BLE001 — audit must not kill
            self.warning("shadow audit replay failed: %s", exc)
            return
        finally:
            _prng.get().set_state(saved_prng)
        device_fps = audit_fingerprints(wf)
        mismatched = []
        for name, dev_fp in device_fps:
            ref = shadow_fps.get(name)
            if ref is None:
                continue
            if _rel_diff(dev_fp, ref) > self.audit_rtol:
                mismatched.append((name, dev_fp, ref))
        if not mismatched:
            _metrics.sdc_audits(wf.name, "match").inc()
            self._audit_streak = 0
            return
        _metrics.sdc_audits(wf.name, "mismatch").inc()
        _metrics.sdc_detected("audit").inc()
        from znicz_tpu.parallel.process_shard import process_info
        pidx = process_info()[0]
        _metrics.sdc_suspects(pidx, "-").inc()
        self._audit_streak += 1
        self.warning(
            "SDC audit MISMATCH at tick %d: device step diverged from "
            "the shadow oracle on %s", self._tick,
            [(n, f"{d:.6g}!={r:.6g}") for n, d, r in mismatched])
        if self._audit_streak >= self.suspect_threshold:
            self._quarantine([pidx], detector="audit")

    # ------------------------------------------------------------------
    # quarantine
    # ------------------------------------------------------------------
    def _quarantine(self, culprits: list[int], detector: str) -> None:
        """Confirmed-corrupt escalation.  Supervised gang: annotate
        the heartbeat channel (culprits + pre-divergence snapshot +
        detection attestations) and exit — the culprit with EXIT_SDC
        (blocklist me), the healthy peers with EXIT_PEER_LOST (their
        next collective can never complete anyway); the
        ElasticSupervisor restarts the survivors from the
        pre-divergence snapshot.  Unsupervised: roll back to the
        last-known-good snapshot in-process and keep going — weights
        poisoned after the divergence are discarded either way."""
        from znicz_tpu.parallel.process_shard import process_info
        from znicz_tpu.resilience import supervisor as _sup
        wf = self.workflow
        pidx = process_info()[0]
        self.quarantined = True
        sup = getattr(wf, "_worker_supervisor", None)
        from znicz_tpu.observe import recorder as _recorder
        _recorder.record("sdc_quarantine", detector=detector,
                         culprits=",".join(str(c) for c in culprits),
                         process=pidx,
                         last_good=self.last_good_snapshot)
        self.warning("SDC quarantine (%s): culprits=%s, self=%d, "
                     "last_good=%s", detector, culprits, pidx,
                     self.last_good_snapshot)
        if sup is not None and getattr(sup, "writer", None) is not None:
            plan = _faults.active()
            sup.writer.annotate(
                sdc_culprits=list(culprits),
                sdc_last_good=self.last_good_snapshot,
                sdc_detected={detector: 1},
                faults_injected=(plan.counts() if plan else {}))
            if pidx in culprits:
                os._exit(_sup.EXIT_SDC)
            os._exit(_sup.EXIT_PEER_LOST)
        _metrics.sdc_quarantined("host").inc()
        path = self.last_good_snapshot
        if path and os.path.exists(path):
            from znicz_tpu.utils.snapshotter import Snapshotter
            wf.load_state(Snapshotter.load(path))
            guard = getattr(wf, "anomaly_guard", None)
            if guard is not None:
                guard.reset_sdc_fingerprint()
            _metrics.recoveries("sdc_rollback").inc()
            self.warning("rolled back to pre-divergence snapshot %s",
                         path)
            self.quarantined = False  # state is clean again
            self._audit_streak = 0
            self._suspect_streak.clear()
        else:
            self.warning("no pre-divergence snapshot recorded — "
                         "sentinel stands down (suspect state kept)")

"""Launcher: workflow lifecycle owner + execution-mode select.

Rebuilds the reference's ``veles/launcher.py``.  The reference Launcher
picked standalone / ``--master`` / ``--slave`` mode, owned the Twisted
reactor, spawned the graphics server and drove ``workflow.run``.

TPU-first deltas (SURVEY.md §2.5, §5.8): the master–slave cluster
(Twisted TCP control + ZeroMQ data plane, ``veles/server.py`` /
``veles/client.py``) is replaced by **synchronous SPMD** — every host
runs the same program over a global device mesh and XLA inserts the
gradient all-reduce over ICI/DCN.  So "mode" here means:

- *standalone*: single process on one device, or — when ``n_model`` /
  ``n_seq`` ask for a mesh — over every locally visible device (one
  process drives all the chips of a host, no ``jax.distributed``);
- *distributed*: ``jax.distributed.initialize`` (PJRT multi-host
  bootstrap over DCN) — the ``--listen`` host is process 0
  ("master" in reference terms: it owns snapshots and logging), every
  other host joins with ``--master host:port`` exactly like reference
  slaves did.  There is no job queue: the loader shards minibatches
  over the mesh's ``data`` axis instead
  (``generate_data_for_slave`` → sharding spec).

Failure handling parity (SURVEY.md §5.3): SPMD is gang-scheduled, so
the reference's elastic drop-slave/requeue becomes **checkpoint +
auto-resume**: SIGINT/SIGTERM write an emergency snapshot, and
``retries > 0`` re-enters the run loop resuming from the newest
snapshot.
"""

from __future__ import annotations

import glob
import os
import signal
import traceback
from typing import Any, Callable

from znicz_tpu.backends import Device
from znicz_tpu.utils.config import root
from znicz_tpu.utils.logger import Logger
from znicz_tpu.utils.snapshotter import Snapshotter
from znicz_tpu.workflow import Workflow


class Launcher(Logger):
    """Owns device selection, distributed bootstrap and the run loop.

    The reference sample protocol is preserved: every sample module
    exposes ``run(load, main)``; :meth:`boot` calls it with closures
    bound to this launcher — ``load(factory, **kwargs)`` constructs
    (or resumes) the workflow, ``main(**kwargs)`` initializes and runs
    it.
    """

    def __init__(self, backend: str | None = None,
                 snapshot: str | None = None,
                 listen: str | None = None,
                 master: str | None = None,
                 n_processes: int | None = None,
                 process_id: int | None = None,
                 retries: int = 0,
                 graphics: bool | None = None,
                 web_status: int | None = None,
                 web_status_host: str = "127.0.0.1",
                 load_kwargs: dict | None = None,
                 chunk: int = 1,
                 n_model: int = 1,
                 n_seq: int = 1,
                 **kwargs) -> None:
        super().__init__(**kwargs)
        if snapshot is None:
            # elastic restart contract (round 18): the gang supervisor
            # hands the relaunched workers the newest digest-verified
            # snapshot through the env so the SAME command line resumes
            snapshot = os.environ.get("ZNICZ_RESUME_SNAPSHOT") or None
        #: model-axis size for the global mesh (tensor parallelism over
        #: the distributed device grid; 1 = pure DP)
        self.n_model = int(n_model)
        #: seq-axis size for the global mesh (sequence parallelism —
        #: the ring rides its own third axis on a data×model×seq grid;
        #: 1 = historical 2-D mesh)
        self.n_seq = int(n_seq)
        #: steps per device dispatch (>1 → StandardWorkflow.run_chunked)
        self.chunk = int(chunk)
        self.backend = backend
        self.snapshot = snapshot
        self.retries = int(retries)
        #: extra kwargs merged into every _load(factory, ...) call —
        #: the channel by which embedding drivers (e.g. --optimize
        #: trials) parameterize a sample's build without editing it
        self.load_kwargs = dict(load_kwargs or {})
        self.web_status = web_status  # port (0 = auto) or None = off
        self.web_status_host = web_status_host  # "0.0.0.0" for remote
        self.web_server = None
        self.workflow: Workflow | None = None
        self.device: Device | None = None
        self._snapshot_state: dict | None = None
        self._graphics = graphics
        self._interrupted = False
        self._old_handlers: dict[int, Any] = {}
        #: round 18: in-process elastic supervision (attached by
        #: run_workflow when the heartbeat channel is configured)
        self._worker_supervisor = None
        # distributed mode ------------------------------------------------
        if listen and master:
            raise ValueError("--listen and --master are exclusive")
        self.coordinator = listen or master
        self.process_id = process_id
        self.n_processes = n_processes
        self.is_master = master is None  # standalone or the --listen host
        if not self.coordinator:
            # env bring-up (parallel.distributed contract): export
            # ZNICZ_COORDINATOR / ZNICZ_NUM_PROCESSES /
            # ZNICZ_PROCESS_ID and run the SAME command on every host
            # — the pod-scale path where flags never differ per host
            from znicz_tpu.parallel import distributed
            spec = distributed.env_spec()
            if spec is not None:
                self.coordinator = spec["coordinator_address"]
                self.n_processes = spec.get("num_processes",
                                            self.n_processes)
                if self.process_id is None:
                    self.process_id = spec.get("process_id")
                self.is_master = (self.process_id or 0) == 0
                self._init_distributed(self.is_master)
                return
        if self.coordinator:
            self._init_distributed(listen is not None)

    # ------------------------------------------------------------------
    # modes
    # ------------------------------------------------------------------
    @property
    def mode(self) -> str:
        if not self.coordinator:
            return "standalone"
        return "master" if self.is_master else "slave"

    def _init_distributed(self, is_coordinator: bool) -> None:
        """PJRT multi-host bootstrap (replaces the reference's
        Server/Client handshake; reference: ``veles/server.py``) —
        idempotent, shared with bench.py via
        ``parallel.distributed.ensure_initialized``."""
        import jax

        from znicz_tpu.parallel import distributed
        process_id = self.process_id
        if process_id is None and is_coordinator:
            process_id = 0
        self.info("distributed init (%s) @ %s",
                  "coordinator" if is_coordinator else "worker",
                  self.coordinator)
        distributed.ensure_initialized(
            coordinator=self.coordinator,
            num_processes=self.n_processes,
            process_id=process_id)
        self.is_master = jax.process_index() == 0

    # ------------------------------------------------------------------
    # device
    # ------------------------------------------------------------------
    def make_device(self) -> Device:
        if self.device is None:
            on_mesh = bool(self.coordinator) or self.n_model > 1 \
                or self.n_seq > 1
            backend = self.backend or root.common.engine.backend
            if on_mesh and backend == "numpy":
                raise ValueError(
                    "a device mesh requires an XLA backend — the "
                    "host-only numpy oracle cannot join one (in "
                    "distributed mode each process would silently "
                    "train an independent replica)")
            if on_mesh:
                # SPMD over a mesh (data × model[, seq]); XLA lays the
                # gradient all-reduce over ICI/DCN.  Distributed mode:
                # the GLOBAL mesh of all hosts' devices — the whole
                # point of the bootstrap, a local-only device would
                # silently train per-host replicas.  Standalone: this
                # host's devices.
                import jax

                from znicz_tpu.backends import TPUDevice, XLADevice
                from znicz_tpu.parallel import make_mesh
                cls = TPUDevice if backend == "tpu" else XLADevice
                self.device = cls(mesh=make_mesh(
                    n_model=self.n_model, n_seq=self.n_seq,
                    devices=jax.devices(cls.platform)))
            else:
                self.device = Device.create(self.backend)
        return self.device

    # ------------------------------------------------------------------
    # reference sample protocol: run(load, main)
    # ------------------------------------------------------------------
    def boot(self, run_fn: Callable) -> Workflow:
        """Drive a sample module's ``run(load, main)``."""
        run_fn(self._load, self._main)
        if self.workflow is None:
            raise RuntimeError(
                "run(load, main) never called load(factory, ...)")
        return self.workflow

    def _load(self, factory: Callable[..., Workflow], **kwargs):
        """Construct the workflow; stage snapshot state when resuming.

        Returns ``(workflow, snapshot_was_loaded)`` like the reference
        ``Main._load``.
        """
        merged = dict(self.load_kwargs)
        merged.update(kwargs)
        self.workflow = factory(**merged)
        loaded = False
        if self.snapshot:
            self._snapshot_state = Snapshotter.load(self.snapshot)
            loaded = True
            self.info("staged snapshot %s", self.snapshot)
        return self.workflow, loaded

    def _main(self, **kwargs) -> None:
        wf = self.workflow
        if wf is None:
            raise RuntimeError("main() called before load()")
        attempt = 0
        while True:
            try:
                self.run_workflow(wf, **kwargs)
                return
            except KeyboardInterrupt:
                raise
            except Exception:
                attempt += 1
                if attempt > self.retries:
                    raise
                latest = self.latest_snapshot(wf)
                self.warning("workflow crashed (attempt %d/%d):\n%s",
                             attempt, self.retries,
                             traceback.format_exc())
                if latest:
                    self.info("auto-resume from %s", latest)
                    self._snapshot_state = Snapshotter.load(latest)

    # ------------------------------------------------------------------
    def run_workflow(self, workflow: Workflow, **kwargs) -> Workflow:
        """initialize → (resume state) → run, with signal-safe
        emergency snapshots."""
        if self._graphics is not None:
            # reference Launcher owned the graphics server spawn; here
            # the render thread starts lazily on first plotter use —
            # the flag force-disables (or pre-warms) it
            root.common.graphics.render = bool(self._graphics)
            if self._graphics:
                from znicz_tpu import graphics
                graphics.get_server()
        if self.web_status is not None and self.web_server is None \
                and self.is_master:
            from znicz_tpu.web_status import WebStatusServer
            self.web_server = WebStatusServer(
                port=self.web_status, host=self.web_status_host)
        if self.web_server is not None:
            self.web_server.register(workflow)
        device = self.make_device()
        if not workflow.is_initialized:
            workflow.initialize(device=device, **kwargs)
        if self._snapshot_state is not None:
            workflow.load_state(self._snapshot_state)
            self._snapshot_state = None
        # round 18: elastic supervision — ZNICZ_HEARTBEAT_DIR (or
        # engine.heartbeat_dir) attaches the per-process heartbeat
        # writer, the preemption handler (SIGTERM → barriered
        # checkpoint-on-signal at the next step boundary) and the
        # collective-hang self-watchdog; process 0 additionally feeds
        # the peer-age gauges /metrics + /readyz expose
        from znicz_tpu.resilience import supervisor as _supervisor
        sup_cfg = _supervisor.worker_config()
        if sup_cfg is not None and self._worker_supervisor is None:
            self._worker_supervisor = _supervisor.WorkerSupervisor(
                workflow, is_master=self.is_master, **sup_cfg)
            self._worker_supervisor.attach()
        self._install_signal_handlers(workflow)
        try:
            if self.chunk > 1 and hasattr(workflow, "run_chunked"):
                workflow.run_chunked(self.chunk)
            else:
                workflow.run()
        except KeyboardInterrupt:
            self._emergency_snapshot(workflow)
            raise
        finally:
            self._restore_signal_handlers()
            if self._worker_supervisor is not None:
                self._worker_supervisor.detach()
                self._worker_supervisor = None
        return workflow

    # ------------------------------------------------------------------
    # failure handling (SURVEY.md §5.3 parity)
    # ------------------------------------------------------------------
    def _install_signal_handlers(self, workflow: Workflow) -> None:
        def handler(signum, frame):
            if self._interrupted:  # second signal: hard exit
                raise KeyboardInterrupt
            supervisor = self._worker_supervisor
            if supervisor is not None and signum == signal.SIGTERM:
                # round 18 preemption path: defer to the NEXT step
                # boundary — the whole gang checkpoints at the same
                # barrier step (master writes, others fence on the
                # sidecar) and exits EXIT_PREEMPTED, losing at most
                # the one in-flight step.  Signal-safe: one flag file.
                self._interrupted = True
                supervisor.request_preempt(f"signal {signum}")
                return
            self._interrupted = True
            self.warning("signal %d: emergency snapshot + stop", signum)
            self._emergency_snapshot(workflow)
            workflow.stop()

        for sig in (signal.SIGINT, signal.SIGTERM):
            try:
                self._old_handlers[sig] = signal.signal(sig, handler)
            except ValueError:  # not the main thread (tests)
                pass

    def _restore_signal_handlers(self) -> None:
        for sig, old in self._old_handlers.items():
            try:
                signal.signal(sig, old)
            except ValueError:
                pass
        self._old_handlers.clear()
        self._interrupted = False

    def _emergency_snapshot(self, workflow: Workflow) -> str | None:
        if not self.is_master:  # reference: master owns snapshots
            return None
        try:
            path = Snapshotter.write(
                workflow.state_dict(), str(root.common.dirs.snapshots),
                workflow.name, "interrupted")
            self.info("emergency snapshot → %s", path)
            return path
        except Exception:  # pragma: no cover - best effort on the way out
            self.exception("emergency snapshot failed")
            return None

    def latest_snapshot(self, workflow: Workflow) -> str | None:
        """Newest snapshot belonging to THIS workflow (for auto-resume).

        The snapshots directory is shared between samples, so only
        files matching the workflow's snapshotter prefix (or the
        workflow name for emergency snapshots) are candidates."""
        snap = getattr(workflow, "snapshotter", None)
        if snap is not None and snap.destination:
            return snap.destination
        # glob fallback: search the workflow's own snapshot directory
        # (it may differ from the global default) plus the default
        directory = str(root.common.dirs.snapshots)
        directories = {directory}
        prefixes = {workflow.name}
        if snap is not None:
            prefixes.add(snap.prefix)
            directories.add(snap.directory)
        files: list[str] = []
        for d in directories:
            for prefix in prefixes:
                files += glob.glob(
                    os.path.join(d, f"{prefix}_*.pickle.gz"))
        files.sort(key=os.path.getmtime)
        return files[-1] if files else None

    # ------------------------------------------------------------------
    def stop(self) -> None:
        if self.workflow is not None:
            self.workflow.stop()

"""Elastic drill worker: one gang member of a supervised streaming run.

Runnable as ``python -m znicz_tpu.resilience.elastic_worker <out.json>
<shard_dir>`` under the :class:`~znicz_tpu.resilience.supervisor.
ElasticSupervisor` env contract (``ZNICZ_COORDINATOR`` /
``ZNICZ_NUM_PROCESSES`` / ``ZNICZ_PROCESS_ID`` /
``ZNICZ_HEARTBEAT_DIR`` / ``ZNICZ_RESUME_SNAPSHOT`` /
``ZNICZ_ELASTIC_ATTEMPT``).  Each process:

1. pins its platform + per-process device count (CPU drills; on a pod
   leave ``ZNICZ_ELASTIC_PLATFORM`` empty and the ambient TPU runtime
   wins),
2. boots the Launcher — the env contract performs the
   ``jax.distributed`` bring-up (bounded by
   ``engine.dist_init_timeout_s``) and attaches the
   :class:`~znicz_tpu.resilience.supervisor.WorkerSupervisor`
   (heartbeats, preemption, watchdog),
3. trains a small streaming-loader MLP (per-process 1/N reads,
   ZeRO-1 on the data axis, a lockstep Snapshotter every epoch), and
4. writes a JSON digest: bitwise weight sha256, resume position,
   warmed-step compile delta, the partition table's bound mesh — what
   the elastic tests and the dryrun attest parity and reshard from.

Chaos rides the normal seeded recipe: the supervisor exports
``ZNICZ_ELASTIC_FAULTS`` (a JSON recipe over the ``host.loss`` /
``host.preempt`` / ``heartbeat.stall`` / ``checkpoint.signal_corrupt``
sites) on attempt 0 only, so the restarted gang runs clean.
"""

from __future__ import annotations

import hashlib
import json
import os
import sys


def build_workflow(shard_dir: str, snapshot_dir: str,
                   minibatch_size: int = 16, max_epochs: int = 6):
    """Streaming 2-layer MLP over the drill shard set — small enough
    for a sub-minute CPU gang, real enough to exercise ZeRO-1 (data
    axis > 1), the counter-based shuffle and mid-epoch resume."""
    from znicz_tpu.loader.streaming import StreamingLoader
    from znicz_tpu.models.standard_workflow import StandardWorkflow

    wf = StandardWorkflow(
        name="elastic_mlp",
        loader_factory=lambda w: StreamingLoader(
            w, shard_dir, minibatch_size=minibatch_size,
            prefetch_depth=2,
            normalization_scale=2.0 / 255.0, normalization_bias=-1.0),
        layers=[
            {"type": "all2all_tanh",
             "->": {"output_sample_shape": 16, "weights_filling": "he"},
             "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
            {"type": "softmax",
             "->": {"output_sample_shape": 4, "weights_filling": "he"},
             "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
        ],
        decision_config={"max_epochs": max_epochs,
                         "fail_iterations": 10 ** 6},
        snapshotter_config={"prefix": "elastic",
                            "directory": snapshot_dir,
                            "keep_last": 10})
    wf._max_fires = 10 ** 6
    # the drill needs a deterministic checkpoint cadence: snapshot at
    # EVERY epoch boundary, not only on validation improvement — and
    # with a UNIQUE suffix per write (the default best-error suffix
    # overwrites same-error epochs, which would mutate the very file a
    # parity reference must later restore from)
    wf.snapshotter.gate_skip = ~wf.decision.epoch_ended
    snap = wf.snapshotter
    snap.snapshot_suffix = (
        lambda: f"ep{int(wf.loader.epoch_number):03d}")
    return wf


def main() -> None:
    out_path = sys.argv[1]
    shard_dir = sys.argv[2]
    snapshot_dir = os.environ.get(
        "ZNICZ_ELASTIC_SNAPSHOT_DIR",
        os.path.join(os.path.dirname(out_path), "snapshots"))
    minibatch = int(os.environ.get("ZNICZ_ELASTIC_BATCH", "16"))
    max_epochs = int(os.environ.get("ZNICZ_ELASTIC_EPOCHS", "6"))
    devices_per_proc = int(os.environ.get("ZNICZ_ELASTIC_DEVICES", "2"))
    platform = os.environ.get("ZNICZ_ELASTIC_PLATFORM", "cpu")

    import jax
    if platform:
        jax.config.update("jax_platforms", platform)
    if platform == "cpu":
        jax.config.update("jax_num_cpu_devices", devices_per_proc)

    from znicz_tpu.launcher import Launcher
    from znicz_tpu.observe import metrics as obs_metrics
    from znicz_tpu.utils import prng
    from znicz_tpu.utils.config import root

    faults_json = os.environ.get("ZNICZ_ELASTIC_FAULTS")
    if faults_json:
        root.common.engine.faults = json.loads(faults_json)
    for env, knob, cast in (
            ("ZNICZ_COLLECTIVE_TIMEOUT_S", "collective_timeout_s", float),
            ("ZNICZ_HEARTBEAT_INTERVAL_S", "heartbeat_interval_s", float),
            ("ZNICZ_HEARTBEAT_TIMEOUT_S", "heartbeat_timeout_s", float),
            ("ZNICZ_DIST_INIT_TIMEOUT_S", "dist_init_timeout_s", float),
            ("ZNICZ_PREEMPT_BARRIER_STEPS", "preempt_barrier_steps",
             int),
            # round 19: the SDC sentinel's drill knobs.  The vote
            # drill turns ZeRO-1 OFF: pure-DP replicas maintain params
            # independently, so a flipped copy STAYS divergent (ZeRO-1
            # re-derives params from shared collectives every step,
            # healing per-host divergence into globally-consistent
            # poison — the audit's territory, not the vote's)
            ("ZNICZ_SDC_VOTE_INTERVAL", "sdc_vote_interval", int),
            ("ZNICZ_SDC_SUSPECT_THRESHOLD", "sdc_suspect_threshold",
             int),
            ("ZNICZ_ZERO1", "zero1", lambda v: bool(int(v)))):
        val = os.environ.get(env)
        if val:
            setattr(root.common.engine, knob, cast(val))

    # the env contract drives the distributed bring-up + the resume
    # snapshot + the WorkerSupervisor attach — nothing per-host here.
    # NOTE: bring-up must precede ANY jax computation (seeding included)
    launcher = Launcher()
    prng.seed_all(1234)

    def run(load, main_fn):  # reference sample protocol
        load(build_workflow, shard_dir=shard_dir,
             snapshot_dir=snapshot_dir, minibatch_size=minibatch,
             max_epochs=max_epochs)
        main_fn()

    wf = launcher.boot(run)  # Preempted (SystemExit 75) propagates

    # -- digest: what the parity drill compares bitwise -----------------
    loader = wf.loader
    region_unit = wf._region_unit
    warmed_delta = -1
    if region_unit is not None:
        compiles = obs_metrics.xla_compiles(f"region:{region_unit.name}")
        before = compiles.value
        loader.run()          # lockstep on every process: one warmed
        region_unit.run()     # extra step must compile NOTHING
        warmed_delta = int(compiles.value - before)
    loader.stop()

    sha = hashlib.sha256()
    sums = []
    import numpy as np
    for fwd in wf.forwards:
        for vec in (fwd.weights, fwd.bias):
            if vec is None or not vec:
                continue
            vec.map_read()
            arr = np.ascontiguousarray(vec.mem)
            sha.update(arr.tobytes())
            sums.append(float(np.asarray(arr, dtype=np.float64).sum()))

    from znicz_tpu.resilience import faults as _faults
    plan = _faults.active()
    digest = {
        "process_id": int(jax.process_index()),
        "n_processes": int(jax.process_count()),
        "faults_injected": dict(plan.counts()) if plan else {},
        "sdc_fingerprint": (
            None if wf.anomaly_guard is None
            or wf.anomaly_guard.read_sdc_fingerprint() is None
            else [float(v) for v in
                  wf.anomaly_guard.read_sdc_fingerprint()]),
        "n_global_devices": len(jax.devices()),
        "attempt": int(os.environ.get("ZNICZ_ELASTIC_ATTEMPT", "0")),
        "resumed_from": os.environ.get("ZNICZ_RESUME_SNAPSHOT") or None,
        "weights_sha256": sha.hexdigest(),
        "weight_sums": sums,
        "min_validation_n_err": int(wf.decision.min_validation_n_err),
        "epochs_done": int(loader.epoch_number),
        "warmed_step_compiles": warmed_delta,
        "local_batch": int(loader.local_batch),
        "bound_mesh": wf.partition.bound_mesh,
        "snapshot_destination": (wf.snapshotter.destination
                                 if wf.snapshotter else None),
    }
    with open(out_path, "w") as fh:
        json.dump(digest, fh)
    print(f"elastic worker {digest['process_id']}: OK "
          f"(mesh={digest['bound_mesh']}, "
          f"sha={digest['weights_sha256'][:12]})", flush=True)


if __name__ == "__main__":
    main()

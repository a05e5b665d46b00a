"""Worker process for the multi-process distributed bootstrap test.

Each OS process runs this script with a distinct ``process_id``; the
Launcher's ``--listen`` / ``--master`` path performs the PJRT
bootstrap (``jax.distributed.initialize``) — the TPU-first equivalent
of the reference's in-process master+slave localhost test (reference:
``veles/tests/test_client_server.py``; SURVEY.md §4 "distributed
tests").  Every process contributes 2 virtual CPU devices, the
Launcher builds the GLOBAL 4-device mesh, and the sample's workflow
trains SPMD over it.  On exit each process writes a JSON digest of the
trained weights; the parent test asserts both digests are identical —
the modern form of "master and slave agree on the trained model".

Run directly (the test spawns two of these):

    python tests/dist_worker.py <process_id> <n_processes> \
        <coordinator host:port> <out.json>
"""

import json
import sys


def build_workflow(tp_dir: "str | None" = None, learning_rate=0.1,
                   max_epochs=3, tp: "bool | None" = None):
    """Tiny blob-classification MLP, mirroring the layer/optimizer
    config of ``tests/test_parallel.build``.  The data generator is
    duplicated here on purpose: importing ``tests.conftest`` (where
    ``make_blobs`` lives) would pin 8 virtual devices per process at
    import time, while this worker needs exactly 2.

    ``tp_dir``: tensor-parallel variant — the hidden FC pair goes
    column+row over the global mesh's model axis and a Snapshotter
    writes into this directory (the lockstep collective-read snapshot
    path for model-sharded state)."""
    import numpy as np

    from znicz_tpu.loader.fullbatch import ArrayLoader
    from znicz_tpu.models.standard_workflow import StandardWorkflow

    tp = (tp_dir is not None) if tp is None else tp
    n_classes, dim, per_class = 3, 12, 40
    rnd = np.random.RandomState(7)
    centers = rnd.uniform(-4.0, 4.0, size=(n_classes, dim))
    data = np.concatenate(
        [centers[c] + rnd.normal(0.0, 1.0, size=(per_class, dim))
         for c in range(n_classes)]).astype(np.float32)
    labels = np.repeat(np.arange(n_classes, dtype=np.int32), per_class)
    order = rnd.permutation(len(data))
    data, labels = data[order], labels[order]
    n_train = 96
    wf = StandardWorkflow(
        name="dist_mlp",
        loader_factory=lambda w: ArrayLoader(
            w,
            train_data=data[:n_train], train_labels=labels[:n_train],
            valid_data=data[n_train:], valid_labels=labels[n_train:],
            minibatch_size=24),
        layers=[
            {"type": "all2all_tanh",
             "->": {"output_sample_shape": 16,
                    "model_parallel": "column" if tp else None},
             "<-": {"learning_rate": learning_rate,
                    "gradient_moment": 0.9}},
            {"type": "all2all_tanh",
             "->": {"output_sample_shape": 12,
                    "model_parallel": "row" if tp else None},
             "<-": {"learning_rate": learning_rate,
                    "gradient_moment": 0.9}},
            {"type": "softmax", "->": {"output_sample_shape": n_classes},
             "<-": {"learning_rate": learning_rate,
                    "gradient_moment": 0.9}},
        ],
        decision_config={"max_epochs": max_epochs},
        snapshotter_config=(
            None if tp_dir is None
            else {"prefix": "dist_tp", "directory": tp_dir}))
    wf._max_fires = 100_000
    return wf


def build_ring_workflow():
    """Sequence classifier with seq-parallel attention: the time axis
    shards over the global mesh's model axis, so the ring's ppermute
    crosses the PROCESS boundary (Gloo on CPU; ICI/DCN on pods) —
    the multi-process proof of the long-context path.  Reuses the
    attention_seq zoo sample (one source of truth for the task)."""
    from znicz_tpu.models.samples import attention_seq

    return attention_seq.build(
        seq_parallel=True, n_heads=2, seq_len=12, features=8,
        n_train=72, n_valid=24, minibatch_size=24, max_epochs=10,
        learning_rate=0.05)


def run_partition(shard_dir: str) -> dict:
    """Round 17: the declarative partition table under REAL
    multi-process SPMD — a TP (column+row) + ZeRO-1 net and a
    streaming-loader net with per-host 1/N reads, both placed
    entirely through the rule engine.  The digest carries the table
    dump and the resolved specs so the parent can assert every
    process resolved the IDENTICAL table (multi-host bring-up is a
    lookup, not a rewrite), plus warmed-step compile counts and the
    trained state for the single-process loss-parity check."""
    import jax
    import numpy as np

    from znicz_tpu.loader.streaming import StreamingLoader
    from znicz_tpu.models.standard_workflow import StandardWorkflow
    from znicz_tpu.observe import metrics as obs_metrics
    from znicz_tpu.utils import prng

    launcher = _partition_launcher
    wf_tp = build_workflow(tp=True, max_epochs=3)
    wf_tp.initialize(device=launcher.make_device())
    wf_tp.run()
    table = wf_tp.partition
    region_unit = wf_tp._region_unit
    compiles = obs_metrics.xla_compiles(f"region:{region_unit.name}")
    before = compiles.value
    wf_tp.loader.run()
    region_unit.run()
    warmed_delta = compiles.value - before
    wf_tp.forwards[0].weights.map_read()
    wf_tp.forwards[1].weights.map_read()

    # streaming net: per-host 1/N reads through put_local_batch
    prng.seed_all(4321)
    stream_wf = StandardWorkflow(
        name="dist_stream",
        loader_factory=lambda w: StreamingLoader(
            w, shard_dir, minibatch_size=16, prefetch_depth=2,
            normalization_scale=2.0 / 255.0, normalization_bias=-1.0),
        layers=[
            {"type": "all2all_tanh",
             "->": {"output_sample_shape": 16, "weights_filling": "he"},
             "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
            {"type": "softmax",
             "->": {"output_sample_shape": 4, "weights_filling": "he"},
             "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
        ],
        decision_config={"max_epochs": 6})
    stream_wf._max_fires = 10 ** 6
    stream_wf.initialize(device=launcher.device)
    loader = stream_wf.loader
    loader.warmup()
    # content proof for the per-host 1/N reads at a PINNED schedule
    # point (the first delivered batch): each host uploaded only its
    # local rows through put_local_batch; the assembled global batch
    # must be row-for-row identical to what one process reads whole.
    # Lockstep collective read (every process executes this).
    loader.run()
    first = np.asarray(launcher.device.get(
        loader.minibatch_raw._devmem), dtype=np.float64)
    first_labels = np.asarray(launcher.device.get(
        loader.minibatch_labels._devmem))
    stream_batch_rows = [float(r) for r in
                         first.reshape(first.shape[0], -1).sum(axis=1)]
    stream_batch_labels = [int(x) for x in first_labels]
    stream_wf.run()
    stream_region = stream_wf._region_unit
    scompiles = obs_metrics.xla_compiles(f"region:{stream_region.name}")
    sbefore = scompiles.value
    loader.run()
    stream_region.run()
    warmed_stream_delta = scompiles.value - sbefore
    stream_wf.forwards[0].weights.map_read()
    stream_wf.stop()

    col = wf_tp.forwards[0]
    return {
        "partition_table": table.dump(),
        "resolved_specs": {path: str(tuple(res.spec))
                           for path, res in sorted(table.leaves.items())},
        "col_weights_spec": str(tuple(
            table.leaves[f"{col.name}/weights"].spec)),
        "zero1_engaged": all(g._zero1 for g in wf_tp.gds
                             if g.weights is not None and g.weights),
        "warmed_step_compiles": int(warmed_delta),
        "warmed_stream_compiles": int(warmed_stream_delta),
        "w0_sum": float(wf_tp.forwards[0].weights.mem.sum()),
        "w1_sum": float(wf_tp.forwards[1].weights.mem.sum()),
        "w0_l2": float((wf_tp.forwards[0].weights.mem ** 2).sum()),
        "w1_l2": float((wf_tp.forwards[1].weights.mem ** 2).sum()),
        "min_validation_n_err": int(wf_tp.decision.min_validation_n_err),
        "stream_w_sum": float(stream_wf.forwards[0].weights.mem.sum()),
        "stream_w_l2": float(
            (np.asarray(stream_wf.forwards[0].weights.mem,
                        dtype=np.float64) ** 2).sum()),
        "stream_batch_rows": stream_batch_rows,
        "stream_batch_labels": stream_batch_labels,
        "stream_final_loss": [None if x is None else float(x)
                              for x in stream_wf.decision.epoch_loss],
        "stream_local_batch": int(loader.local_batch),
        "stream_prefetch_hits": int(loader.prefetch_hits),
        "stream_min_valid_n_err": int(
            stream_wf.decision.min_validation_n_err),
        "n_processes": jax.process_count(),
    }


#: launcher handle for run_partition (set by main before dispatch)
_partition_launcher = None


def run_genetics() -> dict:
    """Process-sharded GA: both processes hold the identical
    deterministic population, train disjoint genome slices on local
    devices, and all-gather the scores — the TPU restatement of the
    reference's genome-per-cluster-node farm (``veles/genetics/``)."""
    from znicz_tpu.genetics import GeneticsOptimizer, Tune

    opt = GeneticsOptimizer(
        build_fn=lambda **kw: build_workflow(**kw),
        space={"learning_rate": Tune(0.1, 0.02, 0.5)},
        population_size=4, generations=2, seed=11,
        train_kwargs={"max_epochs": 2})
    best = opt.run()
    return {
        "ga_best_genome": best,
        "ga_best_fitness": float(opt.best_fitness),
        "ga_local_evaluated": sorted(str(k) for k in opt.local_evaluated),
        "ga_n_unique": len(opt._cache),
    }


def run_ensemble() -> dict:
    """Process-sharded ensemble: 3 members round-robin over 2
    processes (0 trains members 0 and 2, 1 trains member 1), merged
    aggregate evaluation identical everywhere."""
    from znicz_tpu.ensemble import Ensemble
    from znicz_tpu.loader.base import VALID

    ens = Ensemble(build_workflow, n_models=3, base_seed=42,
                   train_kwargs={"max_epochs": 2})
    ens.train()
    result = ens.evaluate(VALID)
    return {
        "ens_member_ids": list(ens.member_ids),
        "ens_member_stats": ens.member_stats,
        "ens_result": result,
    }


def main() -> None:
    process_id = int(sys.argv[1])
    n_processes = int(sys.argv[2])
    coordinator = sys.argv[3]
    out_path = sys.argv[4]
    mode_arg = sys.argv[5] if len(sys.argv) > 5 else None
    ring_mode = mode_arg == "ring"
    shard_mode = mode_arg in ("genetics", "ensemble")
    partition_mode = mode_arg == "partition"
    tp_dir = None if (mode_arg is None or ring_mode or shard_mode
                      or partition_mode) else mode_arg

    # a fixed 4-device GLOBAL mesh split over however many processes
    # run (2 per process for the 2-proc smoke, all 4 for the
    # single-process loss-parity reference), configured BEFORE any jax
    # use.
    devices_per_proc = 4 // n_processes if partition_mode else 2
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", devices_per_proc)
    # (jax_cpu_collectives_implementation=gloo is set by
    # parallel.distributed.ensure_initialized during the Launcher's
    # bootstrap — cross-process CPU computations fail without it)

    from znicz_tpu.launcher import Launcher
    from znicz_tpu.utils import prng

    n_model = 2 if (tp_dir or ring_mode or partition_mode) else 1
    if process_id == 0:
        launcher = Launcher(listen=coordinator, n_processes=n_processes,
                            n_model=n_model)
    else:
        launcher = Launcher(master=coordinator, n_processes=n_processes,
                            process_id=process_id, n_model=n_model)
    assert launcher.mode == ("master" if process_id == 0 else "slave")
    assert jax.process_count() == n_processes
    assert len(jax.devices()) == devices_per_proc * n_processes

    prng.seed_all(1234)

    if partition_mode:
        global _partition_launcher
        _partition_launcher = launcher
        digest = run_partition(sys.argv[6])
        digest.update({
            "process_id": process_id,
            "mode": launcher.mode,
            "n_global_devices": len(jax.devices()),
        })
        with open(out_path, "w") as fh:
            json.dump(digest, fh)
        print(f"worker {process_id}: OK partition", flush=True)
        return

    if shard_mode:
        digest = (run_genetics() if mode_arg == "genetics"
                  else run_ensemble())
        digest.update({
            "process_id": process_id,
            "mode": launcher.mode,
            "n_global_devices": len(jax.devices()),
        })
        with open(out_path, "w") as fh:
            json.dump(digest, fh)
        print(f"worker {process_id}: OK {digest}", flush=True)
        return

    def run(load, main):  # reference sample protocol
        if ring_mode:
            load(build_ring_workflow)
        else:
            load(build_workflow, tp_dir=tp_dir)
        main()

    wf = launcher.boot(run)

    snapshot_keys = -1
    if process_id == 0 and tp_dir is None and not ring_mode:
        # master-only snapshot: must NOT issue collective reads (the
        # slaves are not in lockstep here) — regression for the
        # Vector.needs_collective_read skip in Unit.state_dict
        state = wf.state_dict()
        snapshot_keys = sum(len(unit_state)
                            for unit_state in state["__units__"].values())
    tp_snapshot_full_shapes = None
    if tp_dir is not None:
        # the Snapshotter unit ran in lockstep on every process — its
        # file must hold the FULL (gathered) model-sharded weights
        import glob as _glob

        from znicz_tpu.utils.snapshotter import Snapshotter
        files = sorted(_glob.glob(tp_dir + "/dist_tp_*.pickle.gz"))
        assert files, "lockstep TP snapshot was not written"
        state = Snapshotter.load(files[-1])
        col = state["__units__"]["All2AllTanh"]["weights"]
        row = state["__units__"]["All2AllTanh_2"]["weights"]
        tp_snapshot_full_shapes = [list(col.shape), list(row.shape)]

    wf.forwards[0].weights.map_read()
    wf.forwards[1].weights.map_read()
    digest = {
        "ring_engaged": bool(getattr(wf.forwards[0], "ring_active",
                                     False)),
        "ring_time_sharded": getattr(wf.forwards[0].output,
                                     "model_shard_dim", None) == 1,
        "snapshot_keys": snapshot_keys,
        "tp_snapshot_full_shapes": tp_snapshot_full_shapes,
        "process_id": process_id,
        "mode": launcher.mode,
        "n_global_devices": len(jax.devices()),
        "data_shards": launcher.device.n_data_shards,
        "w0_sum": float(wf.forwards[0].weights.mem.sum()),
        "w1_sum": float(wf.forwards[1].weights.mem.sum()),
        "w0_l2": float((wf.forwards[0].weights.mem ** 2).sum()),
        "w1_l2": float((wf.forwards[1].weights.mem ** 2).sum()),
        "min_validation_n_err": int(wf.decision.min_validation_n_err),
    }
    with open(out_path, "w") as fh:
        json.dump(digest, fh)
    print(f"worker {process_id}: OK {digest}", flush=True)


if __name__ == "__main__":
    sys.path.insert(0, __file__.rsplit("/", 2)[0])
    main()

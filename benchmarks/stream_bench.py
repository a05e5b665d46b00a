"""Streaming data plane A/B: resident (FullBatch) vs streamed
(StreamingLoader) at equal batch, with a dataset LARGER than the
resident-loader budget.

The resident loader is the round-3 winner (13.4k img/s/chip came from
making inputs resident) but it caps every workload at device memory.
This bench proves the round-10 alternative costs ~nothing when the
pipeline keeps up:

- **resident arm** — ``ArrayLoader`` holding the whole dataset
  "in HBM" (on this CPU mesh: host RAM standing in for it; the
  ``resident_budget_mb`` field records the simulated HBM budget the
  dataset EXCEEDS, which is the regime where this arm stops being an
  option at all);
- **streamed arm** — ``StreamingLoader`` over on-disk shards: bounded
  staging ring + background readers + device_put prefetch.  Identical
  seed → identical sample order (the counter-based shuffle), so the
  arms differ ONLY in the input plane.

Acceptance targets (recorded per row, asserted in the summary):
streamed step within 5% of resident at equal batch, and input time
≥ 90% hidden (``1 − wait_sum/stage_sum`` from the round-9 telemetry
series — the link-independent overlap proof, same logic as
``stream_probe``).

Usage: ``python benchmarks/stream_bench.py [batch] [steps]``
Appends one dated JSON line to STREAM_BENCH.jsonl (override with
STREAM_BENCH_OUT=<path>; empty disables).  A chip row on a real TPU
slice is queued per the CHANGES.md convention — no chip in this
container.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax  # noqa: E402

if os.environ.get("STREAM_TPU") != "1":
    try:
        jax.config.update("jax_platforms", "cpu")
    except RuntimeError:
        pass

import numpy as np  # noqa: E402


def build_wf(name, loader_factory):
    from znicz_tpu.models.standard_workflow import StandardWorkflow
    gd = {"learning_rate": 0.01, "gradient_moment": 0.9}
    return StandardWorkflow(
        name=name,
        loader_factory=loader_factory,
        layers=[
            {"type": "conv_relu",
             "->": {"n_kernels": 16, "kx": 5, "ky": 5,
                    "weights_filling": "he"}, "<-": gd},
            {"type": "max_pooling", "->": {"kx": 2, "ky": 2}},
            {"type": "softmax", "->": {"output_sample_shape": 8,
                                       "weights_filling": "he"},
             "<-": gd},
        ],
        decision_config={"max_epochs": 10 ** 6})


def timed_steps(wf, warmup, steps):
    """Median per-step wall: host loader + region dispatch + a value
    fence on the updated weights."""
    times = []
    fence = wf.forwards[-1].weights
    for i in range(warmup + steps):
        t0 = time.perf_counter()
        wf.loader.run()
        wf._region_unit.run()
        fence.devmem.block_until_ready()
        if i >= warmup:
            times.append(time.perf_counter() - t0)
    return float(np.median(times))


def main() -> None:
    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 128
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 40
    warmup = 6
    budget_mb = float(os.environ.get("RESIDENT_BUDGET_MB", 48))

    from znicz_tpu.backends import XLADevice
    from znicz_tpu.loader.fullbatch import ArrayLoader
    from znicz_tpu.loader.streaming import StreamingLoader, write_shards
    from znicz_tpu.observe import metrics as obs_metrics
    from znicz_tpu.utils import prng

    # dataset 1.5× the resident budget: the streamed arm's raison
    # d'être.  uint8 images, synthetic (throughput bench, labels
    # random).
    hw = 24
    sample_bytes = hw * hw * 3
    n_samples = int(budget_mb * 1.5 * 2 ** 20 / sample_bytes)
    n_samples -= n_samples % batch  # exact epochs: no pad rows
    rng = np.random.default_rng(0)
    data = rng.integers(0, 255, size=(n_samples, hw, hw, 3),
                        dtype=np.uint8)
    labels = rng.integers(0, 8, size=n_samples).astype(np.int32)
    dataset_mb = data.nbytes / 2 ** 20

    shard_dir = os.environ.get("STREAM_DATA_DIR") \
        or tempfile.mkdtemp(prefix="stream_bench_")
    write_shards(shard_dir, data, labels, rows_per_shard=8192)

    norm = dict(normalization_scale=2.0 / 255.0,
                normalization_bias=-1.0)

    # -- resident arm ---------------------------------------------------
    prng.seed_all(10)
    res = build_wf("resident_arm", lambda w: ArrayLoader(
        w, train_data=data, train_labels=labels,
        minibatch_size=batch, **norm))
    res._max_fires = 10 ** 9
    res.initialize(device=XLADevice())
    resident_s = timed_steps(res, warmup, steps)
    res.stop()

    # -- streamed arm ---------------------------------------------------
    prefetch_depth = int(os.environ.get("STREAM_PREFETCH_DEPTH", 2))
    prng.seed_all(10)
    stream = build_wf("streamed_arm", lambda w: StreamingLoader(
        w, shard_dir, minibatch_size=batch,
        prefetch_depth=prefetch_depth, n_reader_threads=2, **norm))
    stream._max_fires = 10 ** 9
    stream.initialize(device=XLADevice())
    loader = stream.loader
    wait0 = obs_metrics.input_wait_seconds(loader.name).sum
    stage0 = obs_metrics.input_stage_seconds(loader.name).sum
    streamed_s = timed_steps(stream, warmup, steps)
    wait_s = obs_metrics.input_wait_seconds(loader.name).sum - wait0
    stage_s = obs_metrics.input_stage_seconds(loader.name).sum - stage0
    ring_mb = loader._pipe.ring.nbytes / 2 ** 20
    hits, misses = loader.prefetch_hits, loader.prefetch_misses
    crossings = loader.epoch_cross_prefetches
    stream.stop()

    n_timed = warmup + steps
    hidden = 1.0 - wait_s / max(stage_s, 1e-12)
    ratio = streamed_s / resident_s
    row = {
        "mode": "stream_ab",
        "batch": batch,
        "steps_timed": steps,
        "platform": jax.devices()[0].platform,
        "resident_budget_mb": round(budget_mb, 1),
        "dataset_mb": round(dataset_mb, 1),
        "resident_fits_budget": dataset_mb <= budget_mb,
        "staging_ring_mb": round(ring_mb, 2),
        "prefetch_depth": prefetch_depth,
        "resident_step_ms": round(resident_s * 1e3, 2),
        "streamed_step_ms": round(streamed_s * 1e3, 2),
        "streamed_over_resident": round(ratio, 4),
        "input_stage_ms_per_step": round(1e3 * stage_s / n_timed, 3),
        "input_wait_ms_per_step": round(1e3 * wait_s / n_timed, 3),
        "input_hidden_pct": round(100 * hidden, 1),
        "prefetch_hits": hits,
        "prefetch_misses": misses,
        "epoch_cross_prefetches": crossings,
        "criteria": {
            "step_within_5pct": bool(ratio <= 1.05),
            "input_hidden_ge_90pct": bool(hidden >= 0.90)},
        "note": ("equal seed => identical sample order both arms "
                 "(counter-based shuffle); hidden = 1 - wait/stage "
                 "from the telemetry sums, the link-independent "
                 "overlap proof.  Chip row queued (no chip in this "
                 "container): rerun with STREAM_TPU=1 on a slice."),
        "date": time.strftime("%Y-%m-%d %H:%M"),
    }
    line = json.dumps(row)
    print(line, flush=True)
    out = os.environ.get(
        "STREAM_BENCH_OUT",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "STREAM_BENCH.jsonl"))
    if out:
        with open(out, "a") as fh:
            fh.write(line + "\n")
    if not all(row["criteria"].values()):
        print("WARNING: acceptance criteria not met on this sample "
              "(CPU step jitter? rerun)", file=sys.stderr)
        sys.exit(1)
    os._exit(0)  # skip atexit teardown of the decode/reader pools


if __name__ == "__main__":
    main()

"""Benchmark: AlexNet training throughput (images/sec/chip) + MFU.

North star (BASELINE.json): stock ImageNet AlexNet StandardWorkflow at
≥8000 images/sec on a TPU v4-32 ⇒ 250 images/sec/chip.  This bench
runs the full training step (loader gather → forwards → softmax CE →
backward chain → SGD update, one fused XLA program) on one chip with
synthetic ImageNet-geometry data and reports ONE JSON line:

    {"metric": "alexnet_train_images_per_sec_per_chip",
     "value": <img/s>, "unit": "images/sec/chip",
     "vs_baseline": <img/s ÷ 250>, "mfu": <model-flops util>, ...}

The bench runs on a TPU or not at all: the device is ``TPUDevice()``,
which raises where JAX finds no TPU, and a failure is a traceback and a
non-zero exit.  ``BENCH_PLATFORM=cpu`` is the one explicit way onto
another platform (the 2-process bring-up drill); such a line carries
its platform and no MFU.

Knobs (env): BENCH_BATCH, BENCH_PRECISION (bfloat16|float32),
BENCH_PROFILE=<dir> (where the
jax.profiler trace of the timed loop goes — ON by default into
profiles/bench_default at ~1-2% overhead for the device-resident
mode, OFF by default in stream mode where the trace thread competes
with the single-core decode pool; set BENCH_PROFILE="" to disable
everywhere), BENCH_PEAK_TFLOPS (override
chip peak for MFU), BENCH_INPUT=stream (feed through the streaming
FileImageLoader: real JPEG decode via the native C++ pool with
double-buffered prefetch, instead of the device-resident store —
measures the END-TO-END fed-at-rate number; synthetic JPEGs are
generated once under the cache dir).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

#: batch 768 is the round-5 measured sweet spot on v5e — the bf16
#: LRN-denominator + optimizer-state changes shifted the balance
#: upward from round 3's 384 (sweep in PERF.md round 5: 256→0.493,
#: 384→0.495-0.502, 512→0.488-0.510, 768→0.499-0.513, 1024→0.505)
BATCH = int(os.environ.get("BENCH_BATCH", "768"))
INPUT_MODE = os.environ.get("BENCH_INPUT", "resident")  # resident|stream
#: steps per device dispatch (lax.scan chunk; device-resident schedule).
#: 1 = per-step dispatch (round-2 behavior).  Streaming input is
#: host-fed per step, so stream mode forces 1.
CHUNK = max(1, int(os.environ.get("BENCH_CHUNK", "16")))
if INPUT_MODE == "stream":
    CHUNK = 1
#: canonical AlexNet geometry; smaller for smoke runs on slow backends
IMAGE_SIZE = int(os.environ.get("BENCH_IMAGE_SIZE", "227"))
#: bf16 matmul/conv inputs with f32 params+accumulation — the
#: MXU-native training mode (override: BENCH_PRECISION=float32)
PRECISION = os.environ.get("BENCH_PRECISION", "bfloat16")
#: BENCH_LRN_D_BF16: bf16 STORAGE for the shared LRN denominator
#: tensors (~1.5 GB/step of f32 traffic at b384 — PERF.md round 5,
#: measured +5.4%).  Unset = the engine's auto default (on in bf16
#: mode); 0/1 forces the A/B arm.
LRN_D_BF16 = os.environ.get("BENCH_LRN_D_BF16", "")
#: default ON: every bench run leaves a local trace of the timed loop
#: (~3 MB; ~1-2% overhead) — perf numbers should never be
#: unexplainable.  The default path is GITIGNORED (profiles/ holds
#: regenerable binaries, not version-controlled evidence — the
#: decisions each trace drove live in PERF.md).  BENCH_PROFILE=""
#: disables; set a path to move (user paths are never cleaned).
#: ``--profile <dir>``: wrap the timed loop in
#: ``observe.profile_window`` — the dir receives the jax.profiler
#: device trace AND the window's host spans
#: (``host_spans.trace.json``), so every committed BENCH row can carry
#: a trace readable by ``benchmarks/trace_top.py <dir> <steps>
#: --spans <dir>``.  Unlike BENCH_PROFILE (env), the flag also
#: profiles on CPU and never cleans the target dir.
_PROFILE_FLAG = None
if "--profile" in sys.argv:
    _i = sys.argv.index("--profile")
    if _i + 1 >= len(sys.argv):
        raise SystemExit("--profile requires a directory argument")
    _PROFILE_FLAG = sys.argv[_i + 1]
PROFILE_DIR = _PROFILE_FLAG if _PROFILE_FLAG is not None else \
    os.environ.get(
        "BENCH_PROFILE",
        # stream mode is HOST-bound (single-core decode pool) and the
        # profiler competes for that core — measured 816 → 294 img/s
        # with default tracing on; only the device-resident mode
        # profiles by default
        "" if INPUT_MODE == "stream" else
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "profiles", "bench_default"))
WARMUP_STEPS = 6
TIMED_STEPS = 30
BASELINE_IMG_PER_SEC_PER_CHIP = 250.0  # 8000 img/s ÷ 32 chips (v4-32)
METRIC = "alexnet_train_images_per_sec_per_chip"
UNIT = "images/sec/chip"

#: bf16 MXU peak per chip, TFLOP/s, by device_kind substring (MFU is
#: reported against bf16 peak; f32 runs will show lower utilization)
PEAK_TFLOPS_BY_KIND = (
    ("v6", 918.0), ("v5p", 459.0), ("v5", 197.0),  # v5 lite / v5e
    ("v4", 275.0), ("v3", 123.0), ("v2", 45.0),
)


def peak_tflops(device) -> float:
    """bf16 peak of ``device`` from the table above; a device the
    table does not know is an error, not a default."""
    if "BENCH_PEAK_TFLOPS" in os.environ:
        return float(os.environ["BENCH_PEAK_TFLOPS"])
    kind = device.device_kind.lower()
    for tag, tflops in PEAK_TFLOPS_BY_KIND:
        if tag in kind:
            return tflops
    raise ValueError(
        f"no peak TFLOP/s known for device_kind "
        f"{device.device_kind!r} (platform {device.platform}): add it "
        f"to PEAK_TFLOPS_BY_KIND with its source")


def train_step_flops(wf) -> float:
    """Analytic AlexNet fwd+bwd FLOPs per step: 2·MACs for each conv /
    FC forward, ×3 for training (forward + input-grad + weight-grad
    are each one GEMM of the same volume).  Elementwise/pool/LRN ops
    are not counted (standard model-FLOPs accounting)."""
    import numpy as np

    flops_fwd = 0.0
    for unit in wf.forwards:
        weights = getattr(unit, "weights", None)
        if weights is None or not weights:
            continue
        if hasattr(unit, "kx"):  # conv: output NHWC, kernel kx·ky·Cin
            c_in = unit.input.shape[-1]
            flops_fwd += 2.0 * float(np.prod(unit.output.shape)) \
                * unit.kx * unit.ky * c_in
        else:  # fully-connected: one B×in → B×out GEMM
            batch = unit.output.shape[0]
            flops_fwd += 2.0 * batch * float(np.prod(weights.shape))
    return 3.0 * flops_fwd


def make_jpeg_tree(n_images: int, n_classes: int = 8,
                   hw: tuple = (256, 256)) -> str:
    """Synthetic class-per-subdir JPEG tree for the streaming mode,
    generated once under the cache dir (content doesn't matter for
    throughput; decode cost does)."""
    import numpy as np
    from PIL import Image

    from znicz_tpu.utils.config import root

    base = os.path.join(str(root.common.dirs.cache), "bench_jpegs",
                        f"{n_images}x{hw[0]}")
    marker = os.path.join(base, ".complete")
    if os.path.exists(marker):
        return base
    rng = np.random.default_rng(0)
    for i in range(n_images):
        cls_dir = os.path.join(base, f"class_{i % n_classes:03d}")
        os.makedirs(cls_dir, exist_ok=True)
        Image.fromarray(
            rng.integers(0, 256, size=hw + (3,), dtype=np.uint8)
        ).save(os.path.join(cls_dir, f"img_{i:05d}.jpg"), quality=90)
    with open(marker, "w") as fh:
        fh.write("ok")
    return base


def main() -> None:
    import jax

    from znicz_tpu.backends import (TPUDevice, XLADevice,
                                    configure_compile_cache)

    # BENCH_PLATFORM/BENCH_CPU_DEVICES: the explicit way off the TPU,
    # set before the first backend touch (lets the 2-process bring-up
    # below be exercised on CPU hosts)
    platform = os.environ.get("BENCH_PLATFORM")
    if platform:
        jax.config.update("jax_platforms", platform)
        n_cpu = int(os.environ.get("BENCH_CPU_DEVICES", "0"))
        if n_cpu:
            jax.config.update("jax_num_cpu_devices", n_cpu)
    configure_compile_cache()
    # pod-scale bring-up (env contract: ZNICZ_COORDINATOR /
    # ZNICZ_NUM_PROCESSES / ZNICZ_PROCESS_ID) — must run BEFORE the
    # first backend touch so jax.devices() is the GLOBAL list; a
    # single-process run is untouched
    from znicz_tpu.parallel.distributed import ensure_initialized
    is_distributed = ensure_initialized()
    # no BENCH_PLATFORM = a TPU or a RuntimeError from jax
    devices = jax.devices(platform or "tpu")
    platform = devices[0].platform
    on_tpu = platform == "tpu"

    from znicz_tpu.models.samples import alexnet
    from znicz_tpu.utils.config import root

    root.common.precision_type = PRECISION
    if LRN_D_BF16:
        root.common.engine.lrn_d_bf16 = LRN_D_BF16 != "0"

    # dataset sized a whole number of chunks per epoch so a scanned
    # chunk never spans the epoch-boundary reshuffle (ceil to a
    # CHUNK multiple ≥ 8 steps)
    steps_per_epoch = max(1, -(-8 // CHUNK)) * CHUNK
    n_train = steps_per_epoch * BATCH
    streaming_dir = None
    if INPUT_MODE == "stream":
        streaming_dir = make_jpeg_tree(n_train)
    wf = alexnet.build(
        streaming_dir=streaming_dir,
        minibatch_size=BATCH,
        image_size=IMAGE_SIZE,
        n_train_samples=n_train,
        n_valid_samples=0,  # pure train steps for steady-state timing
        max_epochs=10 ** 6)
    device_cls = TPUDevice if on_tpu else XLADevice
    if is_distributed:
        # SPMD over the global mesh: the batch shards over every
        # host's chips and XLA lays the gradient all-reduce over
        # ICI/DCN — the same workflow, unmodified
        from znicz_tpu.parallel import make_mesh
        device = device_cls(mesh=make_mesh(devices=devices))
    else:
        device = device_cls(devices[0])
    wf.initialize(device=device)
    assert wf._region_unit is not None
    region_unit = wf._region_unit
    jit_region = region_unit.region  # the JitRegion (owns run_chunk)

    # round 18: supervisable pod bench — with the elastic heartbeat
    # channel configured (ZNICZ_HEARTBEAT_DIR), every process beats its
    # dispatch counter so the coordinator-side monitor (or an
    # ElasticSupervisor wrapping the bench) sees a hung chip as a
    # stalled step counter instead of a silent wedge
    from znicz_tpu.resilience.supervisor import (HeartbeatWriter,
                                                 worker_config)
    heartbeat = None
    hb_cfg = worker_config()
    if hb_cfg is not None:
        heartbeat = HeartbeatWriter(hb_cfg["directory"],
                                    jax.process_index()).start()
    dispatches = 0

    def step():
        """One dispatch: CHUNK scanned steps (device-resident
        schedule) or a single region step."""
        nonlocal dispatches
        if CHUNK > 1:
            for _ in range(CHUNK):
                wf.loader.run()   # host bookkeeping only (no uploads)
            jit_region.run_chunk(CHUNK)
        else:
            wf.loader.run()
            region_unit.run()
        dispatches += 1
        if heartbeat is not None:
            heartbeat.beat(dispatches)

    warmup_dispatches = max(1, WARMUP_STEPS // CHUNK)
    timed_dispatches = max(2, TIMED_STEPS // CHUNK)
    for _ in range(warmup_dispatches):
        step()
    wf.forwards[-1].weights.devmem.block_until_ready()

    profiling = bool(PROFILE_DIR) and (on_tpu
                                       or _PROFILE_FLAG is not None)
    from contextlib import nullcontext
    window = nullcontext()
    if profiling:
        if "BENCH_PROFILE" not in os.environ and _PROFILE_FLAG is None:
            # one trace per directory, DEFAULT path only: jax writes a
            # new timestamped subdir per run, which would grow without
            # bound under the default-on policy.  A user-supplied
            # --profile / BENCH_PROFILE dir is never cleaned — it may
            # hold prior results.
            import shutil

            shutil.rmtree(PROFILE_DIR, ignore_errors=True)
        from znicz_tpu import observe

        # device trace + the window's host spans in one capture dir
        window = observe.profile_window(
            PROFILE_DIR, n_steps=timed_dispatches * CHUNK)
    with window:
        start = time.perf_counter()
        for _ in range(timed_dispatches):
            step()
        wf.forwards[-1].weights.devmem.block_until_ready()
        elapsed = time.perf_counter() - start

    step_time = elapsed / (timed_dispatches * CHUNK)
    # per-chip normalization: under a mesh the global batch spread
    # over every chip, so chips divide out of both throughput and MFU
    n_chips = len(devices) if is_distributed else 1
    img_per_sec = BATCH / step_time / n_chips
    # utilization is a statement about the MXU: no TPU, no MFU
    mfu = None
    if on_tpu:
        mfu = round(train_step_flops(wf) / step_time / n_chips
                    / (peak_tflops(devices[0]) * 1e12), 4)
    if heartbeat is not None:
        heartbeat.stop()
    if is_distributed and jax.process_index() != 0:
        return  # master owns the result line
    print(json.dumps({
        "metric": METRIC,
        "value": round(img_per_sec, 2),
        "unit": UNIT,
        "vs_baseline": round(img_per_sec / BASELINE_IMG_PER_SEC_PER_CHIP, 4),
        "mfu": mfu,
        "step_time_ms": round(step_time * 1e3, 3),
        "batch": BATCH,
        "precision": PRECISION,
        "input": INPUT_MODE,
        "chunk": CHUNK,
        "platform": platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "profile": PROFILE_DIR if profiling else None,
    }), flush=True)


if __name__ == "__main__":
    main()

"""Sequence-stack training throughput on the real chip (tokens/s).

The reference predates attention entirely, so there is no baseline to
beat — this artifact pins the absolute capability of the long-context
extension (SURVEY.md §5.7): a transformer-style block
(attention → layer_norm → FC) trained end-to-end through the jit
region at realistic sequence geometry, reported as tokens/s/chip and
attention-FLOPs utilization.

Single-chip measurement: the attention core runs the LOCAL path (the
ring engages on a mesh's model axis — its cross-process correctness
is proven by tests/test_distributed.py; its purpose is fitting longer
sequences, not speeding up one chip).

Run: ``python benchmarks/seq_bench.py`` (env: SEQ_BATCH, SEQ_LEN,
SEQ_DIM, SEQ_HEADS, SEQ_STEPS, SEQ_FLASH=<block_k> for the blocked
flash-style core).  Writes SEQ_BENCH.json at the repo root with one
JSON line per configuration.

Multi-device arm: ``SEQ_DEVICES=<n>`` trains on an (n_data=n) DP mesh
where the mesh-native shard_map kernel paths engage (PERF.md round 6);
``SEQ_SHARD_MAP=0`` forcibly disengages them (fallback gate → XLA
cores) for the A/B.  ``SEQ_INTERPRET=1`` records the arm on the
virtual CPU mesh; without it the arm is the real-slice measurement
hook.

Sequence-parallel arm: ``SEQ_RING=<n>`` shards T over an (n_model=n)
ring mesh; the ring hops fold through the flash kernel
(``ring_fold="pallas"`` in the row) unless ``SEQ_RING_FOLD=0`` forces
the scan fold — the committed A/B for the round-6 kernel-native ring.
Neither the causal tile schedule nor the kernels' address and head
pack has a lever: the kernels derive them from the shapes
(``pallas_attention.sub_tile_for``, ``head_layout``; PERF.md §6, PR 24
and PR 28) and the row records what they resolved (``sub_tile``,
``executed_share``, ``flash_layout``).
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

BATCH = int(os.environ.get("SEQ_BATCH", "16"))
SEQ_LEN = int(os.environ.get("SEQ_LEN", "2048"))
DIM = int(os.environ.get("SEQ_DIM", "512"))
HEADS = int(os.environ.get("SEQ_HEADS", "8"))
STEPS = int(os.environ.get("SEQ_STEPS", "30"))
FLASH = int(os.environ.get("SEQ_FLASH", "0"))  # 0 = plain local core
#: SEQ_PALLAS: the fused flash-attention Pallas kernel A/B lever —
#: unset = the unit's default (ON for TPU, the measured winner);
#: 0 = force the XLA cores; 1 = force the kernel
PALLAS_ENV = os.environ.get("SEQ_PALLAS", "")
#: SEQ_PALLAS_LN: same A/B lever for the fused Pallas layer norm
PALLAS_LN_ENV = os.environ.get("SEQ_PALLAS_LN", "")
#: SEQ_CAUSAL=1: causal attention (the flash kernel skips
#: fully-masked tiles via pl.when — ~half the tile work)
CAUSAL = os.environ.get("SEQ_CAUSAL", "0") != "0"
#: SEQ_DEVICES=<n> (n ≥ 2): the multi-device arm — train on an
#: (n_data=n) DP mesh.  With the mesh-native shard_map path (default)
#: the Pallas kernels ENGAGE per-shard; SEQ_SHARD_MAP=0 forcibly
#: disengages them (the conservative fallback gate: kernels off, XLA
#: cores) — the engaged-vs-disengaged A/B this arm exists to record.
#: On the virtual CPU mesh pair it with SEQ_INTERPRET=1; on a real
#: TPU slice run it as-is (this arm is the TPU measurement hook).
DEVICES = int(os.environ.get("SEQ_DEVICES", "0"))
SHARD_MAP = os.environ.get("SEQ_SHARD_MAP", "") != "0"
#: SEQ_RING=<n> (n ≥ 2): the sequence-parallel arm — shard T over an
#: (n_model=n) ring mesh (seq_parallel attention).  With the round-6
#: kernel fold (default on TPU/interpret) each ring hop is a fused
#: flash pass at its global offset; SEQ_RING_FOLD=0 forces the scan
#: fold (the round-4-rate fallback) for the A/B this arm exists to
#: record.  On the virtual CPU mesh pair it with SEQ_INTERPRET=1; on
#: a real slice run it as-is (the TPU measurement hook, same pattern
#: as SEQ_SHARD_MAP).
RING = int(os.environ.get("SEQ_RING", "0"))
RING_FOLD = os.environ.get("SEQ_RING_FOLD", "") != "0"
#: SEQ_INTERPRET=1: run the Pallas kernels in interpret mode (CPU
#: recording of the multi-device arm; meaningless on a real chip)
INTERPRET = os.environ.get("SEQ_INTERPRET", "0") != "0"
#: steps per device dispatch (lax.scan chunk — the framework's real
#: training loop shape, same as bench.py's BENCH_CHUNK)
CHUNK = max(1, int(os.environ.get("SEQ_CHUNK", "8")))
#: SEQ_PROFILE=<dir>: capture a jax.profiler trace of the timed loop
#: (same discipline as bench.py — a seq perf number should never be
#: unexplainable)
PROFILE_DIR = os.environ.get("SEQ_PROFILE", "")
WARMUP = 5


def build():
    from znicz_tpu.loader.fullbatch import ArrayLoader
    from znicz_tpu.models.standard_workflow import StandardWorkflow

    rng = np.random.default_rng(3)
    # the epoch schedule must hold at least one whole chunk so
    # run_chunk never scans past the device-resident schedule (the
    # run_chunked contract: chunks never span a reshuffle).  In bf16
    # mode the dataset is stored bf16 (the loader keeps original
    # dtype in HBM; the model consumes bf16 anyway): TPU row gathers
    # from a resident table cost ~table-bytes of traffic per step, so
    # storage width is the gather's price — measured in PERF.md round
    # 5.  The f32 arm keeps f32 inputs so SEQ_PRECISION=float32 still
    # measures the real f32 data path.
    n = max(4, CHUNK) * BATCH
    x = rng.normal(0, 0.3, size=(n, SEQ_LEN, DIM))
    if os.environ.get("SEQ_PRECISION", "bfloat16") == "bfloat16":
        import ml_dtypes
        x = x.astype(ml_dtypes.bfloat16)
    else:
        x = x.astype(np.float32)
    y = rng.integers(0, 8, size=n).astype(np.int32)
    gd = {"learning_rate": 0.01, "gradient_moment": 0.9}
    wf = StandardWorkflow(
        name="seq_bench",
        loader_factory=lambda w: ArrayLoader(
            w, train_data=x, train_labels=y, minibatch_size=BATCH),
        layers=[
            {"type": "attention",
             "->": {"n_heads": HEADS, "causal": CAUSAL,
                    "seq_parallel": RING >= 2,
                    "flash_block_k": FLASH or None}, "<-": gd},
            {"type": "layer_norm", "->": {}, "<-": gd},
            {"type": "softmax", "->": {"output_sample_shape": 8},
             "<-": gd},
        ],
        decision_config={"max_epochs": 10 ** 6})
    wf._max_fires = 10 ** 9
    return wf


def attn_train_flops() -> float:
    """Model FLOPs per train step (fwd ×3 for training): attention
    projections (QKV + out: 4 D×D GEMMs over B·T tokens) +
    score/value matmuls (2 × 2·B·H·T²·(D/H)) + the classifier head
    ((T·D) × 8 GEMM)."""
    proj = 4 * 2.0 * BATCH * SEQ_LEN * DIM * DIM
    scores = 2 * 2.0 * BATCH * HEADS * SEQ_LEN * SEQ_LEN * (DIM // HEADS)
    if CAUSAL:
        scores *= 0.5  # only the lower triangle is model work
    head = 2.0 * BATCH * SEQ_LEN * DIM * 8
    return 3.0 * (proj + scores + head)


def main() -> None:
    from bench import peak_tflops

    from znicz_tpu.backends import XLADevice
    from znicz_tpu.utils import prng
    from znicz_tpu.utils.config import root

    root.common.precision_type = os.environ.get("SEQ_PRECISION",
                                                "bfloat16")
    if PALLAS_ENV:
        root.common.engine.flash_attention = PALLAS_ENV != "0"
    if PALLAS_LN_ENV:
        root.common.engine.pallas_layer_norm = PALLAS_LN_ENV != "0"
    root.common.engine.pallas_shard_map = SHARD_MAP
    root.common.engine.ring_pallas_fold = \
        RING_FOLD and "auto" or False
    if INTERPRET:
        root.common.engine.pallas_interpret = True
    prng.seed_all(11)
    wf = build()
    if RING >= 2:
        from znicz_tpu.parallel import make_mesh
        device = XLADevice(mesh=make_mesh(n_data=max(1, DEVICES),
                                          n_model=RING))
    elif DEVICES >= 2:
        from znicz_tpu.parallel import make_mesh
        device = XLADevice(mesh=make_mesh(n_data=DEVICES))
    else:
        device = XLADevice()
    wf.initialize(device=device)
    assert wf._region_unit is not None

    region = wf._region_unit.region

    def step():
        """One dispatch = CHUNK scanned train steps (the framework's
        chunked hot path), or a single region step at CHUNK=1."""
        if CHUNK > 1:
            for _ in range(CHUNK):
                wf.loader.run()   # host bookkeeping only
            region.run_chunk(CHUNK)
        else:
            wf.loader.run()
            wf._region_unit.run()

    def fence() -> None:
        wf.forwards[-1].weights.devmem.block_until_ready()

    dispatches = max(2, STEPS // CHUNK)
    for _ in range(max(1, WARMUP // CHUNK)):
        step()
    fence()
    if PROFILE_DIR:
        import jax
        jax.profiler.start_trace(PROFILE_DIR)
    t0 = time.perf_counter()
    for _ in range(dispatches):
        step()
    fence()
    dt = (time.perf_counter() - t0) / (dispatches * CHUNK)
    if PROFILE_DIR:
        import jax
        jax.profiler.stop_trace()
    n_devices = max(1, DEVICES) * max(1, RING)
    tokens_per_sec = BATCH * SEQ_LEN / dt / n_devices
    jax_device = device.jax_device
    # utilization is a statement about the MXU: no TPU, no MFU
    mfu = None
    if jax_device.platform == "tpu":
        mfu = round(attn_train_flops() / dt / n_devices
                    / (peak_tflops(jax_device) * 1e12), 4)
    attn_unit, ln_unit = wf.forwards[0], wf.forwards[1]
    plan = attn_unit._flash     # pallas_attention.plan, at initialize
    line = json.dumps({
        "metric": "seq_stack_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/sec/chip",
        "batch": BATCH, "seq_len": SEQ_LEN, "dim": DIM,
        "heads": HEADS, "flash_block_k": FLASH or None,
        "pallas": plan.runs, "chunk": CHUNK,
        "causal": CAUSAL,
        # the multi-device arm: devices > 1 means a DP mesh;
        # shard_map records whether the kernels ran MESH-NATIVE
        # (per-shard under shard_map) vs forcibly disengaged
        # (SEQ_SHARD_MAP=0 → XLA cores — the fallback gate)
        "devices": n_devices,
        "shard_map": plan.mesh is not None,
        # the SP arm: ring = model-axis size, ring_fold = which fold
        # the hops actually ran ("pallas" = the round-6 kernel fold,
        # "scan" = the XLA fallback; null = no ring)
        "ring": RING or None,
        "ring_fold": getattr(attn_unit, "_ring_fold", None),
        # where the kernels find a head's tiles and how many heads
        # share a program: from the shapes (pallas_attention.
        # head_layout); null off the one-chip kernel path
        "flash_layout": (plan.layout, plan.head_pack) if plan.runs
        else None,
        # the causal tile schedule the kernels derived: compute
        # sub-tile inside the grid tile, share of T × T executed
        "sub_tile": plan.sub_tile,
        "executed_share": (plan.tiles or {}).get("executed_share"),
        "pallas_ln": bool(getattr(ln_unit, "_pallas_ln", False)),
        "interpret": INTERPRET,
        "step_time_ms": round(dt * 1e3, 3),
        "mfu": mfu,
        "precision": str(root.common.precision_type),
        "platform": jax_device.platform,
        "device_kind": jax_device.device_kind,
    })
    print(line, flush=True)
    with open(os.path.join(REPO, "SEQ_BENCH.json"), "a") as fh:
        fh.write(line + "\n")


if __name__ == "__main__":
    main()

"""Fleet restart-speed bench: compile-free cold start (round 23).

A fleet restart (rolling upgrade, preemption wave, elastic grow) pays
its latency not in weight I/O but in XLA compiles: every cold process
re-traces every program it had yesterday.  Round 23's persisted AOT
executable cache (:mod:`znicz_tpu.serving.aot_cache`) makes that cost
a one-time event per (program, geometry, platform, build) — this bench
measures exactly what a restart recovers, with each arm in a genuinely
COLD subprocess:

* ``serve_miss``  — empty cache: every bucket program compiles
  (populating the store for the arms after it).
* ``serve_hit``   — warm cache: serve-ready with ZERO compiles, every
  program deserialized; outputs bitwise-equal to the miss arm.
* ``serve_corrupt`` — warm cache + ``aotcache.corrupt`` chaos recipe:
  the rotted entry is quarantined (never trusted), the site falls back
  to tracing, the reply stays bitwise-equal and the fallback is
  COUNTED (``znicz_aot_cache_total{outcome="corrupt"}`` +
  ``znicz_recoveries_total{kind="aotcache_fallback"}``).
* ``train_miss`` / ``train_hit`` — elastic resume-to-first-step: a
  cold trainer process reaches its first optimizer step with the
  region programs deserialized instead of re-traced.

Compile/load counters are asserted PER ARM (hit arms must show
``compiles == 0``), so a silent cache regression fails the bench
rather than just slowing it down.  Dispatch counts are deliberately
tiny — the numbers of interest are compile wall-clock, not throughput.

The parent process never touches JAX — a chip belongs to one process
at a time, so a parent that held it would starve its own cold children;
training + export run in a child too.  The arms run on the CPU unless
``JAX_PLATFORMS`` says otherwise, and the report names the platform
the children saw.

Usage::

    python benchmarks/coldstart_bench.py      # writes COLDSTART_BENCH.json
"""

from __future__ import annotations

import time

_T0 = time.monotonic()  # before the heavy imports: child arms bill
#                         interpreter+jax import to the cold start

import hashlib  # noqa: E402
import json     # noqa: E402
import os       # noqa: E402
import subprocess  # noqa: E402
import sys      # noqa: E402
import tempfile  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _ensure_platform() -> None:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()


def _counter(family: str, **labels) -> float:
    from znicz_tpu.observe import metrics as obs
    fam = obs.REGISTRY.get(family)
    if fam is None:
        return 0.0
    want = tuple(str(labels[n]) for n in fam.labelnames)
    total = 0.0
    for key, child in fam.items():
        if all(w in ("*", k) for w, k in zip(want, key)):
            total += float(child.value)
    return total


# ----------------------------------------------------------------------
# child arms (cold processes)
# ----------------------------------------------------------------------
def child_serve(bundle: str) -> dict:
    """Cold serving process: load → warmup → one reply.  Reports the
    serve-ready wall-clock and the compile/load split."""
    _ensure_platform()
    import numpy as np
    from znicz_tpu.utils.config import root
    if os.environ.get("COLDSTART_CHAOS") == "1":
        root.common.engine.faults = {"aotcache.corrupt": {"at": [1]}}
    from znicz_tpu.export import ExportedModel

    t_import = time.monotonic()
    model = ExportedModel.load(bundle, max_batch=8)
    resident = model.warmup()
    t_ready = time.monotonic()
    x = np.random.RandomState(0).randn(4, 16).astype(np.float32)
    out = np.asarray(model(x))
    return {
        "platform": model.device.jax_device.platform,
        "serve_ready_ms": round(1e3 * (t_ready - _T0), 1),
        "import_ms": round(1e3 * (t_import - _T0), 1),
        "warmup_ms": round(1e3 * (t_ready - t_import), 1),
        "programs_resident": resident,
        "compiles": model.compile_count,
        "loads": model.load_count,
        "out_sha256": hashlib.sha256(
            np.ascontiguousarray(out).tobytes()).hexdigest(),
        "metrics": {
            "aot_hit": _counter("znicz_aot_cache_total",
                                site="*", outcome="hit"),
            "aot_miss": _counter("znicz_aot_cache_total",
                                 site="*", outcome="miss"),
            "aot_corrupt": _counter("znicz_aot_cache_total",
                                    site="*", outcome="corrupt"),
            "fallback_recoveries": _counter(
                "znicz_recoveries_total", kind="aotcache_fallback"),
            "xla_compiles": _counter("znicz_xla_compiles_total",
                                     site="*"),
        },
    }


def child_train() -> dict:
    """Cold trainer process: build the deterministic bench workflow
    and run to the FIRST optimizer step — the elastic resume metric.
    With a warm region cache the step program deserializes."""
    _ensure_platform()
    import numpy as np
    from znicz_tpu.backends import XLADevice
    from znicz_tpu.loader.fullbatch import ArrayLoader
    from znicz_tpu.models.standard_workflow import StandardWorkflow
    from znicz_tpu.utils import prng

    rng = np.random.default_rng(13)
    data = rng.normal(size=(96, 12)).astype(np.float32)
    labels = (rng.random(96) * 3).astype(np.int32)
    prng.seed_all(23)
    wf = StandardWorkflow(
        name="coldstart_train",
        loader_factory=lambda w: ArrayLoader(
            w, train_data=data[:72], train_labels=labels[:72],
            valid_data=data[72:], valid_labels=labels[72:],
            minibatch_size=24),
        layers=[{"type": "all2all_tanh",
                 "->": {"output_sample_shape": 16},
                 "<-": {"learning_rate": 0.05,
                        "gradient_moment": 0.9}},
                {"type": "softmax", "->": {"output_sample_shape": 3},
                 "<-": {"learning_rate": 0.05,
                        "gradient_moment": 0.9}}],
        decision_config={"max_epochs": 1})
    wf._max_fires = 10 ** 6
    wf.initialize(device=XLADevice())
    wf.loader.run()
    wf._region_unit.run()  # first optimizer step
    t_step = time.monotonic()
    w0 = np.asarray(wf.forwards[0].weights).copy()
    return {
        "first_step_ms": round(1e3 * (t_step - _T0), 1),
        "region_compiles": _counter("znicz_xla_compiles_total",
                                    site="*"),
        "aot_hit": _counter("znicz_aot_cache_total",
                            site="*", outcome="hit"),
        "weights_sha256": hashlib.sha256(
            np.ascontiguousarray(w0).tobytes()).hexdigest(),
    }


# ----------------------------------------------------------------------
# parent orchestration
# ----------------------------------------------------------------------
def _run_arm(mode: str, cache_dir: str, bundle: str = "",
             chaos: bool = False) -> dict:
    env = dict(os.environ)
    env["ZNICZ_AOT_CACHE"] = cache_dir
    env["COLDSTART_CHAOS"] = "1" if chaos else "0"
    env.setdefault("JAX_PLATFORMS", "cpu")
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         f"--child-{mode}"] + ([bundle] if bundle else []),
        env=env, capture_output=True, text=True, timeout=600)
    wall = round(1e3 * (time.monotonic() - t0), 1)
    if proc.returncode != 0:
        raise RuntimeError(
            f"{mode} arm failed:\n{proc.stdout[-2000:]}\n"
            f"{proc.stderr[-2000:]}")
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    out["process_wall_ms"] = wall
    return out


def run() -> dict:
    work = tempfile.mkdtemp(prefix="coldstart_")
    bundle = os.path.join(work, "model.npz")
    serve_cache = os.path.join(work, "serve_cache")
    train_cache = os.path.join(work, "train_cache")
    _run_arm("export", os.path.join(work, "export_cache"), bundle)

    miss = _run_arm("serve", serve_cache, bundle)
    report: dict = {"platform": miss["platform"] + "-subprocess",
                    "note": ("each arm is a cold python process; "
                             "serve_ready_ms counts interpreter+jax "
                             "import+load+warmup")}
    hit = _run_arm("serve", serve_cache, bundle)
    corrupt = _run_arm("serve", serve_cache, bundle, chaos=True)
    report["serve_miss"], report["serve_hit"] = miss, hit
    report["serve_corrupt"] = corrupt

    # hard gates: a silent cache regression must FAIL, not just slow
    assert miss["compiles"] > 0 and miss["loads"] == 0, miss
    assert hit["compiles"] == 0, f"hit arm traced: {hit}"
    assert hit["loads"] == miss["compiles"], (hit, miss)
    assert hit["metrics"]["xla_compiles"] == 0, hit["metrics"]
    assert hit["serve_ready_ms"] < miss["serve_ready_ms"], (hit, miss)
    assert hit["out_sha256"] == miss["out_sha256"], \
        "hit arm reply not bitwise-equal to traced arm"
    assert corrupt["metrics"]["aot_corrupt"] >= 1, corrupt["metrics"]
    assert corrupt["metrics"]["fallback_recoveries"] >= 1, \
        corrupt["metrics"]
    assert corrupt["compiles"] >= 1, \
        "corrupt arm never fell back to tracing"
    assert corrupt["out_sha256"] == miss["out_sha256"], \
        "corrupt-arm fallback reply not bitwise-equal"

    tmiss = _run_arm("train", train_cache)
    thit = _run_arm("train", train_cache)
    report["train_miss"], report["train_hit"] = tmiss, thit
    assert tmiss["region_compiles"] >= 1, tmiss
    assert thit["region_compiles"] == 0, \
        f"resume arm re-traced: {thit}"
    assert thit["aot_hit"] >= 1, thit
    assert thit["weights_sha256"] == tmiss["weights_sha256"], \
        "first-step weights diverged between traced and loaded arms"

    report["recovered"] = {
        "serve_ready_speedup": round(
            miss["serve_ready_ms"] / max(1e-9, hit["serve_ready_ms"]),
            2),
        "first_step_speedup": round(
            tmiss["first_step_ms"] / max(1e-9, thit["first_step_ms"]),
            2),
        "compiles_eliminated": miss["compiles"]
        + int(tmiss["region_compiles"]),
    }
    return report


def main() -> int:
    if len(sys.argv) > 1 and sys.argv[1].startswith("--child-"):
        mode = sys.argv[1][len("--child-"):]
        if mode == "export":
            _ensure_platform()
            from benchmarks.serve_bench import train_and_export
            out = {"bundle": train_and_export(sys.argv[2], epochs=1)}
        elif mode == "serve":
            out = child_serve(sys.argv[2])
        else:
            out = child_train()
        print(json.dumps(out))
        return 0
    report = run()
    path = os.path.join(REPO, "COLDSTART_BENCH.json")
    with open(path, "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps(report, indent=2))
    print(f"wrote {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

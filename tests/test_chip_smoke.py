"""Bring-up contracts (PR 21): ``chip_smoke.py`` tells the truth with
its exit code, the compile cache can be placed from outside, and the
bench refuses a device it does not know.

The chip itself is not here: the passing run is the ``--cpu-toy`` mode
(same stages, toy sizes, kernels interpreted, every line labelled
``platform=cpu``); that the real sizes pass on a TPU is shown by the
run pasted into CHANGES.md and kept true by the driver.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, tmp_path, code: str | None = None):
    """``chip_smoke.py`` in its own process, on the CPU, with JAX's
    compile cache placed in the test's directory."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
               PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)
    cmd = [sys.executable, "-c", code, *args] if code \
        else [sys.executable, SMOKE, *args]
    return subprocess.run(cmd, cwd=str(tmp_path), env=env, text=True,
                          capture_output=True, timeout=600)


def _checkout_cache_names() -> set:
    try:
        return set(os.listdir(os.path.join(REPO, ".jax_cache")))
    except FileNotFoundError:
        return set()


def test_toy_run_passes_and_labels_every_line_cpu(tmp_path):
    before = _checkout_cache_names()
    proc = _run(["--cpu-toy"], tmp_path)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    stages = [ln for ln in lines if ln.startswith("stage=")]
    assert [ln.split()[0] for ln in stages] == [
        "stage=train", "stage=kernels", "stage=serve"], lines
    for ln in lines[:-1]:
        assert "platform=cpu" in ln, ln
    for ln in stages:
        assert " ok=True " in ln and "setup_s=" in ln and "run_s=" in ln
    kernels = stages[1]
    assert "flash_pallas=True" in kernels and "pallas_ln=True" in kernels
    assert "interpret=True" in kernels       # toy mode says so
    assert "failed_lanes=0" in stages[2]
    assert "programs_after_warmup=0" in stages[0]
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu",
                               "count": 1}}
    # JAX_COMPILATION_CACHE_DIR set: the cache went there and nowhere
    # else
    assert os.listdir(tmp_path / "jax_cache")
    assert _checkout_cache_names() == before
    assert not os.path.exists(os.path.join(REPO, ".chip_smoke"))


def test_no_accelerator_fails_and_prints_no_result(tmp_path):
    """Without ``--cpu-toy`` a machine where JAX finds no TPU is a
    failure with no result line — a CPU run never reads as a chip
    run."""
    proc = _run([], tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == "", proc.stdout
    assert "no TPU" in proc.stderr


def test_failing_stage_fails_the_run_without_hiding_the_next(tmp_path):
    code = (
        "import sys, chip_smoke\n"
        "def boom(ctx):\n"
        "    raise RuntimeError('forced')\n"
        "chip_smoke.STAGES.clear()\n"
        "chip_smoke.STAGES.update(\n"
        "    boom=boom, fine=lambda ctx: {'setup_s': 0, 'run_s': 0})\n"
        "sys.exit(chip_smoke.main(sys.argv[1:]))\n")
    proc = _run(["--cpu-toy", "boom", "fine"], tmp_path, code=code)
    assert proc.returncode == 1, proc.stdout + proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    assert any(ln.startswith("stage=boom") and " ok=False " in ln
               and "forced" in ln for ln in lines), lines
    assert any(ln.startswith("stage=fine") and " ok=True " in ln
               for ln in lines), lines
    assert json.loads(lines[-1])["ok"] is False


def test_kernel_gate_that_does_not_engage_fails_the_run(tmp_path):
    """A gate that silently falls back to the XLA core (here: the
    tiling-legality check refuses every shape) must fail the kernels
    stage, not train on the fallback and pass."""
    code = (
        "import sys, chip_smoke\n"
        "from znicz_tpu.ops import pallas_attention\n"
        "pallas_attention.kernel_legal = lambda *a: False\n"
        "sys.exit(chip_smoke.main(sys.argv[1:]))\n")
    proc = _run(["--cpu-toy", "kernels"], tmp_path, code=code)
    assert proc.returncode == 1, proc.stdout + proc.stderr[-2000:]
    assert "flash gate did not engage" in proc.stdout
    # and the unit said why it fell back
    assert "XLA attention core" in proc.stderr


def test_failed_decode_lane_fails_the_serve_stage(tmp_path, monkeypatch):
    """The decode engine absorbs a failed dispatch (it retries once,
    or fails only the lanes of that step) and keeps serving; the
    smoke must count it as a failure all the same."""
    import chip_smoke
    import jax
    from znicz_tpu.serving.decode import DecodeModel

    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path))
    calls = {"n": 0}
    real = DecodeModel.run_decode

    def flaky(self, *args, **kwargs):
        calls["n"] += 1
        if calls["n"] == 3:
            raise RuntimeError("forced lane failure")
        return real(self, *args, **kwargs)

    monkeypatch.setattr(DecodeModel, "run_decode", flaky)
    ctx = chip_smoke.Ctx(toy=True, devices=jax.devices()[:1],
                         sizes=chip_smoke.TOY)
    with pytest.raises(chip_smoke.SmokeFailure,
                       match="failed lanes or requests"):
        chip_smoke.serve_lm(ctx)
    assert calls["n"] > 3            # the engine itself carried on


# ----------------------------------------------------------------------
# the compile cache and the peaks table
# ----------------------------------------------------------------------
def _cache_config():
    import jax
    return (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs)


def test_compile_cache_placed_from_outside_is_left_alone(monkeypatch):
    from znicz_tpu.backends import configure_compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    before = _cache_config()
    assert configure_compile_cache() == "/some/dir"
    assert _cache_config() == before


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(
        monkeypatch, tmp_path):
    import jax
    from znicz_tpu.backends import configure_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = _cache_config()
    try:
        monkeypatch.chdir(tmp_path)
        first = configure_compile_cache()
        assert jax.config.jax_compilation_cache_dir == first
        monkeypatch.chdir(REPO)
        assert configure_compile_cache() == first
    finally:
        # no program was compiled in between: the suite keeps running
        # without a persistent cache
        jax.config.update("jax_compilation_cache_dir", before[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          before[1])
    assert first == os.path.join(REPO, ".jax_cache")


def test_peak_tflops_refuses_an_unknown_device_kind(monkeypatch):
    import bench

    class Device:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    monkeypatch.delenv("BENCH_PEAK_TFLOPS", raising=False)
    assert bench.peak_tflops(Device()) == 197.0
    Device.device_kind = "TPU v99 mystery"
    with pytest.raises(ValueError, match="v99 mystery"):
        bench.peak_tflops(Device())
    Device.platform, Device.device_kind = "cpu", "cpu"
    with pytest.raises(ValueError, match="device_kind 'cpu'"):
        bench.peak_tflops(Device())

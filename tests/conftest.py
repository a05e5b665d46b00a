"""Test harness config: force a virtual 8-device CPU platform BEFORE
jax initializes, so sharding/DP tests run anywhere (the real-TPU path
is ``chip_smoke.py``, run through the chip tool)."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from znicz_tpu.utils import prng  # noqa: E402
from znicz_tpu.utils.config import reset_root  # noqa: E402

# Opt-in persisted AOT executable cache for the suite (round 23):
# ``ZNICZ_TEST_AOT_CACHE=<dir>`` (or ``=1`` for a throwaway per-run
# dir) points ``ZNICZ_AOT_CACHE`` at a session-scoped store, so every
# warmup/region compile after the first run deserializes instead of
# re-tracing — a large wall-clock cut on repeat runs.  Default is OFF:
# the suite measures tracing behavior unless explicitly asked not to.
# Tests that assert on compile COUNTERS (test_retrace_guard.py,
# test_decode.py, test_export_publish.py, test_fleet.py) opt back out
# per-module via ``root.common.engine.aot_cache = False``.
_aot_dir = os.environ.get("ZNICZ_TEST_AOT_CACHE")
if _aot_dir:
    if _aot_dir in ("1", "true", "yes"):
        import tempfile
        _aot_dir = os.path.join(tempfile.gettempdir(),
                                "znicz_test_aot_cache")
        os.makedirs(_aot_dir, exist_ok=True)
    os.environ["ZNICZ_AOT_CACHE"] = _aot_dir


@pytest.fixture(autouse=True)
def fresh_state(tmp_path):
    """Deterministic seed + pristine config tree per test; all output
    dirs (plots/images/snapshots) redirected into the test's tmp."""
    reset_root()
    from znicz_tpu.utils.config import root
    root.common.dirs.plots = str(tmp_path / "plots")
    root.common.dirs.images = str(tmp_path / "images")
    root.common.dirs.snapshots = str(tmp_path / "snapshots")
    prng.seed_all(1234)
    yield
    from znicz_tpu import graphics
    graphics.reset_server()


@pytest.fixture()
def compile_cache_placed_outside(monkeypatch, tmp_path):
    """For tests that run ``Main`` in this process: it places JAX's
    compile cache in the checkout unless the environment already
    placed it, and in-process that outlives the test — every later
    compile of the worker would be written to ``<checkout>/.jax_cache``
    (which ``test_chip_smoke`` watches from another worker)."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(tmp_path / "jax_cache"))


def make_blobs(n_per_class: int, n_classes: int, dim: int,
               spread: float = 0.35, seed: int = 7):
    """Synthetic gaussian-blob classification data (datasets are not
    downloadable in this environment; functional tests use these the
    way the reference used Wine — a fast, surely-learnable problem)."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(0.0, 1.0, size=(n_classes, dim))
    data = np.concatenate([
        centers[c] + spread * rng.normal(size=(n_per_class, dim))
        for c in range(n_classes)]).astype(np.float32)
    labels = np.repeat(np.arange(n_classes), n_per_class).astype(np.int32)
    order = rng.permutation(len(data))
    return data[order], labels[order]


def blob_classifier(name: str, n_per_class: int, epochs: int,
                    minibatch: int = 12, **kwargs):
    """An UNinitialized two-layer classifier over :func:`make_blobs`
    (TRAIN only, ``3 · n_per_class ÷ minibatch`` steps an epoch) — the
    toy the dispatch and driver tests drive by hand."""
    from znicz_tpu.loader.fullbatch import ArrayLoader
    from znicz_tpu.models.standard_workflow import StandardWorkflow

    data, labels = make_blobs(n_per_class, 3, 10)
    wf = StandardWorkflow(
        name=name,
        loader_factory=lambda w: ArrayLoader(
            w, train_data=data, train_labels=labels,
            minibatch_size=minibatch),
        layers=[{"type": "all2all_tanh",
                 "->": {"output_sample_shape": 16},
                 "<-": {"learning_rate": 0.05}},
                {"type": "softmax", "->": {"output_sample_shape": 3},
                 "<-": {"learning_rate": 0.05}}],
        decision_config={"max_epochs": epochs}, **kwargs)
    wf._max_fires = 100_000
    return wf


def positional_task_workflow(layers, data_seed=9, prng_seed=11,
                             t=9, d=8, n_classes=3, max_epochs=30):
    """Shared builder for 'which third of the sequence carries the
    signal' workflows (attention/PE/layer-norm tests): returns an
    initialized-later StandardWorkflow over the synthetic task."""
    from znicz_tpu.loader.fullbatch import ArrayLoader
    from znicz_tpu.models.standard_workflow import StandardWorkflow
    from znicz_tpu.utils import prng

    rng = np.random.default_rng(data_seed)
    n = 120
    x = rng.normal(0, 0.3, size=(n, t, d)).astype(np.float32)
    y = rng.integers(0, n_classes, size=n).astype(np.int32)
    span = t // n_classes
    for i in range(n):
        x[i, y[i] * span:(y[i] + 1) * span] += 1.0
    prng.seed_all(prng_seed)
    wf = StandardWorkflow(
        name="positional_task",
        loader_factory=lambda w: ArrayLoader(
            w, train_data=x[:96], train_labels=y[:96],
            valid_data=x[96:], valid_labels=y[96:], minibatch_size=24),
        layers=layers,
        decision_config={"max_epochs": max_epochs})
    wf._max_fires = 10 ** 6
    return wf

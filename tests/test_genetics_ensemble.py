"""Genetics hyperparameter search + ensemble tests (reference:
``veles/genetics/`` Tune-range GA, ``veles/ensemble/`` aggregated
evaluation)."""

import numpy as np
import pytest

from znicz_tpu.ensemble import Ensemble, class_forward_pass
from znicz_tpu.genetics import (GeneticsOptimizer, Tune, apply_genome,
                                collect_tunes)
from znicz_tpu.loader.base import VALID
from znicz_tpu.utils.config import root

pytestmark = pytest.mark.usefixtures("compile_cache_placed_outside")


def test_tune_basics():
    t = Tune(0.1, 0.01, 1.0)
    assert not t.is_int
    assert t.clip(5.0) == 1.0 and t.clip(-1) == 0.01
    ti = Tune(8, 2, 64)
    assert ti.is_int
    assert ti.clip(3.4) == 3
    with pytest.raises(ValueError):
        Tune(2.0, 0.0, 1.0)


def test_collect_tunes_and_apply_genome():
    root.gen_test.lr = Tune(0.1, 0.01, 1.0)
    root.gen_test.nested.units = Tune(8, 2, 64)
    space = collect_tunes(root.gen_test)
    assert set(space) == {"lr", "nested.units"}
    kwargs = apply_genome({"gen_test.lr": 0.5, "hidden": 16})
    assert kwargs == {"hidden": 16}
    assert root.gen_test.lr == 0.5


def test_ga_optimizes_quadratic():
    """Pure-GA check on a known optimum — no training involved."""
    space = {"x": Tune(0.0, -4.0, 4.0), "y": Tune(0.0, -4.0, 4.0),
             "k": Tune(10, 1, 20)}

    def fitness(g):
        return -((g["x"] - 2.0) ** 2 + (g["y"] + 1.0) ** 2
                 + 0.05 * (g["k"] - 7) ** 2)

    opt = GeneticsOptimizer(space=space, fitness_fn=fitness,
                            population_size=16, generations=12, seed=5)
    best = opt.run()
    assert opt.best_fitness > -0.5
    assert abs(best["x"] - 2.0) < 0.7
    assert abs(best["y"] + 1.0) < 0.7
    # monotone best-so-far, recorded history per generation
    assert len(opt.history) == 12
    bests = [h["best"] for h in opt.history]
    assert bests[-1] >= bests[0]


def test_ga_caches_fitness_calls():
    calls = {"n": 0}

    def fitness(g):
        calls["n"] += 1
        return -g["x"] ** 2

    opt = GeneticsOptimizer(
        space={"x": Tune(1.0, -2.0, 2.0)}, fitness_fn=fitness,
        population_size=6, generations=4, seed=0)
    opt.run()
    # elites are re-scored each generation but must hit the cache
    assert calls["n"] < 6 * 4


def test_train_fitness_restores_config_leaves():
    """Regression (round 14): a candidate's dotted-key config writes
    must not outlive its evaluation — the Tune leaf the space was
    collected from comes back after each ``_train_fitness`` call."""
    from znicz_tpu.backends import NumpyDevice
    from znicz_tpu.models.samples.wine import build

    root.wine.learning_rate = Tune(0.3, 0.05, 0.8)
    opt = GeneticsOptimizer(
        build_fn=build,
        space={"wine.learning_rate": Tune(0.3, 0.05, 0.8)},
        population_size=2, generations=1, seed=7,
        device_factory=NumpyDevice,
        train_kwargs={"max_epochs": 1})
    opt._train_fitness({"wine.learning_rate": 0.11})
    leaf = root.wine.learning_rate
    assert isinstance(leaf, Tune), (
        f"candidate lr 0.11 leaked into root after evaluation: {leaf}")


def test_ga_run_leaves_best_genome_in_root():
    """After ``run()`` the config tree holds the BEST genome's values
    (callers build the final model straight off root), not whatever
    candidate happened to be evaluated last."""
    from znicz_tpu.backends import NumpyDevice
    from znicz_tpu.models.samples.wine import build

    opt = GeneticsOptimizer(
        build_fn=build,
        space={"wine.learning_rate": Tune(0.3, 0.05, 0.8)},
        population_size=3, generations=2, seed=7,
        device_factory=NumpyDevice,
        train_kwargs={"max_epochs": 2})
    best = opt.run()
    assert root.wine.learning_rate == best["wine.learning_rate"]


def test_snapshot_restore_handles_missing_leaves():
    from znicz_tpu.genetics import (restore_genome_leaves,
                                    snapshot_genome_leaves)

    genome = {"gen_leak.fresh.leaf": 3.5, "plain_kwarg": 1}
    snap = snapshot_genome_leaves(genome)
    apply_genome(genome)
    assert root.gen_leak.fresh.leaf == 3.5
    restore_genome_leaves(snap)
    assert "leaf" not in root.gen_leak.fresh.__dict__


def test_ga_trains_wine():
    """End-to-end: a 2-generation GA over the Wine sample (numpy
    backend so it stays fast)."""
    from znicz_tpu.backends import NumpyDevice
    from znicz_tpu.models.samples.wine import build

    opt = GeneticsOptimizer(
        build_fn=build,
        space={"learning_rate": Tune(0.3, 0.05, 0.8)},
        population_size=3, generations=2, seed=7,
        device_factory=NumpyDevice,
        train_kwargs={"max_epochs": 3})
    best = opt.run()
    assert 0.05 <= best["learning_rate"] <= 0.8
    assert opt.best_fitness >= -100.0  # a valid error percentage


def _wine_build(**overrides):
    from znicz_tpu.models.samples.wine import build
    overrides.setdefault("max_epochs", 4)
    return build(**overrides)


def test_ensemble_votes_better_or_equal():
    from znicz_tpu.backends import NumpyDevice

    ens = Ensemble(_wine_build, n_models=3, base_seed=42,
                   device_factory=NumpyDevice)
    ens.train()
    assert len(ens.workflows) == 3
    result = ens.evaluate(VALID)
    assert result["n_samples"] == 28  # real UCI wine: 178 - 150 train
    assert len(result["member_err_pt"]) == 3
    # the averaged vote should not be (much) worse than the best member
    assert result["ensemble_err_pt"] <= min(result["member_err_pt"]) + 8.0


def test_class_forward_pass_covers_split():
    from znicz_tpu.backends import NumpyDevice
    from znicz_tpu.utils import prng

    prng.seed_all(1)
    wf = _wine_build(max_epochs=2)
    wf.initialize(device=NumpyDevice())
    wf.run()
    outputs, labels = class_forward_pass(wf, VALID)
    assert len(outputs) == 28 and len(labels) == 28
    probs = np.stack(list(outputs.values()))
    np.testing.assert_allclose(probs.sum(axis=1), 1.0, atol=1e-4)


def test_ensemble_evaluate_xla_region():
    """The aggregate pass must also work through the compiled jit
    region (XLA backend)."""
    from znicz_tpu.backends import XLADevice

    ens = Ensemble(_wine_build, n_models=2, base_seed=3,
                   device_factory=XLADevice,
                   train_kwargs={"max_epochs": 2})
    ens.train()
    result = ens.evaluate(VALID)
    assert result["n_samples"] == 28
    assert 0.0 <= result["ensemble_err_pt"] <= 100.0


def test_cli_optimize_wine():
    """--optimize drives the GA through the sample's run(load, main);
    the Tune leaf arrives via a --root override (reference behavior:
    config files wrap leaves in Tune)."""
    from znicz_tpu.__main__ import Main

    main = Main()
    rc = main.run([
        "wine", "--backend", "numpy", "--optimize", "2x3",
        "--root", "wine.max_epochs=2",
        "--root", "wine.learning_rate=Tune(0.3, 0.05, 0.8)"])
    assert rc == 0
    best = main.best_genome
    assert set(best) == {"wine.learning_rate"}
    assert 0.05 <= best["wine.learning_rate"] <= 0.8


def test_cli_optimize_without_tunes_errors():
    from znicz_tpu.__main__ import Main

    rc = Main().run(["wine", "--backend", "numpy", "--optimize", "1x2",
                     "--root", "wine.max_epochs=1"])
    assert rc == 1

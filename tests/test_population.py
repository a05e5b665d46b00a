"""Population engine semantics (round 14): the vmapped K-member step
must BE K independent sequential runs — bitwise — with evolution as
deterministic on-device ops over the stacked tree.

The contract pinned here:

- population-K training ≡ K sequential ``StandardWorkflow`` runs,
  member weights bitwise after N epochs (per-member weight init,
  dropout PRNG chains and epoch shuffle streams all included);
- evolution replays identically under a fixed seed; PBT exploit copies
  the winner's weights+hypers EXACTLY;
- the member axis shards over the 8-device mesh's data axis;
- the canonical population series register;
- a warmed population step / generation performs ZERO new XLA
  compiles (the retrace-guard population case).
"""

from __future__ import annotations

import numpy as np
import pytest

from conftest import make_blobs
from znicz_tpu.backends import XLADevice
from znicz_tpu.loader.base import VALID
from znicz_tpu.loader.fullbatch import ArrayLoader
from znicz_tpu.models.standard_workflow import StandardWorkflow
from znicz_tpu.observe import metrics as obs_metrics
from znicz_tpu.population import PopulationTrainer
from znicz_tpu.utils import prng


DATA, LABELS = make_blobs(24, 3, 10, seed=7)


def build(learning_rate=0.05, max_epochs=3, dropout=True, **kw):
    layers = [{"type": "all2all_tanh",
               "->": {"output_sample_shape": 16},
               "<-": {"learning_rate": learning_rate,
                      "gradient_moment": 0.9}}]
    if dropout:
        layers.append({"type": "dropout",
                       "->": {"dropout_ratio": 0.25}})
    layers.append({"type": "softmax", "->": {"output_sample_shape": 3},
                   "<-": {"learning_rate": learning_rate,
                          "gradient_moment": 0.9}})
    return StandardWorkflow(
        name="pop_net",
        loader_factory=lambda w: ArrayLoader(
            w, train_data=DATA[:48], train_labels=LABELS[:48],
            valid_data=DATA[48:], valid_labels=LABELS[48:],
            minibatch_size=12),
        layers=layers,
        decision_config={"max_epochs": max_epochs})


def _param_vectors(wf):
    out = []
    for fwd, gd_unit in zip(wf.forwards, wf.gds):
        for vec in (fwd.weights, fwd.bias,
                    gd_unit.accumulated_gradient_weights,
                    gd_unit.accumulated_gradient_bias):
            if vec is not None and vec:
                out.append(vec)
    return out


def test_population_step_bitwise_equals_sequential_runs():
    """The tentpole invariant: the vmapped population-K step is the K
    independent runs, not an approximation — per-member weights, bias
    AND momentum accumulators bitwise after 3 epochs (dropout PRNG
    chains and per-member epoch shuffles included), and the
    per-member fitness equals each sequential Decision's metric."""
    k, epochs = 3, 3
    oracle = []
    for i in range(k):
        prng.seed_all(500 + i)
        wf = build()
        wf._max_fires = 10 ** 6
        wf.initialize(device=XLADevice())
        wf.run()
        oracle.append((
            [np.array(np.asarray(v), copy=True)
             for v in _param_vectors(wf)],
            -wf.decision.min_validation_n_err_pt))
    trainer = PopulationTrainer(build, k, base_seed=500, evolve=None,
                                name="pop_bitwise")
    trainer.initialize()
    trainer.run(epochs)
    tmpl = trainer.template
    for i in range(k):
        want_params, want_fit = oracle[i]
        for vec, want in zip(_param_vectors(tmpl), want_params):
            got = np.asarray(trainer.region.read_leaf(vec)[i])
            assert got.dtype == want.dtype
            assert np.array_equal(got, want), (
                f"member {i} leaf {vec.name} diverged from its "
                f"sequential run (max "
                f"{np.max(np.abs(got.astype(np.float64) - want)):.3e})")
        assert trainer.member_best_fitness[i] == pytest.approx(want_fit)


def test_population_install_best_and_oracle_forward():
    """install_best writes the winner's slice back into the template:
    the template's direct forward on held-out rows must match the
    winner's stacked eval output."""
    trainer = PopulationTrainer(build, 3, base_seed=500, evolve=None,
                                name="pop_install")
    trainer.initialize()
    trainer.run(2)
    best = trainer.install_best()
    tmpl = trainer.template
    for vec in _param_vectors(tmpl):
        stacked = trainer.region.read_leaf(vec)
        assert np.array_equal(np.asarray(vec), stacked[best])


def test_population_evolution_deterministic_under_fixed_seed():
    """Same seeds → the identical evolutionary trajectory: history,
    mutated learning rates and final stacked weights all replay."""
    runs = []
    for _ in range(2):
        trainer = PopulationTrainer(
            build, 4, base_seed=300, evolve="pbt", evolve_every=1,
            lr_bounds=(0.005, 0.5), seed=11, name="pop_det")
        trainer.initialize()
        trainer.run(3)
        w = trainer.region.read_leaf(trainer.template.forwards[0].weights)
        runs.append((trainer.history, trainer.region.member_lrs(),
                     np.array(w, copy=True)))
    assert runs[0][0] == runs[1][0]
    assert np.array_equal(runs[0][1], runs[1][1])
    assert np.array_equal(runs[0][2], runs[1][2])


def test_pbt_exploit_copies_winner_bitwise_and_explores_lr():
    """Forced fitness ranking: after one PBT generation the loser's
    weights AND momentum are the winner's bitwise (exploit), its
    learning rate is the winner's times a factor from {0.8, 1.25}
    (explore), and untouched members stay bitwise identical."""
    k = 4
    trainer = PopulationTrainer(
        build, k, base_seed=400, evolve="pbt", truncation=0.25,
        seed=21, name="pop_exploit")
    trainer.initialize()
    trainer.run_epoch()
    region = trainer.region
    tmpl = trainer.template
    watch = _param_vectors(tmpl)
    before = {id(v): np.array(region.read_leaf(v), copy=True)
              for v in watch}
    lrs_before = region.member_lrs()
    # member 3 is the loser, member 0 the only winner (=> the donor)
    trainer.evolve_generation(np.array([3.0, 2.0, 1.0, 0.0]))
    for v in watch:
        after = region.read_leaf(v)
        assert np.array_equal(after[3], before[id(v)][0]), \
            f"exploit did not copy the winner's {v.name} exactly"
        for member in (0, 1, 2):
            assert np.array_equal(after[member],
                                  before[id(v)][member]), \
                f"non-truncated member {member} was disturbed"
    lrs_after = region.member_lrs()
    ratio = lrs_after[3] / lrs_before[0]
    assert min(abs(ratio - 0.8), abs(ratio - 1.25)) < 1e-6, ratio
    assert np.array_equal(lrs_after[:3], lrs_before[:3])


def test_member_axis_shards_over_mesh():
    """K=16 on the 8-device mesh: every member-stacked leaf's dim 0
    splits over the data axis (2 members per chip); an indivisible K
    stays replicated (time-sliced) instead of erroring."""
    import jax
    from znicz_tpu.parallel import make_mesh
    mesh = make_mesh(n_data=8, n_model=1)
    trainer = PopulationTrainer(build, 16, base_seed=600, evolve=None,
                                mesh=mesh, name="pop_shard")
    trainer.initialize()
    tmpl = trainer.template
    w = trainer.region.svec(tmpl.forwards[0].weights)
    assert w.member_axis
    dev = w.devmem
    assert len(dev.sharding.device_set) == 8
    assert dev.sharding.shard_shape(dev.shape)[0] == 2
    acc = trainer.region.svec(
        tmpl.gds[0].accumulated_gradient_weights)
    assert acc.devmem.sharding.shard_shape(acc.devmem.shape)[0] == 2
    trainer.run(1)
    # survives a full epoch; fitness is one number per member
    assert len(trainer.history[0]["fitness"]) == 16
    del trainer

    odd = PopulationTrainer(build, 6, base_seed=600, evolve=None,
                            mesh=mesh, name="pop_shard_odd")
    odd.initialize()
    dev = odd.region.svec(odd.template.forwards[0].weights).devmem
    assert dev.sharding.is_fully_replicated
    assert len(jax.devices()) >= 8


def test_member_axis_vector_validation():
    from znicz_tpu.memory import Vector
    from znicz_tpu.parallel import make_mesh
    mesh = make_mesh(n_data=8, n_model=1)
    dev = XLADevice(mesh=mesh)
    bad = Vector(np.zeros((4, 2), np.float32), member_axis=True)
    bad.batch_major = True
    with pytest.raises(ValueError, match="member_axis"):
        dev.sharding_for(bad)
    bad2 = Vector(np.zeros((4, 2), np.float32), member_axis=True,
                  model_shard_dim=0)
    with pytest.raises(ValueError, match="member axis"):
        dev.sharding_for(bad2)


def test_population_telemetry_series_registered():
    trainer = PopulationTrainer(
        build, 3, base_seed=700, evolve="pbt", evolve_every=1,
        seed=5, name="pop_obs")
    trainer.initialize()
    trainer.run(2)
    reg = obs_metrics.REGISTRY
    fit = reg.get("znicz_population_fitness")
    assert fit is not None
    members = {key[1] for key, _ in fit.items()
               if key[0] == "pop_obs"}
    assert members == {"0", "1", "2"}
    assert obs_metrics.population_members("pop_obs").value == 3
    assert obs_metrics.population_generations("pop_obs").value == 1
    assert obs_metrics.population_evolution("pop_obs",
                                            "exploit").value >= 1
    assert obs_metrics.population_evolution("pop_obs",
                                            "explore").value >= 1
    best = obs_metrics.population_best_fitness("pop_obs").value
    assert best == pytest.approx(trainer.best_fitness)


def test_population_retrace_guard_zero_new_compiles():
    """The retrace-guard population case: once both region variants
    and the evolution program are warmed, further steps AND further
    generations hit the program caches — zero new XLA compiles."""
    trainer = PopulationTrainer(
        build, 4, base_seed=800, evolve="pbt", evolve_every=1,
        seed=9, name="pop_retrace")
    trainer.initialize()
    trainer.run(2)  # warms train+eval variants and one generation
    step_c = obs_metrics.xla_compiles("population:pop_retrace")
    evolve_c = obs_metrics.xla_compiles("population-evolve:pop_retrace")
    warmed_steps, warmed_evolves = step_c.value, evolve_c.value
    assert warmed_steps >= 2 and warmed_evolves == 1
    for _ in range(8):  # cycles through train AND valid minibatches
        trainer.region.step()
    trainer.evolve_generation(np.zeros(4))
    assert step_c.value == warmed_steps, (
        f"warmed population steps recompiled "
        f"{step_c.value - warmed_steps} new programs")
    assert evolve_c.value == warmed_evolves, \
        "a warmed evolution generation recompiled"


def test_population_ga_strategy_runs_and_keeps_elite():
    trainer = PopulationTrainer(
        build, 4, base_seed=900, evolve="ga", evolve_every=1, elite=1,
        lr_bounds=(0.005, 0.5), seed=2, name="pop_ga")
    trainer.initialize()
    trainer.run_epoch()
    region = trainer.region
    w = trainer.template.forwards[0].weights
    before = np.array(region.read_leaf(w), copy=True)
    fitness = np.array([0.0, 5.0, 1.0, 2.0])
    trainer.evolve_generation(fitness)
    after = region.read_leaf(w)
    # the elite slot (member 1, best fitness) is untouched
    assert np.array_equal(after[1], before[1])
    assert obs_metrics.population_evolution("pop_ga",
                                            "crossover").value == 3
    lrs = region.member_lrs()
    assert np.all(lrs >= 0.005) and np.all(lrs <= 0.5)


def test_population_publish_best_feeds_canary_pipeline(tmp_path):
    """The PBT→serving loop: publish_best writes a digest-sidecar
    bundle the round-13 watcher verifies and a SwapController
    promotes into a live engine."""
    from znicz_tpu.backends import NumpyDevice
    from znicz_tpu.export import ExportedModel
    from znicz_tpu.resilience.publisher import (PublicationWatcher,
                                                SwapController,
                                                classifier_score)
    from znicz_tpu.serving import ServingEngine

    trainer = PopulationTrainer(build, 3, base_seed=950, evolve=None,
                                name="pop_publish")
    trainer.initialize()
    trainer.run(2)
    pubdir = str(tmp_path / "published")
    version, path = trainer.publish_best(pubdir)
    assert version == 1
    watcher = PublicationWatcher(pubdir)
    got = watcher.poll()
    assert got is not None and got[0] == 1  # digest verified

    # the published bundle scores like the best member and promotes
    oracle = ExportedModel.load(path, device=NumpyDevice())
    out = np.asarray(oracle(DATA[48:52]))
    assert out.shape == (4, 3)
    with ServingEngine(path, max_batch=4, max_delay_ms=2.0) as engine:
        engine.set_model_version(1)
        controller = SwapController(
            engine, watcher, classifier_score(DATA[48:], LABELS[48:]),
            guard_margin=0.5, probation_steps=1)
        version2, _ = trainer.publish_best(pubdir)
        assert version2 == 2
        events = controller.tick()
        assert any("promoted" in e for e in events), events
        assert engine.model_version == 2


def test_genetics_mesh_backend_matches_process_fitness():
    """One generation scored by the mesh backend == the same genomes
    scored one-by-one by the process backend (the population step is
    the sequential run, so the fitness cache agrees exactly)."""
    from znicz_tpu.genetics import GeneticsOptimizer, Tune

    genomes = [{"learning_rate": v} for v in (0.02, 0.1, 0.3)]
    space = {"learning_rate": Tune(0.05, 0.01, 0.4)}
    proc = GeneticsOptimizer(
        build_fn=build, space=space, population_size=3, generations=1,
        seed=9, train_kwargs={"max_epochs": 2})
    want = [proc._train_fitness(dict(g)) for g in genomes]
    mesh = GeneticsOptimizer(
        build_fn=build, space=space, population_size=3, generations=1,
        seed=9, backend="mesh", train_kwargs={"max_epochs": 2})
    pending = [(tuple(sorted(g.items())), g) for g in genomes]
    mesh._score_population_mesh(pending)
    got = [mesh._cache[k] for k, _ in pending]
    assert got == want
    assert mesh.local_evaluated == [k for k, _ in pending]


def test_genetics_mesh_backend_full_run():
    from znicz_tpu.genetics import GeneticsOptimizer, Tune

    opt = GeneticsOptimizer(
        build_fn=build, space={"learning_rate": Tune(0.05, 0.01, 0.4)},
        population_size=4, generations=2, seed=3, backend="mesh",
        train_kwargs={"max_epochs": 2})
    best = opt.run()
    assert 0.01 <= best["learning_rate"] <= 0.4
    assert len(opt.history) == 2
    assert opt.best_fitness >= opt.history[0]["mean"]


def test_genetics_mesh_backend_rejects_architecture_genomes():
    from znicz_tpu.genetics import GeneticsOptimizer, Tune

    with pytest.raises(ValueError, match="learning_rate"):
        GeneticsOptimizer(
            build_fn=build, backend="mesh",
            space={"hidden": Tune(8, 4, 32)})
    with pytest.raises(ValueError, match="learning_rate"):
        GeneticsOptimizer(
            build_fn=build, backend="mesh",
            space={"learning_rate": Tune(0.05, 0.01, 0.4),
                   "wine.layers": Tune(8, 4, 32)})


def test_ensemble_stacked_matches_sequential():
    """Mesh-backend ensemble ≡ the sequential Ensemble: same member
    validation errors, same aggregated vote."""
    from znicz_tpu.ensemble import Ensemble

    seq = Ensemble(build, n_models=3, base_seed=42,
                   device_factory=XLADevice,
                   train_kwargs={"max_epochs": 2})
    seq.train()
    want = seq.evaluate(VALID)
    stacked = Ensemble(build, n_models=3, base_seed=42,
                       backend="mesh", train_kwargs={"max_epochs": 2})
    stacked.train()
    got = stacked.evaluate(VALID)
    assert got["n_samples"] == want["n_samples"]
    assert got["member_err_pt"] == want["member_err_pt"]
    assert got["ensemble_err_pt"] == want["ensemble_err_pt"]
    assert [s["validation_err_pt"] for s in stacked.member_stats] == \
        [s.get("validation_err_pt") for s in seq.member_stats]


class _KeepsCast(StandardWorkflow):
    """``build``'s net whose first layer's weights keep a bf16 cast of
    themselves (``Vector.keep_cast``), a leaf of that layer's backward
    unit."""

    def initialize(self, device=None, **kwargs) -> None:
        import ml_dtypes
        from znicz_tpu.memory import Vector
        super().initialize(device=device, **kwargs)
        if self.device.is_host_only:
            return
        gd_unit = self.gds[0]
        gd_unit.weights_cast = Vector(name=f"{gd_unit.name}.weights_cast")
        self.forwards[0].weights.keep_cast(gd_unit.weights_cast,
                                           ml_dtypes.bfloat16)


def test_a_kept_cast_stays_the_cast_through_steps_writes_and_blends(
        monkeypatch):
    """A leaf that keeps a cast of itself keeps it in a population:
    after vmapped steps, after a GA generation (whose blend of two
    members' casts is not their blend's cast) and after a host write
    of the stacked leaf, every member's copy is its weights cast."""
    import ml_dtypes
    import znicz_tpu.models.standard_workflow as sw
    monkeypatch.setattr(sw, "StandardWorkflow", _KeepsCast)
    monkeypatch.setattr("test_population.StandardWorkflow", _KeepsCast)
    trainer = PopulationTrainer(
        build, 4, base_seed=910, evolve="ga", evolve_every=1, elite=1,
        lr_bounds=(0.005, 0.5), seed=3, name="pop_cast")
    trainer.initialize()
    region = trainer.region
    weights = trainer.template.forwards[0].weights
    copy = weights.cast_copy
    assert region.svec(weights).cast_copy is region.svec(copy)

    def assert_cast() -> np.ndarray:
        stacked = np.array(region.read_leaf(weights), copy=True)
        np.testing.assert_array_equal(
            region.read_leaf(copy), stacked.astype(ml_dtypes.bfloat16))
        return stacked

    drawn = assert_cast()
    trainer.run_epoch()
    trained = assert_cast()
    assert np.abs(trained - drawn).max() > 0
    trainer.evolve_generation(np.array([0.0, 5.0, 1.0, 2.0]))
    blended = assert_cast()
    assert np.abs(blended - trained).max() > 0
    region.write_leaf(weights, 0.5 * blended)
    region.step()
    assert np.abs(assert_cast() - blended).max() > 0

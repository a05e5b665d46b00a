"""A two-layer OLMoE ``StandardWorkflow`` against the benchmark's plain
reference (``znbench/reference/olmoe.py``) on seeded weights, at the
small size (hidden 64, 4 heads of 16, 8 experts top 2 of width 32,
vocabulary 97, T 32): every layer's output, the probabilities at every
position, the loss with its two auxiliary terms, and EVERY gradient
against the reference's ``jax.value_and_grad``."""

import importlib.util
import os

import numpy as np
import pytest

from znicz_tpu.backends import XLADevice
from znicz_tpu.loader.base import TRAIN
from znicz_tpu.loader.fullbatch import ArrayLoader
from znicz_tpu.models.standard_workflow import StandardWorkflow
from znicz_tpu.observe import metrics as obs_metrics
from znicz_tpu.utils import prng

HIDDEN, HEADS, EXPERTS, TOP_K, WIDTH = 64, 4, 8, 2, 32
VOCAB, SEQ, BATCH = 97, 32, 4
AUX_W, Z_W = 0.01, 0.001


def reference():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "znbench", "reference", "olmoe.py")
    spec = importlib.util.spec_from_file_location("ref_olmoe", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def layers(lr: float, moment: float) -> list:
    gd = {"learning_rate": lr, "gradient_moment": moment}
    block = [
        {"type": "attention",
         "->": {"n_heads": HEADS, "causal": True, "include_bias": False,
                "pre_norm": "rms", "qk_norm": "rms", "residual": True,
                "rope": {"theta": 10000}, "norm_eps": 1e-5}, "<-": gd},
        {"type": "moe",
         "->": {"n_experts": EXPERTS, "top_k": TOP_K, "width": WIDTH,
                "norm_topk": False, "pre_norm": "rms", "residual": True,
                "aux_loss_weight": AUX_W, "z_loss_weight": Z_W,
                "norm_eps": 1e-5}, "<-": gd}]
    return ([{"type": "embedding",
              "->": {"vocab_size": VOCAB, "dim": HIDDEN}, "<-": gd}]
            + [dict(layer) for _ in range(2) for layer in block]
            + [{"type": "rms_norm", "->": {"eps": 1e-5}, "<-": gd},
               {"type": "softmax",
                "->": {"output_sample_shape": VOCAB,
                       "per_position": True, "include_bias": False},
                "<-": gd}])


def params_of(wf) -> dict:
    out = {}
    for i, unit in enumerate(wf.forwards):
        for attr in unit.EXPORT_PARAMS:
            vec = getattr(unit, attr)
            if vec:
                vec.map_read()
                out[f"layer{i}_{attr}"] = np.array(vec.mem, np.float32)
    return out


@pytest.fixture(scope="module")
def one_step():
    """One plain-SGD step at lr 1 (W −= gradient) of the system, with
    the parameters before it and the tokens it saw."""
    # a module fixture is set up BEFORE conftest's per-test reset: start
    # from a pristine config tree, not the previous file's last test's
    from znicz_tpu.utils.config import reset_root
    reset_root()
    rng = np.random.default_rng(17)
    ids = rng.integers(0, VOCAB, (BATCH, SEQ + 1))
    x, y = ids[:, :-1], ids[:, 1:]
    prng.seed_all(31)
    table = layers(1.0, 0.0)
    wf = StandardWorkflow(
        name="olmoe_ref",
        loader_factory=lambda w: ArrayLoader(
            w, train_data=x.astype(np.float32),
            train_labels=y.astype(np.int32), minibatch_size=BATCH,
            shuffle_limit=0),
        layers=table, decision_config={"max_epochs": 1})
    wf.initialize(device=XLADevice())
    rng = np.random.default_rng(18)
    for unit in wf.forwards:      # gains of one would hide their path
        for attr in ("gain_norm", "gain_q", "gain_k"):
            vec = getattr(unit, attr, None)
            if vec:
                vec.map_invalidate()
                vec.mem[...] = rng.uniform(0.7, 1.3, vec.shape)
    before = params_of(wf)
    wf.run()
    return wf, table, before, x, y


def test_layer_outputs_and_probabilities(one_step):
    """f32 on both sides: what is left is the order of summation
    (grouped matmul and gathers against a loop over experts, a fused
    core against query blocks), 1e-5 of a layer's range; 1e-4 is ten
    times that and a hundred times under what bf16 anywhere would
    leave (4e-3)."""
    wf, table, before, x, y = one_step
    ref = reference()
    outs, router = ref.run(before, table, x)
    assert len(outs) == len(wf.forwards) == 7
    for i, (unit, want) in enumerate(zip(wf.forwards, outs)):
        unit.output.map_read()
        got = np.asarray(unit.output.mem, np.float32).reshape(want.shape)
        err = np.abs(got - np.asarray(want)).max() \
            / (np.abs(np.asarray(want)).max() + 1e-12)
        assert err < 1e-4, (i, table[i]["type"], err)
    probs = np.asarray(outs[-1])
    assert probs.shape == (BATCH, SEQ, VOCAB)
    np.testing.assert_allclose(probs.sum(-1), 1.0, rtol=1e-5)
    # the router: same logits, same experts
    for i, unit in enumerate(wf.forwards):
        if table[i]["type"] != "moe":
            continue
        unit.router_logits.map_read()
        unit.last_choice.map_read()
        np.testing.assert_allclose(
            unit.router_logits.mem.reshape(-1, EXPERTS),
            np.asarray(router["logits"][i]), rtol=1e-4, atol=1e-5)
        np.testing.assert_array_equal(
            np.sort(unit.last_choice.mem.reshape(-1, TOP_K), axis=-1),
            np.sort(router["chosen"][i], axis=-1))


def test_loss_and_every_gradient(one_step):
    """The step ran plain SGD at lr 1, so parameter − parameter after
    IS the system's gradient of CE + 0.01·lb + 0.001·z: compared with
    the reference's ``value_and_grad`` for all 23 tensors.  Tolerance
    1e-3 of each gradient's largest entry (f32 both sides; sums over
    128 rows in different orders), a tenth of what a bf16 matmul input
    would leave."""
    wf, table, before, x, y = one_step
    ref = reference()
    value, grads = ref.loss_and_grads(before, table, x, y)
    after = params_of(wf)
    assert set(grads) == set(before) and len(before) == 23
    for name, want in grads.items():
        got = before[name] - after[name]
        scale = np.abs(want).max()
        assert scale > 0, name
        assert np.abs(got - want).max() <= 1e-3 * scale, (
            name, np.abs(got - want).max() / scale)
    # the loss: the evaluator's mean cross-entropy over every position
    # plus the expert layers' own (device-kept) auxiliary terms
    ce = wf.decision.epoch_loss[TRAIN]
    aux = 0.0
    for i, unit in enumerate(wf.forwards):
        if table[i]["type"] == "moe":
            aux += AUX_W * obs_metrics.moe_aux_loss(
                unit.name, "load_balance").value
            aux += Z_W * obs_metrics.moe_aux_loss(unit.name, "z").value
    assert ce == pytest.approx(np.log(VOCAB), rel=0.1)  # random init
    assert ce + aux == pytest.approx(value, rel=1e-4)
    assert wf.decision.epoch_n_err_pt[TRAIN] <= 100.0

"""The toy Olmo-Hybrid ``StandardWorkflow`` (``znbench/tests/data/toy``:
two linear layers and the full layer of its table, every sublayer under
the norm-after placement; hidden 64, 4 linear heads of 12 × 24, 4 full
heads of 16, T 128 = two chunks) against the benchmark's plain
reference (``znbench/reference/olmo_hybrid.py``, which runs the delta
rule token by token) on seeded weights, in f32 with the state kernels
and the flash kernels interpreted: every layer's output, the loss,
EVERY gradient against the reference's ``jax.value_and_grad``; and each
left-out term of ``benchmarks/olmo_hybrid_controls.py`` fails the
cell's stated tolerance."""

import copy
import importlib.util
import json
import os

import numpy as np
import pytest

from benchmarks import olmo_hybrid_controls as controls
from znicz_tpu.backends import XLADevice
from znicz_tpu.loader.base import TRAIN
from znicz_tpu.loader.fullbatch import ArrayLoader
from znicz_tpu.models.standard_workflow import StandardWorkflow
from znicz_tpu.observe import metrics as obs_metrics
from znicz_tpu.ops import attention, delta_net, moe
from znicz_tpu.utils import prng

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ, BATCH = 128, 2


def reference():
    path = os.path.join(REPO, "znbench", "reference", "olmo_hybrid.py")
    spec = importlib.util.spec_from_file_location("ref_olmo_hybrid", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def config(toy: bool = True) -> dict:
    parts = ("tests", "data", "toy") if toy else ()
    with open(os.path.join(REPO, "znbench", *parts, "configs",
                           "olmo_hybrid_7b.json")) as fh:
        return json.load(fh)


#: of the toy cell's eleven layers: the embedding, TWO linear blocks,
#: the full block, the final norm and the head (the cell's third linear
#: block repeats the two kept)
KEPT = (0, 1, 2, 3, 4, 7, 8, 9, 10)


def layers(lr: float, moment: float) -> list:
    table = copy.deepcopy(config()["workflow"]["layers"])
    table = [table[i] for i in KEPT]
    for layer in table:
        layer["<-"] = {"learning_rate": lr, "gradient_moment": moment}
    return table


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
    """One plain-SGD step at lr 1 (W −= gradient) of the system, f32,
    state and flash kernels interpreted, with the parameters before it
    and the tokens it saw."""
    from znicz_tpu.utils.config import reset_root, root
    reset_root()
    engine = root.common.engine
    engine.pallas_interpret = True
    engine.flash_attention = True
    engine.delta_scan_kernel = True
    vocab = config()["input"]["vocab"]
    rng = np.random.default_rng(17)
    ids = rng.integers(0, vocab, (BATCH, SEQ + 1))
    x, y = ids[:, :-1], ids[:, 1:]
    prng.seed_all(31)
    table = layers(1.0, 0.0)
    wf = StandardWorkflow(
        name="olmo_hybrid_ref",
        loader_factory=lambda w: ArrayLoader(
            w, train_data=x.astype(np.float32),
            train_labels=y.astype(np.int32), minibatch_size=BATCH,
            shuffle_limit=0),
        layers=table, decision_config={"max_epochs": 1})
    wf.initialize(device=XLADevice())
    rng = np.random.default_rng(18)
    for unit in wf.forwards:      # gains of one would hide their path
        for attr in ("gain_norm", "gain_out", "gain_q", "gain_k"):
            vec = getattr(unit, attr, None)
            if vec:
                vec.map_invalidate()
                vec.mem[...] = rng.uniform(0.7, 1.3, vec.shape)
    before = params_of(wf)
    wf.run()
    reset_root()
    return wf, table, before, x, y


def test_the_toy_model_is_the_cell_s_model_in_small(one_step):
    wf, table, *_ = one_step
    assert [layer["type"] for layer in table] == [
        "embedding", "gated_delta_net", "gated_mlp", "gated_delta_net",
        "gated_mlp", "attention", "gated_mlp", "rms_norm", "softmax"]
    real = config(toy=False)["workflow"]["layers"]
    toy = config()["workflow"]["layers"]
    assert [layer["type"] for layer in real] \
        == [layer["type"] for layer in toy]
    for big, small in zip(real, toy):        # the same options, smaller
        assert set(big["->"]) == set(small["->"])
    for unit in wf.forwards:
        if isinstance(unit, delta_net.GatedDeltaNet):
            assert unit._kernels and unit._interpret
            assert unit.post_norm == "rms" and not unit.pre_norm
            assert unit.allow_neg_eigval and unit.chunk == 64
            assert unit.weights.shape == (64, 4 * (12 + 12 + 24))
            assert obs_metrics.delta_scan(unit.name, "chunks").value == 2
        if isinstance(unit, attention.MultiHeadAttention):
            assert unit._flash.runs and unit.post_norm == "rms"
            assert unit.rope_theta is None and unit.qk_norm == "rms"
            assert unit.gain_q.shape == (64,)
        if isinstance(unit, moe.GatedMLP):
            assert unit.post_norm == "rms" and unit.gain_norm.shape == (64,)


def test_the_published_widths_and_the_cut_are_in_the_file():
    file = config(toy=False)
    assert file["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert file["published"] == {"num_hidden_layers": 32,
                                 "vocab_size": 100352}
    assert (file["hidden_size"], file["intermediate_size"],
            file["num_attention_heads"], file["linear_key_head_dim"],
            file["linear_value_head_dim"], file["linear_conv_kernel_dim"],
            file["linear_num_key_heads"]) \
        == (3840, 11008, 30, 96, 192, 4, 30)
    assert file["layer_types"] == (["linear_attention"] * 3
                                   + ["full_attention"]) * 8
    assert file["rope_parameters"] == {"rope_theta": None}
    linear = file["workflow"]["layers"][1]["->"]
    assert (linear["n_heads"], linear["key_dim"], linear["value_dim"],
            linear["conv_kernel"], linear["allow_neg_eigval"]) \
        == (30, 96, 192, 4, True)
    assert set(file["reference_tolerance"]) == {"embedding", "layers"}
    for key in ("mixer", "decay_init", "norm_placement", "qk_norm",
                "rotary", "chunk", "optimizer", "data", "init"):
        assert file["assumed"][key]
    # 929 M parameters, as the file's arithmetic says
    d, wide, f, v = 3840, 30 * (2 * 96 + 192), 11008, 12544
    linear_n = d * wide + 4 * wide + 2 * d * 30 * 192 + d * 60 \
        + 2 * 30 + 192 + d + 3 * d * f + d
    full_n = 4 * d * d + 3 * d + 3 * d * f + d
    total = 3 * linear_n + full_n + 2 * v * d + d
    assert total == pytest.approx(929e6, rel=2e-3)


def test_layer_outputs_and_probabilities(one_step):
    """f32 on both sides, the program in chunks and the reference token
    by token: what is left is the order of summation, 1e-5 of a layer's
    range; 1e-4 is a hundred times under what bf16 anywhere leaves."""
    wf, table, before, x, y = one_step
    outs = reference().forward(before, table, x)
    assert len(outs) == len(wf.forwards) == len(KEPT)
    for i, (unit, want) in enumerate(zip(wf.forwards, outs)):
        unit.output.map_read()
        got = np.asarray(unit.output.mem, np.float32).reshape(want.shape)
        err = np.abs(got - want).max() / (np.abs(want).max() + 1e-12)
        assert err < 1e-4, (i, table[i]["type"], err)


def test_loss_and_every_gradient(one_step):
    """The step ran plain SGD at lr 1, so parameter − parameter after
    IS the system's gradient of the loss: compared with the reference's
    ``value_and_grad`` (through the token-by-token scan) for every
    tensor, 1e-3 of each gradient's largest entry."""
    wf, table, before, x, y = one_step
    value, grads = reference().loss_and_grads(before, table, x, y)
    after = params_of(wf)
    # embedding, 2 × 9 (linear mixer), 3 × 4 (MLP), 5 (attention:
    # qkv, out, gain, q and k gains), final gain, head
    assert set(grads) == set(before)
    assert len(before) == 1 + 2 * 9 + 3 * 4 + 5 + 2
    for name, want in grads.items():
        got = before[name] - after[name]
        scale = np.abs(want).max()
        assert scale > 0, name
        assert np.abs(got - want).max() <= 1e-3 * scale, (
            name, np.abs(got - want).max() / scale)
    assert wf.decision.epoch_loss[TRAIN] == pytest.approx(value, rel=1e-4)


CONTROLS = ["alpha_one", "beta_without_2", "no_l2_norm",
            "no_convolution", "no_output_gate", "no_output_norm",
            "pre_norm_for_post_norm", "float8"]


@pytest.mark.parametrize("what", CONTROLS)
def test_a_left_out_term_fails_the_stated_tolerance(one_step, what):
    """The reference made wrong in one stated way differs from the
    (right) system by more than the limit the CELL states."""
    wf, table, before, x, y = one_step
    limit = config(toy=False)["reference_tolerance"]["layers"]
    ref = reference()
    listed = {name: how for name, *how in controls.controls(ref, table)}
    assert set(CONTROLS) == set(listed)
    outs = controls.spoiled(ref, *listed[what]).forward(before, table, x)
    worst = 0.0
    for unit, want in zip(wf.forwards[1:], outs[1:]):
        unit.output.map_read()
        got = np.asarray(unit.output.mem, np.float32).reshape(want.shape)
        worst = max(worst, np.abs(got - want).max()
                    / (np.abs(want).max() + 1e-12))
    assert worst > limit, (what, worst)


def test_a_bf16_state_moves_the_first_mixer_by_what_a_limit_there_sees(
        one_step):
    """The state rounded to bf16 after every token moves the first
    mixer's output by a hundred times the f32 system's own error (1e-5,
    above): a limit at THAT layer separates it.  The cell's one limit
    for every layer does not (PERF.md §7; the controls script prints
    it as a reading) — which this test does not pin."""
    wf, table, before, x, y = one_step
    ref = reference()
    listed = {name: how for name, *how in controls.readings(ref, table)}
    outs = controls.spoiled(
        ref, *listed["bf16_state_in_one_layer"]).forward(before, table, x)
    unit, want = wf.forwards[1], outs[1]
    unit.output.map_read()
    got = np.asarray(unit.output.mem, np.float32).reshape(want.shape)
    assert np.abs(got - want).max() / np.abs(want).max() > 1e-3

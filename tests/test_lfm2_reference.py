"""LFM2-8B-A1B's mechanisms (PR 43) at small widths, on the CPU, seeded:
the gated short convolution's chain as the ``znicz_short_conv_*``
kernels (interpreted) against the ``jax.numpy`` chain — forward and
both cotangents, at a length that is not whole row tiles, at the
sequence's first positions —, the q/k norm per head, the biased
selection with the reference CHOOSING FOR ITSELF, the share test (the
two halves of the experts add up to the uncut layer), and the toy
``StandardWorkflow`` (``znbench/tests/data/toy``: hidden 128, 3 taps,
4 / 2 heads of 32, 8 experts with 4 held, T 64) against the benchmark's
plain reference (``znbench/reference/lfm2.py``): every table entry's
output, the loss, EVERY parameter gradient, on both backends; each
control fails the cell's stated tolerance; export and serving refuse
the table by name."""

import copy
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import lfm2_controls as controls
from znicz_tpu.backends import NumpyDevice, XLADevice
from znicz_tpu.loader.base import TRAIN
from znicz_tpu.loader.fullbatch import ArrayLoader
from znicz_tpu.memory import Vector
from znicz_tpu.models.standard_workflow import StandardWorkflow
from znicz_tpu.observe import metrics as obs_metrics
from znicz_tpu.ops import attention, moe, short_conv
from znicz_tpu.ops import pallas_short_conv as psc
from znicz_tpu.utils import prng
from znicz_tpu.utils.config import reset_root, root
from znicz_tpu.workflow import Workflow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ, BATCH = 64, 2


def reference():
    path = os.path.join(REPO, "znbench", "reference", "lfm2.py")
    spec = importlib.util.spec_from_file_location("ref_lfm2", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def config(toy: bool = True) -> dict:
    parts = ("tests", "data", "toy") if toy else ()
    with open(os.path.join(REPO, "znbench", *parts, "configs",
                           "lfm2_8b_a1b.json")) as fh:
        return json.load(fh)


def rel(got, want) -> float:
    want = np.asarray(want, np.float32)
    return float(np.abs(np.asarray(got, np.float32).reshape(want.shape)
                        - want).max() / (np.abs(want).max() + 1e-30))


# ----------------------------------------------------------------------
# the chain's kernels against jax.numpy
# ----------------------------------------------------------------------
#: (batch, T, D, y's dtype): a length that is not whole row tiles
#: (272 = 256 + 16: the last block reaches past the end, the masked
#: walk), two whole tiles (the cell's walk: a halo either way, nothing
#: masked), one tile, and sub-tiles of 16 with y at the width a bf16
#: matmul takes it
SHAPES = {"ragged": (2, 272, 256, jnp.float32),
          "two_tiles": (1, 512, 128, jnp.float32),
          "one_tile": (1, 128, 128, jnp.float32),
          "bf16_out": (2, 96, 128, jnp.bfloat16)}


@pytest.fixture(scope="module", params=sorted(SHAPES))
def chains(request):
    b, t, d, dtype = SHAPES[request.param]
    rng = np.random.default_rng(7)
    p = jnp.asarray(rng.normal(size=(b, t, 3 * d)), jnp.float32)
    taps = jnp.asarray(rng.normal(size=(d, 3)), jnp.float32)
    weight = jnp.asarray(rng.normal(size=(b, t, d)), jnp.float32)

    def both(chain):
        def run(p, taps):
            return jnp.sum(chain(p, taps).astype(jnp.float32) * weight)
        return chain(p, taps), jax.grad(run, (0, 1))(p, taps)

    kernels = both(lambda p, w: psc.short_conv(p, w, dtype, True))
    plain = both(lambda p, w: short_conv.chain(jnp, p, w).astype(dtype))
    return kernels, plain, (p, taps), dtype


def test_the_kernel_writes_the_chain(chains):
    (y, _), (want, _), _, dtype = chains
    assert y.dtype == want.dtype == dtype
    assert rel(y, want) < 1e-6


def test_the_kernel_s_cotangents_are_the_chain_s(chains):
    (_, (dp, dtaps)), (_, (want_p, want_taps)), (p, _), _ = chains
    assert dp.shape == p.shape          # ONE array, three column blocks
    assert rel(dp, want_p) < 1e-5
    assert rel(dtaps, want_taps) < 1e-5


def test_zeros_stand_before_the_sequence(chains):
    """c_0 = taps[2]·u_0 and c_1 = taps[1]·u_0 + taps[2]·u_1: nothing of
    another sequence or of a halo reaches the first two positions."""
    (y, _), _, (p, taps), dtype = chains
    d = taps.shape[0]
    gate_in, gate_out, x = p[..., :d], p[..., d:2 * d], p[..., 2 * d:]
    u = gate_in * x
    first = gate_out[:, 0] * (taps[:, 2] * u[:, 0])
    second = gate_out[:, 1] * (taps[:, 1] * u[:, 0] + taps[:, 2] * u[:, 1])
    limit = 1e-6 if dtype == jnp.float32 else 1e-2
    assert rel(y[:, 0], first.astype(dtype)) < limit
    assert rel(y[:, 1], second.astype(dtype)) < limit


@pytest.mark.parametrize("t,d,taps,word", [
    (64, 96, 3, "lane"), (60, 128, 3, "row"), (64, 128, 9, "taps")])
def test_a_shape_the_kernels_do_not_tile_is_refused_with_a_reason(
        t, d, taps, word):
    assert word in psc.legal(t, d, taps)
    assert psc.legal(64, 128, 3) is None


def _mixer(device, x, **engine):
    reset_root()
    for key, value in engine.items():
        setattr(root.common.engine, key, value)
    prng.seed_all(5)
    wf = Workflow(name="mixer")
    unit = short_conv.ShortConv(wf, conv_kernel=3, pre_norm="rms",
                                residual=True)
    unit.input = Vector(x.copy())
    unit.initialize(device=device)
    unit.run()
    unit.output.map_read()
    reset_root()
    return unit


def test_the_unit_decides_its_form_once_and_says_which():
    """The form is one value of the unit, set at ``initialize`` and read
    by the gauge: the kernels where the gate and the shape allow, else
    ``jax.numpy`` — the same numbers either way, and the numpy oracle's."""
    x = np.random.default_rng(2).normal(
        0, 1, (BATCH, SEQ, 128)).astype(np.float32)
    plain = _mixer(XLADevice(), x)
    assert not plain._kernels
    assert obs_metrics.short_conv(plain.name, "path").value == 0
    kernels = _mixer(XLADevice(), x, pallas_interpret=True,
                     delta_scan_kernel=True)
    assert kernels._kernels and kernels._interpret
    assert obs_metrics.short_conv(kernels.name, "path").value == 1
    assert obs_metrics.short_conv(kernels.name, "taps").value == 3
    oracle = _mixer(NumpyDevice(), x)
    assert rel(kernels.output.mem, plain.output.mem) < 1e-5
    assert rel(oracle.output.mem, plain.output.mem) < 1e-5
    assert np.abs(plain.output.mem - x).max() > 0.1


# ----------------------------------------------------------------------
# the q/k norm per head
# ----------------------------------------------------------------------
def _attention(device, x, qk_norm):
    reset_root()
    prng.seed_all(9)
    wf = Workflow(name="attn")
    unit = attention.MultiHeadAttention(
        wf, n_heads=4, n_kv_heads=2, head_dim=32, causal=True,
        include_bias=False, qk_norm=qk_norm, rope={"theta": 1e6})
    unit.input = Vector(x.copy())
    unit.initialize(device=device)
    rng = np.random.default_rng(10)
    for gain in (unit.gain_q, unit.gain_k):
        gain.map_invalidate()
        gain.mem[...] = rng.uniform(0.5, 1.5, gain.shape)
        gain.unmap()
    unit.run()
    unit.output.map_read()
    return unit


def test_the_per_head_norm_has_one_gain_of_the_head_s_size():
    x = np.random.default_rng(3).normal(
        0, 1, (BATCH, SEQ, 128)).astype(np.float32)
    unit = _attention(XLADevice(), x, "rms_head")
    assert unit.gain_q.shape == unit.gain_k.shape == (32,)
    whole = _attention(XLADevice(), x, "rms")
    assert whole.gain_q.shape == (128,) and whole.gain_k.shape == (64,)
    oracle = _attention(NumpyDevice(), x, "rms_head")
    assert rel(unit.output.mem, oracle.output.mem) < 1e-5
    # per head by hand: q of head h normed over ITS 32 dims
    qkv = x.reshape(-1, 128) @ oracle.weights.mem
    q = qkv[:, :128].reshape(-1, 4, 32)
    want = oracle.gain_q.mem * q / np.sqrt(
        (q * q).mean(axis=-1, keepdims=True) + 1e-5)
    got = oracle._qk_normed(np, qkv[:, :128].reshape(BATCH, SEQ, 128),
                            oracle.gain_q.mem, 32)
    assert rel(got, want) < 1e-6


def test_the_per_head_norm_is_refused_with_a_latent_and_by_name():
    with pytest.raises(ValueError, match="qk_norm"):
        attention.MultiHeadAttention(
            Workflow(name="w"), n_heads=2, causal=True, qk_norm="rms_head",
            rope={"theta": 1e4}, kv_latent=32, qk_nope=16, qk_rope=8,
            v_head_dim=16, include_bias=False)
    with pytest.raises(ValueError, match="rms_head"):
        attention.MultiHeadAttention(Workflow(name="w"), n_heads=2,
                                     qk_norm="head")


# ----------------------------------------------------------------------
# the share test (model-configs guide, section 4) and the selection
# ----------------------------------------------------------------------
SPEC = {"n_experts": 32, "top_k": 4, "width": 32, "norm_topk": True,
        "score": "sigmoid", "routed_scale": 1.0, "select_bias": True,
        "pre_norm": "rms", "residual": True, "aux_loss_weight": 1e-4,
        "norm_eps": 1e-5}


@pytest.fixture(scope="module")
def expert_layer():
    rng = np.random.default_rng(5)
    d, experts, width = 64, SPEC["n_experts"], SPEC["width"]
    full = {"layer0_weights": rng.normal(0, 0.5, (d, experts)),
            "layer0_gain_norm": rng.uniform(0.7, 1.3, d)}
    for name, shape in (("gate", (experts, d, width)),
                        ("up", (experts, d, width)),
                        ("down", (experts, width, d))):
        full[f"layer0_weights_{name}"] = rng.normal(0, 0.2, shape)
    full = {k: v.astype(np.float32) for k, v in full.items()}
    x = rng.normal(0, 1, (BATCH, SEQ, d)).astype(np.float32)
    bias = rng.uniform(-0.1, 0.1, experts).astype(np.float32)
    return full, x, bias


def _share(full, x, bias, held):
    reset_root()
    unit = moe.MoE(Workflow(name="share"), held=held, **SPEC)
    unit.input = Vector(x.copy())
    for attr in ("weights", "gain_norm", "weights_gate", "weights_up",
                 "weights_down"):
        value = full[f"layer0_{attr}"]
        if attr.startswith("weights_"):
            value = value[list(held)]        # this chip's slabs
        getattr(unit, attr).reset(value.copy())
    unit.initialize(device=XLADevice())
    unit.select_bias.map_write()
    unit.select_bias.mem[...] = bias
    unit.select_bias.unmap()
    unit.run()
    unit.output.map_read()
    unit.last_choice.map_read()
    return unit


def test_the_two_halves_of_the_experts_add_up_to_the_uncut_layer(
        expert_layer):
    """What the chip that holds experts 0–15 adds and what the chip
    that holds 16–31 adds, with the residual (what both compute alike)
    counted once, is the uncut reference's output of the layer — under
    a bias that moves the choice."""
    full, x, bias = expert_layer
    ref = reference()
    with jax.default_matmul_precision("highest"):
        uncut, _, _, chosen = ref.moe_block(x, full, 0, SPEC, bias=bias)
        alike = ref.moe_block(x, full, 0, SPEC, chosen, held=[])[0]
    assert rel(alike, x) == 0               # no shared expert: x itself
    total = np.asarray(alike, np.float64)
    for held in (range(16), range(16, 32)):
        unit = _share(full, x, bias, tuple(held))
        np.testing.assert_array_equal(     # every chip routes over all 32
            np.sort(unit.last_choice.mem.reshape(-1, 4), axis=-1),
            np.sort(chosen, axis=-1))
        part = np.asarray(unit.output.mem, np.float64) - alike
        assert np.abs(part).max() > 0.05
        total += part
    np.testing.assert_allclose(total, np.asarray(uncut), atol=1e-4)


@pytest.mark.parametrize("biased", [True, False],
                         ids=["bias_on", "bias_zero"])
def test_the_reference_choosing_for_itself_picks_the_unit_s_experts(
        expert_layer, biased):
    """Top 4 of s + b with the weights from s: the reference makes the
    choice itself and the unit's is the same set; the bias moves the
    choice (bias on) and weighs nothing."""
    full, x, bias = expert_layer
    ref = reference()
    b = bias if biased else np.zeros_like(bias)
    unit = _share(full, x, b, tuple(range(32)))
    with jax.default_matmul_precision("highest"):
        want, _, _, chosen = ref.moe_block(x, full, 0, SPEC, bias=b)
        plain = ref.moe_block(x, full, 0, dict(SPEC, select_bias=False))[3]
    np.testing.assert_array_equal(
        np.sort(unit.last_choice.mem.reshape(-1, 4), axis=-1),
        np.sort(chosen, axis=-1))
    assert (np.sort(chosen, -1) != np.sort(plain, -1)).any() == biased
    assert rel(unit.output.mem, want) < 1e-5
    scores = np.asarray(ref.route(np.asarray(ref._normed(
        jnp.asarray(x), full, 0, SPEC)).reshape(-1, 64), full, 0)[1])
    picked = np.take_along_axis(scores, chosen, axis=-1)
    np.testing.assert_allclose(
        np.asarray(ref.weights_of(jnp.asarray(scores), chosen, SPEC)),
        picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)


# ----------------------------------------------------------------------
# the toy model against the plain reference
# ----------------------------------------------------------------------
#: of the toy cell's thirteen layers: the embedding, the convolution
#: block over the dense MLP, the attention block and one convolution
#: block over expert layers, the final norm and the head
KEPT = (0, 1, 2, 3, 4, 5, 6, 11, 12)


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


def build(device, table, name="lfm2_ref", steps: int = 1):
    vocab = config()["input"]["vocab"]
    rng = np.random.default_rng(17)
    ids = rng.integers(0, vocab, (BATCH * steps, SEQ + 1))
    x, y = ids[:, :-1], ids[:, 1:]
    prng.seed_all(31)
    wf = StandardWorkflow(
        name=name,
        loader_factory=lambda w: ArrayLoader(
            w, train_data=x.astype(np.float32),
            train_labels=y.astype(np.int32), minibatch_size=BATCH,
            shuffle_limit=0),
        layers=table, decision_config={"max_epochs": 1})
    wf.initialize(device=device)
    rng = np.random.default_rng(18)
    for unit in wf.forwards:
        # gains of one would hide their path, a bias of zero its own
        for attr in ("gain_norm", "gain_q", "gain_k"):
            vec = getattr(unit, attr, None)
            if vec:
                vec.map_invalidate()
                vec.mem[...] = rng.uniform(0.7, 1.3, vec.shape)
        if getattr(unit, "select_bias_on", False):
            unit.select_bias.map_invalidate()
            unit.select_bias.mem[...] = rng.uniform(
                -0.05, 0.05, unit.select_bias.shape)
        if isinstance(unit, attention.MultiHeadAttention):
            # random projections give near-uniform attention: sharpen
            # the scores so that the q/k norm decides something
            unit.gain_q.map_invalidate()
            unit.gain_q.mem[...] *= 3.0
    return wf, x, y


@pytest.fixture(scope="module", params=["xla", "numpy"])
def one_step(request):
    """One plain-SGD step at lr 1 (W −= gradient) of the system in f32
    — on the XLA backend with every kernel interpreted, on the numpy
    backend through the units' oracles — with the parameters and the
    selection biases before it and the tokens it saw."""
    reset_root()
    engine = root.common.engine
    if request.param == "xla":
        engine.pallas_interpret = True
        engine.flash_attention = True
        engine.delta_scan_kernel = True
        engine.moe_grouped_matmul = True
    table = layers(1.0, 0.0)
    wf, x, y = build((XLADevice if request.param == "xla"
                      else NumpyDevice)(), table)
    before = params_of(wf)
    bias = {}
    for i, unit in enumerate(wf.forwards):
        if getattr(unit, "select_bias_on", False):
            unit.select_bias.map_read()
            bias[i] = np.array(unit.select_bias.mem)
    wf.run()
    reset_root()
    return wf, table, before, bias, x, y, request.param


def test_the_toy_model_is_the_cell_s_model_in_small(one_step):
    wf, table, *_, backend = one_step
    assert [layer["type"] for layer in table] == [
        "embedding", "short_conv", "gated_mlp", "attention", "moe",
        "short_conv", "moe", "rms_norm", "softmax"]
    real = config(toy=False)["workflow"]["layers"]
    toy = config()["workflow"]["layers"]
    assert [layer["type"] for layer in real] \
        == [layer["type"] for layer in toy]
    for big, small in zip(real, toy):        # the same options, smaller
        assert set(big["->"]) == set(small["->"])
    for unit in wf.forwards:
        if isinstance(unit, short_conv.ShortConv):
            assert unit.conv_kernel == 3 and unit.residual
            assert unit.weights.shape == (128, 384)
            assert unit.weights_conv.shape == (128, 3)
            assert unit._kernels == (backend == "xla")
        if isinstance(unit, attention.MultiHeadAttention):
            assert unit.qk_norm == "rms_head" and unit.n_kv_heads == 2
            assert unit.gain_q.shape == unit.gain_k.shape == (32,)
            if backend == "xla":
                assert unit._flash.runs and unit._flash.interpret
        if isinstance(unit, moe.MoE):
            assert unit.select_bias_on and unit.groups is None
            assert unit.held == (0, 1, 2, 5) and not unit.shared_width


def test_the_published_widths_and_the_cut_are_in_the_file():
    file = config(toy=False)
    assert file["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size"]
    assert file["published"] == {"num_hidden_layers": 24,
                                 "num_experts": 32, "vocab_size": 65536}
    assert (file["hidden_size"], file["conv_L_cache"], file["conv_bias"],
            file["num_attention_heads"], file["num_key_value_heads"],
            file["intermediate_size"], file["moe_intermediate_size"],
            file["num_experts_per_tok"], file["num_dense_layers"],
            file["rope_theta"], file["routed_scaling_factor"],
            file["use_expert_bias"], file["norm_eps"]) \
        == (2048, 3, False, 32, 8, 7168, 1792, 4, 2, 1000000, 1, True,
            1e-5)
    assert (file["num_hidden_layers"], file["num_experts"],
            file["vocab_size"]) == (5, 16, 8192)
    assert file["layer_types"].count("conv") == 18
    assert file["layer_types"][1:6] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    table = file["workflow"]["layers"]
    assert [layer["type"] for layer in table] == [
        "embedding", "short_conv", "gated_mlp", "attention", "moe"] + [
        "short_conv", "moe"] * 3 + ["rms_norm", "softmax"]
    conv, full, experts = table[1]["->"], table[3]["->"], table[4]["->"]
    assert conv == {"conv_kernel": 3, "pre_norm": "rms",
                    "residual": True, "norm_eps": 1e-5}
    assert (full["n_heads"], full["n_kv_heads"], full["head_dim"],
            full["qk_norm"], full["rope"]) == (32, 8, 64, "rms_head",
                                               {"theta": 1000000})
    assert (experts["n_experts"], experts["top_k"], experts["width"],
            experts["held"], experts["select_bias"], experts["score"],
            experts["routed_scale"], experts["norm_topk"]) \
        == (32, 4, 1792, list(range(16)), True, "sigmoid", 1.0, True)
    assert "groups" not in experts and "shared_width" not in experts
    assert set(file["reference_tolerance"]) == {
        "embedding", "layers", "router_logits", "router_gap"}
    for key in ("tie", "short_conv", "attention", "layer_order", "router",
                "bias_rule", "aux_loss", "optimizer", "data", "init",
                "buffer"):
        assert file["assumed"][key]
    for word in ("2 expert-parallel", "8 chips", "4-stage"):
        assert word in file["deployment"]
    # 860 M parameters, as the file's arithmetic says
    d = 2048
    conv_mixer = d * 3 * d + d * d + 3 * d + d
    attn = d * (2048 + 2 * 512) + 2048 * d + 2 * 64 + d
    dense = 3 * d * 7168 + d
    expert_layer = 16 * 3 * d * 1792 + d * 32 + d
    total = 4 * conv_mixer + attn + dense + 4 * expert_layer \
        + 2 * 8192 * d + d
    assert total == pytest.approx(860.2e6, rel=1e-3)


def test_layer_outputs_and_probabilities(one_step):
    """f32 on both sides, the program in tiles, the reference in whole
    arrays, choosing its own experts: what is left is the order of
    summation."""
    wf, table, before, bias, x, y, _ = one_step
    outs, router = reference().run(before, table, x, bias=bias)
    assert len(outs) == len(wf.forwards) == len(KEPT)
    for i, (unit, want) in enumerate(zip(wf.forwards, outs)):
        unit.output.map_read()
        assert rel(unit.output.mem, want) < 2e-5, (i, table[i]["type"])
        if table[i]["type"] == "moe":      # the same experts, by itself
            unit.last_choice.map_read()
            np.testing.assert_array_equal(
                np.sort(unit.last_choice.mem.reshape(-1, 2), -1),
                np.sort(router["chosen"][i], -1))


def test_loss_and_every_gradient(one_step):
    """The step ran plain SGD at lr 1, so parameter − parameter after
    IS the system's gradient of the loss: compared with the reference's
    ``value_and_grad`` for every tensor — W_in, the taps, W_out, the
    per-head gains, W_r, the experts among them."""
    wf, table, before, bias, x, y, _ = one_step
    value, grads = reference().loss_and_grads(before, table, x, y,
                                              bias=bias)
    after = params_of(wf)
    # embedding, 2 × 4 (conv mixer), 4 (MLP), 5 (attention), 2 × 5
    # (expert layer), final gain, head
    assert set(grads) == set(before)
    assert len(before) == 1 + 2 * 4 + 4 + 5 + 2 * 5 + 2
    for kind in ("layer1_weights", "layer1_weights_conv",
                 "layer1_weights_out", "layer3_gain_q", "layer3_gain_k",
                 "layer4_weights", "layer4_weights_gate"):
        assert kind in grads
    for name, want in grads.items():
        got = before[name] - after[name]
        scale = np.abs(want).max()
        assert scale > 0, name
        assert np.abs(got - want).max() <= 1e-4 * scale, (
            name, np.abs(got - want).max() / scale)
    assert wf.decision.epoch_loss[TRAIN] == pytest.approx(value, rel=1e-4)
    # the bias moved by its rule, and is no parameter of the loss
    for i, unit in enumerate(wf.forwards):
        if getattr(unit, "select_bias_on", False):
            for vec in (unit.select_bias, unit.select_load):
                vec.map_read()
            load = unit.select_load.mem
            np.testing.assert_allclose(
                unit.select_bias.mem,
                bias[i] + 1e-3 * np.sign(load.mean() - load), atol=1e-8)


CONTROLS = ["float8", "no_in_gate", "no_out_gate", "silu_on_the_taps",
            "qk_norm_over_the_projection", "weights_from_biased_scores"]


def _worst(wf, outs) -> float:
    worst = 0.0
    for unit, want in zip(wf.forwards[1:], outs[1:]):
        unit.output.map_read()
        worst = max(worst, rel(unit.output.mem, want))
    return worst


@pytest.mark.parametrize("what", CONTROLS)
def test_a_reference_made_wrong_fails_the_stated_tolerance(one_step,
                                                           what):
    """The reference made wrong in one stated way differs from the
    (right) system by more than the limit the CELL states — the
    weights normalised from s + b too, which at the cell's sizes is a
    READING (the bias has hardly moved after one epoch): here the bias
    decides something."""
    wf, table, before, bias, x, y, _ = one_step
    limit = config(toy=False)["reference_tolerance"]["layers"]
    ref = reference()
    strong = {i: 40.0 * b for i, b in bias.items()}     # up to ± 2
    listed = {name: how for name, *how in controls.controls(ref, table)
              + controls.readings(ref, table, strong)}
    assert set(CONTROLS) == set(listed)
    routing = {}
    for i in bias:                  # the system's own choice, as the
        if i <= listed[what][0]:    # driver hands it to the reference
            wf.forwards[i].last_choice.map_read()
            routing[i] = np.asarray(wf.forwards[i].last_choice.mem) \
                .reshape(-1, 2).astype(np.int64)
    outs = controls.spoiled(ref, *listed[what]).forward(
        before, table, x, routing=routing, bias=bias)
    assert _worst(wf, outs) > limit, (what, _worst(wf, outs))


def test_a_choice_made_without_the_bias_fails_the_stated_tolerance(
        one_step):
    """The driver hands the system's choice to the reference, so this
    is held HERE: the reference choosing for itself without the bias
    picks other experts and its layers differ."""
    wf, table, before, bias, x, y, _ = one_step
    limit = config(toy=False)["reference_tolerance"]["layers"]
    wrong = copy.deepcopy(table)
    for layer in wrong:
        if layer["type"] == "moe":
            layer["->"]["select_bias"] = False
    outs = reference().forward(before, wrong, x, bias=bias)
    assert _worst(wf, outs) > limit, _worst(wf, outs)


# ----------------------------------------------------------------------
# the other driver, export and serving: correct, or refusing by name
# ----------------------------------------------------------------------
def _trained(drive, name):
    reset_root()
    wf, _, _ = build(XLADevice(), layers(0.05, 0.0), name=name, steps=4)
    drive(wf)
    return params_of(wf)


def test_run_chunked_trains_the_table_as_run_does():
    plain = _trained(lambda wf: wf.run(), "lfm2_run")
    chunked = _trained(lambda wf: wf.run_chunked(2), "lfm2_chunked")
    assert set(plain) == set(chunked)
    for name, want in plain.items():
        assert rel(chunked[name], want) < 1e-5, name


@pytest.mark.parametrize("what", ["export_forward", "DecodeModel"])
def test_serving_refuses_the_table_by_name(what, tmp_path):
    reset_root()
    wf, _, _ = build(XLADevice(), layers(0.05, 0.0), name=f"lfm2_{what}")
    from znicz_tpu.export import refuse_unserved
    with pytest.raises(NotImplementedError) as said:
        if what == "export_forward":
            wf.export_forward(str(tmp_path / "bundle.npz"))
        else:
            refuse_unserved(wf.forwards, "DecodeModel")
    assert "short_conv" in str(said.value)         # the first layer
    units = [u for u in wf.forwards
             if isinstance(u, attention.MultiHeadAttention)]
    with pytest.raises(NotImplementedError) as said:
        refuse_unserved(units, what)
    assert "rms_head" in str(said.value)


def test_forward_and_backward_share_one_family_in_the_program_s_map():
    """``observe.op_scopes()`` files the mixer's forward and backward
    units under the family ``ShortConv`` — a name the benchmark's
    ``conv`` row (``Conv*``: AlexNet's) does not take."""
    from znicz_tpu import observe
    from znicz_tpu.ops.nn_units import family_of
    reset_root()
    wf, _, _ = build(XLADevice(), layers(0.05, 0.0), name="lfm2_scopes")
    wf.run()
    pairs = {family_of(unit) for unit in list(wf.forwards) + list(wf.gds)
             if "ShortConv" in type(unit).__name__}
    assert pairs == {("ShortConv", False), ("ShortConv", True)}
    assert not "ShortConv".startswith(("Conv", "All2All"))
    families = set()
    for program in observe.op_scopes().values():
        for entry in program.values():
            families.update([entry.get("family")]
                            + list(entry.get("families", ())))
    assert "ShortConv" in families

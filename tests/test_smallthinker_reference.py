"""SmallThinker-21BA3B (PR 50) against its plain reference, in ONE file —
so that, under ``--dist loadfile``, these compile-heavy tests hold one
worker at a time.

The toy ``StandardWorkflow`` (``znbench/tests/data/toy``: the NoPE
full-attention block and one RoPE block under the window of its table,
the expert layers holding 4 of 16 ReGLU experts whose router reads the
block's input; hidden 64, 7 query heads on 1 K/V head of 16, window 8,
T 32) against the benchmark's plain reference
(``znbench/reference/smallthinker.py``) on seeded weights, in f32 with
the kernels interpreted: every layer's output, the loss, EVERY
gradient against the reference's ``jax.value_and_grad`` — the router's
among them, and the share of the block input's cotangent that comes
through the router; each left-out term fails the cell's stated
tolerance; the share test (the eight shares' routed parts add up to
the uncut layer)."""

import copy
import importlib.util
import json
import os

import jax
import numpy as np
import pytest

from znicz_tpu.backends import XLADevice
from znicz_tpu.loader.base import TRAIN
from znicz_tpu.loader.fullbatch import ArrayLoader
from znicz_tpu.memory import Vector
from znicz_tpu.models.standard_workflow import StandardWorkflow
from znicz_tpu.observe import metrics as obs_metrics
from znicz_tpu.ops import attention, moe
from znicz_tpu.utils import prng
from znicz_tpu.workflow import Workflow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ, BATCH = 32, 2


def reference():
    path = os.path.join(REPO, "znbench", "reference", "smallthinker.py")
    spec = importlib.util.spec_from_file_location("ref_smallthinker",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def toy_config() -> dict:
    with open(os.path.join(REPO, "znbench", "tests", "data", "toy",
                           "configs", "smallthinker_21b_a3b.json")) as fh:
        return json.load(fh)


#: of the toy cell's eleven layers: the embedding, the NoPE full block,
#: ONE RoPE block under the window, the final norm, the head (the
#: cell's two further window blocks repeat the one kept)
KEPT = (0, 1, 2, 3, 4, 9, 10)


def layers(lr: float, moment: float) -> list:
    table = copy.deepcopy(toy_config()["workflow"]["layers"])
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
    flash and grouped-matmul kernels interpreted, with the parameters
    before it and the tokens it saw."""
    from znicz_tpu.utils.config import reset_root, root
    reset_root()
    engine = root.common.engine
    engine.pallas_interpret = True
    engine.flash_attention = True
    engine.moe_grouped_matmul = True
    vocab = toy_config()["input"]["vocab"]
    rng = np.random.default_rng(17)
    ids = rng.integers(0, vocab, (BATCH, SEQ + 1))
    x, y = ids[:, :-1], ids[:, 1:]
    prng.seed_all(31)
    table = layers(1.0, 0.0)
    wf = StandardWorkflow(
        name="smallthinker_ref",
        loader_factory=lambda w: ArrayLoader(
            w, train_data=x.astype(np.float32),
            train_labels=y.astype(np.int32), minibatch_size=BATCH,
            shuffle_limit=0),
        layers=table, decision_config={"max_epochs": 1})
    wf.initialize(device=XLADevice())
    rng = np.random.default_rng(18)
    for unit in wf.forwards:      # gains of one would hide their path
        vec = getattr(unit, "gain_norm", None)
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
        "embedding", "attention", "moe", "attention", "moe", "rms_norm",
        "softmax"]
    full = toy_config()["workflow"]["layers"]
    assert [bool(layer["->"].get("rope")) for layer in full
            if layer["type"] == "attention"] == [False, True, True, True]
    assert [layer["->"].get("window") for layer in full
            if layer["type"] == "attention"] == [None, 8, 8, 8]
    units = wf.forwards
    for unit in units:
        if isinstance(unit, attention.MultiHeadAttention):
            assert unit._flash.runs
            assert unit._flash.n_heads // unit._flash.n_kv_heads == 7
            assert unit.weights.shape == (64, (7 + 2) * 16)
        if isinstance(unit, moe.MoE):
            assert unit.act == "relu"
            assert unit.route_from == "block_input"
            assert unit.weights_gate.shape == (4, 64, 32)   # held only
            assert unit.weights.shape == (64, 16)           # all outputs
    assert units[1].rope_theta is None and units[1].window is None
    assert units[3].rope_theta == 1500000.0 and units[3].window == 8
    # the second forward edge: the router's tensor IS the block's input
    assert units[2].route_input is units[1].input
    assert units[4].route_input is units[3].input is units[2].output
    assert units[2].route_gd is wf.gds[1] and units[4].route_gd is wf.gds[3]


def test_layer_outputs_and_probabilities(one_step):
    """f32 on both sides: what is left is the order of summation, 1e-5
    of a layer's range; 1e-4 is a hundred times under what bf16
    anywhere would leave."""
    wf, table, before, x, y = one_step
    ref = reference()
    outs, router = ref.run(before, table, x)
    assert len(outs) == len(wf.forwards) == len(KEPT)
    for i, (unit, want) in enumerate(zip(wf.forwards, outs)):
        unit.output.map_read()
        got = np.asarray(unit.output.mem, np.float32).reshape(want.shape)
        err = np.abs(got - np.asarray(want)).max() \
            / (np.abs(np.asarray(want)).max() + 1e-12)
        assert err < 1e-4, (i, table[i]["type"], err)
    for i, unit in enumerate(wf.forwards):
        if table[i]["type"] != "moe":
            continue
        unit.router_logits.map_read()
        unit.last_choice.map_read()
        np.testing.assert_allclose(
            unit.router_logits.mem.reshape(-1, 16),
            np.asarray(router["logits"][i]), rtol=1e-4, atol=1e-5)
        np.testing.assert_array_equal(
            np.sort(unit.last_choice.mem.reshape(-1, 3), axis=-1),
            np.sort(router["chosen"][i], axis=-1))


def _gradient_gaps(before, after, grads) -> dict:
    """Per tensor: the system's gradient (the step ran plain SGD at
    lr 1, so parameter − parameter after IS it) against ``grads``, as a
    share of the largest entry of ``grads``."""
    gaps = {}
    for name, want in grads.items():
        scale = np.abs(want).max()
        assert scale > 0, name
        gaps[name] = np.abs(before[name] - after[name] - want).max() \
            / scale
    return gaps


def test_loss_and_every_gradient(one_step):
    """Every tensor's gradient against the reference's
    ``value_and_grad``, 1e-3 of each gradient's largest entry — the
    routers' W_r among them, whose input is the block's."""
    wf, table, before, x, y = one_step
    ref = reference()
    value, grads = ref.loss_and_grads(before, table, x, y)
    after = params_of(wf)
    # embedding, 2 × (qkv, out, gain), 2 × (router, 3 slabs, gain),
    # final gain, head
    assert set(grads) == set(before)
    assert len(before) == 1 + 2 * 3 + 2 * 5 + 2
    gaps = _gradient_gaps(before, after, grads)
    assert max(gaps.values()) <= 1e-3, gaps
    assert wf.decision.epoch_loss[TRAIN] == pytest.approx(value, rel=1e-4)


def test_the_router_s_share_of_the_block_input_s_cotangent(one_step,
                                                           monkeypatch):
    """∂L/∂x of a block gains ∂L/∂r · W_rᵀ through the second backward
    edge.  A reference whose router reads the block's input but hands
    nothing back to it (``stop_gradient``) gives the same loss and
    other gradients upstream of a router — the embedding's, block 0's
    attention's — and the system is NOT that one."""
    wf, table, before, x, y = one_step
    ref = reference()
    rows = ref.router_rows
    monkeypatch.setattr(ref, "router_rows", lambda *a: jax.lax.
                        stop_gradient(rows(*a)))
    value, cut = ref.loss_and_grads(before, table, x, y)
    assert wf.decision.epoch_loss[TRAIN] == pytest.approx(value, rel=1e-4)
    gaps = _gradient_gaps(before, params_of(wf), cut)
    upstream = [name for name in cut if name.startswith(
        ("layer0_", "layer1_", "layer2_"))]     # of block 1's router
    assert min(gaps[name] for name in upstream
               if name != "layer2_weights") > 1e-2, gaps
    # what no router's input feeds is the same either way
    assert max(gaps[name] for name in cut
               if name.startswith(("layer5_", "layer6_"))) <= 1e-3, gaps


def test_what_the_expert_layers_report(one_step):
    wf, table, *_ = one_step
    for i, unit in enumerate(wf.forwards):
        if table[i]["type"] != "moe":
            continue
        held = {stat: obs_metrics.moe_held(unit.name, stat).value
                for stat in ("held", "of", "rows_here", "rows_routed",
                             "rows_over", "fit_steps", "steps")}
        assert held["held"] == 4 and held["of"] == 16
        assert held["rows_routed"] == BATCH * SEQ * 3
        assert 0 < held["rows_here"] < held["rows_routed"]
        assert held["rows_over"] == 0
        live = obs_metrics.moe_hidden(unit.name, "live").value
        total = obs_metrics.moe_hidden(unit.name, "total").value
        if held["fit_steps"] == held["steps"]:
            assert total == held["rows_here"] * 32
        # a ReLU leaves about half of the hidden
        assert 0.3 * total < live < 0.7 * total or total == 0
    text = obs_metrics.REGISTRY.to_prometheus()
    assert "znicz_moe_hidden{" in text
    windowed = wf.forwards[3]
    assert obs_metrics.flash_band(windowed.name, "window").value == 8
    # every attention layer's backward is one pass (PR 55), and the
    # gauge says what its plan says
    for unit in wf.forwards:
        plan = getattr(unit, "_flash", None)
        if plan is None or not plan.runs:
            continue
        assert obs_metrics.flash_backward(
            unit.name, "passes").value == plan.backward == 1
        assert obs_metrics.flash_backward(
            unit.name, "resident_dq_bytes").value == plan.resident_dq
    assert windowed._flash.resident_dq > 0


# ----------------------------------------------------------------------
# each left-out term fails the cell's tolerance
# ----------------------------------------------------------------------
def _without(table, what):
    """The reference's model with one term left out or put in."""
    table = copy.deepcopy(table)
    attention_at = [i for i, layer in enumerate(table)
                    if layer["type"] == "attention"]
    for i, layer in enumerate(table):
        spec = layer["->"]
        if layer["type"] == "moe":
            if what == "router after attention":
                spec["route_from"] = None
            if what == "router input normed":
                spec["route_normed"] = True
            if what == "silu for relu":
                spec["act"] = "silu"
            if what == "no renormalisation":
                spec["norm_topk"] = False
        if what == "rope on the nope layer" and i == attention_at[0]:
            spec["rope"] = {"theta": 1500000}
        if what == "no rope on a window layer" and i == attention_at[1]:
            spec["rope"] = None
        if what == "full attention on a window layer" \
                and i == attention_at[1]:
            spec["window"] = None
    return table


@pytest.mark.parametrize("what", [
    "router after attention", "router input normed", "silu for relu",
    "rope on the nope layer", "no rope on a window layer",
    "full attention on a window layer", "no renormalisation"])
def test_a_left_out_term_fails_the_stated_tolerance(one_step, what):
    wf, table, before, x, y = one_step
    limit = toy_config()["reference_tolerance"]["layers"]
    ref = reference()
    routing = ref.run(before, table, x)[1]["chosen"]
    wrong = _without(table, what)
    assert wrong != table
    outs = ref.forward(before, wrong, x, routing)
    worst = 0.0
    for unit, want in zip(wf.forwards[1:], outs[1:]):
        unit.output.map_read()
        got = np.asarray(unit.output.mem, np.float32).reshape(want.shape)
        worst = max(worst, np.abs(got - want).max()
                    / (np.abs(want).max() + 1e-12))
    assert worst > limit, (what, worst)


# ----------------------------------------------------------------------
# the share test (model-configs guide, section 4)
# ----------------------------------------------------------------------
def test_the_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Over the partition of 64 experts into the deployment's eight
    shares (held = 0–7, 8–15, …), what each chip's layer adds for its
    own experts, with what every chip computes alike (the residual)
    counted once, adds up to the uncut reference's output of the layer;
    every chip routes over all 64 from the block's input."""
    ref = reference()
    rng = np.random.default_rng(5)
    d, width, experts, top_k = 64, 32, 64, 6
    spec = {"n_experts": experts, "top_k": top_k, "width": width,
            "norm_topk": True, "score": "softmax", "pre_norm": "rms",
            "residual": True, "act": "relu",
            "route_from": "block_input", "norm_eps": 1e-6}
    full = {"layer0_weights": rng.normal(0, 0.5, (d, experts)),
            "layer0_gain_norm": rng.uniform(0.7, 1.3, d)}
    for name, shape in (("gate", (experts, d, width)),
                        ("up", (experts, d, width)),
                        ("down", (experts, width, d))):
        full[f"layer0_weights_{name}"] = rng.normal(0, 0.2, shape)
    full = {k: v.astype(np.float32) for k, v in full.items()}
    x = rng.normal(0, 1, (BATCH, SEQ, d)).astype(np.float32)
    block_in = rng.normal(0, 1, (BATCH, SEQ, d)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        uncut, _, chosen = ref.moe_block(x, full, 0, spec,
                                         block_input=block_in)
        # what every chip computes alike: the reference with no expert
        alike = ref.moe_block(x, full, 0, spec, chosen, held=[],
                              block_input=block_in)[0]
        # the router read the block's input, not the experts'
        other = ref.moe_block(x, full, 0, dict(spec, route_from=None))[2]
    assert np.abs(np.asarray(uncut) - np.asarray(alike)).max() > 0.1
    assert (np.sort(other, axis=-1) != np.sort(chosen, axis=-1)).any()

    shares = [list(range(first, first + 8))
              for first in range(0, experts, 8)]
    total = np.asarray(alike, np.float64)
    for share in shares:
        wf = Workflow(name="share")
        unit = moe.MoE(wf, held=share, **spec)
        unit.input = Vector(x.copy())
        unit.route_input = Vector(block_in.copy())
        for attr in unit.EXPORT_PARAMS:
            if f"layer0_{attr}" not in full:
                continue
            value = full[f"layer0_{attr}"]
            if attr in ("weights_gate", "weights_up", "weights_down"):
                value = value[share]        # this chip's slabs
            getattr(unit, attr).reset(value.copy())
        unit.initialize(device=XLADevice())
        unit.route_input.initialize(unit.device)
        unit.run()
        unit.output.map_read()
        unit.last_choice.map_read()
        np.testing.assert_array_equal(
            np.sort(unit.last_choice.mem.reshape(-1, top_k), axis=-1),
            np.sort(chosen, axis=-1))     # every chip routes over all 64
        mine = np.asarray(unit.output.mem, np.float64)
        # … and the reference given the same share agrees with the chip
        same = ref.moe_block(x, {**full, **{
            f"layer0_weights_{n}": full[f"layer0_weights_{n}"][share]
            for n in ("gate", "up", "down")}}, 0, spec, chosen,
            held=share, block_input=block_in)[0]
        np.testing.assert_allclose(mine, np.asarray(same), atol=2e-5)
        total += mine - np.asarray(alike, np.float64)
    np.testing.assert_allclose(total, np.asarray(uncut), atol=1e-4)

"""kanana-2-30b-a3b-instruct-2601's block (PR 52) at small widths, on
the CPU, seeded: the interleaved against the half-split rotation at
theta 1e6, the share test (the eight shares' routed parts plus the
DOUBLED shared expert once add up to the uncut layer, under a bias that
moves the choice), and the toy ``StandardWorkflow``
(``znbench/tests/data/toy``: hidden 64, 2 heads of 128 + 64 / 128, a
latent of 32 + 64, a dense MLP of 96, 16 experts of 32 top 3 with 2
held, a shared expert of 64, T 64) against the benchmark's plain
reference (``znbench/reference/kanana.py``): every table entry's
output, the loss, EVERY parameter's gradient, the bias's move after one
step, on both backends; the reference made wrong in ONE term at a time
is far from the f32 system; export and serving refuse the latent K/V by
name."""

import copy
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import kanana_controls as controls
from benchmarks.ling_controls import spoiled
from znicz_tpu.backends import NumpyDevice, XLADevice
from znicz_tpu.loader.base import TRAIN
from znicz_tpu.loader.fullbatch import ArrayLoader
from znicz_tpu.memory import Vector
from znicz_tpu.models.standard_workflow import StandardWorkflow
from znicz_tpu.ops import attention, moe
from znicz_tpu.ops.moe import GatedMLP
from znicz_tpu.utils import prng
from znicz_tpu.utils.config import reset_root, root
from znicz_tpu.workflow import Workflow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ, BATCH = 64, 2


def reference():
    path = os.path.join(REPO, "znbench", "reference", "kanana.py")
    spec = importlib.util.spec_from_file_location("ref_kanana", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def config(toy: bool = True) -> dict:
    parts = ("tests", "data", "toy") if toy else ()
    with open(os.path.join(REPO, "znbench", *parts, "configs",
                           "kanana_2_30b_a3b.json")) as fh:
        return json.load(fh)


def rel(got, want) -> float:
    want = np.asarray(want, np.float32)
    return float(np.abs(np.asarray(got, np.float32).reshape(want.shape)
                        - want).max() / (np.abs(want).max() + 1e-30))


# ----------------------------------------------------------------------
# the attention's pool of host threads
# ----------------------------------------------------------------------
def test_the_pool_s_threads_stay_on_the_device_the_arrays_lie_on():
    """``drivers/train_lm.check`` sends the reference to the host's CPU
    device with ``jax.default_device``, which is a thread's own: a
    worker of :func:`attention_core`'s pool would fall back to the
    process's default — the chip, full and far slower at f32 — unless
    it names the device itself (PR 52: a run's reference did not end in
    23 minutes on the chip's host for that reason).  Here device 1 of
    the virtual CPUs stands for the chip."""
    ref = reference()
    here, chip = jax.devices("cpu")[:2]
    rng = np.random.default_rng(5)
    seen, attend = [], ref._rows_attend

    def spy(*args):
        out = attend(*args)
        seen.append(next(iter(out.devices())))
        return out

    ref._rows_attend = spy
    jax.config.update("jax_default_device", chip)
    try:
        with jax.default_device(here):
            q, k, v = (jnp.asarray(rng.standard_normal((1, 96, 4, d)),
                                   jnp.float32) for d in (24, 24, 16))
            out = ref.attention_core(q, k, v, 0.2)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 0.2
            s = jnp.where(np.tril(np.ones((96, 96), bool)), s, -jnp.inf)
            want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    finally:
        jax.config.update("jax_default_device", None)
    assert out.devices() == {here} and set(seen) == {here}
    assert len(seen) == 4
    assert rel(out, want) < 1e-5


# ----------------------------------------------------------------------
# the rotation
# ----------------------------------------------------------------------
def test_interleaved_and_half_split_rotation_give_the_same_scores():
    """The unit rotates the two halves of the 64 rotary dims, the
    reference the published pairs (2i, 2i + 1) on the columns in the
    order ``pairs``; q · k against the ONE shared key is the same
    number, and the rotation decides something."""
    ref = reference()
    rng = np.random.default_rng(6)
    q = rng.normal(0, 1, (1, SEQ, 2, 64)).astype(np.float32)
    k = rng.normal(0, 1, (1, SEQ, 1, 64)).astype(np.float32)
    cos, sin = attention.rope_tables(np, SEQ, 64, 1e6, None)
    ours = np.einsum("bqhd,bkd->bhqk", attention.apply_rope(np, q, cos,
                                                            sin),
                     attention.apply_rope(np, k, cos, sin)[:, :, 0])
    q_r, k_r = ref.rotate(jnp.asarray(q), jnp.asarray(k), 1e6)
    theirs = np.einsum("bqhd,bkd->bhqk", np.asarray(q_r),
                       np.asarray(k_r)[:, :, 0])
    assert rel(ours, theirs) < 1e-5
    plain = np.einsum("bqhd,bkd->bhqk", q, k[:, :, 0])
    assert rel(plain, theirs) > 0.1
    assert list(ref.pairs(8)) == [0, 4, 1, 5, 2, 6, 3, 7]


# ----------------------------------------------------------------------
# the share test
# ----------------------------------------------------------------------
SPEC = {"n_experts": 16, "top_k": 3, "width": 32, "norm_topk": True,
        "score": "sigmoid", "routed_scale": 2.448, "shared_width": 64,
        "select_bias": True, "pre_norm": "rms", "residual": True,
        "norm_eps": 1e-6}


def _share(full, x, bias, held):
    reset_root()
    unit = moe.MoE(Workflow(name="share"), held=held, **SPEC)
    unit.input = Vector(x.copy())
    for attr in unit.EXPORT_PARAMS:
        value = full[f"layer0_{attr}"]
        if attr in ("weights_gate", "weights_up", "weights_down"):
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


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """What each of the 8 chips adds for its 2 of 16 experts (``held``
    0–1, 2–3, …), with what every chip computes alike — the skip and
    the shared expert of TWICE the routed width — counted once, is the
    uncut reference's output of the whole layer, under a bias that
    moves the choice."""
    rng = np.random.default_rng(5)
    d, experts, width = 64, SPEC["n_experts"], SPEC["width"]
    shared = SPEC["shared_width"]
    assert shared == 2 * width
    full = {"layer0_weights": rng.normal(0, 0.5, (d, experts)),
            "layer0_gain_norm": rng.uniform(0.7, 1.3, d)}
    for name, shape in (("gate", (experts, d, width)),
                        ("up", (experts, d, width)),
                        ("down", (experts, width, d)),
                        ("shared_gate", (d, shared)),
                        ("shared_up", (d, shared)),
                        ("shared_down", (shared, d))):
        full[f"layer0_weights_{name}"] = rng.normal(0, 0.2, shape)
    full = {k: v.astype(np.float32) for k, v in full.items()}
    x = rng.normal(0, 1, (BATCH, SEQ, d)).astype(np.float32)
    bias = rng.uniform(-0.1, 0.1, experts).astype(np.float32)
    ref = reference()
    with jax.default_matmul_precision("highest"):
        m = ref._normed(jnp.asarray(x), full, 0, SPEC)
        uncut, _, chosen = ref.moe_block(m, full, 0, SPEC, bias=bias)
        alike = ref.moe_block(m, full, 0, SPEC, chosen, held=[])[0]
        unbiased = ref.moe_block(m, full, 0, SPEC)[2]
    assert (np.sort(chosen, -1) != np.sort(unbiased, -1)).any()
    assert np.abs(np.asarray(alike)).max() > 0.05    # the shared expert
    alike = np.asarray(alike, np.float64) + x        # … and the skip
    total = alike.copy()
    for share in range(8):
        held = (2 * share, 2 * share + 1)
        unit = _share(full, x, bias, held)
        np.testing.assert_array_equal(     # every chip routes over all 16
            np.sort(unit.last_choice.mem.reshape(-1, 3), axis=-1),
            np.sort(chosen, axis=-1))
        part = np.asarray(unit.output.mem, np.float64) - alike
        assert np.abs(part).max() > 0.01, share
        total += part
    np.testing.assert_allclose(total, np.asarray(uncut) + x, atol=2e-4)


# ----------------------------------------------------------------------
# the toy model against the plain reference
# ----------------------------------------------------------------------
def layers(lr: float, moment: float) -> list:
    table = copy.deepcopy(config()["workflow"]["layers"])
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


def build(device, table, name="kanana_ref", steps: int = 1):
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
        for attr in ("gain_norm", "gain_latent"):
            vec = getattr(unit, attr, None)
            if vec:
                vec.map_invalidate()
                vec.mem[...] = rng.uniform(0.7, 1.3, vec.shape)
        if getattr(unit, "select_bias_on", False):
            unit.select_bias.map_invalidate()
            unit.select_bias.mem[...] = rng.uniform(
                -0.1, 0.1, unit.select_bias.shape)
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
    kinds = [layer["type"] for layer in table]
    assert kinds == ["embedding", "latent_attention", "gated_mlp",
                     "latent_attention", "moe", "rms_norm", "softmax"]
    real = config(toy=False)["workflow"]["layers"]
    blocks = (len(real) - 5) // 2
    assert blocks >= 4
    assert [layer["type"] for layer in real] == kinds[:3] \
        + ["latent_attention", "moe"] * blocks + kinds[-2:]
    options = {layer["type"]: set(layer["->"]) for layer in real}
    for layer in table:                      # the same options, smaller
        assert set(layer["->"]) == options[layer["type"]]
    for unit in wf.forwards:
        if isinstance(unit, attention.MultiHeadAttention):
            assert unit.kv_latent == 32 and unit.q_latent is None
            assert not unit.head_gate and unit.score_scale is None
            assert unit.residual and unit.rope_theta == 1e6
            assert unit.weights.shape == (64, 2 * 192 + 32 + 64)
            assert unit.weights_kv_up.shape == (32, 2 * 256)
            if backend == "xla":
                assert unit._flash.runs and unit._flash.interpret
        if isinstance(unit, GatedMLP):
            assert unit.weights.shape == (64, 96)
        if isinstance(unit, moe.MoE):
            assert unit.select_bias_on and unit.groups is None
            assert unit.held == (0, 1) and unit.top_k == 3
            assert unit.shared_width == 2 * unit.width == 64
            assert unit.routed_scale == 2.448
            assert unit.residual and unit.aux_loss_weight == 0


def test_the_published_widths_and_the_cut_are_in_the_file():
    file = config(toy=False)
    assert file["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    assert file["published"] == {
        "num_hidden_layers": 48, "n_routed_experts": 128,
        "vocab_size": 128256}
    assert (file["hidden_size"], file["num_attention_heads"],
            file["q_lora_rank"], file["kv_lora_rank"],
            file["qk_nope_head_dim"], file["qk_rope_head_dim"],
            file["v_head_dim"], file["intermediate_size"],
            file["moe_intermediate_size"], file["n_shared_experts"],
            file["num_experts_per_tok"], file["routed_scaling_factor"],
            file["rope_theta"], file["rope_scaling"],
            file["rms_norm_eps"], file["first_k_dense_replace"],
            file["n_group"], file["topk_group"]) \
        == (2048, 32, None, 512, 128, 64, 128, 6144, 768, 2, 6, 2.448,
            1000000, None, 1e-6, 1, 1, 1)
    depth = file["num_hidden_layers"]
    assert depth >= 5
    assert (file["n_routed_experts"], file["vocab_size"]) == (16, 16032)
    table = file["workflow"]["layers"]
    assert len(table) == 1 + 2 * depth + 2
    assert table[0]["->"] == {"vocab_size": 16032, "dim": 2048,
                               "weights_stddev": 2.0}
    mla = table[1]["->"]
    assert mla == {"n_heads": 32, "causal": True, "include_bias": False,
                   "pre_norm": "rms", "residual": True, "kv_latent": 512,
                   "qk_nope": 128, "qk_rope": 64, "v_head_dim": 128,
                   "rope": {"theta": 1000000}, "norm_eps": 1e-6}
    assert table[2]["->"] == {"width": 6144, "pre_norm": "rms",
                              "residual": True, "norm_eps": 1e-6}
    for i in range(3, 1 + 2 * depth, 2):
        assert table[i]["type"] == "latent_attention"
        assert table[i]["->"] == mla
        experts = table[i + 1]["->"]
        assert table[i + 1]["type"] == "moe"
        assert (experts["n_experts"], experts["top_k"], experts["width"],
                experts["shared_width"], experts["held"],
                experts["select_bias"], experts["bias_rate"],
                experts["score"], experts["routed_scale"],
                experts["norm_topk"], experts["residual"],
                experts["aux_loss_weight"]) \
            == (128, 6, 768, 1536, list(range(16)), True, 0.001,
                "sigmoid", 2.448, True, True, 0.0)
        assert "groups" not in experts
    assert table[-1]["->"]["output_sample_shape"] == 16032
    assert set(file["reference_tolerance"]) == {
        "embedding", "layers", "router_logits", "router_gap"}
    for key in ("mla", "rope", "weight_layout", "shared_experts",
                "router", "aux_loss", "head_dim", "optimizer", "data",
                "init", "buffer"):
        assert file["assumed"][key], key
    for word in ("8 chips", "experts 0-15", "16,032", "first"):
        assert word in file["deployment"], word
    assert "576" in file["reduced_why"]["arithmetic"] \
        or "688" in file["reduced_why"]["arithmetic"]


def test_layer_outputs_and_probabilities(one_step):
    """f32 on both sides, the program in tiles, the reference in whole
    arrays, choosing its own experts: what is left is the order of
    summation."""
    wf, table, before, bias, x, y, _ = one_step
    outs, router = reference().run(before, table, x, bias=bias)
    assert len(outs) == len(wf.forwards) == len(table)
    for i, (unit, want) in enumerate(zip(wf.forwards, outs)):
        unit.output.map_read()
        assert unit.output.mem.shape == np.asarray(want).shape
        assert rel(unit.output.mem, want) < 1e-5, (i, table[i]["type"])
        if table[i]["type"] == "moe":      # the same experts, by itself
            unit.last_choice.map_read()
            np.testing.assert_array_equal(
                np.sort(unit.last_choice.mem.reshape(-1, 3), -1),
                np.sort(router["chosen"][i], -1))


def test_loss_and_every_gradient(one_step):
    """The step ran plain SGD at lr 1, so parameter − parameter after
    IS the system's gradient of the loss: compared with the reference's
    ``value_and_grad`` for every tensor — W_up's, g_c's, W_r's and the
    shared expert's among them; and the bias moved by its rule."""
    wf, table, before, bias, x, y, _ = one_step
    value, grads = reference().loss_and_grads(before, table, x, y,
                                              bias=bias)
    after = params_of(wf)
    # embedding, 2 × 5 (mixer), 4 (dense MLP), 8 (expert layer), the
    # final gain, the head
    assert set(grads) == set(before)
    assert len(before) == 1 + 2 * 5 + 4 + 8 + 2
    for name in ("layer1_weights", "layer1_weights_kv_up",
                 "layer1_gain_latent", "layer1_weights_out",
                 "layer3_weights_kv_up", "layer3_gain_latent",
                 "layer4_weights", "layer4_weights_gate",
                 "layer4_weights_shared_gate", "layer4_weights_shared_up",
                 "layer4_weights_shared_down"):
        assert name in grads
    for name, want in grads.items():
        got = before[name] - after[name]
        scale = np.abs(want).max()
        assert scale > 0, name
        # (the embedding's rows are drawn near 2: W − (W − g) keeps a
        # g of 1e-3 to an ulp of 2, 1e-4 of it)
        limit = 5e-4 if name == "layer0_weights" else 1e-4
        assert np.abs(got - want).max() <= limit * scale, (
            name, np.abs(got - want).max() / scale)
    assert wf.decision.epoch_loss[TRAIN] == pytest.approx(value, rel=1e-4)
    # the bias moved by its rule, and is no parameter of the loss
    moved = 0
    for i, unit in enumerate(wf.forwards):
        if getattr(unit, "select_bias_on", False):
            for vec in (unit.select_bias, unit.select_load):
                vec.map_read()
            load = unit.select_load.mem
            np.testing.assert_allclose(
                unit.select_bias.mem,
                bias[i] + 1e-3 * np.sign(load.mean() - load), atol=1e-8)
            moved += int((unit.select_bias.mem != bias[i]).sum())
    assert moved > 0


# ----------------------------------------------------------------------
# one term wrong at a time
# ----------------------------------------------------------------------
def _key_per_head(ref):
    """Every head its OWN rotary key (the shared one's columns rolled
    by the head's number) instead of the one key behind all heads."""
    def head_keys(k_nope, k_r):
        h = k_nope.shape[2]
        own = jnp.concatenate(
            [jnp.roll(k_r, 7 * j, axis=-1) for j in range(h)], axis=2)
        return jnp.concatenate([k_nope, own], axis=-1)
    return head_keys


def _rotated_nope(ref):
    def nope_parts(q_nope, k_nope, theta):
        return (ref.rope_interleaved(q_nope, theta),
                ref.rope_interleaved(k_nope, theta))
    return nope_parts


def _weights_from_biased_scores(ref):
    plain = ref.gate_weights

    def gate_weights(scores, chosen, bias, spec):
        return plain(scores + jnp.asarray(bias), chosen, bias, spec)
    return gate_weights


#: name → (the table entry it spoils, the edit of that entry's options,
#: the functions of the reference replaced); ``own``: the reference
#: chooses its experts itself
def wrong_terms(ref, table) -> dict:
    first = 1                                  # the first mixer
    expert = [i for i, l in enumerate(table) if l["type"] == "moe"][0]
    listed = {name: how for name, *how in controls.controls(ref, table, {})
              + controls.readings(ref, table)}
    zero = jnp.zeros_like
    return {
        "rotary_key_per_head": (first, {},
                                {"head_keys": _key_per_head(ref)}),
        "no_latent_norm": listed["no_latent_norm"][:3],
        "no_rotary_key_product": listed["no_rotary_key_product"][:3],
        "rotation_on_the_nope_dims": (first, {},
                                      {"nope_parts": _rotated_nope(ref)}),
        "scale_by_the_nope_width": (first, {"score_scale": 128 ** -0.5},
                                    {}),
        "softmax_scores_for_sigmoid": listed["sigmoid_to_softmax"][:3],
        "weights_from_biased_scores": (
            expert, {}, {"gate_weights": _weights_from_biased_scores(ref)}),
        "no_routed_scaling": listed["no_routed_scaling"][:3],
        "no_renormalisation": (expert, {"norm_topk": False}, {}),
        "shared_expert_at_one_width": listed["shared_expert_at_768"][:3],
        "no_shared_expert": (expert, {}, {
            "shared_expert": lambda m, p, i, spec: zero(m)}),
        "top_4_for_top_3": (expert, {"top_k": 4}, {}),
    }


WRONG = ["rotary_key_per_head", "no_latent_norm", "no_rotary_key_product",
         "rotation_on_the_nope_dims", "scale_by_the_nope_width",
         "softmax_scores_for_sigmoid", "weights_from_biased_scores",
         "no_routed_scaling", "no_renormalisation",
         "shared_expert_at_one_width", "no_shared_expert",
         "top_4_for_top_3"]


@pytest.mark.parametrize("what", WRONG)
def test_one_term_wrong_is_far_from_the_f32_system(one_step, what):
    """Each of them moves a layer's output by more than 1e-2 of its
    range where the plain reference agrees with the f32 system to 1e-5:
    a thousand times apart, whatever the limit at the cell's sizes."""
    wf, table, before, bias, x, y, _ = one_step
    ref = reference()
    listed = wrong_terms(ref, table)
    assert list(listed) == WRONG
    at, edit, patches = listed[what]
    routing = {}
    if "top_k" not in edit:         # the system's own choice, as the
        for i in bias:              # driver hands it to the reference
            wf.forwards[i].last_choice.map_read()
            routing[i] = np.asarray(wf.forwards[i].last_choice.mem) \
                .reshape(-1, 3).astype(np.int64)
    outs = spoiled(ref, at, edit, patches, None).forward(
        before, table, x, routing=routing, bias=bias)
    assert len(outs) == at + 1
    worst = 0.0
    for unit, want in zip(wf.forwards[1:], outs[1:]):
        unit.output.map_read()
        worst = max(worst, rel(unit.output.mem, want))
    assert worst > 1e-2, (what, worst)


def test_an_expert_layer_in_place_of_the_dense_layer_0(one_step):
    """``first_k_dense_replace`` 1: layer 0's feed-forward is DENSE.
    The same 96 columns read as 3 experts of 32 under a router of their
    own, top 2 — a sparse layer 0 — give another block, far from the
    system's."""
    wf, table, before, bias, x, y, _ = one_step
    ref = reference()
    dense = [layer["type"] for layer in table].index("gated_mlp")
    assert dense == 2 and config(toy=False)["first_k_dense_replace"] == 1
    width, n = 32, 3
    params = dict(before)
    d = before[f"layer{dense}_weights"].shape[0]
    for name, key in (("gate", "weights"), ("up", "weights_up")):
        params[f"layer{dense}_weights_{name}"] = \
            before[f"layer{dense}_{key}"].reshape(d, n, width) \
            .transpose(1, 0, 2)
    params[f"layer{dense}_weights_down"] = \
        before[f"layer{dense}_weights_down"].reshape(n, width, d)
    params[f"layer{dense}_weights"] = np.random.default_rng(3).normal(
        0, 0.5, (d, n)).astype(np.float32)
    sparse = copy.deepcopy(table[:dense + 1])
    sparse[dense] = {"type": "moe", "->": {
        "n_experts": n, "top_k": 2, "width": width, "norm_topk": True,
        "score": "sigmoid", "routed_scale": 1.0, "pre_norm": "rms",
        "residual": True, "norm_eps": 1e-6}}
    outs = ref.forward(params, sparse, x)
    wf.forwards[dense].output.map_read()
    assert rel(wf.forwards[dense].output.mem, outs[dense]) > 1e-2
    # all three chosen at sigmoid(0) = 1/2 each IS half the dense MLP
    sparse[dense]["->"].update(top_k=3, norm_topk=False)
    params[f"layer{dense}_weights"] = np.zeros((d, n), np.float32)
    a = np.asarray(ref.run(before, table[:dense], x)[0][-1])
    halved = np.asarray(ref.run(params, sparse, x)[0][dense]) - a
    want = np.asarray(ref.run(before, table[:dense + 1], x)[0][dense])
    assert rel(a + 2.0 * halved, want) < 1e-5


# ----------------------------------------------------------------------
# the other driver, export and serving: correct, or refusing by name
# ----------------------------------------------------------------------
def _trained(drive, name):
    reset_root()
    wf, _, _ = build(XLADevice(), layers(0.05, 0.0), name=name, steps=4)
    drive(wf)
    return params_of(wf)


def test_run_chunked_trains_the_table_as_run_does():
    plain = _trained(lambda wf: wf.run(), "kanana_run")
    chunked = _trained(lambda wf: wf.run_chunked(2), "kanana_chunked")
    assert set(plain) == set(chunked)
    for name, want in plain.items():
        assert rel(chunked[name], want) < 1e-5, name


@pytest.mark.parametrize("what", ["export_forward", "DecodeModel"])
def test_serving_refuses_the_latent_key_by_name(what, tmp_path):
    reset_root()
    wf, _, _ = build(XLADevice(), layers(0.05, 0.0),
                     name=f"kanana_{what}")
    from znicz_tpu.export import refuse_unserved
    with pytest.raises(NotImplementedError) as said:
        if what == "export_forward":
            wf.export_forward(str(tmp_path / "bundle.npz"))
        else:
            refuse_unserved(wf.forwards, "DecodeModel")
    assert "kv_latent" in str(said.value)

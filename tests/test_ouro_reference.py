"""The toy Ouro ``StandardWorkflow`` (``znbench/tests/data/toy``: two
sandwich-normed layers run four times on shared weights, the final norm
after every pass, an exit at every pass; hidden 64, 4 heads of 16,
T 128) against the benchmark's plain reference
(``znbench/reference/ouro.py``, the passes a Python loop) on seeded
weights, in f32 with the flash kernels interpreted: every table entry's
output, all R exits, the exit distribution, the loss and EVERY
parameter gradient — the one summed over the passes — against the
reference's ``jax.value_and_grad``; each left-out term fails; one pass
of the looped table is the plain chain bit for bit; a parameter is
updated ONCE a step; the other drivers run the passes or refuse by
name."""

import copy
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from znicz_tpu.backends import NumpyDevice, XLADevice
from znicz_tpu.loader.base import TRAIN
from znicz_tpu.loader.fullbatch import ArrayLoader
from znicz_tpu.models.standard_workflow import StandardWorkflow
from znicz_tpu.observe import metrics as obs_metrics
from znicz_tpu.ops import attention, loop_exits, moe, nn_units
from znicz_tpu.utils import prng

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ, BATCH = 128, 2
#: the f32 system against the f32 reference (the flash kernels'
#: online softmax against the reference's plain one)
CLOSE = 2e-4


def reference():
    path = os.path.join(REPO, "znbench", "reference", "ouro.py")
    spec = importlib.util.spec_from_file_location("ref_ouro", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def config(toy: bool = True) -> dict:
    parts = ("tests", "data", "toy") if toy else ()
    with open(os.path.join(REPO, "znbench", *parts, "configs",
                           "ouro_2_6b.json")) as fh:
        return json.load(fh)


def layers(lr: float, moment: float, passes: int | None = 4,
           blocks: int = 2) -> list:
    """The toy cell's table; ``passes`` None writes it as a plain chain
    (no looped span), ``blocks`` 1 keeps one of its two layers."""
    table = copy.deepcopy(config()["workflow"]["layers"])
    table = table[:1 + 2 * blocks] + table[-2:]
    for layer in table:
        layer["<-"] = {"learning_rate": lr, "gradient_moment": moment}
        if "passes" in layer:
            if passes is None:
                del layer["passes"]
            else:
                layer["passes"] = passes
    return table


def tokens(n: int = BATCH, seed: int = 17):
    vocab = config()["input"]["vocab"]
    ids = np.random.default_rng(seed).integers(0, vocab, (n, SEQ + 1))
    return ids[:, :-1], ids[:, 1:]


def params_of(wf) -> dict:
    out = {}
    for i, unit in enumerate(wf.forwards):
        for attr in unit.EXPORT_PARAMS:
            vec = getattr(unit, attr)
            if vec:
                vec.map_read()
                out[f"layer{i}_{attr}"] = np.array(vec.mem, np.float32)
    return out


def build(table, x, y, device=None, **engine_options):
    """An initialized f32 workflow over ``table``, flash kernels
    interpreted, gains away from one, the gate's bias away from zero."""
    from znicz_tpu.utils.config import reset_root, root
    reset_root()
    engine = root.common.engine
    engine.pallas_interpret = True
    engine.flash_attention = True
    for key, value in engine_options.items():
        setattr(engine, key, value)
    prng.seed_all(31)
    wf = StandardWorkflow(
        name="ouro_ref",
        loader_factory=lambda w: ArrayLoader(
            w, train_data=x.astype(np.float32),
            train_labels=y.astype(np.int32), minibatch_size=BATCH,
            shuffle_limit=0),
        layers=table, decision_config={"max_epochs": 1})
    wf.initialize(device=device or XLADevice())
    rng = np.random.default_rng(18)
    for unit in wf.forwards:      # gains of one would hide their path
        for attr in ("gain_norm", "gain_post", "bias_exit"):
            vec = getattr(unit, attr, None)
            if vec:
                vec.map_invalidate()
                vec.mem[...] = rng.uniform(0.7, 1.3, vec.shape)
        if type(unit).__name__ == "RMSNorm":
            unit.weights.map_invalidate()
            unit.weights.mem[...] = rng.uniform(0.7, 1.3,
                                                unit.weights.shape)
    return wf


def relative(got, want) -> float:
    want = np.asarray(want, np.float32)
    return float(np.abs(np.asarray(got, np.float32).reshape(want.shape)
                        - want).max() / (np.abs(want).max() + 1e-12))


@pytest.fixture(scope="module")
def one_step():
    """One plain-SGD step at lr 1 (W −= gradient) of the system with
    the parameters before it, the tokens it saw and what the reference
    makes of them."""
    from znicz_tpu.utils.config import reset_root
    x, y = tokens()
    table = layers(1.0, 0.0)
    wf = build(table, x, y)
    before = params_of(wf)
    wf.run()
    reset_root()
    ref = reference()
    want_loss, want_grads = ref.loss_and_grads(before, table, x, y)
    return {"wf": wf, "table": table, "before": before, "x": x, "y": y,
            "after": params_of(wf), "ref": ref, "loss": want_loss,
            "grads": want_grads,
            "outs": ref.forward(before, table, x),
            "q": ref.exits(before, table, x)}


TABLE_TYPES = ["embedding", "attention", "gated_mlp", "attention",
               "gated_mlp", "rms_norm", "loop_exits"]
PARAMS = sorted(
    f"layer{i}_{attr}" for i, kind in enumerate(TABLE_TYPES)
    for attr in {"embedding": ("weights",),
                 "attention": ("weights", "weights_out", "gain_norm",
                               "gain_post"),
                 "gated_mlp": ("weights", "weights_up", "weights_down",
                               "gain_norm", "gain_post"),
                 "rms_norm": ("weights",),
                 "loop_exits": ("weights", "weights_exit",
                                "bias_exit")}[kind])


def test_the_toy_model_is_the_cell_s_model_in_small(one_step):
    wf, table = one_step["wf"], one_step["table"]
    assert [layer["type"] for layer in table] == TABLE_TYPES
    assert sorted(one_step["before"]) == PARAMS
    real = config(toy=False)["workflow"]["layers"]
    assert [layer["type"] for layer in real] == (
        ["embedding"] + ["attention", "gated_mlp"]
        * config(toy=False)["num_hidden_layers"]
        + ["rms_norm", "loop_exits"])
    toy = {layer["type"]: layer for layer in table}
    for big in real:                         # the same options, smaller
        assert set(big["->"]) == set(toy[big["type"]]["->"])
        assert big.get("passes") == toy[big["type"]].get("passes")
    span, = wf.pass_spans
    assert span.passes == 4 and len(span.forwards) == 5
    assert span.forwards == wf.forwards[1:6] and span.gds == wf.gds[1:6]
    assert len(wf.forwards) == len(wf.gds) == len(table)
    for unit in wf.forwards:
        if isinstance(unit, attention.MultiHeadAttention):
            assert unit._flash.runs and unit.rope_theta == 1e6
            assert unit.pre_norm == unit.post_norm == "rms"
            assert unit.gain_norm.shape == unit.gain_post.shape == (64,)
        if isinstance(unit, moe.GatedMLP):
            assert unit.pre_norm == unit.post_norm == "rms"
            assert unit.gain_post.shape == (64,)
    assert wf.forwards[-1].output.shape == (BATCH, 4, SEQ, 97)
    assert obs_metrics.loop(span.name, "applications").value == 20
    assert span.applications_per_step == 20


def test_the_published_widths_and_the_cut_are_in_the_file():
    file = config(toy=False)
    assert file["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert file["published"] == {"num_hidden_layers": 48,
                                 "vocab_size": 49152}
    assert (file["hidden_size"], file["num_attention_heads"],
            file["head_dim"], file["intermediate_size"],
            file["total_ut_steps"]) == (2048, 16, 128, 5632, 4)
    assert file["num_hidden_layers"] >= 4 and file["vocab_size"] >= 6144
    table = file["workflow"]["layers"]
    assert table[1]["->"]["n_heads"] == 16
    assert table[2]["->"]["width"] == 5632
    assert {layer.get("passes") for layer in table[1:-1]} == {4}
    for key in ("assumed", "deployment", "reduced_why"):
        assert file[key]


@pytest.mark.parametrize("index", range(len(TABLE_TYPES)))
def test_every_table_entry_agrees_with_the_reference(one_step, index):
    """A looped member's output is its LAST pass's; the head's is all
    four exits, sequence leading."""
    unit, want = one_step["wf"].forwards[index], one_step["outs"][index]
    unit.output.map_read()
    limit = 1e-6 if index == 0 else CLOSE
    assert relative(unit.output.mem, want) <= limit


@pytest.mark.parametrize("exit_index", range(4))
def test_every_exit_and_its_mass_agree(one_step, exit_index):
    head = one_step["wf"].forwards[-1]
    for vec in (head.output, head.exit_q):
        vec.map_read()
    assert relative(head.output.mem[:, exit_index],
                    one_step["outs"][-1][:, exit_index]) <= CLOSE
    assert relative(head.exit_q.mem[:, exit_index],
                    one_step["q"][:, exit_index]) <= CLOSE
    assert np.allclose(head.exit_q.mem.sum(axis=1), 1.0, atol=1e-6)


def test_the_loss_is_the_expected_loss_less_the_entropy(one_step):
    wf = one_step["wf"]
    assert wf.decision.epoch_loss[TRAIN] == pytest.approx(
        one_step["loss"], rel=1e-5)
    # the epoch-end read: per-exit loss and mass, the entropy
    stats = wf.forwards[-1].last_exit_stats
    q = one_step["q"]
    assert stats["mass"] == pytest.approx(q.mean(axis=(0, 2)), rel=1e-4)
    name = wf.forwards[-1].name               # … and the gauges
    assert [obs_metrics.loop_exit(name, r, "mass").value
            for r in range(4)] == stats["mass"]
    assert obs_metrics.loop_exit(name, "entropy", "value").value \
        == stats["entropy"]
    entropy = -(q * np.log(q)).sum(axis=1).mean()
    assert stats["entropy"] == pytest.approx(entropy, rel=1e-4)
    p_true = np.take_along_axis(
        one_step["outs"][-1],
        np.broadcast_to(one_step["y"][:, None, :, None],
                        (BATCH, 4, SEQ, 1)), axis=-1)[..., 0]
    assert stats["loss"] == pytest.approx(
        (-np.log(p_true)).mean(axis=(0, 2)), rel=1e-4)
    expected = (q * -np.log(p_true)).sum(axis=1).mean() - 0.1 * entropy
    assert one_step["loss"] == pytest.approx(expected, rel=1e-5)


@pytest.mark.parametrize("name", PARAMS)
def test_every_gradient_is_the_sum_over_the_passes(one_step, name):
    """lr 1, no momentum: what a parameter lost in the step is its
    gradient — the reference's, which differentiates through all four
    applications of the shared weights."""
    got = one_step["before"][name] - one_step["after"][name]
    want = one_step["grads"][name]
    assert np.abs(want).max() > 0
    assert relative(got, want) <= 5e-4


# -- each left-out term fails ------------------------------------------
def _wrong_grads(one_step, **patches):
    ref = reference()
    for name, value in patches.items():
        setattr(ref, name, value)
    return ref.loss_and_grads(one_step["before"], one_step["table"],
                              one_step["x"], one_step["y"])


def _worst(one_step, grads, names=PARAMS) -> float:
    return max(relative(
        one_step["before"][n] - one_step["after"][n], grads[n])
        for n in names)


LEFT_OUT = {
    # what reaches h^r from pass r + 1 is dropped: only the exits teach
    "the exit's cotangent not joined to the next pass's":
        {"carry": jax.lax.stop_gradient},
    # the gate reads u, not RMSNorm(u)
    "the gate taken before the final norm": {"GATE_READS": "raw"},
}


@pytest.mark.parametrize("what", sorted(LEFT_OUT))
def test_a_left_out_term_fails(one_step, what):
    _, grads = _wrong_grads(one_step, **LEFT_OUT[what])
    assert _worst(one_step, grads) > 0.05, what


def test_the_entropy_term_dropped_fails(one_step):
    table = copy.deepcopy(one_step["table"])
    table[-1]["->"]["entropy_weight"] = 0.0
    value, grads = reference().loss_and_grads(
        one_step["before"], table, one_step["x"], one_step["y"])
    assert abs(value - one_step["loss"]) > 1e-2
    assert _worst(one_step, grads,
                  ["layer6_weights_exit", "layer6_bias_exit"]) > 0.05


def test_one_pass_instead_of_four_fails(one_step):
    table = layers(1.0, 0.0, passes=1)
    ref = reference()
    outs = ref.forward(one_step["before"], table, one_step["x"])
    wf = one_step["wf"]
    wf.forwards[5].output.map_read()
    assert relative(wf.forwards[5].output.mem, outs[5]) > 0.05
    _, grads = ref.loss_and_grads(one_step["before"], table,
                                  one_step["x"], one_step["y"])
    assert _worst(one_step, grads) > 0.05


# -- one update a parameter a step -------------------------------------
def test_momentum_sees_the_summed_gradient_once(one_step):
    """After one step from zero momentum the accumulator is −lr · Σ_r
    g_r: the reference's single update.  Four updates from the four
    partial gradients would leave −lr · Σ_r 0.9^(R−1−r) g_r — with
    equal parts 0.86 of it, which the limit refuses."""
    from znicz_tpu.utils.config import reset_root
    lr, moment = 0.5, 0.9
    table = layers(lr, moment)
    wf = build(table, one_step["x"], one_step["y"])
    before = params_of(wf)
    counts = {"update": {}, "fold": {}}
    update = nn_units.GradientDescentBase._update_param_xla
    fold = nn_units.GradientDescentBase._fold_fingerprint

    def counting_update(self, grad, vec, *rest):
        counts["update"][vec.name] = counts["update"].get(vec.name, 0) + 1
        counts["now"] = vec.name
        return update(self, grad, vec, *rest)

    def counting_fold(self, xp, slot, value):
        name = counts.get("now")
        counts["fold"][name] = counts["fold"].get(name, 0) + 1
        return fold(self, xp, slot, value)

    nn_units.GradientDescentBase._update_param_xla = counting_update
    nn_units.GradientDescentBase._fold_fingerprint = counting_fold
    try:
        wf.run()
    finally:
        nn_units.GradientDescentBase._update_param_xla = update
        nn_units.GradientDescentBase._fold_fingerprint = fold
        reset_root()
    # ONE traced step (every step of the epoch runs the one program):
    # each parameter tensor enters the update once, with three folds
    assert len(counts["update"]) == len(PARAMS)
    assert set(counts["update"].values()) == {1}
    assert set(counts["fold"].values()) == {3}
    _, grads = reference().loss_and_grads(before, table, one_step["x"],
                                          one_step["y"])
    for i, (unit, gd) in enumerate(zip(wf.forwards, wf.gds)):
        for attr in unit.EXPORT_PARAMS:
            if not getattr(unit, attr):
                continue
            acc = getattr(gd, f"accumulated_gradient_{attr}")
            acc.map_read()
            assert relative(acc.mem, -lr * grads[f"layer{i}_{attr}"]) \
                <= 5e-4, (i, attr)


# -- one pass of the looped table is the plain chain --------------------
def test_one_pass_is_the_plain_chain_bit_for_bit():
    from znicz_tpu.utils.config import reset_root
    x, y = tokens()
    after = []
    for passes in (1, None):
        wf = build(layers(0.1, 0.9, passes=passes), x, y)
        assert len(wf.pass_spans) == (passes is not None)
        wf.run()
        after.append((params_of(wf), wf.decision.epoch_loss[TRAIN]))
        reset_root()
    (looped, loss_looped), (chain, loss_chain) = after
    assert loss_looped == loss_chain
    for name in PARAMS:
        assert np.array_equal(looped[name], chain[name]), name


# -- the other drivers run the passes, or refuse by name ----------------
def test_run_chunked_runs_the_passes():
    """One block run twice: the scanned chunk applies it as often as
    the per-step program, and leaves the same parameters."""
    from znicz_tpu.utils.config import reset_root
    x, y = tokens(4)
    after = []
    for chunked in (False, True):
        wf = build(layers(0.05, 0.9, passes=2, blocks=1), x, y)
        wf.run_chunked(2) if chunked else wf.run()
        after.append((params_of(wf), wf.pass_spans[0].applications_per_step))
        reset_root()
    (plain, per_step), (chunk, per_step_chunk) = after
    assert per_step == per_step_chunk == 2 * 3
    for name in plain:
        assert relative(chunk[name], plain[name]) <= 1e-6, name


def test_run_accumulated_is_the_mean_of_the_summed_gradients():
    """(Σ over microbatches of Σ over passes) / M: two microbatches of
    two sequences, one block run twice, against the reference's
    gradient over all four sequences."""
    from znicz_tpu.utils.config import reset_root
    x, y = tokens(4)
    table = layers(1.0, 0.0, passes=2, blocks=1)
    wf = build(table, x, y, grad_accum=2)
    before = params_of(wf)
    wf.run_accumulated(2)
    after = params_of(wf)
    reset_root()
    _, grads = reference().loss_and_grads(before, table, x, y)
    for name in before:
        assert relative(before[name] - after[name], grads[name]) \
            <= 5e-4, name


def _refusal(what):
    x, y = tokens()
    from znicz_tpu.utils.config import reset_root
    try:
        if what == "numpy":
            build(layers(0.1, 0.9), x, y, device=NumpyDevice())
            return
        wf = build(layers(0.1, 0.9), x, y,
                   **({"grad_accum": 2} if what == "run_pipelined"
                      else {}))
        if what == "run_pipelined":
            wf.run_pipelined(2, microbatches=2)
        elif what == "export_forward":
            wf.export_forward(os.path.join("/tmp", "ouro_never.npz"))
        else:        # DecodeModel's plan starts with this check; the
            #          head alone is refused likewise
            from znicz_tpu.export import refuse_unserved
            refuse_unserved(wf.forwards[-1:] if what == "loop_exits"
                            else wf.forwards, "DecodeModel")
    finally:
        reset_root()


@pytest.mark.parametrize("what", ["numpy", "run_pipelined",
                                  "export_forward", "DecodeModel",
                                  "loop_exits"])
def test_what_cannot_run_the_passes_refuses_by_name(what):
    with pytest.raises((NotImplementedError, RuntimeError),
                       match="loop_exits" if what == "loop_exits"
                       else "passes"):
        _refusal(what)


def test_a_member_that_keeps_forward_state_is_refused():
    x, y = tokens()
    table = layers(0.1, 0.9)
    table.insert(3, {"type": "dropout", "passes": 4,
                     "->": {"dropout_ratio": 0.1}})
    with pytest.raises((NotImplementedError, RuntimeError),
                       match="passes"):
        build(table, x, y)


def test_both_norms_on_one_sublayer_on_both_backends():
    """``pre_norm`` and ``post_norm`` together, a gain each: the numpy
    oracle and the XLA path agree on a plain chain, outputs and one
    step's parameters."""
    from znicz_tpu.utils.config import reset_root
    x, y = tokens()
    table = layers(0.05, 0.9, passes=None)[:5] + [
        {"type": "rms_norm", "->": {"eps": 1e-6},
         "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
        {"type": "softmax", "->": {"output_sample_shape": 97,
                                   "per_position": True,
                                   "include_bias": False},
         "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}}]
    seen = []
    for device in (XLADevice(), NumpyDevice()):
        wf = build(table, x, y, device=device)
        wf.run()
        outs = []
        for unit in wf.forwards:
            unit.output.map_read()
            outs.append(np.array(unit.output.mem, np.float32))
        seen.append((outs, params_of(wf)))
        reset_root()
    (outs_x, params_x), (outs_n, params_n) = seen
    for got, want in zip(outs_x, outs_n):
        assert relative(got, want) <= CLOSE
    assert "layer1_gain_post" in params_x and "layer2_gain_post" in params_x
    for name in params_x:
        assert relative(params_x[name], params_n[name]) <= CLOSE, name


def test_the_exit_distribution_is_the_product_written_out():
    lam = np.random.default_rng(3).uniform(0.05, 0.95, (2, 4, 5))
    q = loop_exits.exit_distribution(np, lam)
    want = np.asarray(reference().exit_distribution(jnp.asarray(lam)))
    assert np.allclose(q, want, atol=1e-6)
    assert np.allclose(q.sum(axis=1), 1.0)


# -- the program's own map: the sum's phase, the pass in the path -------
LOOP_HLO = """HloModule jit_znicz_step__r, is_scheduled=true

%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %add.1 = f32[8]{0} add(%p, %p), metadata={op_name="jit(znicz_step__r)/GDMlp/pass1/update/pass_sum/add"}
}

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
  %add.2 = f32[8]{0} add(%p.1, %p.1), metadata={op_name="jit(znicz_step__r)/GDMlp/pass0/update/pass_sum/add"}
  ROOT %mul.2 = f32[8]{0} multiply(%add.2, %p.1), metadata={op_name="jit(znicz_step__r)/GDMlp/pass0/update/mul"}
}

ENTRY %main.3 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(znicz_step__r)/GDMlp/pass1/update/pass_sum/add"}
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(znicz_step__r)/GDMlp/pass0/update/mul"}
  ROOT %dot.3 = f32[8]{0} multiply(%fusion.2, %x), metadata={op_name="jit(znicz_step__r)/Mlp/pass3/dot"}
}
"""


def test_the_sum_is_a_phase_and_the_pass_is_in_the_path():
    from znicz_tpu import observe
    from znicz_tpu.observe import scopes
    units = (("Mlp", "GatedMLP", "GatedMLP", False),
             ("GDMlp", "GDGatedMLP", "GatedMLP", True))
    ops = scopes.attribute(LOOP_HLO, units)
    # wholly the adds of the passes' sum: its own phase; with the
    # update fused in: the update's; a pass scope hides no unit
    assert ops["fusion.1"]["phase"] == "pass_sum"
    assert ops["fusion.2"]["phase"] == "update"
    assert ops["dot.3"] == {"unit": "Mlp", "kind": "GatedMLP",
                            "family": "GatedMLP", "phase": "forward"}
    # the running program: every application under <unit>/pass<r>, the
    # adds under update/pass_sum (three a parameter: R − 1)
    scopes.forget()
    x, y = tokens()
    wf = build(layers(0.1, 0.9, blocks=1), x, y)
    wf.run()
    region = wf._region_unit.region
    for vec in region._vectors:
        vec.unmap()
    text = jax.jit(region.build_callable(
        tuple(bool(u.gate_skip) for u in region.units))).lower(
            *[v.devmem for v in region._vectors]).as_text(debug_info=True)
    mlp, gd = wf.forwards[2].name, wf.gds[2].name
    for r in range(4):
        assert f"{mlp}/pass{r}/" in text and f"{gd}/pass{r}/" in text
    for r in range(3):      # R − 1 adds a parameter: none in the first
        assert f"{gd}/pass{r}/update/pass_sum" in text      # pass walked
    assert f"{gd}/pass3/update/pass_sum" not in text
    assert f"{gd}/pass3/update/fingerprint" not in text   # one update
    found = observe.op_scopes()[f"znicz_step__{region.name}"]
    members = {e["unit"] for e in found.values() if e["unit"]}
    assert {u.name for u in wf.forwards[1:] + wf.gds} <= members
    from znicz_tpu.utils.config import reset_root
    reset_root()

"""The dropless top-k expert layer: XLA path against the numpy oracle
(forward, every gradient, the momentum update), droplessness under a
forced router, top-k without renormalising, the two auxiliary losses,
the Pallas grouped matmul interpreted, the device-kept routing totals,
the anomaly guard reading the kernels' Σ g² of a slab's gradient in the
slab's place (PR 44), and the three training drivers agreeing on an
OLMoE-shaped chain."""

import numpy as np
import pytest

from znicz_tpu.backends import NumpyDevice, XLADevice
from znicz_tpu.dummy import DummyUnit, DummyWorkflow
from znicz_tpu.loader.fullbatch import ArrayLoader
from znicz_tpu.memory import Vector
from znicz_tpu.models.standard_workflow import StandardWorkflow
from znicz_tpu.observe import metrics as obs_metrics
from znicz_tpu.ops import moe
from znicz_tpu.utils import prng
from znicz_tpu.utils.config import root

B, T, D = 2, 8, 16
E, K, F = 8, 2, 12
OPTIONS = dict(n_experts=E, top_k=K, width=F, pre_norm="rms",
               residual=True, aux_loss_weight=0.01, z_loss_weight=0.001)
PARAMS = ("weights", "weights_gate", "weights_up", "weights_down",
          "gain_norm")


def build(device, x, params=None, lr=0.05, moment=0.9, guard=False,
          **options):
    prng.seed_all(5)
    wf = DummyWorkflow()
    src = DummyUnit(wf, output=Vector(np.asarray(x), name="x"))
    fwd = moe.MoE(wf, **{**OPTIONS, **options})
    fwd.link_attrs(src, ("input", "output"))
    fwd.initialize(device=device)
    if fwd.gain_norm:             # a gain of ones would hide its path
        fwd.gain_norm.reset(np.random.default_rng(3).uniform(
            0.5, 1.5, D).astype(np.float32))
        fwd.gain_norm.initialize(device)
    for attr, arr in (params or {}).items():
        vec = getattr(fwd, attr)
        vec.reset(np.array(arr, np.float32))
        vec.initialize(device)
    gd_u = moe.GDMoE(wf, learning_rate=lr, gradient_moment=moment)
    gd_u.forward_unit = fwd
    gd_u.link_attrs(fwd, "input", "output", "weights", "bias")
    gd_u.err_output = Vector(np.zeros(np.shape(x), np.float32),
                             name="err")
    if guard:       # the anomaly guard's running flags, as linked
        gd_u.anomaly_flag = Vector(np.ones(2, np.float32),
                                   name="step_flags")
        gd_u.anomaly_flag.initialize(device)
    gd_u.initialize(device=device)
    return fwd, gd_u


def params_of(fwd) -> dict:
    out = {}
    for attr in PARAMS:
        vec = getattr(fwd, attr)
        if vec:
            vec.map_read()
            out[attr] = np.array(vec.mem, np.float32)
    return out


def _data(seed=0):
    rng = np.random.default_rng(seed)
    return (rng.normal(0, 1.0, (B, T, D)).astype(np.float32),
            rng.normal(0, 0.1, (B, T, D)).astype(np.float32))


def step(fwd, gd_u, err):
    fwd.run()
    gd_u.err_output.reset(err.copy())
    gd_u.err_output.initialize(fwd.device)
    gd_u.run()


@pytest.mark.parametrize("kernel", [False, True])
@pytest.mark.parametrize("norm_topk", [False, True])
def test_xla_vs_numpy_oracle_forward_gradients_momentum(kernel,
                                                        norm_topk):
    """Output, err_input and all five parameters after TWO momentum
    steps through ``_apply_param_xla`` agree with the oracle's analytic
    backward — with the XLA grouped matmul and with the Pallas kernels
    interpreted."""
    if kernel:
        root.common.engine.pallas_interpret = True
        root.common.engine.moe_grouped_matmul = True
    x, err = _data()
    np_f, np_g = build(NumpyDevice(), x, norm_topk=norm_topk)
    xla_f, xla_g = build(XLADevice(), x, params=params_of(np_f),
                         norm_topk=norm_topk)
    assert xla_f._gmm_kernel == kernel
    got = []
    for fwd, gd_u in ((np_f, np_g), (xla_f, xla_g)):
        for _ in range(2):
            step(fwd, gd_u, err)
        fwd.output.map_read()
        gd_u.err_input.map_read()
        got.append({**params_of(fwd),
                    "output": np.array(fwd.output.mem, np.float32),
                    "err_input": np.array(gd_u.err_input.mem,
                                          np.float32)})
    assert set(got[0]) == set(PARAMS) | {"output", "err_input"}
    for key, want in got[0].items():
        np.testing.assert_allclose(got[1][key], want, rtol=2e-3,
                                   atol=2e-5, err_msg=key)
    drawn = params_of(build(NumpyDevice(), x, norm_topk=norm_topk)[0])
    for attr in PARAMS:           # and every parameter MOVED
        assert np.abs(got[0][attr] - drawn[attr]).max() > 0, attr


def forced_router(first: int, second: int) -> np.ndarray:
    w = np.zeros((D, E), np.float32)
    w[:, first], w[:, second] = 1.0, 0.5
    return w


@pytest.mark.parametrize("device_cls", [NumpyDevice, XLADevice])
def test_dropless_when_the_router_sends_every_token_to_one_expert(
        device_cls):
    """No capacity, no drop: with every token choosing experts 3 and 5
    both compute all N rows, the other six none, and the output is the
    oracle's."""
    rng = np.random.default_rng(1)
    x = (np.abs(rng.normal(0, 1, (B, T, D))) + 0.5).astype(np.float32)
    fwd, _ = build(device_cls(), x, pre_norm=None,
                   params={"weights": forced_router(3, 5)})
    fwd.run()
    fwd.moe_stats.map_read()
    counts = np.asarray(fwd.moe_stats.mem[:E])
    n = B * T
    assert counts[3] == n and counts[5] == n
    assert counts.sum() == n * K            # exactly top_k per token
    assert counts[[0, 1, 2, 4, 6, 7]].sum() == 0
    # by hand: both experts over every row, weights p3 and p5, raw
    fwd.output.map_read()
    p = fwd.route(np, x.reshape(n, D), forced_router(3, 5))[1]
    want = x.reshape(n, D).copy()
    for e in (3, 5):
        gate = x.reshape(n, D) @ params_of(fwd)["weights_gate"][e]
        up = x.reshape(n, D) @ params_of(fwd)["weights_up"][e]
        hidden = gate / (1 + np.exp(-gate)) * up
        want += p[:, e:e + 1] * (hidden
                                 @ params_of(fwd)["weights_down"][e])
    np.testing.assert_allclose(
        np.asarray(fwd.output.mem, np.float32).reshape(n, D), want,
        rtol=2e-4, atol=2e-5)


def test_top_k_is_not_renormalised():
    """The eight (here two) largest probabilities are used as they are:
    their sum stays below one, and ``norm_topk`` gives another
    output."""
    x, _ = _data(2)
    raw, _ = build(NumpyDevice(), x, residual=False)
    normed, _ = build(NumpyDevice(), x, residual=False, norm_topk=True,
                      params=params_of(raw))
    raw.run()
    normed.run()
    _, (m, _, p, raw_p, top_p, _, _, _) = raw._forward_np(x)
    assert (raw_p.sum(axis=-1) < 1.0).all()
    np.testing.assert_array_equal(raw_p, top_p)
    np.testing.assert_allclose(np.sort(p, axis=-1)[:, -K:][:, ::-1],
                               raw_p, rtol=1e-6)
    scale = raw_p.sum(axis=-1).reshape(B, T, 1)
    np.testing.assert_allclose(normed.output.mem * scale,
                               raw.output.mem, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("device_cls", [NumpyDevice, XLADevice])
def test_auxiliary_losses_and_the_epoch_end_gauges(device_cls):
    """A zero router is uniform: the load-balancing loss is top_k
    (ties go to the first experts, which then hold every row) and the
    z-loss (log E)²; the unit keeps them on the device and publishes
    them, with the rows per expert, when the epoch ends."""
    x, _ = _data(3)
    fwd, _ = build(device_cls(), x,
                   params={"weights": np.zeros((D, E), np.float32)})
    fwd.name = f"moe_cov_{device_cls.__name__}"
    for _ in range(3):
        fwd.run()
    fwd.on_epoch_ended()
    n = B * T
    gauge = obs_metrics.moe_expert_tokens
    assert gauge(fwd.name, "max").value == pytest.approx(n)
    assert gauge(fwd.name, "min").value == pytest.approx(0)
    assert gauge(fwd.name, "mean").value == pytest.approx(n * K / E)
    assert obs_metrics.moe_aux_loss(
        fwd.name, "load_balance").value == pytest.approx(K, rel=1e-5)
    assert obs_metrics.moe_aux_loss(fwd.name, "z").value == \
        pytest.approx(np.log(E) ** 2, rel=1e-5)
    fwd.moe_stats.map_read()      # and the totals start over
    assert not np.asarray(fwd.moe_stats.mem).any()


def test_oracle_gradients_match_finite_differences():
    """The analytic oracle — and so, by the test above, the vjp —
    against central differences of Σ y·c + 0.01·lb + 0.001·z, for the
    input and a sample of every parameter."""
    rng = np.random.default_rng(4)
    x = rng.normal(0, 1, (1, 5, D)).astype(np.float32)
    c = rng.normal(0, 1, x.shape).astype(np.float32)
    fwd, gd_u = build(NumpyDevice(), x, lr=1.0, moment=0.0)
    before = params_of(fwd)

    def loss(x_) -> float:
        y, cache = fwd._forward_np(np.asarray(x_, np.float32))
        lb, z = fwd.aux_losses(np, cache[1].astype(np.float64),
                               cache[2].astype(np.float64), cache[-1])
        return float((y.astype(np.float64) * c).sum()
                     + fwd.aux_loss_weight * lb + fwd.z_loss_weight * z)

    step(fwd, gd_u, c)            # lr 1, no momentum: W −= gradient
    grads = {a: before[a] - params_of(fwd)[a] for a in PARAMS}
    dx = gd_u.err_input.mem.copy()
    for attr in PARAMS:           # put the parameters back
        getattr(fwd, attr).mem[...] = before[attr]
    eps = 2e-3
    for idx in list(np.ndindex(*x.shape))[::7]:
        hi, lo = x.copy(), x.copy()
        hi[idx] += eps
        lo[idx] -= eps
        np.testing.assert_allclose(
            dx[idx], (loss(hi) - loss(lo)) / (2 * eps), rtol=3e-2,
            atol=3e-3, err_msg=f"x{idx}")
    for attr in PARAMS:
        mem = getattr(fwd, attr).mem
        picks = rng.choice(mem.size, size=6, replace=False)
        for flat in picks:
            idx = np.unravel_index(flat, mem.shape)
            keep = float(mem[idx])
            mem[idx] = keep + eps
            hi = loss(x)
            mem[idx] = keep - eps
            lo = loss(x)
            mem[idx] = keep
            np.testing.assert_allclose(
                grads[attr][idx], (hi - lo) / (2 * eps), rtol=3e-2,
                atol=3e-3, err_msg=f"{attr}{idx}")


# ----------------------------------------------------------------------
# the guard's Σ g² of a slab's gradient, from the kernel that made it
# ----------------------------------------------------------------------
SLABS = ("weights_gate", "weights_up", "weights_down")
HELD = {"every_expert_held": None, "four_of_eight_held": (1, 3, 4, 6)}


def kernels_interpreted() -> None:
    root.common.engine.pallas_interpret = True
    root.common.engine.moe_grouped_matmul = True


def state_of(fwd, gd_u) -> dict:
    """Every parameter, its momentum and the guard's flags, read
    back."""
    out = params_of(fwd)
    vecs = {"acc_weights": gd_u.accumulated_gradient_weights,
            **{f"acc_{attr}": acc
               for attr, _, acc in gd_u._extra_pairs()},
            "flags": gd_u.anomaly_flag}
    for key, vec in vecs.items():
        vec.map_read()
        out[key] = np.array(vec.mem, np.float32)
    return out


def guard_sums(fwd) -> float:
    return obs_metrics.moe_guard_sum(fwd.name, "from_kernel").value


def sums_recomputed(monkeypatch) -> None:
    """The update as it was before PR 44: whatever sum is handed over
    is dropped, so the guard makes its own pass over the gradient."""
    handed = moe.GDMoE._apply_weights_xla
    monkeypatch.setattr(
        moe.GDMoE, "_apply_weights_xla",
        lambda self, grad, vec=None, acc_vec=None, grad_sq=None:
        handed(self, grad, vec=vec, acc_vec=acc_vec))


@pytest.mark.parametrize("held", list(HELD))
def test_a_finite_step_is_bitwise_the_step_with_the_sum_recomputed(
        held, monkeypatch):
    """Guard linked, kernels interpreted: two momentum steps with the
    guard reading the kernels' sums leave every parameter, every
    momentum and the flags bitwise what they are when the guard sums
    the slabs itself; the gauge says which of the two ran."""
    kernels_interpreted()
    x, err = _data(7)
    got = []
    for recompute in (False, True):
        if recompute:
            sums_recomputed(monkeypatch)
        fwd, gd_u = build(XLADevice(), x, guard=True, held=HELD[held])
        fwd.name = f"moe_sums_{held}_{recompute}"
        assert fwd._gmm_kernel
        for _ in range(2):
            step(fwd, gd_u, err)
        assert guard_sums(fwd) == (0 if recompute else 3)
        got.append(state_of(fwd, gd_u))
    assert got[0]["flags"][0] == 1.0
    assert set(got[0]) == set(got[1]) and len(got[0]) == 11
    for key, want in got[1].items():
        np.testing.assert_array_equal(got[0][key], want, err_msg=key)
    drawn = params_of(build(XLADevice(), x, held=HELD[held])[0])
    for attr in SLABS:            # and every slab MOVED
        assert np.abs(got[0][attr] - drawn[attr]).max() > 0, attr


@pytest.mark.parametrize("recompute", [False, True],
                         ids=["sum_from_the_kernel", "sum_recomputed"])
@pytest.mark.parametrize("held", list(HELD))
def test_an_inf_in_one_experts_gradient_skips_the_step(
        held, recompute, monkeypatch):
    """An inf planted in ONE row of what ``znicz_tgmm`` takes for the
    down slabs' gradient — no other gradient of the step sees it: the
    slabs and their momentum stay bitwise untouched, the guard's flag
    goes down (and holds back ``gain_norm``, updated after it); the
    tensors updated before it moved.  The same with the guard summing
    the slab itself."""
    from znicz_tpu.ops import pallas_gmm
    kernels_interpreted()
    if recompute:
        sums_recomputed(monkeypatch)
    x, err = _data(8)
    fwd, gd_u = build(XLADevice(), x, guard=True, held=HELD[held],
                      width=20)    # a shape no other test has traced
    fwd.name = f"moe_inf_{held}_{recompute}"
    step(fwd, gd_u, err)
    before = state_of(fwd, gd_u)
    assert before["flags"][0] == 1.0
    real_tgmm = pallas_gmm.znicz_tgmm

    def planted(lhs, grad, group_sizes, **kwargs):
        if grad.shape[1] == D:            # the down slabs' call
            grad = grad.at[0, 5].set(np.inf)   # a row of the first group
        return real_tgmm(lhs, grad, group_sizes, **kwargs)

    monkeypatch.setattr(pallas_gmm, "znicz_tgmm", planted)
    moe.grouped_matmul.clear_cache()
    try:
        step(fwd, gd_u, err)
    finally:
        monkeypatch.setattr(pallas_gmm, "znicz_tgmm", real_tgmm)
        moe.grouped_matmul.clear_cache()
    after = state_of(fwd, gd_u)
    assert guard_sums(fwd) == (0 if recompute else 3)
    assert after["flags"][0] == 0.0
    for key in ("weights_down", "acc_weights_down", "gain_norm",
                "acc_gain_norm"):
        np.testing.assert_array_equal(after[key], before[key],
                                      err_msg=key)
    for key in ("weights", "weights_gate", "weights_up"):
        assert np.abs(after[key] - before[key]).max() > 0, key
        assert np.isfinite(after[key]).all(), key


# ----------------------------------------------------------------------
# the chain: run, run_chunked and run_accumulated agree
# ----------------------------------------------------------------------
VOCAB, SEQ = 29, 8


def olmoe_layers(aux: float) -> list:
    gd = {"learning_rate": 0.05, "gradient_moment": 0.9}
    return [
        {"type": "embedding", "->": {"vocab_size": VOCAB, "dim": D},
         "<-": gd},
        {"type": "attention",
         "->": {"n_heads": 2, "causal": True, "include_bias": False,
                "pre_norm": "rms", "qk_norm": "rms", "residual": True,
                "rope": {"theta": 10000}}, "<-": gd},
        {"type": "moe",
         "->": {"n_experts": E, "top_k": K, "width": F,
                "pre_norm": "rms", "residual": True,
                "aux_loss_weight": aux, "z_loss_weight": 0.001},
         "<-": gd},
        {"type": "rms_norm", "->": {"eps": 1e-5}, "<-": gd},
        {"type": "softmax",
         "->": {"output_sample_shape": VOCAB, "per_position": True,
                "include_bias": False}, "<-": gd},
    ]


def train(driver: str, minibatch: int, aux: float, epochs: int = 2):
    rng = np.random.default_rng(6)
    ids = rng.integers(0, VOCAB, (16, SEQ + 1))
    prng.seed_all(21)
    wf = StandardWorkflow(
        name=f"moe_{driver}",
        loader_factory=lambda w: ArrayLoader(
            w, train_data=ids[:, :-1].astype(np.float32),
            train_labels=ids[:, 1:].astype(np.int32),
            minibatch_size=minibatch, shuffle_limit=0),
        layers=olmoe_layers(aux), decision_config={"max_epochs": epochs})
    wf.initialize(device=XLADevice())
    {"run": wf.run, "chunked": lambda: wf.run_chunked(2),
     "accumulated": wf.run_accumulated}[driver]()
    out = {}
    for i, unit in enumerate(wf.forwards):
        for attr in unit.EXPORT_PARAMS:
            vec = getattr(unit, attr)
            if vec:
                vec.map_read()
                out[f"layer{i}_{attr}"] = np.array(vec.mem, np.float32)
    return out, wf


def test_run_chunked_agrees_with_run():
    want, wf = train("run", 4, aux=0.01)
    got, _ = train("chunked", 4, aux=0.01)
    assert wf.decision.epoch_loss[2] < np.log(VOCAB) + 0.5
    assert set(got) == set(want) and len(want) == 13
    for key in want:
        np.testing.assert_allclose(got[key], want[key], rtol=1e-5,
                                   atol=1e-6, err_msg=key)


def test_run_accumulated_agrees_with_run_at_the_fused_batch():
    """Two microbatches of 4 against one batch of 8.  The z-loss is a
    mean over tokens and splits exactly; the load-balancing loss is a
    product of two batch means and does not, so it is weighted 0
    here."""
    want, _ = train("run", 8, aux=0.0)
    root.common.engine.grad_accum = 2
    got, _ = train("accumulated", 4, aux=0.0)
    for key in want:
        np.testing.assert_allclose(got[key], want[key], rtol=2e-4,
                                   atol=2e-6, err_msg=key)


def _slab_sized_reductions(wf) -> list:
    """``reduce_sum`` equations of the workflow's step program, nested
    ones included, whose operand has the shape of an expert slab."""
    import jax
    from tests.test_integrity import _all_eqns
    region = wf._region_unit.region
    for vec in region._vectors:
        vec.unmap()
    closed = jax.make_jaxpr(region.build_callable(
        tuple(bool(u.gate_skip) for u in region.units)))(
            *[v.devmem for v in region._vectors])
    slabs = {(E, D, F), (E, F, D)}
    return [eqn for eqn in _all_eqns(closed.jaxpr)
            if eqn.primitive.name == "reduce_sum"
            and eqn.invars[0].aval.shape in slabs]


@pytest.mark.parametrize("kernel", [True, False],
                         ids=["kernels_interpreted", "ragged_dot"])
def test_accumulation_keeps_its_pass_and_the_gauge_says_so(kernel):
    """The guard is linked by default.  Under ``run`` with the kernels
    the three slabs' sums come from ``znicz_tgmm`` and the traced step
    holds NO reduction over a slab-shaped operand; under
    ``run_accumulated`` the update applies a mean of microbatches, not
    what the kernel wrote, so the gauge reads 0 and the drivers still
    agree; on the ``ragged_dot`` path no sum is handed over and the
    step holds the three passes it held before."""
    if kernel:
        kernels_interpreted()
    want, wf = train("run", 8, aux=0.0)
    layer = next(u for u in wf.forwards if isinstance(u, moe.MoE))
    assert layer._gmm_kernel == kernel
    assert guard_sums(layer) == (3 if kernel else 0)
    assert len(_slab_sized_reductions(wf)) == (0 if kernel else 3)
    root.common.engine.grad_accum = 2
    got, wf = train("accumulated", 4, aux=0.0)
    layer = next(u for u in wf.forwards if isinstance(u, moe.MoE))
    assert layer._gmm_kernel == kernel
    assert guard_sums(layer) == 0
    for key in want:
        np.testing.assert_allclose(got[key], want[key], rtol=2e-4,
                                   atol=2e-6, err_msg=key)


# ----------------------------------------------------------------------
# serving refuses what it cannot run (ROADMAP R1, serving half)
# ----------------------------------------------------------------------
def test_export_and_decode_refuse_the_expert_layer_and_the_block():
    """``export_forward`` (and with it every serving path, which loads
    a bundle) refuses an expert layer and an attention layer with a
    block option set, naming what is missing; ``DecodeModel`` walks the
    same check over the units of whatever bundle it is handed."""
    from znicz_tpu.export import export_forward, refuse_unserved
    _, wf = train("run", 4, aux=0.01, epochs=1)
    with pytest.raises(NotImplementedError,
                       match="pre_norm, qk_norm, rope, residual"):
        export_forward(wf, "never_written.npz")
    moe_only = [u for u in wf.forwards if isinstance(u, moe.MoE)]
    with pytest.raises(NotImplementedError,
                       match=r"sparse-expert layer \(moe\).*expert "
                             r"dispatch"):
        refuse_unserved(moe_only, "DecodeModel")
    from znicz_tpu.ops.attention import MultiHeadAttention
    for option in ({"rope": {"theta": 10000}}, {"qk_norm": "rms"},
                   {"pre_norm": "rms"}, {"residual": True}):
        unit = MultiHeadAttention(DummyWorkflow(), n_heads=2, **option)
        name = next(iter(option))
        with pytest.raises(NotImplementedError, match=name):
            refuse_unserved([unit], "DecodeModel")
    refuse_unserved([MultiHeadAttention(DummyWorkflow(), n_heads=2,
                                        causal=True)], "DecodeModel")
    import inspect
    from znicz_tpu.serving import decode
    assert "refuse_unserved(units" in inspect.getsource(
        decode.DecodeModel._build_plan)

"""The dropless top-k expert layer: XLA path against the numpy oracle
(forward, every gradient, the momentum update), droplessness under a
forced router, top-k without renormalising, the two auxiliary losses,
the Pallas grouped matmul interpreted, the device-kept routing totals,
the anomaly guard reading the kernels' Σ g² of a slab's gradient in the
slab's place (PR 44), a held share's row buffer at its fit size and at
its capacity (PR 45), and the three training drivers agreeing on an
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
_STEPS, HELD_FIT = moe._STEPS, moe.HELD_FIT
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

    def retrace():     # what holds a trace of the kernel's call
        moe.grouped_matmul.clear_cache()
        moe._fit_or_capacity_bwd.clear_cache()

    monkeypatch.setattr(pallas_gmm, "znicz_tgmm", planted)
    retrace()
    try:
        step(fwd, gd_u, err)
    finally:
        monkeypatch.setattr(pallas_gmm, "znicz_tgmm", real_tgmm)
        retrace()
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
# a held share's row buffer: the fit size, the capacity (PR 45)
# ----------------------------------------------------------------------
#: 32 tokens, top 2 of 16 experts, 3 held: the uniform share is 12
#: pairs, the fit size 15, the capacity 48 of the 64 pairs — and the
#: router's first row, which every token sees as 4.0, says where they
#: all go
WIDE = dict(n_experts=16, top_k=2, held=(1, 6, 11), norm_topk=True,
            score="sigmoid")
FIT, CAPACITY = 15, 48       # four windows of the fit size: a scan
#: … and 4 of 8 held: the fit size 40, the capacity every one of the
#: 64 pairs — two windows, written out
NEAR = dict(n_experts=8, top_k=2, held=(1, 3, 4, 6), norm_topk=True,
            score="sigmoid")
ROUTED = {"under_the_fit_size": {},
          "over_the_fit_size": {1: 50.0},          # one pair a token
          "over_the_capacity": {1: 50.0, 6: 50.0}}  # every pair


def held_pair(routing, kernel, monkeypatch, fit, shared=0, wide=WIDE,
              sizes=(FIT, CAPACITY)):
    """A held layer and its backward, the router pushed as ``ROUTED``
    says; ``fit`` None: the buffer has ONE length, its capacity — the
    layer as it was before it had two."""
    if kernel:
        kernels_interpreted()
    monkeypatch.setattr(moe, "HELD_FIT",
                        moe.HELD_SLACK if fit is None else fit)
    rng = np.random.default_rng(11)
    x = rng.normal(0, 1, (4, T, D)).astype(np.float32)
    x[..., 0] = 4.0
    fwd, gd_u = build(XLADevice(), x, guard=True, pre_norm=None, lr=0.0,
                      shared_width=shared, **wide)    # the same pairs
                                                      # here every step
    fwd.weights.map_write()
    fwd.weights.mem[0] = 0.0
    for expert, logit in ROUTED[routing].items():
        fwd.weights.mem[0, expert] = logit
    fwd.weights.unmap()
    fwd.name = f"moe_fit_{routing}_{kernel}_{fit}_{sizes[0]}"
    assert fwd._gmm_kernel == kernel and fwd._capacity == sizes[1]
    assert fwd._fit == (sizes[1] if fit is None else sizes[0])
    return fwd, gd_u, rng.normal(0, 0.1, x.shape).astype(np.float32)


def leaves_of(tree) -> list:
    import jax
    return [np.array(leaf) for leaf in jax.tree.leaves(tree)]


@pytest.mark.parametrize("kernel", [True, False],
                         ids=["kernels_interpreted", "ragged_dot"])
def test_two_windows_written_out_are_the_buffer_at_its_capacity(
        kernel, monkeypatch):
    """The capacity branch of a layer whose capacity is two windows of
    its fit size (written out, where four are a scan): the same
    equalities, and no scan in either direction."""
    import jax
    test_a_buffer_of_two_lengths_is_the_buffer_at_its_capacity(
        "over_the_fit_size", kernel, monkeypatch, NEAR, (40, 64))
    fwd, _, err = held_pair("over_the_fit_size", kernel, monkeypatch,
                            HELD_FIT, wide=NEAR, sizes=(40, 64))
    text, conds = pullback_text(fwd, err)
    assert len(conds) == 2 and "scan[" not in "".join(
        str(branch.jaxpr) for eqn in conds
        for branch in eqn.params["branches"]).replace(
            "cumsum", "")          # (an interpreted kernel's own apart)


@pytest.mark.parametrize("kernel", [True, False],
                         ids=["kernels_interpreted", "ragged_dot"])
@pytest.mark.parametrize("routing", list(ROUTED))
def test_a_buffer_of_two_lengths_is_the_buffer_at_its_capacity(
        routing, kernel, monkeypatch, wide=WIDE, sizes=(FIT, CAPACITY)):
    """The output, both auxiliary losses, what routing did, the
    cotangent of every argument and the three taps (Σ g² of a slab's
    gradient, on the kernel path) of a held layer whose buffer runs at
    the fit size or at the capacity are those of the layer with the one
    length, to f32 rounding — with the pairs here under the fit size,
    between the two (the capacity branch runs, and makes its forward
    again in the pullback), and over the capacity (the output NaN
    either way, ``rows_over`` raised: the guard of a chain refuses the
    step).  The device's counter says which branch ran, and the
    epoch's gauge hands it out."""
    import jax
    got = []
    for fit in (moe.HELD_FIT, None):
        fwd, gd_u, err = held_pair(routing, kernel, monkeypatch, fit,
                                   wide=wide, sizes=sizes)
        args = fwd.forward_args()
        assert (len(args) == 11) == kernel      # … the taps the last
        primal, pullback, aux = jax.vjp(fwd.xla_forward, *args,
                                        has_aux=True)
        grads = pullback((jax.numpy.asarray(err),
                          (np.float32(0.01), np.float32(0.001))))
        got.append((leaves_of(primal), leaves_of(aux), leaves_of(grads)))
        for _ in range(3):
            step(fwd, gd_u, err)
        fwd.on_epoch_ended()
        held = {stat: obs_metrics.moe_held(fwd.name, stat).value
                for stat in ("rows_here", "rows_over", "capacity", "fit",
                             "fit_steps", "steps")}
        fwd.last_choice.map_read()
        here = np.isin(fwd.last_choice.mem, fwd.held).sum()
        assert {"under_the_fit_size": 0 < here <= sizes[0],
                "over_the_fit_size": sizes[0] < here <= sizes[1],
                "over_the_capacity": here == 64}[routing]
        assert held == {
            "rows_here": here, "rows_over": max(here - sizes[1], 0),
            "capacity": sizes[1], "fit": fwd._fit, "steps": 3,
            "fit_steps": 3 if here <= fwd._fit else 0}
    (y, *losses), aux, grads = got[0]
    assert np.isfinite(y).all() == (routing != "over_the_capacity")
    assert len(grads) == 5 + (3 if kernel else 0)     # … and the taps
    if kernel and routing != "over_the_capacity":
        assert all(tap > 0 for tap in grads[-3:])
    # (what routing did: [rows of the held | here, all, over, and the
    # flag of the branch, which the one length always raises])
    assert aux[0][-1] == (routing == "under_the_fit_size")
    aux[0][-1] = got[1][1][0][-1] = 0
    for mine, want in zip(got[0], got[1]):
        assert len(mine) == len(want)
        for a, b in zip(mine, want):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


#: ``MoE._held_experts`` as it was before PR 45 (the one length; it
#: returns the flag of its only branch besides), kept as the text the
#: layer must still trace where its two sizes are one
def held_experts_of_the_parent(self, m, top_p, top_e, w_g, w_u, w_d, taps):
    import jax.numpy as jnp
    n, d = m.shape
    k, local, cap = self.top_k, self.n_local, self._capacity
    table = np.full(self.n_experts, local, np.int32)
    table[list(self.held)] = np.arange(local, dtype=np.int32)
    slot = jnp.asarray(table)[top_e.reshape(n * k)]
    order = jnp.argsort(slot, stable=True).astype(jnp.int32)
    sizes = (slot[:, None] == jnp.arange(local)[None, :]).sum(
        axis=0, dtype=jnp.int32)
    here = sizes.sum()
    pair = order[:cap]
    live = jnp.arange(cap) < here
    token = pair // k
    sizes = jnp.minimum(sizes, jnp.maximum(
        cap - (jnp.cumsum(sizes) - sizes), 0))
    dt = self.mxu_dtype or jnp.float32
    path = (getattr(self, "_gmm_kernel", False),
            getattr(self, "_gmm_interpret", False))
    rows = jnp.where(live[:, None], jnp.take(m, token, axis=0),
                     0.0).astype(dt)
    gate = moe.grouped_matmul(rows, w_g, sizes, *path, tap=taps[0])
    up = moe.grouped_matmul(rows, w_u, sizes, *path, tap=taps[1])
    hidden = (moe._silu(jnp, gate) * up).astype(dt)
    out = moe.grouped_matmul(hidden, w_d, sizes, *path, tap=taps[2])
    weight = jnp.where(live, jnp.take(top_p.reshape(n * k), pair),
                       0.0)
    f = jnp.zeros((n, d), jnp.float32).at[token].add(
        out * weight[:, None])
    over = jnp.maximum(here - cap, 0)
    f = f + jnp.where(over > 0, jnp.float32(jnp.nan), 0.0)
    return f, sizes, (here, over, here <= cap)


def pullback_text(unit, err) -> tuple:
    """The text of a layer's forward and pullback, traced as the
    backward unit traces them, and the ``cond`` equations of the two
    lengths in it."""
    import jax
    args = unit.forward_args()
    cotangent = jax.numpy.asarray(err)
    if isinstance(unit, moe.MoE):
        cotangent = (cotangent, (np.float32(0.01), np.float32(0.001)))

    def both(*args):
        primal, pullback, *aux = jax.vjp(
            unit.xla_forward, *args, has_aux=isinstance(unit, moe.MoE))
        return primal, aux, pullback(cotangent)

    closed = jax.make_jaxpr(both)(*args)
    return str(closed), conds_of_the_two_lengths(closed)


def conds_of_the_two_lengths(closed) -> list:
    """The ``cond`` equations of ``_fit_or_capacity``'s two jitted
    directions among a traced program's own equations (an interpreted
    kernel's ``cond``s lie further in)."""
    inner = [eqn.params["jaxpr"].jaxpr for eqn in closed.jaxpr.eqns
             if eqn.primitive.name == "jit"
             and eqn.params["name"].startswith("_fit_or_capacity")]
    return [eqn for jaxpr in inner for eqn in jaxpr.eqns
            if eqn.primitive.name == "cond"]


def gated_mlp_unit(x):
    wf = DummyWorkflow()
    src = DummyUnit(wf, output=Vector(np.asarray(x), name="x"))
    unit = moe.GatedMLP(wf, width=F, pre_norm="rms", residual=True)
    unit.link_attrs(src, ("input", "output"))
    unit.initialize(device=XLADevice())
    return unit


@pytest.mark.parametrize("layer", ["held_at_one_length", "dropless",
                                   "gated_mlp"])
def test_where_there_is_one_length_the_text_is_the_parents(
        layer, monkeypatch):
    """A held layer whose fit size IS its capacity traces the text it
    traced before it had two sizes — forward and pullback, no ``cond``
    of the two lengths in it; its rows go back by the scatter-add,
    as below ``HELD_GATHER`` they do, and the pairs' inverse map is not
    even made —, and a dropless
    layer and a dense gated MLP trace one text whatever the fit size:
    the mechanism is in no program but a held layer's with two
    lengths."""
    kernels_interpreted()
    monkeypatch.setattr(moe, "HELD_GATHER", 0)
    x, err = _data(9)
    texts = []
    for fit in (moe.HELD_FIT, moe.HELD_SLACK):
        monkeypatch.setattr(moe, "HELD_FIT", fit)
        if layer == "gated_mlp":
            texts.append(pullback_text(gated_mlp_unit(x), err))
            continue
        held = (1, 3, 4, 6) if layer == "held_at_one_length" else None
        unit = build(XLADevice(), x, held=held)[0]
        texts.append(pullback_text(unit, err))
        if held and fit == moe.HELD_SLACK:
            monkeypatch.setattr(moe.MoE, "_held_experts",
                                held_experts_of_the_parent)
            texts.append(pullback_text(unit, err))
    two_lengths = layer == "held_at_one_length"
    assert len(texts[0][1]) == (2 if two_lengths else 0)
    assert (texts[0][0] != texts[1][0]) == two_lengths
    assert not texts[1][1] and texts[1][0] == texts[-1][0]


def test_a_step_at_the_fit_size_writes_nothing_of_the_capacitys(
        monkeypatch):
    """The forward and the pullback of a held layer with two lengths
    hold ONE ``cond`` each; no result of either has the capacity's
    rows (what ``jax.vjp`` of a plain ``cond`` would hand out: both
    branches' residuals, the absent one's as zeros), and the fit
    branch holds no value of that length at all, forward or backward —
    while the capacity branch, ⌈48 / 15⌉ = 4 windows of the fit size's
    rows, writes zeros of the FIT branch's five saved arrays, when it
    runs."""
    import jax
    from tests.test_integrity import _all_eqns
    fwd, _, err = held_pair("under_the_fit_size", True, monkeypatch,
                            moe.HELD_FIT, shared=F)
    fit, cap = fwd._fit, fwd._capacity

    def both(*args):
        primal, pullback, _ = jax.vjp(fwd.xla_forward, *args,
                                      has_aux=True)
        return primal, pullback((jax.numpy.asarray(err),
                                 (np.float32(0.01), np.float32(0.001))))

    conds = conds_of_the_two_lengths(
        jax.make_jaxpr(both)(*fwd.forward_args()))
    assert len(conds) == 2

    def lengths(eqns) -> set:
        return {var.aval.shape[0] for eqn in eqns
                for var in list(eqn.invars) + list(eqn.outvars)
                if getattr(var.aval, "shape", ())}

    saved = []
    for eqn in conds:
        at_capacity, at_fit = eqn.params["branches"]
        assert cap not in lengths([eqn])
        assert cap not in lengths(_all_eqns(at_fit.jaxpr))
        # … nor has the capacity branch: it is the fit size's body
        # over four windows of the order under a scan (in the pullback
        # forward again, then backward), the same kernels at the same
        # shapes
        assert cap not in lengths(_all_eqns(at_capacity.jaxpr))
        windows = [e for e in at_capacity.jaxpr.eqns
                   if e.primitive.name == "scan"]
        assert len(windows) == (1 if eqn is conds[0] else 2)
        assert {e.params["length"] for e in windows} == {4}
        calls = [{str(e.params["jaxpr"].in_avals)
                  for e in _all_eqns(branch.jaxpr)
                  if e.primitive.name == "jit"
                  and e.params["name"] == "grouped_matmul"}
                 for branch in (at_fit, at_capacity)]
        assert calls[0] and (eqn is conds[1] or calls[0] == calls[1])
        saved.append([var.aval for var in eqn.outvars
                      if var.aval.shape[:1] == (fit,)])
    d = fwd.input.shape[-1]      # rows, gate, up, hidden, out
    assert [aval.shape[1] for aval in saved[0]] == [d, F, F, F, d]
    made = {eqn.outvars[0]: eqn
            for eqn in conds[0].params["branches"][0].jaxpr.eqns}
    for var in conds[0].params["branches"][0].jaxpr.outvars[-5:]:
        zeros = made[var]        # of the capacity branch's five: zeros
        assert zeros.primitive.name == "broadcast_in_dim"
        assert float(zeros.invars[0].val) == 0.0
    assert not saved[1]


# ----------------------------------------------------------------------
# a held share's rows go back to their tokens by gathers (PR 51)
# ----------------------------------------------------------------------
#: 3 of 64 held, top 2 of 32 tokens: the fit size 4, the capacity 12
#: (three windows: a scan) of the 64 pairs — 64 > HELD_GATHER · 4, the
#: share is small and the layer's own form the scatter-add
FAR = dict(n_experts=64, top_k=2, held=(1, 6, 11), norm_topk=True,
           score="sigmoid")
#: (options, (fit, capacity), routing, the layer's own form)
GATHERED = {
    # 26 pairs here of the fit size's 40: dead rows; tokens with no
    # pair here, with one and with both
    "at_the_fit_size_with_dead_rows": (NEAR, (40, 64),
                                       "under_the_fit_size", "gather"),
    "at_the_capacity_in_two_windows": (NEAR, (40, 64),
                                       "over_the_fit_size", "gather"),
    "at_the_capacity_in_a_scan": (WIDE, (FIT, CAPACITY),
                                  "over_the_fit_size", "gather"),
    "over_the_capacity": (WIDE, (FIT, CAPACITY), "over_the_capacity",
                          "gather"),
    "a_small_share": (FAR, (4, 12), "under_the_fit_size", "scatter"),
}


def routed_sum_and_pullback(fwd, err):
    """A held layer's routed sum (``MoE._held_experts``: what follows
    the router) with its pullback, as one function of the rows, the
    weights and the slabs — and its arguments."""
    import jax
    rng = np.random.default_rng(13)
    n, k = B * 2 * T, fwd.top_k
    m = rng.normal(0, 1, (n, D)).astype(np.float32)
    fwd.last_choice.map_read()
    top_e = np.array(fwd.last_choice.mem).reshape(n, k)
    top_p = rng.uniform(0.1, 1.0, (n, k)).astype(np.float32)
    slabs = [host(getattr(fwd, attr)) for attr in SLABS]

    def both(m, top_p, *slabs):
        f, pullback = jax.vjp(
            lambda *args: fwd._held_experts(
                args[0], args[1], top_e, *args[2:], (None,) * 3)[0],
            m, top_p, *slabs)
        return f, pullback(jax.numpy.asarray(err.reshape(n, D)))

    return both, (m, top_p, *slabs)


@pytest.mark.parametrize("rows", ["f32_rows", "bf16_rows"])
@pytest.mark.parametrize("case", list(GATHERED))
def test_rows_by_gathers_are_the_rows_by_a_scatter_add(case, rows,
                                                       monkeypatch):
    """The output, both auxiliary losses, what routing did and the
    cotangent of every argument (the rows' ``m``, through the router
    the weights ``top_p``, the three slabs) of a held layer whose rows
    come and go by gathers through the pairs' inverse map are those of
    the layer with the gather and the scatter-add under autodiff, to
    f32 rounding — the same terms, added in another order —, on either
    side of the rule, at the fit size with dead rows, at the capacity
    in two windows and in a scan, with f32 rows and with bf16 rows; a
    step over its capacity is NaN either way.  The gauge names the form
    the module's rule picks."""
    import jax
    options, sizes, routing, own = GATHERED[case]
    if rows == "bf16_rows":
        bf16_matmuls()
    got = []
    for form, above in (("own", moe.HELD_GATHER), ("gather", 10 ** 9),
                        ("scatter", 0)):
        monkeypatch.setattr(moe, "HELD_GATHER", above)
        fwd, gd_u, err = held_pair(routing, False, monkeypatch, HELD_FIT,
                                   wide=options, sizes=sizes)
        if form == "own":
            assert {f: obs_metrics.moe_combine("MoE", f).value
                    for f in ("gather", "scatter")} \
                == {"gather": own == "gather", "scatter": own != "gather"}
            continue
        primal, pullback, aux = jax.vjp(
            fwd.xla_forward, *fwd.forward_args(), has_aux=True)
        grads = pullback((jax.numpy.asarray(err),
                          (np.float32(0.01), np.float32(0.001))))
        got.append((leaves_of(primal), leaves_of(aux), leaves_of(grads)))
        step(fwd, gd_u, err)
        here = np.isin(host(fwd.last_choice), fwd.held)
        if case == "at_the_fit_size_with_dead_rows":
            assert 0 < here.sum() < sizes[0]
            assert set(here.sum(axis=-1).ravel()) == {0, 1, 2}
    assert np.isfinite(got[0][0][0]).all() \
        == (routing != "over_the_capacity")
    # (bf16 rows: a slab's kept copy beside it, which takes no
    # cotangent)
    assert len(got[0][2]) == (8 if rows == "bf16_rows" else 5)
    for mine, want in zip(*got):
        assert len(mine) == len(want)
        for a, b in zip(mine, want):
            # bf16 rows: the input's cotangent is the sum of the gate
            # and the up matmul's row gradients, a bf16 sum, which the
            # gathers read as rounded and XLA hands the scatter-add's
            # f32 cast unrounded (excess precision): one bf16 rounding
            loose = rows == "bf16_rows" and a is mine[0] \
                and mine is got[0][2]
            np.testing.assert_allclose(
                a, b, rtol=1e-6, atol=(2.0 ** -8 if loose else 1e-6)
                * np.abs(np.nan_to_num(b)).max())


@pytest.mark.parametrize("lengths", ["two_lengths", "one_length"])
def test_no_scatter_is_left_where_the_rows_go_back_by_gathers(
        lengths, monkeypatch):
    """The lowered text of a held layer's routed sum and its pullback:
    by gathers NO ``scatter`` — autodiff cannot bring one back unseen
    through the transpose of a ``take``, the primitives' pullbacks are
    their own —, below the rule the two of the parent where the buffer
    has one length (the weighted rows' and, the transpose of the rows'
    gather, their gradient's; the pairs' weights' a third, of N · k
    numbers) and those of every window besides where it has two."""
    import jax
    counts = []
    for above in (10 ** 9, 0):
        monkeypatch.setattr(moe, "HELD_GATHER", above)
        fwd, gd_u, err = held_pair(
            "over_the_fit_size", False, monkeypatch,
            HELD_FIT if lengths == "two_lengths" else None, wide=NEAR,
            sizes=(40, 64))
        step(fwd, gd_u, err)         # (the router's choice, kept)
        both, args = routed_sum_and_pullback(fwd, err)
        text = jax.jit(both).lower(*args).as_text()
        assert "stablehlo.gather" in text
        counts.append(text.count('"stablehlo.scatter"('))
    assert counts[0] == 0, counts
    assert (counts[1] == 3) if lengths == "one_length" \
        else (counts[1] > 3), counts


def test_a_large_buffer_is_gathered_by_column_blocks(monkeypatch):
    """A buffer larger than ``GATHER_OPERAND_BYTES`` is gathered a block
    of whole 128-lane tiles at a time — SmallThinker's f32 rows in four,
    its bf16 rows and LFM2's f32 rows in two, Laguna's as they are, a
    width of no whole tile never — and the sum over a token's slots is
    the same numbers either way."""
    import jax.numpy as jnp
    blocks = moe._column_blocks
    assert [blocks(15360, 2560, 4), blocks(15360, 2560, 2),
            blocks(10240, 2048, 4), blocks(2048, 3072, 4),
            blocks(1 << 20, 2560 + 64, 4), blocks(1 << 20, 128, 4)] \
        == [4, 2, 2, 1, 1, 1]
    rng = np.random.default_rng(17)
    rows = jnp.asarray(rng.normal(0, 1, (40, 384)).astype(np.float32))
    at = jnp.asarray(rng.integers(-5, 60, (32, 3)).astype(np.int32))
    ok = (at >= 0) & (at < 40)
    want = np.where(np.asarray(ok)[..., None],
                    np.asarray(rows)[np.clip(at, 0, 39)], 0).sum(axis=1)
    whole = moe._slots_sum(rows, at, ok)
    monkeypatch.setattr(moe, "GATHER_OPERAND_BYTES", 40 * 128 * 4)
    assert blocks(40, 384, 4) == 3
    for got in (whole, moe._slots_sum(rows, at, ok)):
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_a_snapshot_from_before_the_fit_size_restores():
    """``moe_stats`` of a held layer saved before PR 45 is one slot
    short (no count of the steps at the fit size): the layer starts
    its totals over at the shape it has now, as it does for a snapshot
    from before PR 34 (two slots short)."""
    x, _ = _data(10)
    prng.seed_all(5)
    wf = DummyWorkflow()
    src = DummyUnit(wf, output=Vector(np.asarray(x), name="x"))
    unit = moe.MoE(wf, **{**OPTIONS, "held": (1, 3, 4, 6)})
    unit.link_attrs(src, ("input", "output"))
    unit.moe_stats.reset(np.full(4 + 5 + 3 + 2, 7.0, np.float32))
    unit.initialize(device=XLADevice())
    assert unit.moe_stats.shape == (4 + 5 + 4 + 2,)
    unit.moe_stats.map_read()
    assert not np.asarray(unit.moe_stats.mem).any()
    unit.run()
    unit.moe_stats.map_read()
    stats = np.asarray(unit.moe_stats.mem)
    assert stats[4 + _STEPS] == 1
    assert stats[4 + 5 + 3] == (stats[4 + 5] <= unit._fit)


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
# a slab's copy in the matmuls' dtype: a leaf the update writes (PR 47)
# ----------------------------------------------------------------------
COPIES = tuple(zip(SLABS, moe.MoE.SLAB_COPIES))
FORMS = {
    # four of eight held: the fit size 40, the capacity all 64 pairs
    "held_two_lengths": (NEAR, None),
    "held_one_length": (NEAR, moe.HELD_SLACK),
    "dropless": ({}, None),
}


def bf16_matmuls() -> None:
    root.common.precision_type = "bfloat16"


def cast_in_the_step(monkeypatch) -> None:
    """The layer as it was before it kept copies: the matmuls are
    handed the plain slabs and cast them where they read them."""
    monkeypatch.setattr(moe.MoE, "_keep_slab_copies", lambda self: None)


def host(vec) -> np.ndarray:
    vec.map_read()
    return np.array(vec.mem)


def assert_copies_are_the_casts(fwd) -> None:
    import ml_dtypes
    for attr, kept in COPIES:
        slab, copy = getattr(fwd, attr), getattr(fwd, kept)
        assert slab.cast_copy is copy and copy.dtype == ml_dtypes.bfloat16
        np.testing.assert_array_equal(
            host(copy), host(slab).astype(ml_dtypes.bfloat16),
            err_msg=kept)


def everything_of(fwd, gd_u) -> dict:
    return {**state_of(fwd, gd_u),
            "output": host(fwd.output).astype(np.float32),
            "err_input": host(gd_u.err_input).astype(np.float32)}


@pytest.mark.parametrize("kernel", [True, False],
                         ids=["kernels_interpreted", "ragged_dot"])
@pytest.mark.parametrize("form", list(FORMS))
def test_the_update_writes_the_copy_and_the_steps_are_bitwise_the_steps_that_cast(
        form, kernel, monkeypatch):
    """bf16 matmuls, a dropless layer and a held one at two lengths
    and at one: after each of three momentum steps a slab's copy IS the
    slab cast, and the output, the input's gradient, every parameter,
    every momentum and the guard's flags are, bit for bit, those of the
    same steps with the plain slabs cast where the matmuls read them;
    the gauge says which of the two the program reads."""
    options, fit = FORMS[form]
    if fit is not None:
        monkeypatch.setattr(moe, "HELD_FIT", fit)
    rng = np.random.default_rng(9)
    x = rng.normal(0, 1.0, (4, T, D)).astype(np.float32)
    err = rng.normal(0, 0.1, (4, T, D)).astype(np.float32)
    runs = []
    for copies in (True, False):
        reset_engine(kernel)
        if not copies:
            cast_in_the_step(monkeypatch)
        fwd, gd_u = build(XLADevice(), x, guard=True, **options)
        fwd.name = f"moe_copy_{form}_{kernel}_{copies}"
        assert fwd._gmm_kernel == kernel
        if options:
            assert (fwd._fit, fwd._capacity) == (64 if fit else 40, 64)
        runs.append([])
        for _ in range(3):
            step(fwd, gd_u, err)
            if copies:
                assert_copies_are_the_casts(fwd)
            runs[-1].append(everything_of(fwd, gd_u))
        assert obs_metrics.moe_slab_copy(fwd.name, "slabs").value == \
            (3 if copies else 0)
        assert obs_metrics.moe_slab_copy(fwd.name,
                                         "refreshed").value == 0
    for mine, want in zip(*runs):
        assert set(mine) == set(want) and len(want) == 13
        for key in want:
            np.testing.assert_array_equal(mine[key], want[key],
                                          err_msg=key)
    first, last = runs[0][0], runs[0][-1]
    for attr in SLABS:            # and every slab MOVED, every step
        assert np.abs(last[attr] - first[attr]).max() > 0, attr


def reset_engine(kernel: bool) -> None:
    from znicz_tpu.utils.config import reset_root
    reset_root()
    bf16_matmuls()
    if kernel:
        kernels_interpreted()


def test_a_step_the_guard_refuses_leaves_the_slab_and_its_copy():
    """A non-finite gradient: the guard's flag goes down and every
    slab, its momentum AND its copy stay what they were, bit for bit;
    the next finite step is refused likewise (the flag is the
    run's)."""
    bf16_matmuls()
    x, err = _data(4)
    fwd, gd_u = build(XLADevice(), x, guard=True)
    step(fwd, gd_u, err)
    before = {**state_of(fwd, gd_u),
              **{kept: host(getattr(fwd, kept)) for _, kept in COPIES}}
    assert before["flags"][0] == 1.0
    bad = err.copy()
    bad[0, 0, 0] = np.inf
    step(fwd, gd_u, bad)
    after = {**state_of(fwd, gd_u),
             **{kept: host(getattr(fwd, kept)) for _, kept in COPIES}}
    assert after["flags"][0] == 0.0
    for key in before:
        if key != "flags":
            np.testing.assert_array_equal(after[key], before[key],
                                          err_msg=key)
    assert_copies_are_the_casts(fwd)


def test_float32_matmuls_keep_no_copy_and_the_leaves_are_the_parents(
        monkeypatch):
    """The copy exists by dtype: under float32 matmuls the layer holds
    no such leaf, its programs' leaves are those of the layer that
    never kept one, and the matmuls are handed the plain slabs."""
    x, err = _data(2)
    leaves = []
    for copies in (True, False):
        if not copies:
            cast_in_the_step(monkeypatch)
        fwd, gd_u = build(XLADevice(), x)
        assert fwd.mxu_dtype is None
        for attr, kept in COPIES:
            assert getattr(fwd, attr).cast_copy is None
            assert not getattr(fwd, kept)
        assert not any(isinstance(arg, tuple)
                       for arg in fwd.forward_args())
        step(fwd, gd_u, err)
        assert obs_metrics.moe_slab_copy(fwd.name, "slabs").value == 0
        leaves.append([[vec.name for vec in unit.region_vectors()]
                       for unit in (fwd, gd_u)])
    assert leaves[0] == leaves[1]
    assert not any("_cast" in name for unit in leaves[0] for name in unit)
    # … and under bf16 matmuls there are the three, no leaf else
    bf16_matmuls()
    monkeypatch.undo()
    fwd, gd_u = build(XLADevice(), x)
    more = [[vec.name for vec in unit.region_vectors()]
            for unit in (fwd, gd_u)]
    for had, has in zip(leaves[0], more):
        assert sorted(set(has) - set(had)) == sorted(
            f"MoE.{kept}" for kept in moe.MoE.SLAB_COPIES)
        assert set(had) <= set(has)


def test_a_host_only_device_keeps_no_copy():
    bf16_matmuls()
    fwd, _ = build(NumpyDevice(), _data()[0])
    assert all(getattr(fwd, attr).cast_copy is None for attr in SLABS)
    assert not any(getattr(fwd, kept) for kept in moe.MoE.SLAB_COPIES)


def another_epoch(wf) -> None:
    wf.decision.max_epochs += 1
    wf.decision.complete.value = False
    wf.run()


def moe_pair(wf):
    layer = next(u for u in wf.forwards if isinstance(u, moe.MoE))
    return layer, next(g for g in wf.gds if g.forward_unit is layer)


def slab_writes_to_bf16(wf) -> dict:
    """``{(unit, inside its update): count}`` of the step program's
    ``convert`` instructions that write an expert slab's shape in bf16
    — compiled, read from the text as ``observe.op_scopes()`` reads
    it; under ``None`` those the compiler made itself, with no scope
    (the bf16 momentum's store, after the guard's select)."""
    import collections
    import re
    import jax
    from znicz_tpu.observe import scopes
    region = wf._region_unit.region
    for vec in region._vectors:
        vec.unmap()
    leaves = [vec.devmem for vec in region._vectors]
    try:
        text = jax.jit(region.build_callable(
            tuple(bool(u.gate_skip) for u in region.units))).lower(
                *leaves).compile().as_text()
    finally:
        for vec, leaf in zip(region._vectors, leaves):
            vec._devmem = leaf
    names = [unit.name for unit in region.units]
    slab = re.compile(
        rf"= bf16\[{E},(?:{D},{F}|{F},{D})\]\S* convert\(")
    found = collections.Counter()
    for line in text.splitlines():
        if slab.search(line):
            scoped = scopes._OP_NAME.search(line)
            at = scoped and scopes.scope_of(scoped.group(1), names)
            found[at and (names[at[0]], at[1])] += 1
    return dict(found)


def test_the_compiled_step_casts_no_slab_outside_its_update(
        monkeypatch):
    """The chain's compiled step program under bf16 matmuls, the
    kernels interpreted: every ``convert`` that writes a slab's shape
    in bf16 lies in the update of the expert layer's backward unit —
    the copies' three writes beside the bf16 momentum's —, none in the
    layer's forward or backward; the layer that casts in the step has
    its three there and three fewer in the update.  (On the
    ``ragged_dot`` path the weight gradient is rounded to bf16 in the
    slab's shape inside the backward either way: the cast's own
    transpose.)"""
    reset_engine(True)
    _, wf = train("run", 4, aux=0.01, epochs=1)
    layer, gd_u = moe_pair(wf)
    assert layer._gmm_kernel
    assert obs_metrics.moe_slab_copy(layer.name, "slabs").value == 3
    kept = slab_writes_to_bf16(wf)
    assert set(kept) == {(gd_u.name, True), None}, kept
    cast_in_the_step(monkeypatch)
    _, wf = train("run", 4, aux=0.01, epochs=1)
    layer, gd_u = moe_pair(wf)
    assert obs_metrics.moe_slab_copy(layer.name, "slabs").value == 0
    cast = slab_writes_to_bf16(wf)
    assert cast == {(gd_u.name, True): kept[(gd_u.name, True)] - 3,
                    None: kept[None], (layer.name, False): 3}, cast


def test_host_writes_of_a_slab_reach_its_copy_before_the_next_program(
        monkeypatch):
    """A restored slab (``load_state``), the guard's seeded flip and a
    plain ``map_write`` … ``unmap``: each is followed by steps whose
    copy is the cast of the slab written (the slab's upload made it
    again — counted), and the run stays, bit for bit, the run of the
    layer that casts in the step.  What a workflow saves, folds into
    its SDC fingerprint and counts as parameters is what it was."""
    from types import SimpleNamespace
    from znicz_tpu.publishing import _layer_rows
    from znicz_tpu.resilience.guard import AnomalyGuard
    bf16_matmuls()
    runs = []
    for copies in (True, False):
        if not copies:
            cast_in_the_step(monkeypatch)
        _, wf = train("run", 4, aux=0.01, epochs=1)
        layer, gd_u = moe_pair(wf)
        refreshed = obs_metrics.moe_slab_copy(layer.name, "refreshed")
        assert refreshed.value == 0 or not copies

        def flip():
            AnomalyGuard._host_flip_param(SimpleNamespace(
                workflow=SimpleNamespace(gds=[SimpleNamespace(
                    weights=layer.weights_up)]),
                device=wf.device, warning=lambda *args: None), 1.5)

        def edit():
            layer.weights_down.map_write()
            layer.weights_down.mem[1] *= 0.5
            layer.weights_down.unmap()

        for count, write in enumerate((
                lambda: layer.load_state(
                    {"weights_gate": 1.25 * host(layer.weights_gate)}),
                flip, edit), 1):
            write()
            another_epoch(wf)
            if copies:
                assert_copies_are_the_casts(layer)
                assert refreshed.value == count
        units = list(wf.forwards) + list(wf.gds)
        runs.append({
            "saved": {u.name: sorted(u.state_dict()) for u in units},
            "folded": [vec.name for g in wf.gds
                       for vec in g._fp_folded.values()],
            "counted": [row["parameters"] for row in _layer_rows(wf)],
            "params": {f"{u.name}.{attr}": host(getattr(u, attr))
                       for u in wf.forwards for attr in u.EXPORT_PARAMS
                       if getattr(u, attr)}})
    assert not any("_cast" in name for names in
                   runs[0]["saved"].values() for name in names)
    assert not any("_cast" in name for name in runs[0]["folded"])
    for key in ("saved", "folded", "counted"):
        assert runs[0][key] == runs[1][key], key
    assert len(runs[0]["params"]) == 13
    for key, want in runs[1]["params"].items():
        np.testing.assert_array_equal(runs[0]["params"][key], want,
                                      err_msg=key)


def test_a_forward_only_program_reads_the_copy_of_the_slab_written(
        monkeypatch):
    """A forward with no backward (validation) after a host write of a
    slab: its output is the output of the layer that casts the slab it
    reads."""
    bf16_matmuls()
    x, err = _data(8)
    got = []
    for copies in (True, False):
        if not copies:
            cast_in_the_step(monkeypatch)
        fwd, gd_u = build(XLADevice(), x)
        step(fwd, gd_u, err)
        fwd.weights_gate.map_write()
        fwd.weights_gate.mem[...] *= -1.5
        fwd.weights_gate.unmap()
        fwd.run()
        if copies:
            assert_copies_are_the_casts(fwd)
        got.append(host(fwd.output))
    np.testing.assert_array_equal(got[0], got[1])


def copy_writes(body, leaves, shapes) -> int:
    """Equations of a traced body, under the scope ``update``, that
    cast f32 of one of ``shapes`` to bf16 (elsewhere the trace holds
    the casts ``_kept`` leaves dead)."""
    import jax
    import jax.numpy as jnp
    from tests.test_integrity import _all_eqns
    return sum(
        eqn.primitive.name == "convert_element_type"
        and eqn.params["new_dtype"] == jnp.bfloat16
        and eqn.invars[0].aval.dtype == jnp.float32
        and eqn.invars[0].aval.shape in shapes
        and "update" in str(eqn.source_info.name_stack)
        for eqn in _all_eqns(jax.make_jaxpr(body)(*leaves).jaxpr))


def test_an_accumulated_step_writes_the_copy_once_with_the_parameter():
    """``run_accum`` with two microbatches: the body of the microbatch
    that only sums gradients passes slabs and copies through untouched
    (the very leaves that came in), the one that applies writes each
    copy once; after the run a copy is its slab cast, and the drivers
    agree as they do without copies."""
    reset_engine(True)        # (``ragged_dot`` rounds a slab's gradient
    root.common.engine.grad_accum = 2            # to bf16 besides)
    root.common.engine.bf16_optimizer_state = False   # no cast but the
    _, wf = train("accumulated", 4, aux=0.0)           # copies'
    layer, _ = moe_pair(wf)
    assert_copies_are_the_casts(layer)
    region = wf._region_unit.region
    for vec in region._vectors:
        vec.unmap()
    leaves = [vec.devmem for vec in region._vectors]
    skips = tuple(bool(u.gate_skip) for u in region.units)
    slabs = {(E, D, F), (E, F, D)}
    kept = [i for i, vec in enumerate(region._vectors)
            if any(vec is getattr(layer, name)
                   for pair in COPIES for name in pair)]
    assert len(kept) == 6
    import jax
    closed = jax.make_jaxpr(region.build_callable(
        skips, accum_phase=("accum", 2)))(*leaves)
    for i in kept:
        assert closed.jaxpr.outvars[i] is closed.jaxpr.invars[i]
    for vec, leaf in zip(region._vectors, leaves):
        vec._devmem = leaf
    assert copy_writes(region.build_callable(
        skips, accum_phase=("apply", 2)), leaves, slabs) == 3


def test_a_looped_span_writes_a_kept_cast_once_with_the_parameter():
    """No expert layer may loop, so the rule is shown where it lives,
    on the update: a gated MLP inside a span of two passes whose
    ``weights_up`` keeps a bf16 cast (``Vector.keep_cast``) — the step
    casts it ONCE, where the summed gradient is applied, and the copy
    is the updated matrix cast."""
    import ml_dtypes
    gd = {"learning_rate": 0.05, "gradient_moment": 0.9}
    table = olmoe_layers(0.0)
    table[2] = {"type": "gated_mlp", "passes": 2,
                "->": {"width": F, "pre_norm": "rms", "residual": True},
                "<-": gd}
    del table[1]
    rng = np.random.default_rng(6)
    ids = rng.integers(0, VOCAB, (4, SEQ + 1))
    prng.seed_all(21)
    wf = StandardWorkflow(
        name="kept_cast_span",
        loader_factory=lambda w: ArrayLoader(
            w, train_data=ids[:, :-1].astype(np.float32),
            train_labels=ids[:, 1:].astype(np.int32),
            minibatch_size=4, shuffle_limit=0),
        layers=table, decision_config={"max_epochs": 1})
    wf.initialize(device=XLADevice())
    assert wf.pass_spans and wf.pass_spans[0].passes == 2
    mlp = next(u for u in wf.forwards if isinstance(u, moe.GatedMLP))
    copy = Vector(name=f"{mlp.name}.weights_up_cast")
    mlp.weights_up.keep_cast(copy, ml_dtypes.bfloat16)
    drawn = host(mlp.weights_up)
    wf.run()
    moved = host(mlp.weights_up)
    assert np.abs(moved - drawn).max() > 0
    np.testing.assert_array_equal(host(copy),
                                  moved.astype(ml_dtypes.bfloat16))
    region = wf._region_unit.region
    assert any(vec is copy for vec in region._vectors)
    for vec in region._vectors:
        vec.unmap()
    assert copy_writes(
        region.build_callable(tuple(bool(u.gate_skip)
                                    for u in region.units)),
        [vec.devmem for vec in region._vectors], {(D, F)}) == 1


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


# ----------------------------------------------------------------------
# the experts' gate function and a router that reads the block's input
# (PR 50)
# ----------------------------------------------------------------------
def block(device, x, held, params=None, act="relu"):
    """A residual sublayer (a dense gated MLP) and, after it, an expert
    layer whose router reads THAT sublayer's input, with both backward
    units, wired as ``StandardWorkflow`` wires them."""
    prng.seed_all(5)
    wf = DummyWorkflow()
    src = DummyUnit(wf, output=Vector(np.asarray(x), name="x"))
    sub = moe.GatedMLP(wf, width=F, pre_norm="rms", residual=True,
                       act=act)
    sub.link_attrs(src, ("input", "output"))
    fwd = moe.MoE(wf, **{**OPTIONS, "held": held, "act": act,
                         "norm_topk": True,
                         "route_from": "block_input"})
    fwd.link_attrs(sub, ("input", "output"))
    fwd.link_attrs(sub, ("route_input", "input"))
    sub.initialize(device=device)
    fwd.initialize(device=device)
    rng = np.random.default_rng(3)
    for unit in (sub, fwd):
        unit.gain_norm.reset(rng.uniform(0.5, 1.5, D).astype(np.float32))
        unit.gain_norm.initialize(device)
    for (unit, attr), arr in (params or {}).items():
        vec = getattr((sub, fwd)[unit], attr)
        vec.reset(np.array(arr, np.float32))
        vec.initialize(device)
    gds = []
    for unit, cls in ((sub, moe.GDGatedMLP), (fwd, moe.GDMoE)):
        gd_u = cls(wf, learning_rate=0.05, gradient_moment=0.9)
        gd_u.forward_unit = unit
        gd_u.link_attrs(unit, "input", "output", "weights", "bias")
        gds.append(gd_u)
    gds[1].err_output = Vector(np.zeros(np.shape(x), np.float32),
                               name="err")
    gds[1].initialize(device=device)
    gds[0].link_attrs(gds[1], ("err_output", "err_input"))
    gds[0].initialize(device=device)
    fwd.route_gd = gds[0]
    return (sub, fwd), gds


BLOCK_PARAMS = [(0, attr) for attr in ("weights", "weights_up",
                                       "weights_down", "gain_norm")] \
    + [(1, attr) for attr in PARAMS]


def block_state(units, gds) -> dict:
    out = {}
    for unit, attr in BLOCK_PARAMS:
        vec = getattr(units[unit], attr)
        vec.map_read()
        out[unit, attr] = np.array(vec.mem, np.float32)
    for vec, key in ((units[1].output, "output"),
                     (gds[0].err_input, "err_input")):
        vec.map_read()
        out[key] = np.array(vec.mem, np.float32)
    return out


def block_steps(units, gds, err, n=2) -> dict:
    for _ in range(n):
        for unit in units:
            unit.run()
        gds[1].err_output.reset(err.copy())
        gds[1].err_output.initialize(units[0].device)
        for gd_u in reversed(gds):
            gd_u.run()
    return block_state(units, gds)


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["ragged_dot", "kernels_interpreted"])
@pytest.mark.parametrize("share", ["dropless", "held_two_lengths",
                                   "held_one_length"])
def test_relu_experts_and_the_early_router_against_the_numpy_oracle(
        share, kernel, monkeypatch):
    """``act="relu"`` and ``route_from="block_input"`` — XLA path
    against the numpy oracle after two momentum steps: the layer's
    output, the cotangent that leaves the BLOCK (the sublayer's own
    plus the router's share, joined after the sublayer's backward) and
    every parameter of both units, the router's among them — dropless,
    and a held share at both of its buffer lengths."""
    if kernel:
        kernels_interpreted()
    if share == "held_one_length":
        monkeypatch.setattr(moe, "HELD_FIT", moe.HELD_SLACK)
    held = None if share == "dropless" else (1, 3, 4, 6)
    rng = np.random.default_rng(2)
    x = rng.normal(0, 1.0, (4, T, D)).astype(np.float32)
    err = rng.normal(0, 0.1, x.shape).astype(np.float32)
    np_units, np_gds = block(NumpyDevice(), x, held)
    drawn = block_state(np_units, np_gds)
    xla_units, xla_gds = block(XLADevice(), x, held, params={
        key: drawn[key] for key in BLOCK_PARAMS})
    assert xla_units[1]._gmm_kernel == kernel
    if held:
        assert (xla_units[1]._fit < xla_units[1]._capacity) \
            == (share == "held_two_lengths")
    want = block_steps(np_units, np_gds, err)
    got = block_steps(xla_units, xla_gds, err)
    for key, value in want.items():
        np.testing.assert_allclose(got[key], value, rtol=2e-3, atol=3e-5,
                                   err_msg=str(key))
    for key in BLOCK_PARAMS:          # and every parameter MOVED
        assert np.abs(want[key] - drawn[key]).max() > 0, key
    # the count of the hidden beside moe_stats: about half is not zero
    for units in (np_units, xla_units):
        stats = units[1].hidden_stats
        stats.map_read()
        live, total = stats.mem
        assert total > 0 and 0.25 * total < live < 0.75 * total
        assert total % F == 0
    assert np_units[0].act == xla_units[0].act == "relu"


def test_the_router_s_cotangent_joins_the_sublayer_s(monkeypatch):
    """The block's input cotangent is ``jax.grad`` of the composed
    block — sublayer, then the expert layer with its router on the
    block's input — and NOT the chain's alone: without the second
    backward edge the router's share is missing."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(4)
    x = rng.normal(0, 1.0, (4, T, D)).astype(np.float32)
    err = rng.normal(0, 0.1, x.shape).astype(np.float32)
    (sub, fwd), gds = block(XLADevice(), x, (1, 3, 4, 6))
    fwd.weights.map_write()
    fwd.weights.mem[...] *= 4.0       # a router that matters
    fwd.weights.unmap()
    got = block_steps((sub, fwd), gds, err, n=1)["err_input"]

    (sub, fwd), gds = block(XLADevice(), x, (1, 3, 4, 6))
    fwd.weights.map_write()
    fwd.weights.mem[...] *= 4.0
    fwd.weights.unmap()

    def composed(x_, detach):
        a = sub.xla_forward(x_, *sub.forward_args()[1:])
        route = jax.lax.stop_gradient(x_) if detach else x_
        (y, (lb, z)), _ = fwd.xla_forward(
            a, *fwd.forward_args()[1:-1], x_route=route)
        return jnp.sum(y * jnp.asarray(err)) \
            + fwd.aux_loss_weight * lb + fwd.z_loss_weight * z

    whole = np.asarray(jax.grad(composed)(jnp.asarray(x), False))
    chain = np.asarray(jax.grad(composed)(jnp.asarray(x), True))
    np.testing.assert_allclose(got, whole, rtol=2e-4, atol=2e-6)
    assert np.abs(whole - chain).max() > 1e-3 * np.abs(whole).max()


@pytest.mark.parametrize("device_cls", [NumpyDevice, XLADevice])
def test_the_dense_gated_mlp_takes_the_gate_function_too(device_cls):
    rng = np.random.default_rng(6)
    x = rng.normal(0, 1.0, (B, T, D)).astype(np.float32)
    outs = {}
    for act in ("silu", "relu"):
        prng.seed_all(5)
        wf = DummyWorkflow()
        src = DummyUnit(wf, output=Vector(x.copy(), name="x"))
        unit = moe.GatedMLP(wf, width=F, act=act)
        unit.link_attrs(src, ("input", "output"))
        unit.initialize(device=device_cls())
        unit.run()
        unit.output.map_read()
        for vec in (unit.weights, unit.weights_up, unit.weights_down):
            vec.map_read()
        gate = x.reshape(-1, D) @ unit.weights.mem
        hidden = (gate / (1 + np.exp(-gate)) if act == "silu"
                  else np.maximum(gate, 0)) \
            * (x.reshape(-1, D) @ unit.weights_up.mem)
        np.testing.assert_allclose(
            np.asarray(unit.output.mem, np.float32).reshape(-1, D),
            hidden @ unit.weights_down.mem, rtol=2e-4, atol=2e-5)
        outs[act] = np.array(unit.output.mem)
    assert np.abs(outs["silu"] - outs["relu"]).max() > 1e-2


def test_what_the_two_options_refuse():
    from znicz_tpu.export import refuse_unserved
    wf = DummyWorkflow()
    with pytest.raises(ValueError, match="act"):
        moe.MoE(wf, **{**OPTIONS, "act": "gelu"})
    with pytest.raises(ValueError, match="act"):
        moe.GatedMLP(wf, width=F, act="tanh")
    with pytest.raises(ValueError, match="route_from"):
        moe.MoE(wf, **{**OPTIONS, "route_from": "the_embedding"})
    # no route_input linked: the unit says what links it
    unit = moe.MoE(wf, **{**OPTIONS, "route_from": "block_input"})
    unit.link_attrs(DummyUnit(wf, output=Vector(
        np.zeros((B, T, D), np.float32), name="x")), ("input", "output"))
    with pytest.raises(AttributeError, match="route_input"):
        unit.initialize(device=NumpyDevice())
    # a table whose expert layer has no residual sublayer before it
    ids = np.zeros((4, T), np.float32)
    back = {"learning_rate": 0.1}
    for before in ([], [{"type": "gated_mlp", "->": {"width": F},
                         "<-": back}]):
        with pytest.raises(ValueError, match="layer %d: route_from"
                           % (1 + len(before))):
            StandardWorkflow(
                name="refused",
                loader_factory=lambda w: ArrayLoader(
                    w, train_data=ids,
                    train_labels=np.zeros((4, T), np.int32),
                    minibatch_size=2),
                layers=[{"type": "embedding",
                         "->": {"vocab_size": 8, "dim": D}, "<-": back},
                        *before,
                        {"type": "moe", "->": {
                            **OPTIONS, "route_from": "block_input"},
                         "<-": back}])
    # serving refuses both by name
    with pytest.raises(NotImplementedError, match="route_from"):
        refuse_unserved([moe.GatedMLP(wf, width=F, residual=True), unit],
                        "export_forward")
    with pytest.raises(NotImplementedError, match="act=relu"):
        refuse_unserved([moe.MoE(wf, **{**OPTIONS, "act": "relu"})],
                        "DecodeModel")
    with pytest.raises(NotImplementedError, match="act=relu"):
        refuse_unserved([moe.GatedMLP(wf, width=F, act="relu")],
                        "DecodeModel")


def test_a_unit_says_itself_why_it_is_not_served():
    """The seam: ``refuse_unserved`` is a loop over the units' own
    words (``Forward.unserved``) — a layer type defined HERE is refused
    by its own sentence behind the caller's name and its index, and the
    function names no unit class and reads no option of one."""
    import inspect
    from znicz_tpu import export
    from znicz_tpu.ops.all2all import All2All

    class Unserved(All2All):
        def unserved(self):
            return (f"is a toy of {self.neurons} neurons; serving has "
                    f"no step for it (ROADMAP R-never, serving half)")

    wf = DummyWorkflow()
    served = All2All(wf, output_sample_shape=3)
    assert served.unserved() is None and served.unserved_beside() is None
    export.refuse_unserved([served, served], "export_forward")
    with pytest.raises(NotImplementedError) as refused:
        export.refuse_unserved(
            [served, Unserved(wf, output_sample_shape=5)],
            "export_forward")
    assert str(refused.value) == (
        "export_forward: layer 1 is a toy of 5 neurons; serving has no "
        "step for it (ROADMAP R-never, serving half)")
    # an edge beside the chain's speaks before a layer's own word
    early = moe.MoE(wf, **{**OPTIONS, "route_from": "block_input"})
    with pytest.raises(NotImplementedError, match="layer 1 takes its "
                       "router's logits from the input of the sublayer"):
        export.refuse_unserved(
            [Unserved(wf, output_sample_shape=5), early], "DecodeModel")
    source = inspect.getsource(export.refuse_unserved)
    code = source[source.index('"""', source.index('"""') + 3):]
    for word in ("getattr(", "isinstance(", "__name__", "moe", "MoE",
                 "Stream", "GatedMLP", "GatedDeltaNet", "ShortConv",
                 "All2AllExits", "Attention", "route_from"):
        assert word not in code, word
    assert not [line for line in inspect.getsource(export).splitlines()
                if line.startswith(("import ", "from "))
                and any(module in line for module in (
                    "ops.moe", "ops.streams", "ops.delta_net",
                    "ops.short_conv", "ops.loop_exits"))]


# ----------------------------------------------------------------------
# an eighth of a wide router's experts held, chosen under a selection
# bias with no group limit, beside a shared expert of twice the routed
# width (PR 52: kanana-2-30b-a3b's expert layer in small)
# ----------------------------------------------------------------------
BIASED = dict(n_experts=128, top_k=6, width=F, shared_width=2 * F,
              held=tuple(range(16)), norm_topk=True, score="sigmoid",
              routed_scale=2.448, select_bias=True, bias_rate=1e-3,
              aux_loss_weight=0.0, z_loss_weight=0.0)
BIASED_PARAMS = PARAMS + moe.MoE.SHARED


def biased_state(fwd, gd_u) -> dict:
    out = {}
    for attr in BIASED_PARAMS + ("select_bias", "output"):
        vec = getattr(fwd, attr)
        vec.map_read()
        out[attr] = np.array(vec.mem, np.float32)
    gd_u.err_input.map_read()
    out["err_input"] = np.array(gd_u.err_input.mem, np.float32)
    return out


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["ragged_dot", "kernels_interpreted"])
@pytest.mark.parametrize("lengths", ["two_lengths", "one_length"])
def test_sixteen_of_128_under_a_bias_beside_a_doubled_shared_expert(
        lengths, kernel, monkeypatch):
    """``select_bias`` WITHOUT ``groups``, 16 of 128 experts held, top
    6, ``routed_scale`` 2.448, a ``shared_width`` TWICE ``width`` — the
    XLA path (``ragged_dot`` and the interpreted kernels; the buffer at
    its fit size and at its one length of before PR 45) against the
    numpy oracle after two momentum steps: the output, the input's
    cotangent, every parameter — the shared expert's three among them
    — and the bias after its rule's two moves."""
    if kernel:
        kernels_interpreted()
    if lengths == "one_length":
        monkeypatch.setattr(moe, "HELD_FIT", moe.HELD_SLACK)
    rng = np.random.default_rng(52)
    x = rng.normal(0, 1.0, (4, 32, D)).astype(np.float32)
    err = rng.normal(0, 0.1, x.shape).astype(np.float32)
    bias = rng.uniform(-0.2, 0.2, BIASED["n_experts"]).astype(np.float32)
    np_fwd, np_gd = build(NumpyDevice(), x, **BIASED)
    drawn = {attr: np.array(getattr(np_fwd, attr).mem)
             for attr in BIASED_PARAMS}
    xla_fwd, xla_gd = build(XLADevice(), x, params=drawn, **BIASED)
    assert xla_fwd._gmm_kernel == kernel and xla_fwd.groups is None
    assert xla_fwd.weights_shared_gate.shape == (D, 2 * F)
    assert xla_fwd.weights_gate.shape == (16, D, F)
    # 128 tokens × 6 pairs × 16 / 128 = 96 pairs here under uniform
    # routing: the fit size 1.25 ×, the capacity 4 ×
    assert xla_fwd._capacity == 384
    assert xla_fwd._fit == (120 if lengths == "two_lengths" else 384)
    got = []
    for fwd, gd_u in ((np_fwd, np_gd), (xla_fwd, xla_gd)):
        fwd.select_bias.map_write()
        fwd.select_bias.mem[...] = bias
        fwd.select_bias.unmap()
        for _ in range(2):
            step(fwd, gd_u, err)
        got.append(biased_state(fwd, gd_u))
        fwd.last_choice.map_read()
        got[-1]["chosen"] = np.sort(
            np.array(fwd.last_choice.mem).reshape(-1, 6), axis=-1)
    want, have = got
    np.testing.assert_array_equal(have.pop("chosen"), want.pop("chosen"))
    for key, value in want.items():
        np.testing.assert_allclose(have[key], value, rtol=2e-3, atol=3e-5,
                                   err_msg=key)
    for attr in BIASED_PARAMS:        # every parameter MOVED
        assert np.abs(want[attr] - drawn[attr]).max() > 0, attr
    # the bias moved twice by its rate, every entry, and the choice
    # under it is not the unbiased one
    moved = np.abs(want["select_bias"] - bias)
    assert moved.max() <= 2e-3 + 1e-7 and moved.max() > 0
    logits = moe.rms_norm(np, x, drawn["gain_norm"],
                          np_fwd.norm_eps).reshape(-1, D) \
        @ drawn["weights"]
    scores = 1.0 / (1.0 + np.exp(-logits))
    plain = np.sort(np.argsort(-scores, axis=-1)[:, :6], axis=-1)
    under = np.sort(np.argsort(-(scores + bias), axis=-1)[:, :6], axis=-1)
    assert (plain != under).any()

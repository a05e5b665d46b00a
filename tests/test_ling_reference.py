"""Ling-3.0-flash's three mechanisms (PR 37) at small widths, on the
CPU, seeded: the delta rule with a decay PER KEY CHANNEL (the
``znicz_kda_*`` kernels interpreted, against ``chunk_local`` in
``jax.numpy`` and against the recurrence token by token), the latent
K/V attention (the two-width flash kernels interpreted against the
plain core, all five cotangents), the group-limited, biased router
(against a plain top-k twice; the bias's own rule, under the guard,
through a snapshot) — and the toy ``StandardWorkflow``
(``znbench/tests/data/toy``: hidden 64, 2 linear heads of 16 × 16, 2
latent-attention heads of 128 + 64 / 128 over a latent of 32, 16
experts in 4 groups, T 128 = two chunks) against the benchmark's plain
reference (``znbench/reference/ling.py``): every table entry's output,
the loss, EVERY parameter gradient, on both backends; each left-out
term fails the cell's stated tolerance; the other drivers and serving
run the table or refuse it by name."""

import copy
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import ling_controls as controls
from znicz_tpu.backends import NumpyDevice, XLADevice
from znicz_tpu.dummy import DummyUnit, DummyWorkflow
from znicz_tpu.loader.base import TRAIN
from znicz_tpu.loader.fullbatch import ArrayLoader
from znicz_tpu.memory import Vector
from znicz_tpu.models.standard_workflow import StandardWorkflow
from znicz_tpu.observe import metrics as obs_metrics
from znicz_tpu.ops import attention, delta_net, moe
from znicz_tpu.ops import pallas_delta as pd
from znicz_tpu.ops import pallas_mla
from znicz_tpu.utils import prng
from znicz_tpu.utils.config import reset_root, root

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ, BATCH = 128, 2


def reference():
    path = os.path.join(REPO, "znbench", "reference", "ling.py")
    spec = importlib.util.spec_from_file_location("ref_ling", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def config(toy: bool = True) -> dict:
    parts = ("tests", "data", "toy") if toy else ()
    with open(os.path.join(REPO, "znbench", *parts, "configs",
                           "ling_3_0_flash.json")) as fh:
        return json.load(fh)


def rel(got, want) -> float:
    want = np.asarray(want, np.float32)
    return float(np.abs(np.asarray(got, np.float32).reshape(want.shape)
                        - want).max() / (np.abs(want).max() + 1e-30))


# ----------------------------------------------------------------------
# the delta rule with a decay per key channel
# ----------------------------------------------------------------------
def recurrence(q, k, v, log_alpha, beta):
    """S_t = Diag(α_t) S_{t−1} + β_t k_t (v_t − (Diag(α_t) S_{t−1})ᵀ
    k_t)ᵀ, o_t = S_tᵀ q_t, token by token."""
    b, _, h, dk = q.shape

    def token(s, row):
        q_t, k_t, v_t, a_t, b_t = row
        s = s * jnp.exp(a_t)[..., None]
        seen = jnp.einsum("bhkv,bhk->bhv", s, k_t)
        s = s + b_t[..., None, None] * jnp.einsum(
            "bhk,bhv->bhkv", k_t, v_t - seen)
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    rows = tuple(jnp.moveaxis(a, 1, 0)
                 for a in (q, k, v, log_alpha, beta))
    start = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    return jnp.moveaxis(jax.lax.scan(token, start, rows)[1], 0, 1)


#: log α drawn over the whole bounded range, and AT the bound in every
#: position of a chunk (16 × 5 = 80: the largest exponent the sub-block
#: algebra forms, ``pallas_delta.MAX_EXPONENT``)
DECAYS = {"mixed": (-5.0, -1e-3), "at_the_bound": (-5.0, -5.0)}


@pytest.fixture(scope="module")
def kda_rules():
    """Output and the five cotangents of the rule, three ways, per
    decay case: the kernels (interpreted), ``chunk_local`` in
    ``jax.numpy``, the recurrence."""
    b, t, h, dk, dv, chunk = 1, 128, 2, 16, 24, 64
    rng = np.random.default_rng(3)

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    k = draw(b, t, h, dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    q, v, weight = draw(b, t, h, dk), draw(b, t, h, dv), draw(b, t, h, dv)
    out = {}
    for case, (lo, hi) in DECAYS.items():
        log_alpha = jnp.asarray(rng.uniform(lo, hi, (b, t, h, dk)),
                                jnp.float32)
        beta = jnp.asarray(rng.uniform(0, 1, (b, t, h)), jnp.float32)

        def both(rule):
            def run(*args):
                with jax.default_matmul_precision("highest"):
                    return rule(*args), jax.grad(
                        lambda *a: jnp.sum(rule(*a) * weight),
                        (0, 1, 2, 3, 4))(*args)
            o, grads = jax.jit(run)(q, k, v, log_alpha, beta)
            return (o,) + tuple(grads)
        out[case] = {
            "kernels": both(lambda *a: pd.gated_delta_rule(
                *a, chunk=chunk, kernel=True, interpret=True)),
            "chunk_local": both(lambda *a: pd.gated_delta_rule(
                *a, chunk=chunk)),
            "recurrence": both(recurrence)}
    return out


NAMES = ("o", "dq", "dk", "dv", "dlog_alpha", "dbeta")


@pytest.mark.parametrize("against", ["chunk_local", "recurrence"])
@pytest.mark.parametrize("case", list(DECAYS))
def test_the_per_channel_kernels_forward_and_backward(kda_rules, case,
                                                      against):
    """1e-5 of each tensor's range; d log α where EVERY decay sits at
    the bound takes 1e-4: its factors e^(±80) cancel to within f32's
    24 bits of an exponent of 80."""
    for name, got, want in zip(NAMES, kda_rules[case]["kernels"],
                               kda_rules[case][against]):
        limit = 1e-4 if (case, name) == ("at_the_bound", "dlog_alpha") \
            else 1e-5
        assert rel(got, want) < limit, (case, against, name,
                                        rel(got, want))


def test_a_scalar_decay_still_takes_the_scalar_body():
    """The decay's SHAPE picks the body: (…, H) the ``znicz_gdr_chunk``
    / ``znicz_delta_state`` kernels, (…, H, d_k) the ``znicz_kda`` ones,
    and neither name holds the other."""
    b, t, h, dk, dv = 1, 64, 2, 16, 16
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.float32)
               for d in (dk, dk, dv))
    beta = jnp.asarray(rng.uniform(0, 1, (b, t, h)), jnp.float32)

    def names(log_alpha):
        text = str(jax.make_jaxpr(lambda *a: pd.gated_delta_rule(
            *a, kernel=True, interpret=True))(q, k, v, log_alpha, beta))
        return {word for word in ("znicz_gdr_chunk", "znicz_delta_state",
                                  "znicz_kda_chunk", "znicz_kda_state")
                if word in text}
    assert names(-jnp.ones((b, t, h))) == {"znicz_gdr_chunk",
                                            "znicz_delta_state"}
    assert names(-jnp.ones((b, t, h, dk))) == {"znicz_kda_chunk",
                                                "znicz_kda_state"}


def _linear_unit(device, t: int, **options):
    x = np.random.default_rng(0).normal(size=(2, t, 64)).astype(
        np.float32)
    prng.seed_all(5)
    wf = DummyWorkflow()
    src = DummyUnit(wf, output=Vector(x.copy(), name="x"))
    spec = dict(n_heads=2, key_dim=16, value_dim=16, conv_kernel=4,
                decay="channel", lower_bound=-5.0, gate="sigmoid",
                pre_norm="rms", residual=True, norm_eps=1e-6)
    spec.update(options)
    unit = delta_net.GatedDeltaNet(wf, **spec)
    unit.link_attrs(src, ("input", "output"))
    unit.initialize(device=device)
    return unit, x


@pytest.mark.parametrize("t", [128, 96])
def test_the_linear_unit_in_chunks_is_the_numpy_recurrence(t):
    """The unit through the interpreted kernels against its numpy
    oracle (a Python loop over the positions), at whole chunks and at
    T 96 = one chunk and a half (padded positions write nothing and
    decay nothing); the gauges say which body ran."""
    root.common.engine.pallas_interpret = True
    root.common.engine.delta_scan_kernel = True
    fast, _ = _linear_unit(XLADevice(), t)
    assert fast._kernels and fast.decay_channels == 16
    assert obs_metrics.delta_scan(fast.name, "decay_channels").value == 16
    assert obs_metrics.delta_scan(fast.name, "sub_block").value == 16
    slow, _ = _linear_unit(NumpyDevice(), t)
    fast.run()
    slow.run()
    fast.output.map_read()
    assert rel(fast.output.mem, slow.output.mem) < 1e-5


def test_a_bound_the_sub_blocks_cannot_hold_is_refused_by_name():
    """16 positions × |lower_bound| is the largest exponent formed; past
    ``pallas_delta.MAX_EXPONENT`` (80) ``initialize`` refuses, and a
    decay per channel with no bound at all likewise."""
    assert pd.SUB_BLOCK * 5.0 <= pd.MAX_EXPONENT < 88.7
    for bound in (-5.5, None):
        with pytest.raises(ValueError, match="MAX_EXPONENT"):
            _linear_unit(XLADevice(), 128, lower_bound=bound)
    with pytest.raises(ValueError, match="lower_bound"):
        _linear_unit(XLADevice(), 128, lower_bound=0.5)


def test_the_bounded_gate_s_gradient_is_finite_where_exp_overflows():
    """exp(A) · (m W_f + b) reaches tens in either direction; the
    gate's own form keeps the gradient finite there."""
    x = jnp.asarray([-200.0, -90.0, 0.0, 90.0, 200.0])
    grads = jax.grad(lambda a: delta_net._logistic(jnp, a).sum())(x)
    assert np.isfinite(np.asarray(grads)).all()
    np.testing.assert_allclose(
        delta_net._logistic(np, np.asarray(x)),
        np.asarray(delta_net._logistic(jnp, x)), atol=1e-7)


# ----------------------------------------------------------------------
# latent attention: keys of two widths, one rotary key for all heads
# ----------------------------------------------------------------------
def _mla_plain(qn, qr, kn, kr, v, heads):
    b, t, _ = qn.shape
    q = jnp.concatenate([qn.reshape(b, t, heads, 128),
                         qr.reshape(b, t, heads, 64)], -1)
    k = jnp.concatenate([kn.reshape(b, t, heads, 128), jnp.broadcast_to(
        kr[:, :, None, :], (b, t, heads, 64))], -1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest")
    seen = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where(seen, s, -1e30), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.reshape(b, t, heads, 128),
                      precision="highest").reshape(b, t, heads * 128)


@pytest.fixture(scope="module")
def mla_calls():
    """The two-width flash calls (interpreted, tiles of 128 so that the
    256 positions are a 2 × 2 walk) and the plain core with K assembled
    in full: o and the five cotangents."""
    b, t, heads = 1, 256, 4
    rng = np.random.default_rng(0)

    def draw(width):
        return jnp.asarray(rng.normal(size=(b, t, width)) * 0.3,
                           jnp.float32)
    rows = (draw(heads * 128), draw(heads * 64), draw(heads * 128),
            draw(64), draw(heads * 128))
    weight = draw(heads * 128)

    def both(rule):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return rule(*args), jax.grad(
                    lambda *a: jnp.sum(rule(*a) * weight),
                    (0, 1, 2, 3, 4))(*args)
        o, grads = jax.jit(run)(*rows)
        return (o,) + tuple(grads)
    block, pallas_mla.BLOCK = pallas_mla.BLOCK, 128
    try:
        got = both(lambda *a: pallas_mla.latent_flash_attention(
            *a, interpret=True))
    finally:
        pallas_mla.BLOCK = block
    return got, both(lambda *a: _mla_plain(*a, heads)), \
        both(lambda *a: attention.latent_attention_plain(*a, heads))


@pytest.mark.parametrize("which", range(6), ids=[
    "o", "dq_nope", "dq_rope", "dk_nope", "dk_rope_summed_over_heads",
    "dv"])
def test_the_two_width_flash_calls_against_the_plain_core(mla_calls,
                                                          which):
    kernels, plain, unit_core = mla_calls
    assert rel(kernels[which], plain[which]) < 1e-5
    assert rel(unit_core[which], plain[which]) < 1e-5
    # the shared key's cotangent is ONE key's: (B, T, 64)
    assert kernels[4].shape == (1, 256, 64)


def test_what_the_two_width_kernels_tile():
    assert pallas_mla.kernel_legal(4096, 32, 128, 64, 128)
    assert pallas_mla.kernel_legal(128, 2, 128, 64, 128)
    for t, heads, nope, rope, v in ((4096, 31, 128, 64, 128),
                                    (4096, 32, 128, 64, 192),
                                    (4096, 32, 64, 64, 128),
                                    (4096, 32, 128, 32, 128),
                                    (4100, 32, 128, 64, 128)):
        assert not pallas_mla.kernel_legal(t, heads, nope, rope, v)


def test_interleaved_pairs_are_the_half_split_on_permuted_columns():
    """``rope_interleave``: columns (2i, 2i + 1) turn together, as
    published.  The units turn (i, i + r/2) together.  Taking the
    rotary columns of W_q and W_kv↓ in the order ``pairs`` makes the
    one the other — applied to q_rope and k_r alike, so every score is
    the same."""
    ref = reference()
    rng = np.random.default_rng(1)
    b, t, h, rot, theta = 2, 32, 3, 64, 6e6
    q = rng.normal(size=(b, t, h, rot)).astype(np.float32)
    k = rng.normal(size=(b, t, 1, rot)).astype(np.float32)
    order = ref.pairs(rot)
    assert sorted(order) == list(range(rot)) and list(order[:4]) \
        == [0, 32, 1, 33]
    cos, sin = attention.rope_tables(np, t, rot, theta)
    ours_q = attention.apply_rope(np, q, cos, sin)
    ours_k = attention.apply_rope(np, k, cos, sin)
    theirs_q = np.asarray(ref.rope_interleaved(q[..., order], theta))
    theirs_k = np.asarray(ref.rope_interleaved(k[..., order], theta))
    ours = np.einsum("bqhd,bkd->bhqk", ours_q, ours_k[:, :, 0])
    theirs = np.einsum("bqhd,bkd->bhqk", theirs_q, theirs_k[:, :, 0])
    np.testing.assert_allclose(ours, theirs, atol=2e-4)
    # … and it is a rotation of PAIRS as published: column 2i of the
    # permuted key is the unit's column i, turned by the same angle
    np.testing.assert_allclose(theirs_k[..., 0::2], ours_k[..., :32],
                               atol=1e-5)


def test_what_a_latent_layer_refuses():
    wf = DummyWorkflow()
    base = dict(n_heads=2, causal=True, include_bias=False,
                kv_latent=32, qk_nope=128, qk_rope=64, v_head_dim=128,
                rope={"theta": 6e6})
    for extra, said in (({"window": 8}, "window"),
                        ({"n_kv_heads": 1}, "n_kv_heads"),
                        ({"qk_norm": "rms"}, "qk_norm"),
                        ({"include_bias": True}, "include_bias"),
                        ({"causal": False}, "causal"),
                        ({"qk_rope": 63}, "qk_rope")):
        with pytest.raises(ValueError, match=said):
            attention.MultiHeadAttention(wf, **{**base, **extra})


# ----------------------------------------------------------------------
# the router: group-limited, chosen by score + bias
# ----------------------------------------------------------------------
ROUTER = dict(n_experts=16, top_k=3, width=8, norm_topk=True,
              score="sigmoid", routed_scale=2.5, shared_width=8,
              held=(0, 1), select_bias=True, groups=(4, 2),
              pre_norm="rms", residual=True, norm_eps=1e-6)


def _expert_layer(device, bias, x=None, **options):
    if x is None:
        x = np.random.default_rng(0).normal(size=(2, 8, 16)).astype(
            np.float32)
    prng.seed_all(5)
    wf = DummyWorkflow()
    src = DummyUnit(wf, output=Vector(x.copy(), name="x"))
    unit = moe.MoE(wf, **{**ROUTER, **options})
    unit.link_attrs(src, ("input", "output"))
    unit.initialize(device=device)
    unit.select_bias.map_write()
    unit.select_bias.mem[...] = bias
    unit.select_bias.unmap()
    unit.run()
    for vec in (unit.output, unit.last_choice, unit.select_load,
                unit.router_logits):
        vec.map_read()
    return unit


@pytest.mark.parametrize("device", [NumpyDevice, XLADevice],
                         ids=["numpy", "xla"])
def test_the_choice_is_a_plain_top_k_twice(device):
    """Groups by the sum of their 2 largest biased scores, the best 2 of
    4 kept, then the top 3 of score + bias among their 8 experts — the
    reference's ``choose`` on the unit's own logits (no ties: seeded
    normal draws)."""
    ref = reference()
    bias = np.linspace(-0.2, 0.2, 16).astype(np.float32)
    unit = _expert_layer(device(), bias)
    scores = 1.0 / (1.0 + np.exp(-unit.router_logits.mem.reshape(-1, 16)))
    spec = {"groups": [4, 2], "top_k": 3}
    want = ref.choose(scores, bias, spec)
    got = unit.last_choice.mem.reshape(-1, 3)
    np.testing.assert_array_equal(np.sort(got, -1), np.sort(want, -1))
    # the bias and the limit each change the choice here
    assert (np.sort(ref.choose(scores, None, spec), -1)
            != np.sort(want, -1)).any()
    assert (np.sort(ref.choose(scores, bias, {"top_k": 3}), -1)
            != np.sort(want, -1)).any()
    # every one of the 16 experts is counted, held or not
    assert unit.select_load.mem.sum() == 2 * 8 * 3
    np.testing.assert_array_equal(
        unit.select_load.mem,
        np.bincount(got.ravel(), minlength=16).astype(np.float32))
    assert obs_metrics.moe_router(unit.name, "groups_kept").value == 2


def test_the_weights_are_free_of_the_bias():
    """A bias that leaves the choice where it was leaves the OUTPUT
    where it was: the weights come from the scores alone.  (And the two
    backends agree.)"""
    bias = np.linspace(-0.2, 0.2, 16).astype(np.float32)
    one = _expert_layer(XLADevice(), bias)
    other = _expert_layer(XLADevice(), bias * 1.001 + 1e-5)
    np.testing.assert_array_equal(one.last_choice.mem,
                                  other.last_choice.mem)
    np.testing.assert_array_equal(one.output.mem, other.output.mem)
    oracle = _expert_layer(NumpyDevice(), bias)
    assert rel(one.output.mem, oracle.output.mem) < 1e-5


def test_a_router_s_options_are_checked():
    wf = DummyWorkflow()
    for groups in ((3, 2), (4, 5), (16, 2), (4, 0)):
        with pytest.raises(ValueError, match="groups"):
            moe.MoE(wf, **{**ROUTER, "groups": groups})
    with pytest.raises(ValueError, match="groups"):     # 1 × 4 < top 8
        moe.MoE(wf, **{**ROUTER, "top_k": 8, "groups": (4, 1)})


#: embedding → expert layer → norm → head: the smallest table whose
#: step program moves a selection bias
BIAS_TABLE = [
    {"type": "embedding", "->": {"vocab_size": 50, "dim": 16}},
    {"type": "moe", "->": {**ROUTER, "held": [0, 1, 2, 3],
                           "aux_loss_weight": 1e-4, "bias_rate": 1e-3}},
    {"type": "rms_norm", "->": {"eps": 1e-6}},
    {"type": "softmax", "->": {"output_sample_shape": 50,
                               "per_position": True,
                               "include_bias": False}}]


def _bias_workflow(device, steps: int, name: str, snap_dir=None,
                   lr: float = 0.01):
    rng = np.random.default_rng(17)
    ids = rng.integers(0, 50, (steps * 2, 17))
    prng.seed_all(31)
    table = copy.deepcopy(BIAS_TABLE)
    for layer in table:
        layer["<-"] = {"learning_rate": lr, "gradient_moment": 0.9}
    wf = StandardWorkflow(
        name=name,
        loader_factory=lambda w: ArrayLoader(
            w, train_data=ids[:, :-1].astype(np.float32),
            train_labels=ids[:, 1:].astype(np.int32), minibatch_size=2,
            shuffle_limit=0),
        layers=table, decision_config={"max_epochs": 1},
        snapshotter_config=({"directory": snap_dir, "prefix": name}
                            if snap_dir else None))
    wf.initialize(device=device)
    return wf


def _bias_of(wf) -> np.ndarray:
    unit = wf.forwards[1]
    unit.select_bias.map_read()
    return np.array(unit.select_bias.mem)


@pytest.mark.parametrize("device", [NumpyDevice, XLADevice],
                         ids=["numpy", "xla"])
@pytest.mark.parametrize("steps", [1, 3])
def test_the_bias_moves_by_its_rule_once_a_step(device, steps):
    """b_e += γ·sign(mean load − load_e): after one step every entry is
    0 or ±γ by that step's loads; after three it has moved three
    times, never by more than γ a step, and the epoch-end read says
    so."""
    wf = _bias_workflow(device(), steps, f"bias_{steps}_{device.__name__}")
    wf.run()
    unit, bias = wf.forwards[1], _bias_of(wf)
    unit.select_load.map_read()
    load = np.array(unit.select_load.mem)
    assert load.sum() == 2 * 16 * 3          # all 16 experts counted
    if steps == 1:
        np.testing.assert_allclose(
            bias, 1e-3 * np.sign(load.mean() - load), atol=1e-9)
    assert np.abs(bias).max() <= steps * 1e-3 + 1e-9
    assert np.abs(bias).max() > 0
    assert set(np.round(np.abs(bias) / 1e-3).astype(int)) \
        <= set(range(steps + 1))
    assert obs_metrics.moe_router(unit.name, "bias_steps").value == steps
    assert obs_metrics.moe_router(unit.name, "bias_abs_max").value \
        == pytest.approx(np.abs(bias).max())


def test_a_guard_skipped_step_moves_no_bias():
    """The rule is gated by the guard's running flag, like every
    update: a step whose loss is made non-finite leaves b where it
    was."""
    root.common.engine.faults = {"train.nonfinite_loss": {"at": [1]}}
    wf = _bias_workflow(XLADevice(), 1, "bias_skipped")
    before = obs_metrics.step_anomalies(wf.name, "loss").value
    wf.run()
    assert obs_metrics.step_anomalies(wf.name, "loss").value - before == 1
    np.testing.assert_array_equal(_bias_of(wf), np.zeros(16))
    unit = wf.forwards[1]
    assert obs_metrics.moe_router(unit.name, "bias_steps").value == 0


def test_the_bias_is_in_no_gradient_and_outside_the_fingerprint():
    """b enters the choice only: no parameter's gradient depends on it
    beyond the choice, it has no momentum and no accumulator, and the
    SDC fold (what ``_apply_param_xla`` updates) does not know it."""
    wf = _bias_workflow(XLADevice(), 1, "bias_leaf")
    unit, gd_unit = wf.forwards[1], wf.gds[1]
    names = {vec.name for vec in gd_unit.region_vectors()}
    assert unit.select_bias.name in names        # a leaf of the step
    assert not any("select_bias" in name and "acc" in name
                   for name in names)
    assert "select_bias" not in unit.EXPORT_PARAMS
    args = unit.forward_args()
    assert args[-1] is unit.select_bias.devmem
    (_, _), pullback, _ = jax.vjp(unit.xla_forward, *args, has_aux=True)
    y = jnp.ones(unit.output.shape, jnp.float32)
    cotangents = pullback((y, (jnp.float32(1.0), jnp.float32(0.0))))
    assert not np.asarray(cotangents[-1]).any()


def test_the_rule_has_a_phase_of_its_own_in_the_program_s_map():
    """``observe.op_scopes()`` gives the rule's instructions the phase
    ``router_bias`` (alone, or among a fusion's phases): no update, no
    backward, and inside the ONE step program."""
    from znicz_tpu import observe
    wf = _bias_workflow(XLADevice(), 1, "bias_scopes")
    wf.run()
    phases = set()
    for name, program in observe.op_scopes().items():
        assert name.startswith("znicz_step__")     # a STEP program
        for entry in program.values():
            phases.update([entry.get("phase")]
                          + list(entry.get("phases", ())))
    assert "router_bias" in phases


def test_a_snapshot_carries_the_bias(tmp_path):
    wf = _bias_workflow(XLADevice(), 3, "bias_snap")
    wf.run()
    bias = _bias_of(wf)
    assert np.abs(bias).max() > 0
    state = wf.state_dict()
    again = _bias_workflow(XLADevice(), 3, "bias_snap")
    np.testing.assert_array_equal(_bias_of(again), np.zeros(16))
    again.load_state(state)
    np.testing.assert_array_equal(_bias_of(again), bias)


# ----------------------------------------------------------------------
# the share test (model-configs guide, section 4)
# ----------------------------------------------------------------------
def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Over a partition of the 16 experts into shares of 4, 4, 5 and 3,
    what each chip's layer adds for its own experts, with what every
    chip computes alike (the residual, the shared expert) counted once,
    adds up to the uncut reference's output of the layer — under the
    group limit and a bias that moves the choice."""
    from znicz_tpu.workflow import Workflow
    ref = reference()
    rng = np.random.default_rng(5)
    d, width, experts, top_k = 64, 32, 16, 3
    spec = {"n_experts": experts, "top_k": top_k, "width": width,
            "norm_topk": True, "score": "sigmoid", "routed_scale": 2.5,
            "shared_width": 32, "select_bias": True, "groups": [4, 2],
            "pre_norm": "rms", "residual": True,
            "aux_loss_weight": 1e-4, "norm_eps": 1e-6}
    bias = rng.uniform(-0.1, 0.1, experts).astype(np.float32)
    full = {"layer0_weights": rng.normal(0, 0.5, (d, experts)),
            "layer0_gain_norm": rng.uniform(0.7, 1.3, d)}
    for name, shape in (("gate", (experts, d, width)),
                        ("up", (experts, d, width)),
                        ("down", (experts, width, d))):
        full[f"layer0_weights_{name}"] = rng.normal(0, 0.2, shape)
        full[f"layer0_weights_shared_{name}"] = rng.normal(
            0, 0.2, shape[1:])
    full = {k: v.astype(np.float32) for k, v in full.items()}
    x = rng.normal(0, 1, (BATCH, SEQ, d)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        uncut, _, _, chosen = ref.moe_block(x, full, 0, spec, bias=bias)
        alike = ref.moe_block(x, full, 0, spec, chosen, held=[])[0]
    assert np.abs(np.asarray(uncut) - np.asarray(alike)).max() > 0.1
    shares = [[0, 1, 2, 3], [4, 6, 8, 10], [5, 7, 9, 11, 12], [13, 14, 15]]
    total = np.asarray(alike, np.float64)
    for share in shares:
        wf = Workflow(name="share")
        unit = moe.MoE(wf, held=share, **spec)
        unit.input = Vector(x.copy())
        for attr in unit.EXPORT_PARAMS:
            value = full[f"layer0_{attr}"]
            if attr in ("weights_gate", "weights_up", "weights_down"):
                value = value[share]        # this chip's slabs
            getattr(unit, attr).reset(value.copy())
        unit.initialize(device=XLADevice())
        unit.select_bias.map_write()
        unit.select_bias.mem[...] = bias
        unit.select_bias.unmap()
        unit.run()
        unit.output.map_read()
        unit.last_choice.map_read()
        np.testing.assert_array_equal(
            np.sort(unit.last_choice.mem.reshape(-1, top_k), axis=-1),
            np.sort(chosen, axis=-1))     # every chip routes over all 16
        total += np.asarray(unit.output.mem, np.float64) \
            - np.asarray(alike, np.float64)
    np.testing.assert_allclose(total, np.asarray(uncut), atol=1e-4)


# ----------------------------------------------------------------------
# the toy model against the plain reference
# ----------------------------------------------------------------------
#: of the toy cell's seventeen layers: the embedding, the linear block
#: over the dense MLP, one linear block and the latent-attention block
#: over expert layers, the final norm and the head
KEPT = (0, 1, 2, 3, 4, 9, 10, 15, 16)


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


def build(device, table, name="ling_ref", steps: int = 1):
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
        for attr in ("gain_norm", "gain_out", "gain_latent"):
            vec = getattr(unit, attr, None)
            if vec:
                vec.map_invalidate()
                vec.mem[...] = rng.uniform(0.7, 1.3, vec.shape)
        if getattr(unit, "select_bias_on", False):
            unit.select_bias.map_invalidate()
            unit.select_bias.mem[...] = rng.uniform(
                -0.05, 0.05, unit.select_bias.shape)
        if getattr(unit, "kv_latent", None):
            # random projections give near-uniform attention and a
            # latent of unit scale: sharpen the scores and shrink the
            # latent so that the rotary key and the latent's norm each
            # decide something
            h, nope, rope = unit.n_heads, unit.qk_nope, unit.qk_rope
            unit.weights.map_invalidate()
            unit.weights.mem[:, :h * (nope + rope)] *= 3.0
            unit.weights.mem[:, h * (nope + rope):-rope] *= 0.25
            unit.weights.mem[:, -rope:] *= 3.0
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
        "embedding", "gated_delta_net", "gated_mlp", "gated_delta_net",
        "moe", "latent_attention", "moe", "rms_norm", "softmax"]
    real = config(toy=False)["workflow"]["layers"]
    toy = config()["workflow"]["layers"]
    assert [layer["type"] for layer in real] \
        == [layer["type"] for layer in toy]
    for big, small in zip(real, toy):        # the same options, smaller
        assert set(big["->"]) == set(small["->"])
    for unit in wf.forwards:
        if isinstance(unit, delta_net.GatedDeltaNet):
            assert unit.decay == "channel" and unit.lower_bound == -5.0
            assert unit.gate == "sigmoid" and not unit.allow_neg_eigval
            assert unit.weights_ba.shape == (64, 2 * (1 + 16))
            assert unit.decay_bias.shape == (2 * 16,)
            if backend == "xla":
                assert unit._kernels and unit._interpret
        if isinstance(unit, attention.MultiHeadAttention):
            assert (unit.kv_latent, unit.qk_nope, unit.qk_rope,
                    unit.v_head_dim) == (32, 128, 64, 128)
            assert unit.weights.shape == (64, 2 * 192 + 32 + 64)
            assert unit.weights_kv_up.shape == (32, 2 * 256)
            assert obs_metrics.attention_latent(
                unit.name, "qk_rope").value == 64
            if backend == "xla":
                assert unit._flash.runs and unit._flash.interpret
        if isinstance(unit, moe.MoE):
            assert unit.select_bias_on and unit.groups == (4, 2)
            assert unit.held == (0, 1)


def test_the_published_widths_and_the_cut_are_in_the_file():
    file = config(toy=False)
    assert file["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size", "num_nextn_predict_layers"]
    assert file["published"] == {
        "num_hidden_layers": 42, "num_experts": 512,
        "vocab_size": 157184, "num_nextn_predict_layers": 1}
    assert (file["hidden_size"], file["num_attention_heads"],
            file["head_dim"], file["kv_lora_rank"],
            file["qk_nope_head_dim"], file["qk_rope_head_dim"],
            file["v_head_dim"], file["intermediate_size"],
            file["moe_intermediate_size"], file["num_experts_per_tok"],
            file["n_group"], file["topk_group"],
            file["short_conv_kernel_size"], file["kda_lower_bound"],
            file["layer_group_size"], file["routed_scaling_factor"]) \
        == (2560, 32, 128, 512, 128, 64, 128, 6144, 768, 8, 8, 4, 4, -5,
            6, 2.5)
    table = file["workflow"]["layers"]
    assert [layer["type"] for layer in table[1:15]] == [
        "gated_delta_net", "gated_mlp"] + [
        "gated_delta_net", "moe"] * 3 + ["latent_attention", "moe"] + [
        "gated_delta_net", "moe"] * 2
    linear, full, experts = table[1]["->"], table[9]["->"], table[4]["->"]
    assert (linear["n_heads"], linear["key_dim"], linear["value_dim"],
            linear["conv_kernel"], linear["decay"],
            linear["lower_bound"]) == (32, 128, 128, 4, "channel", -5.0)
    assert (full["n_heads"], full["kv_latent"], full["qk_nope"],
            full["qk_rope"], full["v_head_dim"], full["head_gate"],
            full["rope"]["theta"]) == (32, 512, 128, 64, 128, True, 6e6)
    assert (experts["n_experts"], experts["top_k"], experts["width"],
            experts["shared_width"], experts["groups"], experts["held"],
            experts["select_bias"], experts["routed_scale"]) \
        == (512, 8, 768, 768, [8, 4], list(range(8)), True, 2.5)
    assert set(file["reference_tolerance"]) == {
        "embedding", "layers", "router_logits", "router_gap"}
    for key in ("layer_pattern", "kda", "mla", "rope", "weight_layout",
                "router", "aux_loss", "swiglu_limit", "chunk",
                "optimizer", "data", "init", "buffer"):
        assert file["assumed"][key]
    assert "64" in file["deployment"] and "6-stage" in file["deployment"]
    # 884.5 M parameters, as the file's arithmetic says
    d, h = 2560, 32
    kda = 3 * d * 4096 + 4 * 12288 + d * (h + 4096) + h + 4096 \
        + d * 4096 + 128 + 4096 * d + d
    mla = d * (h * 192 + 576) + 512 + 512 * h * 256 + d * h \
        + 4096 * d + d
    dense = 3 * d * 6144 + d
    expert_layer = 8 * 3 * d * 768 + 3 * d * 768 + d * 512 + d
    total = 6 * kda + mla + dense + 6 * expert_layer \
        + 2 * 19648 * d + d
    assert total == pytest.approx(884.5e6, rel=2e-3)


def test_layer_outputs_and_probabilities(one_step):
    """f32 on both sides, the program in chunks and tiles, the
    reference token by token with K assembled in full, choosing its own
    experts: what is left is the order of summation."""
    wf, table, before, bias, x, y, _ = one_step
    ref = reference()
    outs, router = ref.run(before, table, x, bias=bias)
    assert len(outs) == len(wf.forwards) == len(KEPT)
    for i, (unit, want) in enumerate(zip(wf.forwards, outs)):
        unit.output.map_read()
        assert rel(unit.output.mem, want) < 1e-4, (i, table[i]["type"])
        if table[i]["type"] == "moe":      # the same experts, by itself
            unit.last_choice.map_read()
            np.testing.assert_array_equal(
                np.sort(unit.last_choice.mem.reshape(-1, 3), -1),
                np.sort(router["chosen"][i], -1))


def test_loss_and_every_gradient(one_step):
    """The step ran plain SGD at lr 1, so parameter − parameter after
    IS the system's gradient of the loss: compared with the reference's
    ``value_and_grad`` (through the token-by-token scan) for every
    tensor, 1e-3 of each gradient's largest entry."""
    wf, table, before, bias, x, y, _ = one_step
    value, grads = reference().loss_and_grads(before, table, x, y,
                                              bias=bias)
    after = params_of(wf)
    # embedding, 2 × 9 (linear mixer), 4 (MLP), 2 × 8 (expert layer),
    # 6 (latent attention), final gain, head
    assert set(grads) == set(before)
    assert len(before) == 1 + 2 * 9 + 4 + 2 * 8 + 6 + 2
    for name, want in grads.items():
        got = before[name] - after[name]
        scale = np.abs(want).max()
        assert scale > 0, name
        assert np.abs(got - want).max() <= 1e-3 * scale, (
            name, np.abs(got - want).max() / scale)
    assert wf.decision.epoch_loss[TRAIN] == pytest.approx(value, rel=1e-3)
    # the bias moved by its rule, and is no parameter of the loss
    for i, unit in enumerate(wf.forwards):
        if getattr(unit, "select_bias_on", False):
            for vec in (unit.select_bias, unit.select_load):
                vec.map_read()
            load = unit.select_load.mem
            np.testing.assert_allclose(
                unit.select_bias.mem,
                bias[i] + 1e-3 * np.sign(load.mean() - load), atol=1e-8)


CONTROLS = ["float8", "decay_mean_over_channels", "no_output_gate",
            "no_shared_rotary_key", "no_latent_norm", "no_head_gate",
            "no_routed_scaling"]


def _worst(wf, outs) -> float:
    worst = 0.0
    for unit, want in zip(wf.forwards[1:], outs[1:]):
        unit.output.map_read()
        worst = max(worst, rel(unit.output.mem, want))
    return worst


@pytest.mark.parametrize("what", CONTROLS)
def test_a_left_out_term_fails_the_stated_tolerance(one_step, what):
    """The reference made wrong in one stated way differs from the
    (right) system by more than the limit the CELL states."""
    wf, table, before, bias, x, y, _ = one_step
    limit = config(toy=False)["reference_tolerance"]["layers"]
    ref = reference()
    # five are the cell's controls; the latent layer's two the one
    # limit cannot refuse at the cell's widths are its READINGS there,
    # and are held here, at the toy's
    listed = {name: how for name, *how in controls.controls(ref, table)
              + controls.readings(ref, table)[:2]}
    assert set(CONTROLS) == set(listed)
    outs = controls.spoiled(ref, *listed[what]).forward(
        before, table, x, bias=bias)
    assert _worst(wf, outs) > limit, (what, _worst(wf, outs))


@pytest.mark.parametrize("what,edit", [
    ("the bias in the selection", {"select_bias": False}),
    ("the group limit", {"groups": None})])
def test_a_choice_made_without_a_term_fails_the_stated_tolerance(
        one_step, what, edit):
    """The driver hands the system's choice to the reference, so these
    two are held HERE: the reference choosing for itself without the
    term picks other experts and its layers differ."""
    wf, table, before, bias, x, y, _ = one_step
    limit = config(toy=False)["reference_tolerance"]["layers"]
    wrong = copy.deepcopy(table)
    for layer in wrong:
        if layer["type"] == "moe":
            layer["->"].update(edit)
    outs = reference().forward(before, wrong, x, bias=bias)
    assert _worst(wf, outs) > limit, (what, _worst(wf, outs))


# ----------------------------------------------------------------------
# the other drivers, export and serving: correct, or refusing by name
# ----------------------------------------------------------------------
def _trained(drive, name, grad_accum=None):
    """The table's parameters after one epoch of four steps through
    ``drive``, f32, plain XLA (no kernels)."""
    reset_root()
    if grad_accum:          # the micro-accumulators are laid out at
        root.common.engine.grad_accum = grad_accum     # ``initialize``
    wf, _, _ = build(XLADevice(), layers(0.05, 0.0), name=name, steps=4)
    biased = [(i, unit) for i, unit in enumerate(wf.forwards)
              if getattr(unit, "select_bias_on", False)]
    start = {}
    for i, unit in biased:
        unit.select_bias.map_read()
        start[i] = np.array(unit.select_bias.mem)
    drive(wf)
    out = params_of(wf)
    for i, unit in biased:      # how far the rule moved the bias
        unit.select_bias.map_read()
        out[f"layer{i}_select_bias"] = unit.select_bias.mem - start[i]
    return out


def test_run_chunked_trains_the_table_as_run_does():
    plain = _trained(lambda wf: wf.run(), "ling_run")
    chunked = _trained(lambda wf: wf.run_chunked(2), "ling_chunked")
    assert set(plain) == set(chunked)
    for name, want in plain.items():
        assert rel(chunked[name], want) < 1e-5, name


@pytest.mark.parametrize("driver", ["run_accumulated", "run_pipelined"])
def test_the_microbatched_drivers_run_the_table_or_refuse_by_name(
        driver):
    """Two microbatches a step: the bias's rule runs with the step that
    applies.  Either the driver trains (finite parameters, a bias that
    moved at most once per APPLIED step) or it refuses by name."""
    def drive(wf):
        if driver == "run_accumulated":
            wf.run_accumulated()
        else:
            wf.run_pipelined(2)
    try:
        got = _trained(drive, f"ling_{driver}", grad_accum=2)
    except NotImplementedError as exc:
        assert any(word in str(exc) for word in (
            "select_bias", "MoE", "moe", "GatedDeltaNet",
            "gated_delta_net", "kv_latent")), exc
        return
    for name, value in got.items():
        assert np.isfinite(value).all(), name
        if name.endswith("select_bias"):
            assert 0 < np.abs(value).max() <= 2 * 1e-3 + 1e-9


@pytest.mark.parametrize("what", ["export_forward", "DecodeModel"])
def test_serving_refuses_the_table_by_name(what, tmp_path):
    reset_root()
    wf, _, _ = build(XLADevice(), layers(0.05, 0.0), name=f"ling_{what}")
    from znicz_tpu.export import refuse_unserved
    with pytest.raises(NotImplementedError) as said:
        if what == "export_forward":
            wf.export_forward(str(tmp_path / "bundle.npz"))
        else:
            refuse_unserved(wf.forwards, "DecodeModel")
    assert "decay=channel" in str(said.value)      # the first layer
    # … and each new option has its own refusal
    for kinds, words in (
            ((attention.MultiHeadAttention,), ("kv_latent", "latent page")),
            ((moe.MoE,), ("select_bias", "groups"))):
        units = [u for u in wf.forwards if type(u) in kinds]
        with pytest.raises(NotImplementedError) as said:
            refuse_unserved(units, what)
        for word in words:
            assert word in str(said.value)

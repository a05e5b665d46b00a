"""The gated-delta-rule unit (``ops/delta_net.py``, PR 31), each piece
against something plain:

1. the convolution's first positions; a sequence that is not whole
   chunks is padded (and says so); the XLA path (chunked, kernels
   interpreted) against the numpy oracle's token loop, every parameter
   moved through the base's update rule; the gauge; the decay's init;
2. serving refuses the new unit by name.

(The chunked rule and its kernels against the recurrence:
``tests/test_pallas_delta.py``; ``post_norm`` on the units that had
``pre_norm`` only: ``tests/test_post_norm.py``.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_laguna_reference import _params, _two_steps
from znicz_tpu.backends import NumpyDevice, XLADevice
from znicz_tpu.dummy import DummyUnit, DummyWorkflow
from znicz_tpu.memory import Vector
from znicz_tpu.models.standard_workflow import layer_type
from znicz_tpu.observe import metrics as obs_metrics
from znicz_tpu.ops import attention, delta_net, moe
from znicz_tpu.utils import prng
from znicz_tpu.utils.config import root


# ======================================================================
# 1. the unit
# ======================================================================
def test_convolution_sees_zeros_before_the_sequence():
    rng = np.random.default_rng(1)
    u = rng.normal(size=(2, 6, 5)).astype(np.float32)
    taps = rng.normal(size=(5, 4)).astype(np.float32)
    for xp in (np, jnp):
        out = np.asarray(delta_net.causal_conv(xp, xp.asarray(u),
                                               xp.asarray(taps)))
        # position 0 meets only the last tap, position 2 the last three
        np.testing.assert_allclose(out[:, 0], u[:, 0] * taps[:, 3],
                                   rtol=1e-6)
        np.testing.assert_allclose(
            out[:, 1], u[:, 0] * taps[:, 2] + u[:, 1] * taps[:, 3],
            rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(
            out[:, 2], u[:, 0] * taps[:, 1] + u[:, 1] * taps[:, 2]
            + u[:, 2] * taps[:, 3], rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(
            out[:, 5], sum(u[:, 2 + j] * taps[:, j] for j in range(4)),
            rtol=1e-5, atol=1e-6)


D = 32
DELTA = dict(n_heads=3, key_dim=8, value_dim=12, conv_kernel=4,
             allow_neg_eigval=True, residual=True, norm_eps=1e-6,
             chunk=16)


def _build(device, x, make, pair, params=None):
    prng.seed_all(5)
    wf = DummyWorkflow()
    src = DummyUnit(wf, output=Vector(np.asarray(x), name="x"))
    fwd = make(wf)
    fwd.link_attrs(src, ("input", "output"))
    fwd.initialize(device=device)
    if isinstance(device, XLADevice):
        # one compiled program, as inside a JitRegion; run op by op the
        # same forward and its pullback are ~400 small compilations
        fwd.xla_forward = jax.jit(fwd.xla_forward)
    rng = np.random.default_rng(3)
    for attr in ("gain_norm", "gain_out", "gain_q", "gain_k"):
        vec = getattr(fwd, attr, None)     # gains of one hide their path
        if vec:
            vec.reset(rng.uniform(0.5, 1.5, vec.shape).astype(np.float32))
            vec.initialize(device)
    for attr, arr in (params or {}).items():
        vec = getattr(fwd, attr)
        vec.reset(np.array(arr, np.float32))
        vec.initialize(device)
    gd_u = pair(wf, learning_rate=0.05, gradient_moment=0.9)
    gd_u.forward_unit = fwd
    gd_u.link_attrs(fwd, "input", "output", "weights", "bias")
    gd_u.err_output = Vector(np.zeros(np.shape(x), np.float32),
                             name="err")
    gd_u.initialize(device=device)
    return fwd, gd_u


def _agree(make, pair, t=32, rtol=2e-3, atol=1e-4):
    rng = np.random.default_rng(0)
    x = rng.normal(0, 1.0, (2, t, D)).astype(np.float32)
    err = rng.normal(0, 0.1, (2, t, D)).astype(np.float32)
    np_f, np_g = _build(NumpyDevice(), x, make, pair)
    drawn = _params(np_f)
    xla_f, xla_g = _build(XLADevice(), x, make, pair, params=drawn)
    want, got = _two_steps(np_f, np_g, err), _two_steps(xla_f, xla_g, err)
    for key, value in want.items():
        np.testing.assert_allclose(got[key], value, rtol=rtol, atol=atol,
                                   err_msg=key)
    for attr in drawn:            # and every parameter MOVED
        assert np.abs(want[attr] - drawn[attr]).max() > 0, attr
    return xla_f, drawn


def _forward_of(make, x):
    fwd, _ = _build(XLADevice(), x, make, lambda wf, **kw: _NoGD())
    return fwd, fwd.xla_forward(*fwd.forward_args())


class _NoGD:
    """Stands where a GD pair would, for forward-only builds."""
    forward_unit = err_output = None

    def link_attrs(self, *a, **kw):
        pass

    def initialize(self, **kw):
        pass


@pytest.fixture
def interpreted_kernels():
    from znicz_tpu.utils.config import reset_root
    reset_root()
    root.common.engine.pallas_interpret = True
    root.common.engine.delta_scan_kernel = True
    root.common.engine.flash_attention = True
    yield
    reset_root()


@pytest.mark.parametrize("norm", ["post_norm", "pre_norm", None])
def test_unit_against_its_token_loop(norm, interpreted_kernels):
    """XLA (chunked, kernels interpreted) against the numpy oracle (the
    recurrence as a loop over tokens): output, err_input and every
    parameter after two momentum steps."""
    assert layer_type("gated_delta_net") is delta_net.GatedDeltaNet
    options = dict(DELTA, **({norm: "rms"} if norm else {}))
    unit, drawn = _agree(
        lambda wf: delta_net.GatedDeltaNet(wf, **options),
        delta_net.GDGatedDeltaNet)
    expect = set(delta_net.GatedDeltaNet.EXPORT_PARAMS)
    if not norm:
        expect.discard("gain_norm")
    assert set(drawn) == expect
    assert unit._kernels and unit._interpret
    h, dk, dv = 3, 8, 12
    assert unit.weights.shape == (D, h * (2 * dk + dv))
    assert unit.weights_conv.shape == (h * (2 * dk + dv), 4)
    assert unit.weights_gate.shape == (D, h * dv)
    assert unit.weights_ba.shape == (D, 2 * h)
    assert unit.decay_log.shape == unit.decay_bias.shape == (h,)
    assert unit.gain_out.shape == (dv,)
    assert unit.weights_out.shape == (h * dv, D)


def test_a_sequence_that_is_not_whole_chunks_is_padded():
    """T 40 over chunks of 16: eight positions that write nothing and
    decay nothing are appended; the 40 real rows are the recurrence's
    (the numpy oracle's token loop), and so is the input's gradient."""
    x = np.random.default_rng(0).normal(size=(2, 40, D)).astype(
        np.float32)
    make = lambda wf: delta_net.GatedDeltaNet(             # noqa: E731
        wf, **DELTA, post_norm="rms")
    unit, got = _forward_of(make, x)
    assert not unit._kernels               # off a TPU: the plain scan
    assert obs_metrics.delta_scan(unit.name, "chunks").value == 3
    assert obs_metrics.delta_scan(unit.name, "path").value == 0
    assert obs_metrics.delta_scan(unit.name, "chunk_path").value == 0
    for attr in unit.EXPORT_PARAMS:
        getattr(unit, attr).map_read()
    want = unit._forward_np(x)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=1e-4)
    whole, _ = _forward_of(make, np.concatenate(
        [x, np.zeros((2, 8, D), np.float32)], axis=1))
    for attr in unit.EXPORT_PARAMS:
        vec = getattr(whole, attr)
        vec.reset(np.array(getattr(unit, attr).mem))
        vec.initialize(whole.device)
    np.testing.assert_allclose(
        got, whole.xla_forward(*whole.forward_args())[:, :40],
        rtol=1e-4, atol=1e-5)


def test_what_the_unit_reports(interpreted_kernels):
    x = np.zeros((2, 32, D), np.float32)
    unit, _ = _build(XLADevice(), x,
                     lambda wf: delta_net.GatedDeltaNet(wf, **DELTA),
                     delta_net.GDGatedDeltaNet)
    stats = {stat: obs_metrics.delta_scan(unit.name, stat).value
             for stat in ("chunk", "chunks", "key_dim", "value_dim",
                          "padded_share", "state_mb", "path",
                          "chunk_path")}
    assert stats == {
        "chunk": 16, "chunks": 2, "key_dim": 8, "value_dim": 12,
        "padded_share": pytest.approx(128 * 128 / (8 * 12)),
        "state_mb": pytest.approx(2 * 3 * 2 * 8 * 12 * 4 / 1e6),
        "path": 1, "chunk_path": 1}
    scrape = obs_metrics.REGISTRY.to_prometheus()
    assert "znicz_delta_scan{" in scrape and 'stat="chunk_path"' in scrape
    assert "znicz_gdr_chunk_fwd" in obs_metrics.delta_scan.__doc__ \
        or "znicz_gdr_chunk_*" in obs_metrics.delta_scan.__doc__


def test_decay_parameters_are_drawn_as_the_paper_s_layer_draws_them():
    unit, _ = _build(NumpyDevice(), np.zeros((1, 16, D), np.float32),
                     lambda wf: delta_net.GatedDeltaNet(
                         wf, **dict(DELTA, n_heads=64, key_dim=2,
                                    value_dim=2)),
                     delta_net.GDGatedDeltaNet)
    rate = np.exp(unit.decay_log.mem)
    step = np.logaddexp(unit.decay_bias.mem, 0.0)       # softplus(b)
    assert 0 < rate.min() and rate.max() <= 16
    assert 1e-3 * 0.99 <= step.min() and step.max() <= 0.1 * 1.01
    assert rate.std() > 1 and np.log(step).std() > 0.5


def test_both_norm_placements_at_once_are_refused():
    """… by the linear mixer, whose block has ONE norm; attention and
    the gated MLP take both since PR 35 (the sandwich norm), with a
    gain each."""
    x = np.zeros((1, 16, D), np.float32)

    def made(make):
        wf = DummyWorkflow()
        unit = make(wf)
        unit.link_attrs(DummyUnit(wf, output=Vector(x, name="x")),
                        ("input", "output"))
        return unit

    with pytest.raises(ValueError, match="pre_norm and post_norm"):
        made(lambda wf: delta_net.GatedDeltaNet(
            wf, **DELTA, pre_norm="rms", post_norm="rms")).initialize(
                device=NumpyDevice())
    for make in (
            lambda wf: attention.MultiHeadAttention(
                wf, n_heads=4, pre_norm="rms", post_norm="rms"),
            lambda wf: moe.GatedMLP(wf, width=8, pre_norm="rms",
                                    post_norm="rms")):
        unit = made(make)
        unit.initialize(device=NumpyDevice())
        assert unit.gain_norm.shape == unit.gain_post.shape == (D,)
    with pytest.raises(ValueError, match="post_norm must be"):
        moe.GatedMLP(DummyWorkflow(), width=8, post_norm="layer")
    with pytest.raises(ValueError, match="post_norm must be"):
        attention.MultiHeadAttention(DummyWorkflow(), n_heads=2,
                                     post_norm="layer")


# ======================================================================
# 2. serving refuses what it cannot run, by name
# ======================================================================
def test_serving_refuses_the_gated_delta_net_by_name():
    import inspect
    from znicz_tpu.export import refuse_unserved
    from znicz_tpu.serving import decode
    unit = delta_net.GatedDeltaNet(DummyWorkflow(), n_heads=2, key_dim=4,
                                   value_dim=8)
    with pytest.raises(NotImplementedError,
                       match=r"layer 0 is a gated-delta-rule .*"
                             r"\(gated_delta_net\).*ROADMAP R6"):
        refuse_unserved([unit], "DecodeModel")
    assert "refuse_unserved(units" in inspect.getsource(
        decode.DecodeModel._build_plan)

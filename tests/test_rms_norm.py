"""RMS normalization: oracle↔XLA agreement forward and backward, the
analytic backward against finite differences, the layer-table entry."""

import numpy as np

from znicz_tpu.backends import NumpyDevice, XLADevice
from znicz_tpu.dummy import DummyUnit, DummyWorkflow
from znicz_tpu.memory import Vector
from znicz_tpu.ops import rms_norm
from znicz_tpu.models.standard_workflow import layer_type

B, T, D = 3, 5, 16


def build(device, x, gain, err):
    wf = DummyWorkflow()
    src = DummyUnit(wf, output=Vector(np.asarray(x), name="x"))
    fwd = rms_norm.RMSNorm(wf, eps=1e-5)
    fwd.link_attrs(src, ("input", "output"))
    fwd.initialize(device=device)
    fwd.weights.reset(gain.copy())
    fwd.weights.initialize(device)
    gd = rms_norm.GDRMSNorm(wf, learning_rate=0.1, gradient_moment=0.9)
    gd.forward_unit = fwd
    gd.link_attrs(fwd, "input", "output", "weights", "bias")
    gd.err_output = Vector(err.copy(), name="err")
    gd.initialize(device=device)
    return fwd, gd


def _data(seed=0):
    rng = np.random.default_rng(seed)
    return (rng.normal(0.3, 1.5, (B, T, D)).astype(np.float32),
            rng.uniform(0.5, 1.5, D).astype(np.float32),
            rng.normal(0, 0.2, (B, T, D)).astype(np.float32))


def test_registered_as_layer_type():
    assert layer_type("rms_norm") is rms_norm.RMSNorm


def test_forward_is_the_definition():
    x, gain, err = _data()
    fwd, _ = build(NumpyDevice(), x, gain, err)
    fwd.run()
    want = gain * x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(fwd.output.mem, want, rtol=1e-6)
    assert not fwd.bias          # gain only


def test_oracle_vs_xla_forward_and_gd():
    x, gain, err = _data(1)
    got = {}
    for device in (NumpyDevice(), XLADevice()):
        fwd, gd = build(device, x, gain, err)
        for _ in range(2):       # the second step rides the momentum
            fwd.run()
            gd.run()
        for vec in (fwd.output, fwd.weights, gd.err_input):
            vec.map_read()
        got[type(device).__name__] = [
            np.array(v.mem, np.float32)
            for v in (fwd.output, fwd.weights, gd.err_input)]
    for a, b in zip(got["NumpyDevice"], got["XLADevice"]):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)


def test_backward_matches_finite_differences():
    rng = np.random.default_rng(2)
    x = rng.normal(0, 1, (2, 6)).astype(np.float64)
    gain = rng.uniform(0.5, 1.5, 6)
    cot = rng.normal(0, 1, (2, 6))

    def loss(x_, g_):
        return float((rms_norm.rms_norm(np, x_, g_, 1e-5) * cot).sum())

    dx, dgain = rms_norm.rms_norm_backward(np, x, gain, 1e-5, cot)
    eps = 1e-6
    for idx in np.ndindex(*x.shape):
        hi, lo = x.copy(), x.copy()
        hi[idx] += eps
        lo[idx] -= eps
        fd = (loss(hi, gain) - loss(lo, gain)) / (2 * eps)
        np.testing.assert_allclose(dx[idx], fd, rtol=1e-5, atol=1e-7)
    for i in range(6):
        hi, lo = gain.copy(), gain.copy()
        hi[i] += eps
        lo[i] -= eps
        fd = (loss(x, hi) - loss(x, lo)) / (2 * eps)
        np.testing.assert_allclose(dgain[i], fd, rtol=1e-5, atol=1e-7)

"""Conv backward: numpy col2im oracle vs the XLA vjp path, plus
numeric gradient checks (reference pattern:
``znicz/tests/unit/test_gd_conv.py``)."""

import numpy as np
import pytest

from znicz_tpu.backends import NumpyDevice, XLADevice
from znicz_tpu.dummy import DummyUnit, DummyWorkflow
from znicz_tpu.memory import Vector
from znicz_tpu.ops import conv, gd_conv

PAIRS = [
    (conv.Conv, gd_conv.GradientDescentConv),
    (conv.ConvTanh, gd_conv.GDTanhConv),
    (conv.ConvRELU, gd_conv.GDRELUConv),
    (conv.ConvStrictRELU, gd_conv.GDStrictRELUConv),
]

RNG = np.random.default_rng(31)
X = RNG.normal(size=(3, 6, 6, 2)).astype(np.float32)
LR = 0.05
GEOM = dict(n_kernels=4, kx=3, ky=3, sliding=(2, 2), padding=1)


def build_pair(fwd_cls, gd_cls, device, err):
    wf = DummyWorkflow()
    src = DummyUnit(wf, output=Vector(X.copy(), name="x"))
    fwd = fwd_cls(wf, **GEOM)
    fwd.link_attrs(src, ("input", "output"))
    fwd.initialize(device=device)
    err_src = DummyUnit(wf, err=Vector(err.copy(), name="err"))
    bwd = gd_cls(wf, learning_rate=LR)
    bwd.forward_unit = fwd
    bwd.link_attrs(fwd, "input", "output", "weights", "bias")
    bwd.link_attrs(err_src, ("err_output", "err"))
    bwd.initialize(device=device)
    return fwd, bwd


def make_err(fwd):
    return np.random.default_rng(9).normal(
        size=fwd.output.shape).astype(np.float32)


@pytest.mark.parametrize("fwd_cls,gd_cls", PAIRS)
def test_numpy_xla_agreement(fwd_cls, gd_cls):
    probe = build_pair(fwd_cls, gd_cls, NumpyDevice(), np.zeros(1))[0]
    err = make_err(probe)
    results = {}
    for name, device in (("np", NumpyDevice()), ("xla", XLADevice())):
        fwd, bwd = build_pair(fwd_cls, gd_cls, device, err)
        if name == "xla":
            fwd.weights.reset(results["w0"])
            fwd.weights.initialize(device)
            fwd.bias.reset(results["b0"])
            fwd.bias.initialize(device)
        else:
            results["w0"] = fwd.weights.mem.copy()
            results["b0"] = fwd.bias.mem.copy()
        fwd.run()
        bwd.run()
        for vec in (bwd.err_input, bwd.weights, bwd.bias):
            vec.map_read()
        results[f"{name}_err_input"] = bwd.err_input.mem.copy()
        results[f"{name}_w"] = bwd.weights.mem.copy()
        results[f"{name}_b"] = bwd.bias.mem.copy()
    for key in ("err_input", "w", "b"):
        np.testing.assert_allclose(results[f"np_{key}"],
                                   results[f"xla_{key}"],
                                   rtol=1e-3, atol=1e-4, err_msg=key)


def test_numeric_gradient_linear_conv():
    device = NumpyDevice()
    probe, _ = build_pair(conv.Conv, gd_conv.GradientDescentConv,
                          device, np.zeros(1))
    err = make_err(probe)
    fwd, bwd = build_pair(conv.Conv, gd_conv.GradientDescentConv,
                          device, err)
    w0 = fwd.weights.mem.copy()
    b0 = fwd.bias.mem.copy()
    fwd.run()
    bwd.run()
    grad_w = (w0 - bwd.weights.mem) / LR
    err_input = bwd.err_input.mem.copy()

    def loss(w, b, x):
        wf = DummyWorkflow()
        src = DummyUnit(wf, output=Vector(x, name="x"))
        f = conv.Conv(wf, **GEOM)
        f.link_attrs(src, ("input", "output"))
        f.initialize(device=device)
        f.weights.reset(w.copy())
        f.bias.reset(b.copy())
        f.run()
        return float(np.sum(err * f.output.mem))

    eps = 1e-2
    rng = np.random.default_rng(4)
    flat = w0.reshape(-1)
    for _ in range(4):
        k = rng.integers(flat.size)
        wp, wm = flat.copy(), flat.copy()
        wp[k] += eps
        wm[k] -= eps
        numeric = (loss(wp.reshape(w0.shape), b0, X)
                   - loss(wm.reshape(w0.shape), b0, X)) / (2 * eps)
        np.testing.assert_allclose(grad_w.reshape(-1)[k], numeric,
                                   rtol=2e-2, atol=1e-2)
    xflat = X.reshape(-1)
    for _ in range(4):
        k = rng.integers(xflat.size)
        xp_, xm_ = xflat.copy(), xflat.copy()
        xp_[k] += eps
        xm_[k] -= eps
        numeric = (loss(w0, b0, xp_.reshape(X.shape))
                   - loss(w0, b0, xm_.reshape(X.shape))) / (2 * eps)
        np.testing.assert_allclose(err_input.reshape(-1)[k], numeric,
                                   rtol=2e-2, atol=1e-2)

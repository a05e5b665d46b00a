"""LRN fwd+bwd: analytic numpy oracle vs XLA vjp path (reference
pattern: ``znicz/tests/unit/test_normalization.py``)."""

import numpy as np

from znicz_tpu.backends import NumpyDevice, XLADevice
from znicz_tpu.dummy import DummyUnit, DummyWorkflow
from znicz_tpu.memory import Vector
from znicz_tpu.ops import normalization

RNG = np.random.default_rng(61)
X = RNG.normal(size=(2, 4, 4, 8)).astype(np.float32)
ERR = RNG.normal(size=(2, 4, 4, 8)).astype(np.float32)


def build_pair(device, **kw):
    wf = DummyWorkflow()
    src = DummyUnit(wf, output=Vector(X.copy(), name="x"))
    fwd = normalization.LRNormalizerForward(wf, **kw)
    fwd.link_attrs(src, ("input", "output"))
    fwd.initialize(device=device)
    err_src = DummyUnit(wf, err=Vector(ERR.copy(), name="err"))
    bwd = normalization.LRNormalizerBackward(wf)
    bwd.forward_unit = fwd
    bwd.link_attrs(fwd, "input", "output")
    bwd.link_attrs(err_src, ("err_output", "err"))
    bwd.initialize(device=device)
    return fwd, bwd


import pytest


@pytest.mark.parametrize("n", [3, 4, 5])  # even n: asymmetric window,
def test_backend_agreement(n):           # regression for the adjoint
    outs = {}
    for name, device in (("np", NumpyDevice()), ("xla", XLADevice())):
        fwd, bwd = build_pair(device, alpha=1e-3, beta=0.75, k=2.0, n=n)
        fwd.run()
        bwd.run()
        fwd.output.map_read()
        bwd.err_input.map_read()
        outs[f"{name}_y"] = fwd.output.mem.copy()
        outs[f"{name}_e"] = bwd.err_input.mem.copy()
    np.testing.assert_allclose(outs["np_y"], outs["xla_y"],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(outs["np_e"], outs["xla_e"],
                               rtol=1e-4, atol=1e-5)


def test_numeric_gradient():
    device = NumpyDevice()
    fwd, bwd = build_pair(device, alpha=1e-2, beta=0.75, k=2.0, n=3)
    fwd.run()
    bwd.run()
    eps = 1e-3

    def loss(x):
        wf = DummyWorkflow()
        src = DummyUnit(wf, output=Vector(x, name="x"))
        f = normalization.LRNormalizerForward(wf, alpha=1e-2, beta=0.75,
                                              k=2.0, n=3)
        f.link_attrs(src, ("input", "output"))
        f.initialize(device=device)
        f.run()
        return float(np.sum(ERR * f.output.mem))

    rng = np.random.default_rng(3)
    flat = X.reshape(-1)
    for _ in range(6):
        k = rng.integers(flat.size)
        xp_, xm_ = flat.copy(), flat.copy()
        xp_[k] += eps
        xm_[k] -= eps
        numeric = (loss(xp_.reshape(X.shape))
                   - loss(xm_.reshape(X.shape))) / (2 * eps)
        np.testing.assert_allclose(bwd.err_input.mem.reshape(-1)[k],
                                   numeric, rtol=1e-2, atol=1e-4)


def test_normalization_shrinks_large_activations():
    fwd, _ = build_pair(NumpyDevice(), alpha=1.0, beta=0.75, k=1.0, n=5)
    fwd.run()
    assert np.all(np.abs(fwd.output.mem) <= np.abs(X) + 1e-6)

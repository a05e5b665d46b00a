"""Conv forward correctness: numpy im2col oracle vs XLA native conv
(reference pattern: ``znicz/tests/unit/test_conv.py``)."""

import numpy as np
import pytest

from znicz_tpu.backends import NumpyDevice, XLADevice
from znicz_tpu.dummy import DummyUnit, DummyWorkflow
from znicz_tpu.memory import Vector
from znicz_tpu.ops import conv

RNG = np.random.default_rng(21)
X = RNG.normal(size=(4, 8, 8, 3)).astype(np.float32)


def build(cls, device, x, **kwargs):
    wf = DummyWorkflow()
    src = DummyUnit(wf, output=Vector(np.asarray(x), name="x"))
    unit = cls(wf, **kwargs)
    unit.link_attrs(src, ("input", "output"))
    unit.initialize(device=device)
    return unit


def run_both(cls, x, **kwargs):
    np_u = build(cls, NumpyDevice(), x, **kwargs)
    xla_u = build(cls, XLADevice(), x, **kwargs)
    xla_u.weights.reset(np_u.weights.mem.copy())
    xla_u.weights.initialize(xla_u.device)
    if xla_u.include_bias:
        xla_u.bias.reset(np_u.bias.mem.copy())
        xla_u.bias.initialize(xla_u.device)
    np_u.run()
    xla_u.run()
    np_u.output.map_read()
    xla_u.output.map_read()
    return np_u, xla_u


@pytest.mark.parametrize("cls", [conv.Conv, conv.ConvTanh, conv.ConvRELU,
                                 conv.ConvStrictRELU])
def test_numpy_xla_agreement(cls):
    np_u, xla_u = run_both(cls, X, n_kernels=5, kx=3, ky=3)
    np.testing.assert_allclose(np_u.output.mem, xla_u.output.mem,
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("sliding,padding", [
    ((1, 1), 0), ((2, 2), 0), ((1, 1), 1), ((2, 2), (1, 2)),
    ((1, 2), (1, 0, 2, 1)), ((3, 3), 2)])
def test_geometry_variants(sliding, padding):
    np_u, xla_u = run_both(conv.Conv, X, n_kernels=4, kx=3, ky=2,
                           sliding=sliding, padding=padding)
    np.testing.assert_allclose(np_u.output.mem, xla_u.output.mem,
                               rtol=1e-4, atol=1e-5)


def test_golden_identity_kernel():
    """1×1 identity kernel reproduces the input channel."""
    wf = DummyWorkflow()
    x = RNG.normal(size=(2, 5, 5, 2)).astype(np.float32)
    src = DummyUnit(wf, output=Vector(x, name="x"))
    unit = conv.Conv(wf, n_kernels=2, kx=1, ky=1)
    unit.link_attrs(src, ("input", "output"))
    unit.initialize(device=NumpyDevice())
    unit.weights.reset(np.eye(2, dtype=np.float32).reshape(1, 1, 2, 2))
    unit.bias.reset(np.zeros(2, dtype=np.float32))
    unit.run()
    np.testing.assert_allclose(unit.output.mem, x, rtol=1e-6)


def test_output_shape():
    np_u = build(conv.Conv, NumpyDevice(), X, n_kernels=7, kx=3, ky=3,
                 sliding=(2, 2), padding=1)
    assert np_u.output.shape == (4, 4, 4, 7)
    assert np_u.weights.shape == (3, 3, 3, 7)


def test_non_nhwc_input_rejected():
    wf = DummyWorkflow()
    src = DummyUnit(wf, output=Vector(np.zeros((4, 10), np.float32),
                                      name="x"))
    unit = conv.Conv(wf, n_kernels=2, kx=3, ky=3)
    unit.link_attrs(src, ("input", "output"))
    with pytest.raises(ValueError, match="NHWC"):
        unit.initialize(device=NumpyDevice())

"""Debug-mode checkify: NaN/inf/OOB faults inside a jit region raise
a located error (SURVEY.md §5.2 — the rebuild's equivalent of a debug
sanitizer for in-program faults; the Vector state machine covers the
host side)."""

import numpy as np
import pytest

import jax.numpy as jnp

from znicz_tpu.accelerated_units import AcceleratedUnit, JitRegion
from znicz_tpu.backends import XLADevice
from znicz_tpu.dummy import DummyWorkflow
from znicz_tpu.memory import Vector
from znicz_tpu.utils.config import root


class LogUnit(AcceleratedUnit):
    """log(input) — NaN for negative inputs."""

    def __init__(self, workflow, **kwargs):
        super().__init__(workflow, **kwargs)
        self.input = Vector(name="log.in")
        self.output = Vector(name="log.out")

    def initialize(self, device=None, **kwargs):
        super().initialize(device=device, **kwargs)
        self.output.reset(np.zeros_like(self.input.mem))
        self.init_vectors(self.input, self.output)

    def xla_run(self):
        self.output.devmem = jnp.log(self.input.devmem)


def _make_region(values):
    wf = DummyWorkflow()
    device = XLADevice()
    wf.device = device
    unit = LogUnit(wf)
    unit.input.reset(np.asarray(values, dtype=np.float32))
    unit.initialize(device=device)
    unit.link_from(wf.start_point)
    return unit, JitRegion("dbg", [unit], device)


def test_nan_raises_located_error():
    root.common.engine.debug_checks = True
    unit, region = _make_region([1.0, -1.0])
    with pytest.raises(Exception, match="nan"):
        region.run()


def test_clean_run_passes_with_checks_on():
    root.common.engine.debug_checks = True
    unit, region = _make_region([1.0, 2.0])
    region.run()
    unit.output.map_read()
    np.testing.assert_allclose(unit.output.mem,
                               np.log([1.0, 2.0]), rtol=1e-6)


def test_checks_off_is_silent_default():
    assert root.common.engine.get("debug_checks", False) is False
    unit, region = _make_region([1.0, -1.0])
    region.run()  # no error machinery; NaN flows through
    unit.output.map_read()
    assert np.isnan(unit.output.mem[1])


class DoubleUnit(AcceleratedUnit):
    """Writes ``weights`` on the device; ``weights`` keeps a bf16 cast
    of itself (``Vector.keep_cast``), which the writer has to make
    again — and does only where ``recasts``."""

    def __init__(self, workflow, recasts: bool, **kwargs):
        super().__init__(workflow, **kwargs)
        self.recasts = recasts
        self.weights = Vector(name="double.weights")
        self.copy = Vector(name="double.weights_cast")

    def initialize(self, device=None, **kwargs):
        super().initialize(device=device, **kwargs)
        self.weights.reset(np.arange(1, 9, dtype=np.float32) / 7)
        self.init_vectors(self.weights)
        self.weights.keep_cast(self.copy, jnp.bfloat16)

    def xla_run(self):
        self.weights.devmem = self.weights.devmem * 2
        if self.recasts:
            self.weights.recast()


def _doubling_region(recasts: bool):
    wf = DummyWorkflow()
    device = XLADevice()
    wf.device = device
    unit = DoubleUnit(wf, recasts)
    unit.initialize(device=device)
    unit.link_from(wf.start_point)
    return unit, JitRegion("dbg_cast", [unit], device)


@pytest.mark.parametrize("recasts", [True, False],
                         ids=["recast", "left_stale"])
def test_a_device_write_that_leaves_a_kept_cast_stale_is_named(recasts):
    """After every dispatch under the checks a kept cast is compared
    with the cast of its Vector: a device-side writer that forgot
    ``recast()`` is an error with the Vector's name, not a matmul
    reading last step's weights."""
    root.common.engine.debug_checks = True
    unit, region = _doubling_region(recasts)
    if recasts:
        region.run()
        np.testing.assert_array_equal(
            np.asarray(unit.copy.devmem),
            np.asarray(unit.weights.devmem.astype(jnp.bfloat16)))
    else:
        with pytest.raises(AssertionError, match="double.weights"):
            region.run()


def test_a_stale_cast_is_silent_with_checks_off():
    unit, region = _doubling_region(False)
    region.run()
    assert not np.array_equal(
        np.asarray(unit.copy.devmem),
        np.asarray(unit.weights.devmem.astype(jnp.bfloat16)))

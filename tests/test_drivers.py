"""The one step loop of ``StandardWorkflow`` (PR 27), held to the same
four properties under each driver that walks it — ``run_chunked(4)``,
``run_accumulated(2)`` and ``run_pipelined(2, 2)``:

- the ``max_fires`` valve raises, naming the driver's unit of work;
- ``wf.stop()`` from a step hook ends the loop at the next boundary;
- the decision's epoch side chain fires once per epoch and the LR
  adjuster once per optimizer step (per chunk under ``run_chunked``,
  where it still counts every step);
- a ``train.nonfinite_grad`` fault plan is armed: the guard's host
  hook runs once per dispatch, so under ``run_chunked(k)`` the fault
  holds for the dispatch's k steps.
"""

from __future__ import annotations

import numpy as np
import pytest

from conftest import blob_classifier
from znicz_tpu.units import Unit
from znicz_tpu.utils.config import root

EPOCHS = 2
STEPS_PER_EPOCH = 8       # TRAIN minibatches; there is no other class

#: driver → (the call, its noun in the max_fires error, dispatches per
#: epoch, steps a fault armed for one dispatch spoils)
DRIVERS = {
    "chunked": (lambda wf: wf.run_chunked(4), "chunks", 2, 4),
    "accumulated": (lambda wf: wf.run_accumulated(2),
                    "accumulated steps", 4, 1),
    "pipelined": (lambda wf: wf.run_pipelined(2, 2),
                  "pipelined steps", 4, 1),
}
drivers = pytest.mark.parametrize("driver", list(DRIVERS))


class CountingUnit(Unit):
    def __init__(self, workflow, **kwargs) -> None:
        super().__init__(workflow, **kwargs)
        self.fired = 0

    def run(self) -> None:
        self.fired += 1


def _toy(name: str, **kwargs):
    from znicz_tpu.backends import XLADevice

    root.common.engine.grad_accum = 2   # the accumulation buffers
    wf = blob_classifier(name, n_per_class=STEPS_PER_EPOCH * 12 // 3,
                         epochs=EPOCHS, **kwargs)
    side = CountingUnit(wf, name="epoch_counter")
    wf._epoch_side_unit(side)
    wf.initialize(device=XLADevice())
    return wf, side


@drivers
def test_max_fires_raises_naming_the_driver(driver):
    drive, noun, _, _ = DRIVERS[driver]
    wf, _ = _toy(f"drive_valve_{driver}")
    wf._max_fires = 1
    with pytest.raises(RuntimeError,
                       match=f"max_fires=1 {noun} .runaway loop"):
        drive(wf)


@drivers
def test_stop_from_a_step_hook_ends_the_loop_at_the_next_boundary(driver):
    drive, _, per_epoch, _ = DRIVERS[driver]
    wf, side = _toy(f"drive_stop_{driver}")
    boundaries = []

    def hook():
        boundaries.append(len(boundaries))
        if len(boundaries) == 2:
            wf.stop()

    wf.add_step_hook(hook)
    drive(wf)
    assert len(boundaries) == 2, "a dispatch ran after stop()"
    assert not wf.decision.complete
    assert side.fired == 2 // per_epoch   # the epochs those two ended


@drivers
def test_side_chain_once_per_epoch_lr_adjuster_once_per_step(driver):
    drive, _, per_epoch, _ = DRIVERS[driver]
    wf, side = _toy(f"drive_sides_{driver}", lr_adjuster_config={
        "lr_policy": ("exp", {"gamma": 0.9})})
    adjuster = wf.lr_adjuster
    applied = []
    run = adjuster.run
    adjuster.run = lambda: (applied.append(adjuster._n_iterations),
                            run())
    drive(wf)
    assert wf.decision.complete
    assert side.fired == EPOCHS
    assert len(applied) == EPOCHS * per_epoch
    if driver == "chunked":     # every scanned step is still counted
        assert adjuster._n_iterations == EPOCHS * STEPS_PER_EPOCH
    else:                       # one optimizer step per dispatch
        assert adjuster._n_iterations == EPOCHS * per_epoch


@drivers
def test_a_nonfinite_grad_plan_is_armed(driver):
    drive, _, _, spoiled = DRIVERS[driver]
    root.common.engine.faults = {"train.nonfinite_grad": {"at": [2]}}
    wf, _ = _toy(f"drive_fault_{driver}")
    assert wf.anomaly_guard.fault_inject is not None
    drive(wf)
    _, loss_total, grad_total = wf.anomaly_guard.read_state()
    assert (loss_total, grad_total) == (0, spoiled)
    wf.forwards[0].weights.map_read()
    assert np.isfinite(wf.forwards[0].weights.mem).all()

#!/usr/bin/env python3
"""Do the limits of ``laguna_train_1of32``'s comparison have teeth AT
THE CELL'S SIZES?  (PERF.md §6, PR 29.)

    chiprun --timeout 1800 -- python3 benchmarks/laguna_controls.py --seed <n>
    python3 benchmarks/laguna_controls.py --seed 3 --toy        # here, CPU

Builds the cell's workflow as ``znbench/drivers/train_lm.py`` does, runs
one epoch of steps, and calls the driver's own ``check`` on it: once
with the plain reference (has to pass), then once per CONTROL, the
reference replaced by one that is wrong in a stated way (has to come
out as not correct, by ``reference_tolerance.layers``):

- ``float8``: every matmul input of the reference rounded to e4m3, the
  nearest precision below the configuration's bf16 inputs;
- a left-out term: no head gate, full attention on the first window
  layer and on every window layer, no shared expert, no routed scaling.
  (48 heads for 72 does not load the same parameters: that one is
  shape-checked, not toleranced.)

A control that spoils one layer computes the layers up to it and no
further (``check`` compares the layers it is given).  One JSON line per
check, ``ok`` last.
"""

from __future__ import annotations

import copy
import os
import sys
import types

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

CELL = "laguna_train_1of32"


def first(layers: list, kind: str, option: str | None = None) -> int:
    """The first layer of a type (that has an option set)."""
    return next(i for i, layer in enumerate(layers)
                if layer["type"] == kind
                and (option is None or layer["->"].get(option)))


def controls(layers: list) -> list:
    """``(name, layers spoiled, last layer computed, the edit of the
    spoiled layers' options, matmul inputs)``."""
    import jax.numpy as jnp
    gate = first(layers, "attention", "head_gate")
    bands = [i for i, layer in enumerate(layers)
             if layer["->"].get("window")]
    experts = first(layers, "moe")
    return [
        ("float8", [], experts, {}, jnp.float8_e4m3fn),
        ("no_head_gate", [gate], gate, {"head_gate": False}, None),
        ("full_attention_on_one_window_layer", bands[:1], bands[0],
         {"window": None}, None),
        ("full_attention_on_every_window_layer", bands, len(layers) - 1,
         {"window": None}, None),
        ("no_shared_expert", [experts], experts, {"shared_width": 0},
         None),
        ("no_routed_scaling", [experts], experts, {"routed_scale": 1.0},
         None),
    ]


def spoiled(reference, where: list, last: int, edit: dict, inputs):
    """The reference's module, its ``forward`` wrong as stated."""
    def forward(params, layers, x, routing=None, held=None):
        table = copy.deepcopy(layers[:last + 1])
        for i in where:
            table[i]["->"].update(edit)
        if inputs is None:
            return reference.forward(params, table, x, routing, held)
        with reference.matmul_inputs(inputs):
            return reference.forward(params, table, x, routing, held)
    return types.SimpleNamespace(forward=forward, route=reference.route,
                                 rms_norm=reference.rms_norm)


def main() -> int:
    from benchmarks.controls import run_checks
    return run_checks(CELL, lambda reference, layers, _workflow: [
        (name, spoiled(reference, *how))
        for name, *how in controls(layers)], doc=__doc__)


if __name__ == "__main__":
    sys.exit(main())

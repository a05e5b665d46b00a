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

import argparse
import copy
import json
import os
import sys
import tempfile
import time
import types

T_START = time.perf_counter()
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
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--toy", action="store_true")
    args = parser.parse_args()

    from znbench.harness import discovery, programs
    from znbench.harness.program import engine_options, layer_table
    from znbench.harness.window import Context
    import znbench.run as bench

    cell = discovery.find_cell(CELL, toy=args.toy)
    devices = bench.take_devices(cell, args.toy)
    programs.listen()
    driver = discovery.load_module("drivers", cell.driver)
    reference = discovery.load_module("reference",
                                      cell.config["reference"])
    scratch = tempfile.mkdtemp(prefix="znbench-")
    ctx = Context(cell, args.seed, 0.0, False, args.toy, devices,
                  T_START, scratch)
    layers = layer_table(cell.config)
    load_module = discovery.load_module
    ok = True
    with engine_options(cell.traffic.get("engine", {})):
        wf, _ = driver.build(ctx, layers)
        trainer = driver.train.Trainer(ctx, wf)
        trainer.epoch()
        trainer.fence()
        checks = [("reference", None)] + [
            (name, spoiled(reference, *how))
            for name, *how in controls(layers)]
        for name, module in checks:
            if module is not None:
                discovery.load_module = (
                    lambda kind, what, module=module: module
                    if kind == "reference" else load_module(kind, what))
            t0 = time.perf_counter()
            try:
                problems, notes = driver.check(ctx, wf, layers)
            finally:
                discovery.load_module = load_module
            said = next((n for n in notes if "worst layer" in n), "")
            good = (not problems) if module is None else any(
                "forward differs" in p for p in problems)
            ok = ok and good
            print(json.dumps({
                "check": name, "as_expected": good,
                "correct": not problems, "problems": problems,
                "layers": said.split("reference: ", 1)[-1],
                "limit": cell.config["reference_tolerance"]["layers"],
                "seq_len": cell.traffic["seq_len"],
                "platform": devices[0].platform,
                "seconds": round(time.perf_counter() - t0, 1)}),
                flush=True)
    print(json.dumps({"ok": ok}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

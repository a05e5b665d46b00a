#!/usr/bin/env python3
"""Do the limits of ``smallthinker_train_1of8``'s comparison have teeth
AT THE CELL'S SIZES?  (PERF.md §6, PR 50.)

    chiprun --timeout 2400 -- python3 benchmarks/smallthinker_controls.py --seed <n>
    python3 benchmarks/smallthinker_controls.py --seed 3 --toy   # here, CPU

Builds the cell's workflow as ``znbench/drivers/train_lm_early_router.py``
does, runs one epoch of steps, and calls the driver's own ``check`` on
it: once with the plain reference (has to pass), then once per CONTROL,
the reference replaced by one that is wrong in a stated way (has to
come out as not correct, by ``reference_tolerance.layers``):

- ``float8``: every matmul input of the reference rounded to e4m3, the
  nearest precision below the configuration's bf16 inputs;
- the router read after attention (the usual place: the expert block's
  own normed input), and the router's input normed (the block's input
  under the attention block's norm);
- SiLU for the experts' ReLU;
- RoPE on layer 0, the NoPE layer;
- no window on the first window layer, and on every one.

A control that spoils one layer computes the layers up to it and no
further (``check`` compares the layers it is given).  One JSON line per
check, ``ok`` last.
"""

from __future__ import annotations

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks.laguna_controls import spoiled  # noqa: E402

CELL = "smallthinker_train_1of8"


def controls(layers: list) -> list:
    """``(name, layers spoiled, last layer computed, the edit of the
    spoiled layers' options, matmul inputs)``."""
    import jax.numpy as jnp
    attention = [i for i, layer in enumerate(layers)
                 if layer["type"] == "attention"]
    bands = [i for i in attention if layers[i]["->"].get("window")]
    experts = next(i for i, layer in enumerate(layers)
                   if layer["type"] == "moe")
    theta = layers[bands[0]]["->"]["rope"]["theta"]
    return [
        ("float8", [], experts, {}, jnp.float8_e4m3fn),
        ("router_after_attention", [experts], experts,
         {"route_from": None}, None),
        ("router_input_normed", [experts], experts,
         {"route_normed": True}, None),
        ("silu_experts", [experts], experts, {"act": "silu"}, None),
        ("rope_on_the_nope_layer", attention[:1], attention[0],
         {"rope": {"theta": theta}}, None),
        ("no_window_on_one_window_layer", bands[:1], bands[0],
         {"window": None}, None),
        ("no_window_on_every_window_layer", bands, len(layers) - 1,
         {"window": None}, None),
    ]


def main() -> int:
    from benchmarks.controls import arguments, run_checks
    parser = arguments(__doc__)
    parser.add_argument("--only", nargs="+", default=None,
                        help="the controls to run, by name")
    args = parser.parse_args()
    return run_checks(CELL, lambda reference, layers, _workflow: [
        (name, spoiled(reference, *how))
        for name, *how in controls(layers)
        if args.only is None or name in args.only], doc=__doc__,
        args=args)


if __name__ == "__main__":
    sys.exit(main())

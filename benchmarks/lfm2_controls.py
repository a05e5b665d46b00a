#!/usr/bin/env python3
"""Do the limits of ``lfm2_train_1of2``'s comparison have teeth AT THE
CELL'S SIZES?  (PERF.md §6, PR 43.)

    chiprun --timeout 1800 -- python3 benchmarks/lfm2_controls.py --seed <n>
    python3 benchmarks/lfm2_controls.py --seed 3 --toy      # here, CPU

Builds the cell's workflow as ``znbench/drivers/train_lm.py`` does, runs
one epoch of steps, and calls the driver's own ``check`` on it: once
with the plain reference (has to pass), then once per CONTROL, the
reference replaced by one that is wrong in a stated way (has to come
out as not correct, by ``reference_tolerance.layers``):

- ``float8``: every matmul input of the reference rounded to e4m3, the
  nearest precision below the configuration's bf16 inputs, through the
  first short-convolution mixer and its MLP;
- a gate of the short convolution left out: B (u = x̃), then C (y = c);
- a SiLU put on the taps' sum (``delta_net.causal_conv``'s form, which
  this mixer does NOT have);
- the q/k norm over the whole projection instead of per head.

and one READING, which is run and printed and decides nothing
(``benchmarks/controls.py``): the routed weights normalised from
``s + b`` instead of ``s`` — after the one epoch this script runs the
bias has moved by at most ``bias_rate`` × steps, under two hundredths
of a score, so the ONE ``layers`` limit cannot separate it here;
``tests/test_lfm2_reference.py`` holds it at the toy's widths under a
bias that decides something.

What changes only WHICH experts are chosen — the bias in the selection
— the driver cannot see (it hands the system's choice to the
reference): the same test file holds it, the reference choosing for
itself.

Every control computes the layers up to the one it spoils and no
further (``check`` compares the layers it is given).  One JSON line per
check, ``ok`` last.

``--bias-rate-times N`` is the control of ``router_gap``, the one limit
that sees the bias's RULE at the cell's sizes: here the SYSTEM is made
wrong, not the reference — the workflow is built with every expert
layer's ``bias_rate`` N times the configuration's, trained for as many
steps as a run of the cell takes (warm-up + ``min_segments`` epochs),
and checked against the plain reference; it has to come out as not
correct BY THAT LIMIT (a chosen expert trails the reference's k-th by
more than ``router_gap`` of the logits' spread).  N = 1 gives the
system's own reading the same way.  One JSON line, exit code 0 if as
expected.
"""

from __future__ import annotations

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks.ling_controls import _first, spoiled  # noqa: E402

CELL = "lfm2_train_1of2"


def controls(reference, layers: list) -> list:
    """``(name, the last layer computed — the spoiled one —, the edit
    of its options (none here), functions of the reference's module to
    replace, the precision lowered: a switch of the reference and its
    dtype)``: ``ling_controls.spoiled``'s arguments."""
    import jax
    import jax.numpy as jnp
    conv, attn = _first(layers, "short_conv"), _first(layers, "attention")
    taps, norm = reference.conv_taps, reference.rms_norm

    def whole_projection(x, gain, eps):
        b, t, heads, dh = x.shape
        return norm(x.reshape(b, t, heads * dh), jnp.tile(gain, heads),
                    eps).reshape(x.shape)

    return [
        ("float8", conv + 1, {}, {},
         ("matmul_inputs", jnp.float8_e4m3fn)),
        ("no_in_gate", conv, {}, {"in_gate": lambda gate, z: z}, None),
        ("no_out_gate", conv, {}, {"out_gate": lambda gate, c: c}, None),
        ("silu_on_the_taps", conv, {},
         {"conv_taps": lambda u, w: jax.nn.silu(taps(u, w))}, None),
        ("qk_norm_over_the_projection", attn, {},
         {"head_norm": whole_projection}, None),
    ]


def bias_of(workflow) -> dict:
    """Layer index → the selection bias the workflow holds now."""
    import numpy as np
    out = {}
    for i, unit in enumerate(workflow.forwards):
        if getattr(unit, "select_bias_on", False):
            unit.select_bias.map_read()
            out[i] = np.array(unit.select_bias.mem, np.float32)
    return out


def readings(reference, layers: list, bias: dict) -> list:
    """As :func:`controls`: what the cell's one limit does not separate
    from the stated model after one epoch of steps.  ``bias``:
    :func:`bias_of` the workflow."""
    import jax.numpy as jnp
    moe = _first(layers, "moe")
    b = jnp.asarray(bias[moe])

    def from_biased(scores, chosen, spec):
        biased = jnp.take_along_axis(scores + b, jnp.asarray(chosen),
                                     axis=-1)
        return biased / (biased.sum(axis=-1, keepdims=True) + 1e-6) \
            * float(spec.get("routed_scale", 1.0))

    return [("weights_from_biased_scores", moe, {},
             {"weights_of": from_biased}, None)]


def bias_rate_control(args, cell_name: str = CELL,
                      through: int | None = None) -> int:
    """The system with its bias ``args.bias_rate_times`` times as fast,
    after a run's steps, under the driver's own ``check`` (module
    docstring); ``cell_name``: another cell with such a router
    (``benchmarks/xing_controls.py``); ``through``: the last layer the
    reference's forward computes (``check`` compares the layers it is
    given, and reads every router on the system's own input BEFORE
    that forward: where the whole stack is minutes of the host's time,
    ``router_gap`` does not need it)."""
    import json
    import time

    from benchmarks.controls import trained
    times = args.bias_rate_times

    def faster(layers: list) -> None:
        for spec in {id(layer["->"]): layer["->"] for layer in layers
                     if layer["type"] == "moe"}.values():
            spec["bias_rate"] = times * float(spec.get("bias_rate", 1e-3))

    from znbench.harness import discovery
    traffic = discovery.find_cell(cell_name, toy=args.toy).traffic
    epochs = int(traffic.get("warmup_epochs", 2)) \
        + int(traffic.get("min_segments", 10)) \
        * int(traffic.get("epochs_per_segment", 1))
    with trained(cell_name, args, epochs, faster) as (
            ctx, driver, wf, layers, _reference, devices):
        t0 = time.perf_counter()
        load_module = discovery.load_module
        if through is not None:
            short = spoiled(_reference, through, {}, {}, None)
            discovery.load_module = lambda kind, what: short \
                if kind == "reference" else load_module(kind, what)
        try:
            problems, notes = driver.check(ctx, wf, layers)
        finally:
            discovery.load_module = load_module
        refused = any("trails the reference's k-th" in p
                      for p in problems)
        good = refused if times != 1 else not problems
        said = next((n for n in notes if "router logits" in n), "")
        worst = max(float(abs(b).max()) for b in bias_of(wf).values())
        print(json.dumps({
            "check": f"bias_rate_x{times:g}", "as_expected": good,
            "correct": not problems, "problems": problems,
            "router": said.split("reference: ", 1)[-1],
            "limit": ctx.cell.config["reference_tolerance"]["router_gap"],
            "bias_rate": sorted({layer["->"]["bias_rate"]
                                 for layer in layers
                                 if layer["type"] == "moe"}),
            "largest_bias": worst,
            "steps": epochs * int(traffic["steps_per_epoch"]),
            "seq_len": traffic["seq_len"],
            "platform": devices[0].platform,
            "seconds": round(time.perf_counter() - t0, 1)}), flush=True)
    print(json.dumps({"ok": good}), flush=True)
    return 0 if good else 1


def main() -> int:
    from benchmarks.controls import arguments, run_checks
    parser = arguments(__doc__)
    parser.add_argument(
        "--bias-rate-times", type=float, default=None,
        help="run the control of router_gap instead (module docstring)")
    args = parser.parse_args()
    if args.bias_rate_times is not None:
        return bias_rate_control(args)
    return run_checks(
        CELL,
        lambda reference, layers, workflow: [
            (name, spoiled(reference, *how))
            for name, *how in controls(reference, layers)],
        lambda reference, layers, workflow: [
            (name, spoiled(reference, *how))
            for name, *how in readings(reference, layers,
                                       bias_of(workflow))],
        args=args)


if __name__ == "__main__":
    sys.exit(main())

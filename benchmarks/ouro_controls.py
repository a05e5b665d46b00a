#!/usr/bin/env python3
"""Do the limits of ``ouro_train_loop4_t4096``'s comparison have teeth
AT THE CELL'S SIZES?  (PERF.md §6, PR 35.)

    chiprun --timeout 2400 -- python3 benchmarks/ouro_controls.py --seed <n>
    python3 benchmarks/ouro_controls.py --seed 3 --toy      # here, CPU

Builds the cell's workflow as ``znbench/drivers/train_lm.py`` does, runs
one epoch of steps, and calls the driver's own ``check`` on it
(``benchmarks/controls.py``): once with the plain reference (has to
pass), then once per CONTROL, the reference replaced by one that is
wrong in a stated way (has to come out as not correct, by
``reference_tolerance.layers``):

- ``float8``: every matmul input of the reference rounded to e4m3, the
  nearest precision below the configuration's bf16 inputs;
- ``one_pass``: the stack run once instead of four times;
- ``no_output_norm``: a sublayer without the norm on its output
  (x + f(RMSNorm(x)): the pre-norm block, not the sandwich);
- ``final_norm_not_carried``: the next pass takes up u, not
  RMSNorm_f(u) — the assumption of the configuration's ``assumed``;

and one READING, which is run and printed and decides nothing:
``bf16_carry``, the state handed from pass to pass rounded to bf16 — a
precision below the f32 carry the configuration states.  The cell's ONE
``layers`` limit is not expected to hold it out: a state rounded to
eight bits of mantissa three times moves the last pass's output by
about what the stated bf16 matmul inputs do in one sublayer.  The exit
gate's precision is in NO layer's output (the head's ``output`` is the
four softmaxes; the exit distribution is not a table entry), so the
driver's comparison cannot see a bf16 gate at all:
``tests/test_ouro_reference.py`` holds the gate, the exit distribution
and the loss to the reference at the toy widths.  A bf16 embedding
table is in the driver's own log line (limit ``embedding``).

One JSON line per check, ``ok`` last.
"""

from __future__ import annotations

import copy
import os
import sys
import types

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

CELL = "ouro_train_loop4_t4096"


def spoiled(reference, edit=None, patches=None, lowered=None,
            after=lambda outs: outs):
    """The reference's module, its ``forward`` wrong as stated:
    ``edit(table)`` changes a copy of the layer table, ``patches``
    replaces functions of the module, ``lowered`` rounds a part,
    ``after`` shapes the outputs for the comparison."""
    def forward(params, layers, x, routing=None):
        table = copy.deepcopy(layers)
        if edit:
            edit(table)
        old = {name: getattr(reference, name) for name in patches or {}}
        for name, fn in (patches or {}).items():
            setattr(reference, name, fn)
        try:
            with reference.lowered(**(lowered or {})):
                return after(reference.forward(params, table, x,
                                               routing))
        finally:
            for name, fn in old.items():
                setattr(reference, name, fn)
    return types.SimpleNamespace(forward=forward,
                                 rms_norm=reference.rms_norm)


def controls(reference, layers: list) -> list:
    import jax.numpy as jnp

    passes = max(int(layer.get("passes", 1)) for layer in layers)

    def once(table):
        for layer in table:
            if "passes" in layer:
                layer["passes"] = 1

    def every_exit_the_one(outs):   # one exit where the system has R
        import numpy as np
        return outs[:-1] + [np.repeat(outs[-1], passes, axis=1)]

    def pre_norm_only(u, p, i, kind, spec):
        eps = float(spec.get("norm_eps", 1e-5))
        n = reference.rms_norm(u, reference._param(p, i, "gain_norm"), eps)
        return u + reference.MIXERS[kind](n, p, i, spec)

    raw, norm = {}, reference.rms_norm

    def keep_raw(x, gain, eps):     # a pass's last norm is the final one
        raw["u"] = x
        return norm(x, gain, eps)

    return [
        ("float8", spoiled(reference,
                           lowered={"matmul": jnp.float8_e4m3fn})),
        ("one_pass", spoiled(reference, edit=once,
                             after=every_exit_the_one)),
        ("no_output_norm", spoiled(reference,
                                   patches={"sublayer": pre_norm_only})),
        # … which the next pass takes up instead of its output
        ("final_norm_not_carried", spoiled(
            reference, patches={"rms_norm": keep_raw,
                                "carry": lambda h: raw["u"]})),
    ]


def readings(reference, layers: list) -> list:
    import jax.numpy as jnp
    return [("bf16_carry", spoiled(reference,
                                   lowered={"carry": jnp.bfloat16}))]


def main() -> int:
    from benchmarks.controls import run_checks
    return run_checks(
        CELL, lambda reference, layers, _workflow: controls(reference, layers),
        lambda reference, layers, _workflow: readings(reference, layers),
        doc=__doc__)


if __name__ == "__main__":
    sys.exit(main())

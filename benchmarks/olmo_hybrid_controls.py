#!/usr/bin/env python3
"""Do the limits of ``olmo_hybrid_train_4of32``'s comparison have teeth
AT THE CELL'S SIZES?  (PERF.md §6, PR 31.)

    chiprun --timeout 1800 -- python3 benchmarks/olmo_hybrid_controls.py --seed <n>
    python3 benchmarks/olmo_hybrid_controls.py --seed 3 --toy      # here, CPU

Builds the cell's workflow as ``znbench/drivers/train_lm.py`` does, runs
one epoch of steps, and calls the driver's own ``check`` on it: once
with the plain reference (has to pass), then once per CONTROL, the
reference replaced by one that is wrong in a stated way (has to come
out as not correct, by ``reference_tolerance.layers``):

- ``float8``: every matmul input of the reference rounded to e4m3, the
  nearest precision below the configuration's bf16 inputs, through the
  first linear mixer and its MLP;
- a left-out term of the linear mixer: α ≡ 1 (no decay), β without its
  factor 2, no L2 norm of q and k, no convolution, no output gate, no
  per-head norm under the gate, pre-norm for post-norm.  (d_k and d_v
  swapped is not among them: with d_k ≠ d_v the fused projection no
  longer splits into its width — a shape error, not a wrong number —
  and its one silent form, q scaled by d_v^-1/2, is removed by the
  per-head RMSNorm, which no scale of its input survives.)

and two READINGS, which are run and printed and decide nothing
(``benchmarks/controls.py``): ``bf16_state_in_every_layer`` and
``bf16_state_in_one_layer``, the recurrence's state rounded to bf16
after every token — a precision below the f32 state the configuration
states — in all three linear layers, and in the first alone.  The cell's
ONE ``layers`` limit does not hold them out: on the chip the first read
0.164 on one seed and 6.9e-2 on another, where the system itself read
5.4e-2, and the second 1.4e-2 — a rounded state moves the output by
about what the stated bf16 matmul inputs do eight sublayers deep, and
the driver's ``check`` takes one number for every layer.  A limit per
layer (2e-2 after the first block) would: PERF.md §7, the next
``benchmark`` issue's first item.

Every control spoils the FIRST linear layer and computes the layers up
to it and no further (``check`` compares the layers it is given).  One
JSON line per check, ``ok`` last.  ``tests/test_olmo_hybrid_reference.py``
holds the same controls against the f32 system at the toy widths.
"""

from __future__ import annotations

import copy
import os
import sys
import types

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

CELL = "olmo_hybrid_train_4of32"


def controls(reference, layers: list) -> list:
    """``(name, the last layer computed — the spoiled one —, the edit
    of its options, functions of the reference's module to replace,
    parameters to replace, the precision lowered: which of the
    reference's switches — ``matmul_inputs`` or ``state_dtype`` — and
    the dtype it is set to)``."""
    import jax
    import jax.numpy as jnp
    at = next(i for i, layer in enumerate(layers)
              if layer["type"] == "gated_delta_net")
    norm = reference.rms_norm
    return [
        ("float8", at + 1, {}, {}, {},
         ("matmul_inputs", jnp.float8_e4m3fn)),
        ("alpha_one", at, {}, {}, {"decay_log": -50.0}, None),
        ("beta_without_2", at, {"allow_neg_eigval": False}, {}, {}, None),
        ("no_l2_norm", at, {}, {"l2_norm": lambda x, eps: x}, {}, None),
        ("no_convolution", at, {}, {"short_conv": lambda u, taps: u}, {},
         None),
        ("no_output_gate", at, {},
         {"gated_norm": lambda o, gain, gate, eps: norm(o, gain, eps)},
         {}, None),
        ("no_output_norm", at, {},
         {"gated_norm": lambda o, gain, gate, eps: o * jax.nn.silu(gate)},
         {}, None),
        ("pre_norm_for_post_norm", at,
         {"post_norm": None, "pre_norm": "rms"}, {}, {}, None),
    ]


def readings(reference, layers: list) -> list:
    """As :func:`controls`: wrong references the cell's one limit is
    known not to separate."""
    import jax.numpy as jnp
    at = next(i for i, layer in enumerate(layers)
              if layer["type"] == "gated_delta_net")
    return [
        ("bf16_state_in_one_layer", at, {}, {}, {},
         ("state_dtype", jnp.bfloat16)),
        ("bf16_state_in_every_layer", len(layers) - 1, {}, {}, {},
         ("state_dtype", jnp.bfloat16)),
    ]


def spoiled(reference, at: int, edit: dict, patches: dict, fills: dict,
            lowered):
    """The reference's module, its ``forward`` wrong as stated."""
    import numpy as np

    def forward(params, layers, x, routing=None):
        table = copy.deepcopy(layers[:at + 1])
        table[at]["->"].update(edit)
        params = dict(params)
        for attr, value in fills.items():
            key = f"layer{at}_{attr}"
            params[key] = np.full_like(params[key], value)
        old = {name: getattr(reference, name) for name in patches}
        for name, fn in patches.items():
            setattr(reference, name, fn)
        try:
            if lowered is None:
                return reference.forward(params, table, x, routing)
            switch, dtype = lowered
            with getattr(reference, switch)(dtype):
                return reference.forward(params, table, x, routing)
        finally:
            for name, fn in old.items():
                setattr(reference, name, fn)
    return types.SimpleNamespace(forward=forward,
                                 rms_norm=reference.rms_norm)


def main() -> int:
    from benchmarks.controls import run_checks

    def made(listed):
        return lambda reference, layers, _workflow: [
            (name, spoiled(reference, *how))
            for name, *how in listed(reference, layers)]
    return run_checks(CELL, made(controls), made(readings), doc=__doc__)


if __name__ == "__main__":
    sys.exit(main())

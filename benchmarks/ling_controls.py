#!/usr/bin/env python3
"""Do the limits of ``ling_train_1of64``'s comparison have teeth AT THE
CELL'S SIZES?  (PERF.md §6, PR 37.)

    chiprun --timeout 2400 -- python3 benchmarks/ling_controls.py --seed <n>
    python3 benchmarks/ling_controls.py --seed 3 --toy      # here, CPU

Builds the cell's workflow as ``znbench/drivers/train_lm.py`` does, runs
one epoch of steps, and calls the driver's own ``check`` on it: once
with the plain reference (has to pass), then once per CONTROL, the
reference replaced by one that is wrong in a stated way (has to come
out as not correct, by ``reference_tolerance.layers``):

- ``float8``: every matmul input of the reference rounded to e4m3, the
  nearest precision below the configuration's bf16 inputs, through the
  first linear mixer and its MLP;
- a left-out term of the linear mixer: the decay per key channel
  replaced by its mean over the head's channels (a scalar-decay delta
  rule), no output gate;
- a left-out term of the latent attention: the head gate;
- the routed experts' scaling 2.5 left out.

and five READINGS, which are run and printed and decide nothing
(``benchmarks/controls.py``): what the cell's ONE ``layers`` limit does
not hold out at the cell's widths (PERF.md §6, PR 37: 0.054 and 0.027
after the latent layer where the system itself reads 0.027 there and
0.045 at its last layer) — the shared rotary key's product left out
(k_r ≡ 0) and the latent's norm left out, both held at the toy's widths
by ``tests/test_ling_reference.py`` — and the precisions below the
stated f32: the recurrence's state rounded to bf16 after every token,
log α rounded to bf16 before it is exponentiated, the normed latent
rounded to bf16, each in every layer that has one.  (A bf16 embedding
table and a bf16 router are read by ``check`` itself, beside their own
limits.)

Two left-out terms change only WHICH experts are chosen — the bias in
the selection and the group limit — and the driver hands the system's
choice to the reference, so no control here can see them:
``tests/test_ling_reference.py`` holds them at the toy's widths (the
reference choosing for itself), with every control above against the
f32 system.

Every control computes the layers up to the one it spoils and no
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

CELL = "ling_train_1of64"


def _first(layers: list, kind: str) -> int:
    return next(i for i, layer in enumerate(layers)
                if layer["type"] == kind)


def controls(reference, layers: list) -> list:
    """``(name, the last layer computed — the spoiled one —, the edit
    of its options, functions of the reference's module to replace,
    the precision lowered: a switch of the reference and its dtype)``."""
    import jax.numpy as jnp
    kda, mla = _first(layers, "gated_delta_net"), _first(
        layers, "latent_attention")
    moe = _first(layers, "moe")
    decays = reference.decays

    def mean_decay(m, p, i, spec):
        beta, alpha = decays(m, p, i, spec)
        log_alpha = jnp.log(alpha).mean(axis=-1, keepdims=True)
        return beta, jnp.broadcast_to(jnp.exp(log_alpha), alpha.shape)

    return [
        ("float8", kda + 1, {}, {},
         ("matmul_inputs", jnp.float8_e4m3fn)),
        ("decay_mean_over_channels", kda, {}, {"decays": mean_decay},
         None),
        ("no_output_gate", kda, {}, {"output_gate": lambda gate: 1.0},
         None),
        ("no_head_gate", mla, {"head_gate": False}, {}, None),
        ("no_routed_scaling", moe, {"routed_scale": 1.0}, {}, None),
    ]


def readings(reference, layers: list) -> list:
    """As :func:`controls`: what the cell's one limit does not separate
    from the stated model at the cell's widths — lower precisions, and
    the two terms of the latent layer that move its output by less than
    the stated bf16 inputs move the last layer's."""
    import jax.numpy as jnp
    last, mla = len(layers) - 1, _first(layers, "latent_attention")
    latent = int(layers[mla]["->"]["kv_latent"])
    norm, rotate = reference.rms_norm, reference.rope_interleaved
    return [
        ("no_shared_rotary_key", mla, {}, {"rope_interleaved": (
            lambda x, theta: rotate(x, theta) * (x.shape[2] != 1))},
         None),
        ("no_latent_norm", mla, {}, {"rms_norm": (
            lambda x, gain, eps: x * gain if x.shape[-1] == latent
            else norm(x, gain, eps))}, None),
        ("bf16_state", last, {}, {}, ("state_dtype", jnp.bfloat16)),
        ("bf16_log_alpha", last, {}, {}, ("decay_dtype", jnp.bfloat16)),
        ("bf16_latent_norm", last, {}, {},
         ("latent_dtype", jnp.bfloat16)),
    ]


def spoiled(reference, at: int, edit: dict, patches: dict, lowered):
    """The reference's module, its ``forward`` wrong as stated."""
    def forward(params, layers, x, routing=None, held=None, bias=None):
        table = copy.deepcopy(layers[:at + 1])
        table[at]["->"].update(edit)
        old = {name: getattr(reference, name) for name in patches}
        for name, fn in patches.items():
            setattr(reference, name, fn)
        try:
            if lowered is None:
                return reference.forward(params, table, x, routing, held,
                                         bias)
            switch, dtype = lowered
            with getattr(reference, switch)(dtype):
                return reference.forward(params, table, x, routing, held,
                                         bias)
        finally:
            for name, fn in old.items():
                setattr(reference, name, fn)
    return types.SimpleNamespace(forward=forward, route=reference.route,
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

#!/usr/bin/env python3
"""Do the limits of ``kanana2_train_1of8``'s comparison have teeth AT
THE CELL'S SIZES?  (PERF.md §6, PR 52.)

    chiprun --timeout 2400 -- python3 benchmarks/kanana_controls.py --seed <n>
    python3 benchmarks/kanana_controls.py --seed 3 --toy      # here, CPU

Builds the cell's workflow as ``znbench/drivers/train_lm.py`` does, runs
one epoch of steps, and calls the driver's own ``check`` on it: once
with the plain reference (has to pass), then once per CONTROL, the
reference replaced by one that is wrong in ONE stated way (has to come
out as not correct, by ``reference_tolerance.layers``):

- ``float8``: every matmul input of the reference rounded to e4m3, the
  nearest precision below the configuration's bf16 inputs, through the
  first block (latent attention + the dense MLP);
- ``no_rotary_key_product``: q_rope · k_r left out of every head's
  score in every latent layer computed (the shared key's 64 columns
  zeroed where the keys are assembled), through the first expert layer
  — two latent layers; in Ling one such layer in seven read 5.4e-2
  under a limit of 0.10; here every block carries it, and a pre-norm
  stack only adds to the difference with depth;
- ``sigmoid_to_softmax``: an expert's score the softmax over all 128
  logits for its sigmoid, through the first expert layer;
- ``shared_expert_at_768``: ONE of the two shared experts — the first
  768 of the 1,536 columns — through the first expert layer;
- ``no_routed_scaling``: the chosen experts' weights sum to 1, not
  2.448, through the first expert layer.

and READINGS, which are run and printed and decide nothing
(``benchmarks/controls.py``) — what the cell's ONE ``layers`` limit is
not expected to separate, held by ``tests/test_kanana_reference.py`` at
the toy's widths in f32 instead:

- ``no_latent_norm``: c' goes to W_up as the down-projection gave it,
  in the first latent layer (a latent of 512 drawn 1/sqrt(D) from a
  normed input has an RMS near 1 already: the norm is a factor near 1 a
  token until training moves the projection; Ling's read 2.7e-2).

Every control computes the layers up to the one it names and no further
(``check`` compares the layers it is given), and so does the plain
reference that has to pass first: through the first expert layer — the
whole stack is five minutes of the host's time, and every run of the
cell spends them on exactly that.  What the controls of the first
expert layer share with it (the two latent layers and the dense MLP
before it, the same parameters and tokens) is computed once and kept.
One JSON line per check, ``ok`` last.

``--bias-rate-times N`` is the control of ``router_gap``
(``benchmarks/lfm2_controls.bias_rate_control`` on this cell): the
SYSTEM built with every expert layer's ``bias_rate`` N times the
configuration's, trained a run's steps, has to come out as not correct
BY THAT LIMIT; N = 1 gives the system's own reading.  The reference's
forward stops at the embedding there: ``check`` reads every router on
the system's own input before it, and compares the layers it is given.
"""

from __future__ import annotations

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks.ling_controls import _first, spoiled  # noqa: E402

CELL = "kanana2_train_1of8"


def half_shared(reference):
    """E_shared over the first half of its stored columns: one of the
    model's two shared experts."""
    def shared(m, p, i, spec):
        gate, up, down = (reference._param(p, i, f"weights_shared_{name}")
                          for name in ("gate", "up", "down"))
        half = gate.shape[1] // 2
        return reference.gated(m, gate[:, :half], up[:, :half],
                               down[:half])
    return shared


def kept(reference) -> dict:
    """The mixers' and the dense MLP's outputs by layer, computed once:
    patches for the plain reference and for the controls that differ
    from it in the first expert layer alone."""
    seen: dict = {}

    def once(name: str):
        plain = getattr(reference, name)

        def layer(m, p, i, spec):
            if (name, i) not in seen:
                seen[name, i] = plain(m, p, i, spec)
            return seen[name, i]
        return layer
    return {name: once(name) for name in ("latent_mixer", "dense_mlp")}


def controls(reference, layers: list, before: dict) -> list:
    """``(name, the last layer computed, the edit of ITS options,
    functions of the reference's module to replace, the precision
    lowered)``: ``ling_controls.spoiled``'s arguments; ``before``:
    :func:`kept`'s patches."""
    import jax
    import jax.numpy as jnp
    dense, moe = _first(layers, "gated_mlp"), _first(layers, "moe")
    head_keys = reference.head_keys
    return [
        ("float8", dense, {}, {},
         ("matmul_inputs", jnp.float8_e4m3fn)),
        ("no_rotary_key_product", moe, {},
         {"head_keys": lambda k_nope, k_r: head_keys(k_nope, 0.0 * k_r)},
         None),
        ("sigmoid_to_softmax", moe, {},
         {**before, "scores_of":
          lambda logits: jax.nn.softmax(logits, axis=-1)}, None),
        ("shared_expert_at_768", moe, {},
         {**before, "shared_expert": half_shared(reference)}, None),
        ("no_routed_scaling", moe, {"routed_scale": 1.0}, before, None),
    ]


def readings(reference, layers: list) -> list:
    return [
        ("no_latent_norm", _first(layers, "latent_attention"), {},
         {"latent_norm": lambda c, gain, eps: c}, None),
    ]


def main() -> int:
    from benchmarks.controls import arguments, run_checks
    parser = arguments(__doc__)
    parser.add_argument(
        "--bias-rate-times", type=float, default=None,
        help="run the control of router_gap instead (module docstring)")
    args = parser.parse_args()
    if args.bias_rate_times is not None:
        from benchmarks.lfm2_controls import bias_rate_control
        # the routers are read on their own inputs; the stack's five
        # minutes of reference are the other controls' to spend
        return bias_rate_control(args, CELL, through=0)

    before: dict = {}

    def plain(reference, layers, _workflow):
        before.update(kept(reference))
        return spoiled(reference, _first(layers, "moe"), {}, before, None)

    def made(listed, *more):
        return lambda reference, layers, _workflow: [
            (name, spoiled(reference, *how))
            for name, *how in listed(reference, layers, *more)]
    return run_checks(CELL, made(controls, before), made(readings),
                      args=args, make_plain=plain)


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Do the limits of ``xing_train_1of8``'s comparison have teeth AT THE
CELL'S SIZES?  (PERF.md §6, PR 46.)

    chiprun --timeout 1800 -- python3 benchmarks/xing_controls.py --seed <n>
    python3 benchmarks/xing_controls.py --seed 3 --toy      # here, CPU

Builds the cell's workflow as ``znbench/drivers/train_lm.py`` does, runs
one epoch of steps, and calls the driver's own ``check`` on it: once
with the plain reference (has to pass), then once per CONTROL, the
reference replaced by one that is wrong in ONE stated way (has to come
out as not correct, by ``reference_tolerance.layers``):

- ``float8``: every matmul input of the reference rounded to e4m3, the
  nearest precision below the configuration's bf16 inputs, through the
  first latent-attention layer, the dense MLP and their WRITEs;
- ``h_post_without_its_2``: H_post = sigmoid(..) for 2 sigmoid(..);
- ``no_stream_norm``: x~ = vec X, the norm over n·D left out;
- ``one_sinkhorn_iteration``: 1 iteration for 20;
- ``score_scale_without_yarn``: the scores times (nope + rope)^-1/2
  alone, the factor (0.1 ln 64 + 1)² left out;
- ``no_routed_scaling``: the chosen experts' weights sum to 1, not 2.

and READINGS, which are run and printed and decide nothing
(``benchmarks/controls.py``) — what the cell's ONE ``layers`` limit is
not expected to separate, each held by ``tests/test_xing_reference.py``
at the toy's widths in f32 instead:

- ``rows_only``: Sinkhorn's row normalisation without the columns'
  (H_res near the identity: the columns' turn moves an entry by a few
  hundredths);
- ``no_query_latent_norm``: c_q goes to W_uq as W_dq gave it (a latent
  of 768 drawn 1/sqrt(D) from a normed input has an RMS near 1 already:
  the norm is a factor near 1 a token until training moves W_dq).

Every control computes the layers up to the one it spoils
(for a map: up to the SECOND sublayer's WRITE — the first sublayer's
streams are four copies of one row, which a wrong H_res mixes into the
same row) and no further (``check`` compares the layers it is given).
One JSON line per check, ``ok`` last.

``--bias-rate-times N`` is the control of ``router_gap``
(``benchmarks/lfm2_controls.bias_rate_control`` on this cell): the
SYSTEM built with every expert layer's ``bias_rate`` N times the
configuration's, trained a run's steps, has to come out as not correct
BY THAT LIMIT; N = 1 gives the system's own reading.
"""

from __future__ import annotations

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks.ling_controls import _first, spoiled  # noqa: E402

CELL = "xing_train_1of8"


def _nth(layers: list, kind: str, n: int) -> int:
    return [i for i, layer in enumerate(layers)
            if layer["type"] == kind][n]


def controls(reference, layers: list) -> list:
    """``(name, the last layer computed, the edit of ITS options,
    functions of the reference's module to replace, the precision
    lowered)``: ``ling_controls.spoiled``'s arguments."""
    import jax
    import jax.numpy as jnp
    mla, moe = _first(layers, "latent_attention"), _first(layers, "moe")
    second = _nth(layers, "stream_write", 1)
    sinkhorn = reference.sinkhorn
    spec = layers[mla]["->"]
    plain = (int(spec["qk_nope"]) + int(spec["qk_rope"])) ** -0.5
    return [
        ("float8", second, {}, {},
         ("matmul_inputs", jnp.float8_e4m3fn)),
        ("h_post_without_its_2", second, {},
         {"post_map": jax.nn.sigmoid}, None),
        ("no_stream_norm", second, {},
         {"stream_norm": lambda rows, eps: rows}, None),
        ("one_sinkhorn_iteration", second, {},
         {"sinkhorn": lambda m, iters, eps: sinkhorn(m, 1, eps)}, None),
        ("score_scale_without_yarn", mla, {"score_scale": plain}, {},
         None),
        ("no_routed_scaling", moe, {"routed_scale": 1.0}, {}, None),
    ]


def readings(reference, layers: list) -> list:
    second = _nth(layers, "stream_write", 1)
    mla = _first(layers, "latent_attention")

    def rows_only(m, iters, eps):
        for _ in range(iters):
            m = m / (m.sum(axis=-1, keepdims=True) + eps)
        return m

    return [
        ("rows_only", second, {}, {"sinkhorn": rows_only}, None),
        ("no_query_latent_norm", mla, {},
         {"query_latent": lambda c_q, gain, eps: c_q}, None),
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
        return bias_rate_control(args, CELL)

    def made(listed):
        return lambda reference, layers, _workflow: [
            (name, spoiled(reference, *how))
            for name, *how in listed(reference, layers)]
    return run_checks(CELL, made(controls), made(readings), args=args)


if __name__ == "__main__":
    sys.exit(main())

"""The delta-rule state kernels' share of their roofline: the least
time the chip could take for what they are given to do per step
(``flops_delta.delta_train_cost``: the d_k × d_v products' FLOPs over
the bf16 peak, or the rows, states and cotangents they read and write,
at their stored width, over the HBM peak — 21 FLOP a byte forward
against the chip's 240: memory bounds it) over their measured time.
The lanes the tiles pad are not in the numerator:
``delta_pad_overwork`` says how much they are."""

from znbench import flops, flops_delta
from znbench.harness import discovery


def read(obs):
    if obs.peaks is None:
        return None
    per_step_ms = discovery.load_module(
        "layer_metrics", "delta_ms_per_step").read(obs)
    if not per_step_ms:
        return None
    seen = obs.observations
    cost = flops_delta.delta_train_cost(
        seen["layers"], seen["sample_shape"][0], seen["batch"])
    least_s, _bound = flops.roofline_seconds(cost, obs.peaks)
    return 100.0 * least_s / (per_step_ms / 1e3)

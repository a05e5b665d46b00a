"""The per-channel delta rule's kernels' share of their roofline: the
least time the chip could take for what the four ``znicz_kda_*``
kernels are given to do per step (``flops_latent.kda_train_cost``: the
chunk products as the program does them — sub-blocks, the inverse's
whole-chunk products, the backward's recomputed M — and the state
walk's, over the bf16 peak; or the rows, matrices, states and
cotangents they read and write at their stored f32 width over the HBM
peak: memory bounds it) over their measured time."""

from znbench import flops, flops_latent
from znbench.harness import discovery


def read(obs):
    if obs.peaks is None:
        return None
    per_step_ms = discovery.load_module(
        "layer_metrics", "kda_ms_per_step").read(obs)
    if not per_step_ms:
        return None
    seen = obs.observations
    cost = flops_latent.kda_train_cost(
        seen["layers"], seen["sample_shape"][0], seen["batch"])
    least_s, _bound = flops.roofline_seconds(cost, obs.peaks)
    return 100.0 * least_s / (per_step_ms / 1e3)

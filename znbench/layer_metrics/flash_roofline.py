"""The flash kernels' share of their roofline: the least time the
chip could take for what the kernels need per step
(``flops.flash_train_cost``: max of FLOPs ÷ bf16 peak and bytes ÷ HBM
peak — compute bounds it at T 2048) over the kernels' measured time."""

from znbench import flops
from znbench.harness import discovery


def read(obs):
    if obs.peaks is None:
        return None
    per_step_ms = discovery.load_module(
        "layer_metrics", "flash_ms_per_step").read(obs)
    if not per_step_ms:
        return None
    seen = obs.observations
    cost = flops.flash_train_cost(
        seen["layers"], seen["sample_shape"][0], seen["model_dim"],
        seen["batch"])
    least_s, _bound = flops.roofline_seconds(cost, obs.peaks)
    return 100.0 * least_s / (per_step_ms / 1e3)

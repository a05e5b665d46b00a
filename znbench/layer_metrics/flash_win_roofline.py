"""The windowed flash kernels' share of their roofline: the least time
the chip could take for what a step's windowed layers need
(``flops_band.flash_win_train_cost``: the band's FLOPs over the bf16
peak, or the q, k, v, o bytes at the group's sharing over the HBM
peak) over the kernels' measured time.  What the tiling computes
beyond the band is not in the numerator: ``flash_band_overwork`` says
how much that is."""

from znbench import flops, flops_band
from znbench.harness import discovery


def read(obs):
    if obs.peaks is None:
        return None
    per_step_ms = discovery.load_module(
        "layer_metrics", "flash_win_ms_per_step").read(obs)
    if not per_step_ms:
        return None
    seen = obs.observations
    cost = flops_band.flash_win_train_cost(
        seen["layers"], seen["sample_shape"][0], seen["batch"])
    least_s, _bound = flops.roofline_seconds(cost, obs.peaks)
    return 100.0 * least_s / (per_step_ms / 1e3)

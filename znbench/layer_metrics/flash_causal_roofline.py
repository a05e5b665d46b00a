"""The un-windowed causal flash kernels' share of their roofline: the
least time the chip could take for what a step's full-attention layers
need (``flops_gqa.flash_causal_train_cost``: 14·dh·H FLOPs a pair of
the causal half over the bf16 peak, or the q, k, v, o bytes at the
group's sharing over the HBM peak) over the kernels' measured time
(``flash_causal_ms_per_step``).  The scores a two-pass backward makes
a second time are not in the numerator.  Nothing where the kernels
left no operation to time."""

from znbench import flops, flops_gqa
from znbench.harness import discovery


def read(obs):
    if obs.peaks is None:
        return None
    per_step_ms = discovery.load_module(
        "layer_metrics", "flash_causal_ms_per_step").read(obs)
    if not per_step_ms:
        return None
    seen = obs.observations
    cost = flops_gqa.flash_causal_train_cost(
        seen["layers"], seen["sample_shape"][0], seen["batch"])
    least_s, _bound = flops.roofline_seconds(cost, obs.peaks)
    return 100.0 * least_s / (per_step_ms / 1e3)

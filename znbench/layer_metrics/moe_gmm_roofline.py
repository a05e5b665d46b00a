"""The grouped matmuls' share of their roofline: the least time the
chip could take for what they need per step
(``flops_moe.gmm_train_cost``: 18·N·k·D·F FLOPs a layer over the bf16
peak, or their bytes over the HBM peak — compute bounds it at the
OLMoE widths) over their measured time."""

from znbench import flops, flops_moe
from znbench.harness import discovery


def read(obs):
    if obs.peaks is None:
        return None
    per_step_ms = discovery.load_module(
        "layer_metrics", "moe_gmm_ms_per_step").read(obs)
    if not per_step_ms:
        return None
    seen = obs.observations
    cost = flops_moe.gmm_train_cost(
        seen["layers"], seen["batch"] * seen["sample_shape"][0],
        seen["model_dim"])
    least_s, _bound = flops.roofline_seconds(cost, obs.peaks)
    return 100.0 * least_s / (per_step_ms / 1e3)

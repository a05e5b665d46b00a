"""The two-width flash kernels' share of their roofline: the least
time the chip could take for what they are given per step
(``flops_latent.mla_flash_train_cost``: over the causal half the
forward's two products and the backward's five at keys of
``qk_nope + qk_rope`` and values of ``v_head_dim``, over the bf16 peak;
q, k, v, o, the shared key once, statistics and cotangents over the HBM
peak: compute bounds it at T 4,096) over their measured time."""

from znbench import flops, flops_latent
from znbench.harness import discovery


def read(obs):
    if obs.peaks is None:
        return None
    per_step_ms = discovery.load_module(
        "layer_metrics", "mla_flash_ms_per_step").read(obs)
    if not per_step_ms:
        return None
    seen = obs.observations
    cost = flops_latent.mla_flash_train_cost(
        seen["layers"], seen["sample_shape"][0], seen["batch"])
    least_s, _bound = flops.roofline_seconds(cost, obs.peaks)
    return 100.0 * least_s / (per_step_ms / 1e3)

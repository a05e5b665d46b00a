"""Model FLOP/s utilization of the traced run of a language-model
training cell: the FLOPs forward and backward need per step
(``znbench/flops_moe.py``: projections, the causal half of the scores,
the top-k experts, router, head; recomputed work not counted) times
steps per second, over chips times the published bf16 peak.  An
end-to-end utilization from the host clock — not a roofline share."""

from znbench import flops_moe


def read(obs):
    if obs.peaks is None:       # no published peak off a TPU: no MFU
        return None
    seen = obs.observations
    per_step = flops_moe.lm_train_flops(
        seen["layers"], seen["sample_shape"][0], seen["batch"])
    rate = seen["steps"] / obs.window_s
    return 100.0 * per_step * rate / (
        obs.chips * obs.peaks["bf16_flops_per_s"])

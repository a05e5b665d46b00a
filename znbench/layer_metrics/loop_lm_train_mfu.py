"""Model FLOP/s utilization of the traced run of a language-model
training cell whose layer stack runs R times a step on shared weights
with an exit at every pass: the FLOPs forward and backward need per
step (``znbench/flops_loop.py``: R × N blocks' projections, causal-half
scores and MLPs, R exits of the head and the gate, × 3; rematerialised
work and the adds of the passes' gradient sum not counted) times steps
per second, over chips times the published bf16 peak.  An end-to-end
utilization from the host clock — not a roofline share.  Nothing where
the table has no looped span."""

from znbench import flops_loop


def read(obs):
    if obs.peaks is None:       # no published peak off a TPU: no MFU
        return None
    seen = obs.observations
    if not any("passes" in layer for layer in seen["layers"]):
        return None
    per_step = flops_loop.lm_train_flops(
        seen["layers"], seen["sample_shape"][0], seen["batch"])
    rate = seen["steps"] / obs.window_s
    return 100.0 * per_step * rate / (
        obs.chips * obs.peaks["bf16_flops_per_s"])

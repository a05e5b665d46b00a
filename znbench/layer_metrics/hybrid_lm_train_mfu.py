"""Model FLOP/s utilization of the traced run of a language-model
training cell that mixes gated-delta-rule and full-attention layers:
the FLOPs forward and backward need per step
(``znbench/flops_delta.py``: the five projections of a linear layer at
their own widths, its convolution, the chunked rule as the program's
algebra runs it, the full layers' projections and causal-half scores,
the gated MLPs, the head; rematerialised work not counted) times steps
per second, over chips times the published bf16 peak.  An end-to-end
utilization from the host clock — not a roofline share."""

from znbench import flops_delta


def read(obs):
    if obs.peaks is None:       # no published peak off a TPU: no MFU
        return None
    seen = obs.observations
    if not any(layer["type"] == "gated_delta_net"
               for layer in seen["layers"]):
        return None
    per_step = flops_delta.lm_train_flops(
        seen["layers"], seen["sample_shape"][0], seen["batch"])
    rate = seen["steps"] / obs.window_s
    return 100.0 * per_step * rate / (
        obs.chips * obs.peaks["bf16_flops_per_s"])

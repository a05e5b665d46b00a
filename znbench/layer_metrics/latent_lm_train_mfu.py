"""Model FLOP/s utilization of the traced run of a language-model
training cell that mixes per-channel delta-rule layers with latent-K/V
attention over expert layers that hold a share: the FLOPs forward and
backward need per step (``znbench/flops_latent.py``: every projection
at its own width, the latent's down and up, the convolution, the
chunked rule, the causal-half scores at keys of two widths, the dense
and shared MLPs, the routed rows this chip computed — read from
``znicz_moe_held`` where the program has it, else expected under
uniform routing — the routers, the head; recomputed work not counted)
times steps per second, over chips times the published bf16 peak.  An
end-to-end utilization from the host clock — not a roofline share."""

from znbench import flops_latent
from znbench.harness import discovery


def read(obs):
    if obs.peaks is None:       # no published peak off a TPU: no MFU
        return None
    seen = obs.observations
    if not flops_latent.kda_layers(seen["layers"]) \
            and not flops_latent.latent_layers(seen["layers"]):
        return None
    rows = discovery.load_module(
        "layer_metrics", "band_lm_train_mfu").routed_rows(obs)
    per_step = flops_latent.lm_train_flops(
        seen["layers"], seen["sample_shape"][0], seen["batch"], rows)
    rate = seen["steps"] / obs.window_s
    return 100.0 * per_step * rate / (
        obs.chips * obs.peaks["bf16_flops_per_s"])

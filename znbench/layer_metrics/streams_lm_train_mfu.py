"""Model FLOP/s utilization of the traced run of a language-model
training cell whose residual path is n streams around latent-K/V
attention with a query latent and expert layers that hold a share: the
FLOPs forward and backward need per step (``znbench/flops_streams.py``:
every projection at its own width incl. both latents' down and up, the
causal-half scores at keys of two widths, the maps' x~ phi and the
n² + 2n mixes, the dense and shared MLPs, the routed rows this chip
computed — read from ``znicz_moe_held`` where the program has it, else
expected under uniform routing — the routers, the head; norms,
Sinkhorn and softmaxes not counted, recomputed work not counted) times
steps per second, over chips times the published bf16 peak.  An
end-to-end utilization from the host clock — not a roofline share."""

from znbench import flops_streams
from znbench.harness import discovery


def read(obs):
    if obs.peaks is None:       # no published peak off a TPU: no MFU
        return None
    seen = obs.observations
    if not flops_streams.stream_reads(seen["layers"]):
        return None
    rows = discovery.load_module(
        "layer_metrics", "band_lm_train_mfu").routed_rows(obs)
    per_step = flops_streams.lm_train_flops(
        seen["layers"], seen["sample_shape"][0], seen["batch"], rows)
    rate = seen["steps"] / obs.window_s
    return 100.0 * per_step * rate / (
        obs.chips * obs.peaks["bf16_flops_per_s"])

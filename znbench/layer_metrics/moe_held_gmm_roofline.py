"""The grouped matmuls' share of their roofline where an expert layer
holds a share: the least time the chip could take for what they are
given per step (``flops_conv.held_gmm_train_cost``: 18·rows·D·F FLOPs a
layer over the bf16 peak, rows the (token, expert) pairs this chip
COMPUTED — ``rows_here`` of the gauge ``znicz_moe_held``, as
``band_lm_train_mfu.routed_rows`` reads it — or their bytes over the
HBM peak, the slabs counted for the experts HELD) over
``moe_gmm_ms_per_step``.  ``moe_gmm_roofline`` counts N·k rows over all
E experts — twice what 16 held of 32 compute.  Nothing where no layer
holds a share, the gauge is not there, or the kernels did not run."""

from znbench import flops, flops_conv
from znbench.harness import discovery


def read(obs):
    if obs.peaks is None:
        return None
    per_step_ms = discovery.load_module(
        "layer_metrics", "moe_gmm_ms_per_step").read(obs)
    rows = discovery.load_module(
        "layer_metrics", "band_lm_train_mfu").routed_rows(obs)
    if not per_step_ms or not rows:
        return None
    seen = obs.observations
    cost = flops_conv.held_gmm_train_cost(
        seen["layers"], rows, seen["batch"] * seen["sample_shape"][0])
    least_s, _bound = flops.roofline_seconds(cost, obs.peaks)
    return 100.0 * least_s / (per_step_ms / 1e3)

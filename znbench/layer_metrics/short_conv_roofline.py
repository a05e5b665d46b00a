"""The short convolution's chain kernels' share of their roofline: the
least time the chip could take for what the chain must move and do per
step (``flops_conv.short_conv_train_cost``: forward the projection's
three blocks read and y written, backward the projection and y's
cotangent read and the projection's cotangent written, at the widths
the program stores them; some 20 FLOPs an element — memory bounds it)
over the ``znicz_short_conv_*`` kernels' measured time."""

from znbench import flops, flops_conv
from znbench.harness import discovery


def out_bytes(obs) -> int:
    """Bytes of an element of y as the program stores it: the matmul
    input width of the configuration's precision."""
    precision = obs.cell.config["precision"]["precision_type"]
    return 2 if precision == "bfloat16" else 4


def read(obs):
    if obs.peaks is None:
        return None
    per_step_ms = discovery.load_module(
        "layer_metrics", "short_conv_ms_per_step").read(obs)
    if not per_step_ms:
        return None
    seen = obs.observations
    cost = flops_conv.short_conv_train_cost(
        seen["layers"], seen["sample_shape"][0], seen["batch"],
        out_bytes(obs))
    least_s, _bound = flops.roofline_seconds(cost, obs.peaks)
    return 100.0 * least_s / (per_step_ms / 1e3)

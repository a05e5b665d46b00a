"""The stream units' share of their roofline: the least time the chip
could take for what ANY implementation of them must move per step
(``flops_streams.stream_train_cost``: four passes over the (T, n·D) f32
stream a sublayer — X read by the READ and by the WRITE, h, f, X' once;
backward their cotangents and X again — over the HBM peak; memory
bounds it by far) over their measured time
(``stream_unit_ms_per_step``, the family's units forward + backward as
plain XLA, or a kernel's if one is written: the same reader reads it).
How far the maps are from what a fused kernel could reach.  Nothing
where the program holds no such unit."""

from znbench import flops, flops_streams
from znbench.harness import discovery


def read(obs):
    if obs.peaks is None:
        return None
    per_step_ms = discovery.load_module(
        "layer_metrics", "stream_unit_ms_per_step").read(obs)
    if not per_step_ms:
        return None
    seen = obs.observations
    cost = flops_streams.stream_train_cost(
        seen["layers"], seen["sample_shape"][0], seen["batch"])
    least_s, _bound = flops.roofline_seconds(cost, obs.peaks)
    return 100.0 * least_s / (per_step_ms / 1e3)

"""Model FLOP/s utilization of the traced run: the FLOPs the forward
and backward passes need per step (``znbench/flops.py``, recomputed
work not counted) times steps per second, over chips times the
published bf16 peak.  An end-to-end utilization from the host clock —
not a roofline share, and it says nothing of idle time."""

from znbench import flops


def read(obs):
    if obs.peaks is None:       # no published peak off a TPU: no MFU
        return None
    seen = obs.observations
    per_step = flops.train_step_flops(
        seen["layers"], seen["sample_shape"], seen["batch"])
    rate = seen["steps"] / obs.window_s
    return 100.0 * per_step * rate / (
        obs.chips * obs.peaks["bf16_flops_per_s"])

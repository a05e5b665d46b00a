"""Model FLOP/s utilization of the traced run of a language-model
training cell whose layers differ in head count and window and whose
expert layers hold a share: the FLOPs forward and backward need per
step (``znbench/flops_band.py``: projections at their own widths, band
and causal-half scores, gate, dense and shared MLPs, the routed rows
this chip computed — read from ``znicz_moe_held`` where the program has
it, else expected under uniform routing — router, head; recomputed
work not counted) times steps per second, over chips times the
published bf16 peak.  An end-to-end utilization from the host clock —
not a roofline share."""

from znbench import flops_band


def routed_rows(obs) -> dict:
    """Layer index → pairs computed here per token, from the gauge."""
    from znicz_tpu.observe import metrics
    gauge = getattr(metrics, "moe_held", None)
    seen = obs.observations
    tokens = seen["batch"] * seen["sample_shape"][0]
    moe_at = [i for i, layer in enumerate(seen["layers"])
              if layer["type"] == "moe"]
    if gauge is None:
        return {}
    return {i: gauge(unit, "rows_here").value / tokens
            for i, unit in zip(moe_at, seen.get("moe_units") or [])
            if gauge(unit, "rows_here").value}


def read(obs):
    if obs.peaks is None:       # no published peak off a TPU: no MFU
        return None
    seen = obs.observations
    per_step = flops_band.lm_train_flops(
        seen["layers"], seen["sample_shape"][0], seen["batch"],
        routed_rows(obs))
    rate = seen["steps"] / obs.window_s
    return 100.0 * per_step * rate / (
        obs.chips * obs.peaks["bf16_flops_per_s"])

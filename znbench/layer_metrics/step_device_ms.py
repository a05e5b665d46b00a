"""Device busy time (union of operation intervals, mean over chips)
per training step of the traced window."""


def read(obs):
    steps = obs.observations.get("steps")
    if not steps or not obs.busy["busy_s"]:
        return None
    return 1e3 * obs.busy["busy_s"] / steps

"""Share of the traced window in which no operation ran on the device:
1 − union(busy) ÷ window, mean over the chips used."""


def read(obs):
    idle = obs.busy["idle_share"]
    return None if idle is None or not obs.busy["busy_s"] \
        else 100.0 * idle

"""Time the training driver spent inside blocking device→host reads
per step: the summed duration of the window's ``host_read:<vector>``
spans over its steps — mostly waiting for the device to finish what
was dispatched, so it is large where the host runs ahead and the
device is busy, and a cost only where the device then idles (see
``device_idle_share`` and the idle gaps)."""

from znbench.harness import discovery


def read(obs):
    steps = obs.observations.get("steps")
    reads = discovery.load_module(
        "layer_metrics", "host_reads_per_step").host_reads(obs)
    if not steps or not reads:
        return None
    return 1e3 * sum(s["t1"] - s["t0"] for s in reads) / steps

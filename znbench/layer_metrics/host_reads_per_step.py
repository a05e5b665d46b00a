"""Blocking device→host reads per training step: the program's
``host_read:<vector>`` spans (``Vector.map_read`` of a buffer the
device holds: the guard's state, the epoch-end accumulators, the SDC
vote's parameters) in the window over its steps.  Each makes the host
wait for every step dispatched before it; at 1 or more the driver
cannot run ahead of the device.  Nothing where the program records no
such span (every training window has at least an epoch-end read)."""

from znbench.harness.window import WINDOW_SPAN


def in_window(obs) -> list:
    """The program's spans that lie inside the measured window.  The
    ring is read after the correctness check, which reads every
    parameter back: those reads are not the window's."""
    edges = next(((t0, t1) for name, t0, t1 in obs.spans
                  if name == WINDOW_SPAN), None)
    if edges is None:
        return list(obs.program_spans)
    return [s for s in obs.program_spans
            if edges[0] <= s["t0"] and s["t1"] <= edges[1]]


def host_reads(obs) -> list:
    return [s for s in in_window(obs)
            if s["name"].startswith("host_read:")]


def read(obs):
    steps = obs.observations.get("steps")
    reads = host_reads(obs)
    if not steps or not reads:
        return None
    return len(reads) / steps

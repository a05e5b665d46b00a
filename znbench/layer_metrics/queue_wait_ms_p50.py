"""Median of the request spans' queue phase (``req.queue``): submit to
the moment the scheduler admits the prompt."""

import statistics


def read(obs):
    waits = [s["t1"] - s["t0"] for s in obs.program_spans
             if s["name"] == "req.queue"]
    return 1e3 * statistics.median(waits) if waits else None

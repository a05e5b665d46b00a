"""Median duration of the engine's ``decode_step`` spans in the window
(the program's own tracer: one span around each token step's
dispatch, d2h and return)."""

import statistics


def read(obs):
    steps = [s["t1"] - s["t0"] for s in obs.program_spans
             if s["name"] == "decode_step"]
    return 1e3 * statistics.median(steps) if steps else None

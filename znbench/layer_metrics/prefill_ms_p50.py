"""Median of the request spans' prefill phase (``req.prefill``): page
set-up, the prefill dispatch, its d2h and the first sample."""

import statistics


def read(obs):
    spans = [s["t1"] - s["t0"] for s in obs.program_spans
             if s["name"] == "req.prefill"]
    return 1e3 * statistics.median(spans) if spans else None

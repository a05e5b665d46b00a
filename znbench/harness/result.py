"""What a driver hands back, and the one line the command prints."""

from __future__ import annotations

import dataclasses
import json


@dataclasses.dataclass
class Outcome:
    """A driver's verdict and readings for one window."""
    correct: bool
    attempted: int
    failed: int
    #: end-to-end values by metric name, all from the host clock
    end_to_end: dict
    #: whatever the per-layer readers need beyond spans, counters and
    #: the trace: steps, items, samples of engine stats, …
    observations: dict = dataclasses.field(default_factory=dict)
    #: why ``correct`` is what it is, for the log above the line
    notes: list = dataclasses.field(default_factory=list)


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile of unsorted ``values``."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("percentile of nothing")
    pos = (len(ordered) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (pos - lo)


def median(values) -> float:
    return percentile(values, 50)


def device_info(devices: list) -> dict:
    """The device as JAX reports it; the peak is that of the fullest
    chip (``None`` where the backend reports no memory stats)."""
    peaks = []
    for dev in devices:
        stats = dev.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    first = devices[0]
    return {"platform": first.platform, "kind": first.device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(peaks) if peaks else None}


def result_line(outcome: Outcome, metrics: dict, units: dict,
                device: dict, breakdown: dict | None = None,
                extra: dict | None = None) -> str:
    line = {
        "correct": bool(outcome.correct),
        "attempted": int(outcome.attempted),
        "failed": int(outcome.failed),
        "metrics": {name: {"value": float(value), "unit": units[name]}
                    for name, value in metrics.items()
                    if value is not None},
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line.update(extra or {})
    return json.dumps(line)

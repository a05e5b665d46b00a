"""Counters the benchmark reads and never re-times: XLA programs built
or loaded (``jax.monitoring``) and the program's own metric registry
(``znicz_*`` families).  Copied from ``chip_smoke.py``'s checks."""

from __future__ import annotations

import collections

#: every program jax builds OR loads from its persistent cache ends one
#: backend-compile span; a load also counts one cache hit
PROGRAM_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_events: collections.Counter = collections.Counter()
_listening = False


def listen() -> None:
    """Start counting (listeners cannot be removed: once a process)."""
    global _listening
    if _listening:
        return
    import jax
    jax.monitoring.register_event_listener(
        lambda event, **kw: _events.update([event]))
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _secs, **kw: _events.update([event]))
    _listening = True


def programs() -> int:
    """XLA programs this process has built or loaded so far."""
    return _events[PROGRAM_EVENT]


def cache_hits() -> int:
    return _events[CACHE_HIT_EVENT]


def registry_sum(family_name: str) -> float:
    """Sum over every child of one ``znicz_*`` family: a counter's
    value, a histogram's sum of observations.  0 where the family was
    never touched."""
    from znicz_tpu.observe import metrics
    family = metrics.REGISTRY.get(family_name)
    if family is None:
        return 0.0
    total = 0.0
    for _key, child in family.items():
        value = getattr(child, "sum", None)
        if value is None:
            value = child.value
        total += float(value)
    return total


#: the registry families the per-layer readers use, read at both edges
#: of the window
FAMILIES = ("znicz_xla_compiles_total", "znicz_region_steps_total",
            "znicz_input_wait_seconds", "znicz_step_anomalies_total")


def snapshot() -> dict:
    out = {name: registry_sum(name) for name in FAMILIES}
    out["jax_programs"] = float(programs())
    return out


def delta(before: dict, after: dict) -> dict:
    return {name: after[name] - before[name] for name in after}

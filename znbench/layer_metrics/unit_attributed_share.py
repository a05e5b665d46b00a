"""How much of the device's time the per-unit rows see: the self time
of the operations that the program's own map
(``znicz_tpu.observe.op_scopes()``: HLO instruction → unit, class,
family, phase, read from the compiled step program's ``op_name``
metadata) puts in exactly ONE unit, over all self time in the window,
in percent.  The rest is in operations fused from several units
(mixed) or in none the map knows (unattributed).

This file also holds what the per-unit readers share.  Every device
operation of the window (``trace_reduce.op_seconds``: self seconds by
operation name, mean over the chips) goes into exactly one bucket:

- a family — ``attention`` (family ``MultiHeadAttention``), ``moe``
  (``MoE``), ``gated_mlp`` (``GatedMLP``), ``delta_net``
  (``GatedDeltaNet``), ``dense`` (``All2All*``), ``conv`` (``Conv*``)
  — where the map puts it in one unit of that family, phase forward or
  backward (a backward unit's family is the forward class it is paired
  with);
- ``update`` where its phase is ``update`` or ``fingerprint``, whatever
  the unit (``fingerprint`` is the part of it inside the SDC fold);
- ``other``: in one unit of no family above (embedding, positions,
  evaluator, norms, pooling, dropout, the loader, the guard);
- ``mixed``: fused from instructions of several units that do NOT
  all fall into one bucket above.  (An operation of several units of
  ONE bucket — a layer's weight gradient with its forward unit's cast
  fused in, two units' updates in one horizontal fusion — is that
  bucket's: the rows are per family, and at that grain it is not
  ambiguous.  ``unit_attributed_share`` counts it as not in one unit
  all the same);
- ``unattributed``: a name in no program's map, or a name to which two
  programs of the process give different entries (a trace's operation
  line does not say which program an event ran in).

So in every cell families + ``update`` + ``other`` + ``mixed`` +
``unattributed`` = the window's summed self time, per step the sum of
``<family>_unit_ms_per_step`` + ``update_ms_per_step`` +
``other_units_ms_per_step`` + the two that have no entry of their own
(:func:`table` gives them; ``znbench/tests/test_unit_device_ms.py``
holds the identity).  A family's row leaves its units' updates out, so
no two rows overlap.  Every reader returns nothing where the program
hands out no map (a program from before ``observe.op_scopes``) or an
empty one.
"""

import collections

from znbench import trace_reduce

FAMILIES = {
    "attention": lambda family: family == "MultiHeadAttention",
    "moe": lambda family: family == "MoE",
    "gated_mlp": lambda family: family == "GatedMLP",
    "delta_net": lambda family: family == "GatedDeltaNet",
    "dense": lambda family: family.startswith("All2All"),
    "conv": lambda family: family.startswith("Conv"),
}

def merged(scopes: dict) -> dict:
    """One map over the process's programs: operation name → entry,
    ``None`` where two programs disagree."""
    out: dict = {}
    for ops in scopes.values():
        for name, entry in ops.items():
            if out.setdefault(name, entry) != entry:
                out[name] = None
    return out


def bucket_of(entry: dict | None) -> tuple[str, str]:
    """``(bucket, phase)`` of one operation's entry.  An operation
    fused from several units whose parts ALL fall into one bucket is
    that bucket's (phase ``fused`` where the parts' phases differ)."""
    if entry is None:
        return "unattributed", ""
    if entry["unit"] is None:
        parts = {bucket_of({"unit": unit, "family": family,
                            "phase": phase})
                 for unit, family, phase in zip(
                     entry["units"], entry.get("families", ()),
                     entry.get("phases", ()))}
        buckets = {bucket for bucket, _phase in parts}
        if len(buckets) != 1:
            return "mixed", ""
        return buckets.pop(), \
            parts.pop()[1] if len(parts) == 1 else "fused"
    if entry["phase"] in ("update", "fingerprint"):
        return "update", entry["phase"]
    for bucket, accepts in FAMILIES.items():
        if accepts(entry["family"]):
            return bucket, entry["phase"]
    return "other", entry["phase"]


def split(seconds: dict, names: dict) -> collections.Counter:
    """``(bucket, phase)`` → seconds, of self seconds by operation
    name and a merged map."""
    out: collections.Counter = collections.Counter()
    for name, value in seconds.items():
        out[bucket_of(names.get(name))] += value
    return out


def program_map() -> dict | None:
    """The merged map of the running process, or ``None`` where the
    program hands none out."""
    from znicz_tpu import observe
    scopes = getattr(observe, "op_scopes", None)
    return (merged(scopes()) or None) if scopes else None


def joined(obs) -> tuple | None:
    """``(self seconds by operation name in the window, the merged
    map)``, or ``None`` where either is empty (made once per
    observation)."""
    if "unit_join" not in obs.__dict__:
        names = program_map() if obs.trace.devices else None
        obs.__dict__["unit_join"] = names and (trace_reduce.op_seconds(
            obs.trace, obs.trace_window), names)
    return obs.__dict__["unit_join"]


def table(obs) -> collections.Counter | None:
    """``(bucket, phase)`` → self seconds in the window."""
    join = joined(obs)
    return split(*join) if join else None


def ms_per_step(obs, bucket: str, phase: str | None = None):
    """One bucket's (one phase of it) self time per step in ms."""
    steps = obs.observations.get("steps")
    seconds = table(obs)
    if not steps or not seconds:
        return None
    return 1e3 * sum(value for (b, p), value in seconds.items()
                     if b == bucket and phase in (None, p)) / steps


def read(obs):
    seconds, names = joined(obs) or ({}, {})
    total = sum(seconds.values())
    if not total:
        return None
    return 100.0 * sum(value for name, value in seconds.items()
                       if (names.get(name) or {}).get("unit")) / total

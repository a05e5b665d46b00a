"""Device time of the residual streams' units per training step: the
self time of the operations the program's map puts in one unit of
family ``Streams`` (``ops/streams.py``: the open, every READ — the norm
over n·D, x~ phi, the sigmoids, Sinkhorn's iterations, the mix —, every
WRITE, the close), forward + backward, updates left out, ÷ steps.  Read
from ``unit_attributed_share.joined(obs)`` by the family's name,
because that file's ``FAMILIES`` has no row for it: until a
``benchmark`` issue adds the family there, the SAME time also lies in
this cell's ``other_units_ms_per_step`` (a unit of no family it knows),
and the two must not be added.  An operation fused from several units
counts here only where all of them are stream units.  Nothing where the
program hands out no map or holds no such unit."""

from znbench.harness import discovery

FAMILY = "Streams"


def is_ours(entry) -> bool:
    """An operation of stream units only, forward or backward (a
    unit's update is ``update_ms_per_step``'s)."""
    if not entry:
        return False
    if entry["unit"] is not None:
        return entry["family"] == FAMILY \
            and entry["phase"] in ("forward", "backward")
    families = entry.get("families", ())
    return bool(families) and set(families) == {FAMILY} and all(
        phase in ("forward", "backward")
        for phase in entry.get("phases", ()))


def read(obs):
    steps = obs.observations.get("steps")
    join = discovery.load_module(
        "layer_metrics", "unit_attributed_share").joined(obs)
    if not steps or not join:
        return None
    seconds, names = join
    ours = sum(value for name, value in seconds.items()
               if is_ours(names.get(name)))
    return 1e3 * ours / steps if ours else None

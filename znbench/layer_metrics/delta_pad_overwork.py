"""What the delta-rule kernels' tiles hold beyond the state: the
elements of the 128-lane tiles a d_k × d_v product occupies over
d_k · d_v (1 = nothing padded; 1.78 at 96 × 192, both going to the next
128), from the gauge ``znicz_delta_scan{unit,stat}`` that a
``GatedDeltaNet`` unit sets at ``initialize`` (``padded_share``); mean
over the units whose kernels run (``path`` 1).  Nothing where the
program has no such gauge (the parent of PR 31) or no unit runs the
kernels."""


def read(obs):
    from znicz_tpu.observe import metrics
    family = metrics.REGISTRY.get("znicz_delta_scan")
    if family is None:
        return None
    stats: dict = {}
    for (unit, stat), gauge in family.items():
        stats.setdefault(unit, {})[stat] = gauge.value
    shares = [s["padded_share"] for s in stats.values()
              if s.get("path") and s.get("padded_share")]
    return sum(shares) / len(shares) if shares else None

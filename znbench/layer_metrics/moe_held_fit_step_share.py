"""How often a held share's row buffer ran at its fit size: steps whose
(token, expert) pairs routed here fit the shorter of the buffer's two
lengths, over the steps run, in percent (``fit_steps`` ÷ ``steps`` of
the gauge ``znicz_moe_held{unit,stat}``, which an expert layer that
holds a share sets at an epoch's end from totals it keeps on the device
— the last epoch of the window); mean over the expert layers.  100 =
no step paid for the capacity; under 100 the router sends this chip
more than 1.25 times its uniform share in some steps, and each of them
runs the whole capacity and makes its forward again in the backward.
Nothing where the program has no such gauge or no such count (the
parent of PR 45) or no layer holds a share."""


def read(obs):
    from znicz_tpu.observe import metrics
    family = metrics.REGISTRY.get("znicz_moe_held")
    if family is None:
        return None
    stats: dict = {}
    for (unit, stat), gauge in family.items():
        stats.setdefault(unit, {})[stat] = gauge.value
    shares = [100.0 * s["fit_steps"] / s["steps"] for s in stats.values()
              if "fit_steps" in s and s.get("steps")]
    return sum(shares) / len(shares) if shares else None

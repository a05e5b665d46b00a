"""How near the deployment's load an expert here runs: the (token,
expert) pairs routed to the experts this chip holds, per step, over
the experts held (``rows_here`` ÷ ``held`` of the gauge
``znicz_moe_held{unit,stat}``, which an expert layer that holds a
share sets at an epoch's end from totals it keeps on the device — the
last epoch of the window); mean over the expert layers.  Nothing where
the program has no such gauge (the parent of PR 29) or no layer holds
a share."""


def read(obs):
    from znicz_tpu.observe import metrics
    gauge = getattr(metrics, "moe_held", None)
    units = obs.observations.get("moe_units")
    if gauge is None or not units:
        return None
    rows = [gauge(unit, "rows_here").value / gauge(unit, "held").value
            for unit in units if gauge(unit, "held").value]
    return sum(rows) / len(rows) if rows else None

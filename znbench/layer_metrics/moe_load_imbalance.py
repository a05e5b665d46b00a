"""How unevenly the router loaded the experts: the fullest expert's
rows over the mean rows per expert (1 = even), per step, from the
gauge ``znicz_moe_expert_tokens{unit,stat}`` that each expert layer
sets at an epoch's end from totals it keeps on the device — the last
epoch of the window; mean over the expert layers.  A dropless layer
pays the imbalance in its grouped matmul's longest group.  Nothing
where the program has no such gauge (the parent of PR 25) or no expert
layer ran."""


def read(obs):
    from znicz_tpu.observe import metrics
    gauge = getattr(metrics, "moe_expert_tokens", None)
    units = obs.observations.get("moe_units")
    if gauge is None or not units:
        return None
    shares = []
    for unit in units:
        mean = gauge(unit, "mean").value
        if mean:
            shares.append(gauge(unit, "max").value / mean)
    return sum(shares) / len(shares) if shares else None

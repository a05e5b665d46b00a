"""What the grouped-matmul kernels' grids run beyond the rows routed:
the rows their visits cover over the rows that are real (1 = every
group ends on a row-tile edge; a tile that straddles a group boundary
is visited once per group, computed whole and masked), from the gauge
``znicz_moe_gmm_rows{unit,stat}`` that an expert layer sets at an
epoch's end from totals it keeps on the device (``visited`` ÷ ``real``,
per call and step over the window's last epoch); mean over the expert
layers.  Nothing where the program has no such gauge (the parent of
PR 34) or no layer runs the kernels (``ragged_dot``, off a TPU)."""


def read(obs):
    from znicz_tpu.observe import metrics
    family = metrics.REGISTRY.get("znicz_moe_gmm_rows")
    if family is None:
        return None
    stats: dict = {}
    for (unit, stat), gauge in family.items():
        stats.setdefault(unit, {})[stat] = gauge.value
    ratios = [s["visited"] / s["real"] for s in stats.values()
              if s.get("real") and s.get("visited")]
    return sum(ratios) / len(ratios) if ratios else None

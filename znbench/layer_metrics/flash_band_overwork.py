"""What the windowed layers' tiling runs beyond their band: the share
of the T × T square their kernels execute over the share inside the
band (1 = nothing computed and masked away), from the gauge
``znicz_flash_band{unit,stat}`` that a windowed attention unit sets at
``initialize`` (``executed_share`` ÷ ``band_share``); mean over the
windowed layers.  Nothing where the program has no such gauge (the
parent of PR 29) or no layer has a window."""


def read(obs):
    from znicz_tpu.observe import metrics
    family = metrics.REGISTRY.get("znicz_flash_band")
    if family is None:
        return None
    stats: dict = {}
    for (unit, stat), gauge in family.items():
        stats.setdefault(unit, {})[stat] = gauge.value
    ratios = [s["executed_share"] / s["band_share"]
              for s in stats.values()
              if s.get("band_share") and s.get("executed_share")]
    return sum(ratios) / len(ratios) if ratios else None

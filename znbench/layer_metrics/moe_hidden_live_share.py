"""How much of the ReLU experts' hidden is not zero, in percent: of
``relu(W_gate m) ⊙ W_up m`` over the rows of the experts held here
(rows × expert width), the elements that are not zero over the
elements there are, from the count an expert layer with ``act="relu"``
keeps on the device beside ``moe_stats`` and hands out at the epoch's
end (gauge ``znicz_moe_hidden{unit,stat}``, ``live`` ÷ ``total``: the
steps since the last read); mean over the layers.  ≈ 50 at
initialisation.  The zeros are work a sparse down-projection would
skip: the program counts them and does not exploit them.  Nothing
where the program has no such gauge (the parent of PR 50) or no layer
has ReLU experts."""


def read(obs):
    from znicz_tpu.observe import metrics
    family = metrics.REGISTRY.get("znicz_moe_hidden")
    if family is None:
        return None
    stats: dict = {}
    for (unit, stat), gauge in family.items():
        stats.setdefault(unit, {})[stat] = gauge.value
    shares = [s["live"] / s["total"] for s in stats.values()
              if s.get("total")]
    return 100.0 * sum(shares) / len(shares) if shares else None

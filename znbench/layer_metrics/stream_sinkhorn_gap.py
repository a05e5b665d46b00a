"""How near the doubly stochastic matrices Sinkhorn's iterations came:
the worst |row sum − 1| or |column sum − 1| of H_res over the last
epoch's tokens and sublayers (the larger of ``row_gap`` and ``col_gap``
of the gauge ``znicz_stream_maps{unit,stat}``, which the streams' OPEN
unit sets at an epoch's end from totals every READ keeps on the device;
the iteration ends on the columns, so the rows carry the gap).  Whether
``sinkhorn_iters`` iterations still reach the manifold as training
moves b_res.  Nothing where the program has no such gauge (the parent
of PR 46) or holds no stream unit."""


def read(obs):
    from znicz_tpu.observe import metrics
    family = metrics.REGISTRY.get("znicz_stream_maps")
    if family is None:
        return None
    gaps = [child.value for (_unit, stat), child in family.items()
            if stat in ("row_gap", "col_gap")]
    return max(gaps) if gaps else None

"""Short-convolution layers whose chain runs as the
``znicz_short_conv_fwd`` / ``_bwd`` kernels: the units whose gauge
``znicz_short_conv{unit,stat="path"}`` reads 1 (set once at
``initialize``; 0: both gates and the taps in ``jax.numpy``).  4 in the
LFM2 cell, so that a silent fall to ``jax.numpy`` shows.  Nothing where
the program has no such gauge (the parent of PR 43) or no such unit."""


def read(obs):
    from znicz_tpu.observe import metrics
    family = metrics.REGISTRY.get("znicz_short_conv")
    if family is None:
        return None
    paths = [gauge.value for (_unit, stat), gauge in family.items()
             if stat == "path"]
    return float(sum(1 for path in paths if path)) if paths else None

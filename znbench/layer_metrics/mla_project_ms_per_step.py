"""Device time per training step of the latent-K/V attention layers'
MATMULS OUTSIDE THE KERNELS: the self time of the operations the
program's map puts in phase ``project`` of an attention layer — the
phase reads by its PRODUCTS: a fusion whose every matmul lies in the
scope, with whatever the compiler fused around them
(``observe/scopes.py``; read by all its instructions the phase held a
seventh of its layer, PR 52) — (``ops/attention.py``
``_latent_forward``'s scope: the fused
down-projection over the normed input, the K/V up-projection's two
products, the query's up-projection where a query latent exists, the
head gate's logits where a layer has one, the out-projection; forward
and pullback, the weights' gradients among them) ÷ steps.  The
up-projection is the part an absorbed decode path would fold into the
query and the output (ROADMAP R5) and the part a training step could
recompute in its backward rather than keep.  The kernels between are
``mla_flash_ms_per_step``, the element-wise passes around them
``mla_rotate_norm_ms_per_step``, as far as they stand in operations
of their own.  Buckets and
their identity: ``unit_attributed_share``.  Nothing where the program
hands out no map, or knows no such phase (the parent of PR 52)."""

from znbench.harness import discovery


def read(obs, phase: str = "project"):
    from znicz_tpu.observe import scopes
    if phase not in getattr(scopes, "UNIT_PHASES", ()):
        return None                            # the parent of PR 52
    return discovery.load_module(
        "layer_metrics", "unit_attributed_share").ms_per_step(
            obs, "attention", phase)

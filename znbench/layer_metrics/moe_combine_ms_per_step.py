"""Device time per training step of the expert layers' way between
their tokens and their experts' rows: the self time of the operations
the program's map puts wholly in phase ``combine`` of an expert layer
(``ops/moe.py``'s scope: a held share's rows gathered from their
tokens, and the experts' results weighted and summed back into (N, D)
— by gathers through the pairs' inverse map, or by a scatter-add where
the share held is small; a dropless layer's un-permutation and weighted
sum; forward and pullback) ÷ steps.  The grouped matmuls between the
two are ``moe_gmm_ms_per_step``, the plan ``moe_route_ms_per_step``.
Operations XLA fused with a neighbour outside the scope are that
neighbour's.  Buckets and their identity: ``unit_attributed_share``.
Nothing where the program hands out no map, or knows no such phase
(the parent of PR 51)."""

from znbench.harness import discovery


def read(obs):
    from znicz_tpu.observe import metrics
    if not hasattr(metrics, "moe_combine"):    # the parent of PR 51
        return None
    return discovery.load_module(
        "layer_metrics", "unit_attributed_share").ms_per_step(
            obs, "moe", "combine")

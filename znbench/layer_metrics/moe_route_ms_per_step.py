"""Device time of the expert layers' ROUTING per training step: the
self time of the operations the program's map puts wholly in phase
``route`` of an expert layer (``ops/moe.py``'s scope: the logits from
the tensor the router reads, the scores, the top k and its weights, the
sort and the group sizes that plan the dispatch, forward and pullback)
÷ steps.  With a router that reads the block's input
(``route_from``) this is what a deployment would overlap with
attention — the choice is known a sublayer ahead; here it says what the
early edge costs.  Operations XLA fused with a neighbour outside the
scope are that neighbour's.  Buckets and their identity:
``unit_attributed_share``.  Nothing where the program hands out no map,
or knows no such phase (the parent of PR 50)."""

from znbench.harness import discovery


def read(obs):
    from znicz_tpu.observe import metrics
    if not hasattr(metrics, "moe_hidden"):    # the parent of PR 50
        return None
    return discovery.load_module(
        "layer_metrics", "unit_attributed_share").ms_per_step(
            obs, "moe", "route")

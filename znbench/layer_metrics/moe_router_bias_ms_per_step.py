"""Device time of the selection bias's own rule per training step: the
self time of the operations the program's map puts wholly in phase
``router_bias`` of an expert layer (``GDMoE._move_select_bias``'s
scope: b_e += γ·sign(mean load − load_e), E numbers a layer) ÷ steps.
The reading should be ≈ 0; it is the guard that the rule never becomes
a host read or a second dispatch.  0 where XLA fused the rule into a
neighbour (the operation is then that neighbour's).  Buckets and their
identity: ``unit_attributed_share``.  Nothing where the program hands
out no map, or knows no such phase (the parent of PR 37)."""

from znbench.harness import discovery


def read(obs):
    from znicz_tpu.observe import metrics
    if not hasattr(metrics, "moe_router"):
        return None
    return discovery.load_module(
        "layer_metrics", "unit_attributed_share").ms_per_step(
            obs, "moe", "router_bias")

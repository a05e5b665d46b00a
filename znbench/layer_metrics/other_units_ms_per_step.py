"""Device time per training step of the operations the program's map
puts in exactly one unit of none of the named families (embedding,
positions, evaluator, standalone norms, LRN, pooling, dropout, the
loader, the guard), updates left out, ÷ steps.  Buckets and their
identity: ``unit_attributed_share``.  Nothing where the program hands
out no map."""

from znbench.harness import discovery


def read(obs):
    return discovery.load_module(
        "layer_metrics", "unit_attributed_share").ms_per_step(
            obs, "other")

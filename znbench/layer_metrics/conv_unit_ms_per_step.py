"""Device time of the convolution layers per training step: the self
time of the operations the program's map puts in one unit of a family
``Conv*``, forward + backward, updates left out, ÷ steps.  Buckets and their
identity: ``unit_attributed_share``.  Nothing where the program hands
out no map."""

from znbench.harness import discovery


def read(obs):
    return discovery.load_module(
        "layer_metrics", "unit_attributed_share").ms_per_step(
            obs, "conv")

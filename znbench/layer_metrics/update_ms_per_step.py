"""Device time of the parameter updates per training step: the self
time of the operations the program's map puts in phase ``update`` or
``fingerprint`` of any unit (``_apply_param_xla``'s scope: momentum,
decay, clip, the guard's select, the SDC folds) ÷ steps.  Weight-sized
work that multiplies nothing.  Buckets and their
identity: ``unit_attributed_share``.  Nothing where the program hands
out no map."""

from znbench.harness import discovery


def read(obs):
    return discovery.load_module(
        "layer_metrics", "unit_attributed_share").ms_per_step(
            obs, "update")

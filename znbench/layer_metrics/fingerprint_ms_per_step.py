"""Device time of the SDC fingerprint folds per training step: the self
time of the operations the program's map puts in phase ``fingerprint``
(``_fold_fingerprint``'s scope inside ``update``: three sampled
gathers a parameter tensor) ÷ steps.  A part of ``update_ms_per_step``;
tensor-sized work here is the relayout PR 26 removed.  Buckets and their
identity: ``unit_attributed_share``.  Nothing where the program hands
out no map."""

from znbench.harness import discovery


def read(obs):
    return discovery.load_module(
        "layer_metrics", "unit_attributed_share").ms_per_step(
            obs, "update", "fingerprint")

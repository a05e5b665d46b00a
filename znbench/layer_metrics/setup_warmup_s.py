"""What is left of set-up after ``initialize:<workflow>`` once the
making of programs is taken out: the step programs' first executions,
the driver's warm-up epochs with their epoch-end reads, the region
unit's making and — in a traced run — the profiler's start.  A row of
the partition in ``setup_initialize_s.py``."""

from znbench.harness import discovery


def read(obs):
    return discovery.load_module(
        "layer_metrics", "setup_initialize_s").row(obs, "warmup")

"""The host drawing random parameters: the summed ``param_fill`` spans
(``RandomGenerator.fill_normal`` / ``fill_uniform``: the draw and its
cast) that begin inside a root ``initialize:<workflow>`` span — a PART
of ``setup_initialize_s``, not beside it.  The counter beside the span
is ``znicz_setup_seconds{phase="param_fill"}``."""

from znbench.harness import discovery


def read(obs):
    return discovery.load_module(
        "layer_metrics", "setup_initialize_s").row(obs, "param_fill")

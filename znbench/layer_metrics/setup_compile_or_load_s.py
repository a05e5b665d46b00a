"""Making the step programs, the compiler's half: between the end of
``initialize:<workflow>`` and the window's open, the time inside a
``jax:backend_compile`` span — XLA compiling, or the executable's load
from JAX's persistent cache, which ends such a span too (the load
itself is ``jax:cache_load``, inside it).  A row of the partition in
``setup_initialize_s.py``; the counter beside the span is
``znicz_setup_seconds{phase="backend_compile"}``."""

from znbench.harness import discovery


def read(obs):
    return discovery.load_module(
        "layer_metrics", "setup_initialize_s").row(obs, "compile_or_load")

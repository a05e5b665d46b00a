"""Making the step programs, the host's half: between the end of
``initialize:<workflow>`` and the window's open, the time inside a
``jax:trace`` or ``jax:lower`` span (JAX's own stamps of tracing a
function to a jaxpr and lowering it to a module, Pallas kernel bodies
included) and inside no backend compile.  A row of the partition in
``setup_initialize_s.py``; the counters beside the spans are
``znicz_setup_seconds{phase="trace"}`` and ``{phase="lower"}``."""

from znbench.harness import discovery


def read(obs):
    return discovery.load_module(
        "layer_metrics", "setup_initialize_s").row(obs, "trace_lower")

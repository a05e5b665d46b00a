"""XLA programs built or loaded inside the window — must be 0: every
shape is warmed in set-up.  ``jax.monitoring`` backend-compile events
plus the program's own ``znicz_xla_compiles_total``."""


def read(obs):
    return (obs.counters["jax_programs"]
            + obs.counters["znicz_xla_compiles_total"])

"""Region dispatches per training step: the program's dispatch spans
(``chunk:<region>`` per scanned chunk, the region unit's fire per
step) over ``znicz_region_steps_total``.  1/16 where 16 steps are
scanned per dispatch, 1 under ``wf.run()``."""


def read(obs):
    steps = obs.counters.get("znicz_region_steps_total", 0)
    if not steps:
        return None
    return obs.observations["dispatches"] / steps

"""Share of the window the training step spent blocked on the input
pipeline: the sum of ``znicz_input_wait_seconds`` over the window.  0
where the schedule is resident in HBM."""


def read(obs):
    return 100.0 * obs.counters.get("znicz_input_wait_seconds", 0.0) \
        / obs.window_s

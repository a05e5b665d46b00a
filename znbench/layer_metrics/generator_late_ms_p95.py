"""How late the benchmark's own generator sent: submit time minus due
time, 95th percentile.  Must stay far below ``ttft_p95_ms``, or the
tails measure the generator."""


def read(obs):
    return obs.observations.get("late_p95_ms")

"""The load generator: everything from the seed, sent on schedule,
timed from the due time, never retried."""

import concurrent.futures
import time

import numpy as np

from znbench.harness import openloop
from znbench.harness.result import percentile

#: ISSUE 22's interactive mix: what the generator has to read
TRAFFIC = {
    "arrivals": {"rate_per_s": 40.0},
    "prompt_tokens": {"median": 256, "sigma": 1.0, "min": 16,
                      "max": 1024},
    "output_tokens": {"median": 64, "sigma": 0.7, "min": 8, "max": 256}}


def null_span(_name):
    import contextlib
    return contextlib.nullcontext()


def test_same_seed_same_traffic_other_seed_other_traffic():
    a = openloop.make_schedule(TRAFFIC, 32768, 7, 10.0, rate=50.0)
    b = openloop.make_schedule(TRAFFIC, 32768, 7, 10.0, rate=50.0)
    c = openloop.make_schedule(TRAFFIC, 32768, 8, 10.0, rate=50.0)
    assert [r.due for r in a] == [r.due for r in b]
    assert all((x.prompt == y.prompt).all() and x.max_new == y.max_new
               for x, y in zip(a, b))
    assert [r.due for r in a] != [r.due for r in c]


def test_arrivals_and_lengths_follow_the_file():
    schedule = openloop.make_schedule(TRAFFIC, 32768, 3, 100.0)
    assert 3600 < len(schedule) < 4400          # Poisson at 40/s
    gaps = np.diff([r.due for r in schedule])
    assert 0.9 < gaps.std() / gaps.mean() < 1.1  # exponential gaps
    due = [r.due for r in schedule]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < 100.0
    prompt = np.array([r.prompt.size for r in schedule])
    output = np.array([r.max_new for r in schedule])
    spec_p, spec_o = TRAFFIC["prompt_tokens"], TRAFFIC["output_tokens"]
    assert prompt.min() >= spec_p["min"] and prompt.max() <= spec_p["max"]
    assert output.min() >= spec_o["min"] and output.max() <= spec_o["max"]
    assert abs(np.median(prompt) - spec_p["median"]) < 0.1 * spec_p["median"]
    assert abs(np.median(output) - spec_o["median"]) < 0.1 * spec_o["median"]
    assert all(0 <= r.prompt.min() and r.prompt.max() < 32768
               for r in schedule)


class FakeEngine:
    """Answers after ``service_s`` on a worker thread; refuses every
    ``refuse_every``-th request."""

    def __init__(self, service_s=0.002, refuse_every=0):
        self.pool = concurrent.futures.ThreadPoolExecutor(4)
        self.service_s, self.refuse_every = service_s, refuse_every
        self.calls = 0

    def submit(self, prompt, max_new_tokens):
        self.calls += 1
        if self.refuse_every and self.calls % self.refuse_every == 0:
            raise RuntimeError("QueueFull")
        future = concurrent.futures.Future()

        def work():
            time.sleep(self.service_s)
            future.ttft_s = self.service_s / 2
            future.set_result(np.zeros(max_new_tokens, np.int32))
        self.pool.submit(work)
        return future


def test_open_loop_sends_on_schedule_and_times_from_due():
    schedule = openloop.make_schedule(TRAFFIC, 100, 5, 0.5, rate=200.0)
    engine = FakeEngine()
    t0 = openloop.run_open(schedule, engine.submit, null_span)
    assert all(r.ok for r in schedule)
    sent = np.array([r.t_submit - t0 for r in schedule])
    due = np.array([r.due for r in schedule])
    assert (sent >= due - 1e-4).all()              # never early
    assert percentile(sent - due, 95) < 0.02       # and not late
    one = schedule[0]
    assert one.ttft_from_due_s == one.late_s + one.ttft_s
    assert one.tpot_s > 0 and one.n_tokens == one.max_new


def test_a_slow_server_does_not_slow_the_generator():
    schedule = openloop.make_schedule(TRAFFIC, 100, 5, 0.3, rate=100.0)
    engine = FakeEngine(service_s=0.2)             # 4 workers: backlog
    t0 = openloop.run_open(schedule, engine.submit, null_span)
    last_sent = max(r.t_submit for r in schedule) - t0
    assert last_sent < 0.35                        # open, not closed
    assert max(r.t_done for r in schedule) - t0 > 0.5


def test_a_refusal_is_a_failure_and_is_not_retried():
    schedule = openloop.make_schedule(TRAFFIC, 100, 5, 0.2, rate=100.0)
    engine = FakeEngine(refuse_every=3)
    openloop.run_open(schedule, engine.submit, null_span)
    assert engine.calls == len(schedule)           # one call each
    refused = [r for r in schedule if not r.ok]
    assert len(refused) == len(schedule) // 3
    assert all("QueueFull" in r.error for r in refused)

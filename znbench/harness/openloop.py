"""The load generator: one general reader of a traffic mix's data file.

A mix is parameters, not code: the rate of a Poisson arrival process,
and log-normal prompt and output lengths (median, sigma, clipped).
Everything is drawn at random from ``--seed``; the program receives only
the generated inputs.  Every prompt is its own (no shared prefix).

Open loop: a request is sent when it is DUE, whether or not earlier
ones finished, and every time is counted from the due time, so a stall
costs the requests queued behind it.  How late the generator itself ran
is reported (``late_s``): a starved generator must not read as a fast
server.  A refusal is a failure; nothing is retried.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np


@dataclasses.dataclass
class Request:
    index: int
    due: float                  # seconds after the window opens
    prompt: np.ndarray          # int32 token ids
    max_new: int
    # filled in as the request lives
    t_submit: float | None = None     # monotonic
    late_s: float | None = None
    ttft_s: float | None = None       # engine clock, from submit
    t_done: float | None = None       # monotonic, by done-callback
    n_tokens: int = 0
    tokens: np.ndarray | None = None
    error: str | None = None

    @property
    def ok(self) -> bool:
        return self.error is None and self.t_done is not None \
            and self.ttft_s is not None

    @property
    def ttft_from_due_s(self) -> float:
        return self.late_s + self.ttft_s

    @property
    def tpot_s(self) -> float | None:
        if not self.ok or self.n_tokens < 2:
            return None
        first = self.t_submit + self.ttft_s
        return (self.t_done - first) / (self.n_tokens - 1)


def _lengths(rng, n: int, spec: dict) -> np.ndarray:
    """Log-normal lengths with the given median, clipped."""
    raw = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(int)


def make_schedule(traffic: dict, vocab: int, seed: int, seconds: float,
                  rate: float | None = None) -> list[Request]:
    """Every request due in ``[0, seconds)``: Poisson arrival times,
    prompts and output budgets, all from ``seed``."""
    rng = np.random.default_rng([int(seed), 0x5EED])
    rate = float(rate if rate is not None
                 else traffic["arrivals"]["rate_per_s"])
    n_guess = int(seconds * rate * 1.5) + 16
    due = np.cumsum(rng.exponential(1.0 / rate, size=n_guess))
    due = due[due < seconds]
    prompt_len = _lengths(rng, len(due), traffic["prompt_tokens"])
    output_len = _lengths(rng, len(due), traffic["output_tokens"])
    return [Request(i, float(due[i]),
                    rng.integers(0, vocab, size=int(prompt_len[i]),
                                 dtype=np.int32),
                    int(output_len[i]))
            for i in range(len(due))]


def _on_done(request: Request):
    def stamp(future) -> None:
        request.t_done = time.monotonic()
        request.ttft_s = getattr(future, "ttft_s", None)
        exc = future.exception()
        if exc is not None:
            request.error = f"{type(exc).__name__}: {exc}"[:200]
        else:
            request.tokens = np.asarray(future.result())
            request.n_tokens = int(request.tokens.size)
    return stamp


def _send(request: Request, submit, t0: float) -> None:
    request.t_submit = time.monotonic()
    request.late_s = max(0.0, request.t_submit - (t0 + request.due))
    try:
        future = submit(request.prompt, max_new_tokens=request.max_new)
    except Exception as exc:  # noqa: BLE001 — a refusal is a failure
        request.error = f"{type(exc).__name__}: {exc}"[:200]
        return
    future.add_done_callback(_on_done(request))


def run_open(schedule: list[Request], submit, span,
             drain_s: float = 120.0) -> float:
    """Send every request at its due time from this one thread, then
    wait until all have ended (or ``drain_s`` passed).  Returns the
    monotonic time the window opened."""
    t0 = time.monotonic()
    for request in schedule:
        delay = t0 + request.due - time.monotonic()
        if delay > 0:
            with span("znbench.wait_due"):
                time.sleep(delay)
        with span("znbench.submit"):
            _send(request, submit, t0)
    with span("znbench.drain"):
        wait_all(schedule, t0 + schedule[-1].due + drain_s
                 if schedule else t0)
    return t0


def wait_all(requests: list[Request], deadline: float) -> None:
    for request in requests:
        while request.t_done is None and request.error is None \
                and time.monotonic() < deadline:
            time.sleep(0.002)

"""Serving cells on the paged, continuously batched ``DecodeEngine``.

Set-up is what a serving process pays: the workflow of the
configuration's layer table is initialized from ``--seed`` (no
training), exported with ``export_forward``, loaded by
``DecodeEngine(bundle, …)`` with the geometry of the traffic file, and
``start()`` warms every program of the ladders; one small wave of
requests then runs the whole path once.  The window is the load
generator's (``harness/openloop.py``): requests due in
``[0, --seconds)`` sent on schedule, then drained.

End-to-end, all on the benchmark's clock around the engine's own
``Future.ttft_s``: ``ttft_p95_ms`` from the DUE time (generator
lateness + engine TTFT), ``tpot_p50_ms`` / ``tpot_p95_ms`` per request
as (completion − first token) ÷ (tokens − 1), completion stamped by a
done-callback.  A refused, failed or unfinished request is ``failed``.

``correct``, decided after the window: every request due in it
finished with the tokens it asked for; for a seeded sample of those
requests the tokens THE WINDOW produced (whatever shared their steps)
are checked one by one against the plain reference's full forward (the
token is the reference's arg-max, or trails it by no more than a
stated sliver of the logit spread); no program was built in the
window.

``--sweep r1,r2,…`` offers each rate for the window's length in one
process and prints rate → completed, backlog, TTFT, TPOT, lateness:
how the knee in the traffic file was found.
"""

from __future__ import annotations

import json
import os
import threading
import time

import numpy as np

from znbench.harness import discovery, openloop
from znbench.harness.program import (engine_options,
                                     layer_table, make_device)
from znbench.harness.result import Outcome, median, percentile


def export_bundle(ctx, layers: list) -> str:
    """The configuration's model with weights from ``--seed``, as the
    bundle a serving process loads."""
    from znicz_tpu.loader.fullbatch import ArrayLoader
    from znicz_tpu.models.standard_workflow import StandardWorkflow
    from znicz_tpu.utils import prng
    from znicz_tpu.utils.config import root

    config = ctx.cell.config
    root.common.precision_type = config["precision"]["precision_type"]
    prng.seed_all(ctx.seed)
    # the loader only fixes shapes here: nothing is trained
    tokens = np.zeros((2, 16), np.float32)
    wf = StandardWorkflow(
        name=config["workflow"]["name"],
        loader_factory=lambda w: ArrayLoader(
            w, train_data=tokens, train_labels=np.zeros(2, np.int32),
            minibatch_size=2),
        layers=layers, decision_config={"max_epochs": 1})
    with engine_options(config["precision"].get("engine", {})):
        wf.initialize(device=make_device(ctx))
    path = os.path.join(ctx.scratch, "bundle.npz")
    wf.export_forward(path)
    return path


def start_engine(ctx, path: str):
    from znicz_tpu.serving import DecodeEngine
    geometry = dict(ctx.cell.traffic["engine"])
    engine = DecodeEngine(path, device=make_device(ctx), **geometry)
    engine.start()
    if not engine.model.paged:
        raise discovery.BenchmarkError("the decode engine is not paged")
    return engine


class StatsSampler(threading.Thread):
    """Engine ``stats()`` a few times a second, in traced runs only:
    live lanes and the engine's own per-token window."""

    def __init__(self, engine, period_s: float = 0.25) -> None:
        super().__init__(name="znbench-stats", daemon=True)
        self.engine, self.period_s = engine, period_s
        self.samples: list[dict] = []
        self._halt = threading.Event()

    def run(self) -> None:
        while not self._halt.wait(self.period_s):
            stats = self.engine.stats()
            self.samples.append({
                "live_slots": stats["live_slots"],
                "queued": stats["queued_prompts"],
                "pages_used": stats["pages"]["used"],
                "token_ms": stats["token_ms"]})

    def stop(self) -> None:
        self._halt.set()
        self.join()


def summarize(requests: list) -> dict:
    done = [r for r in requests if r.ok and r.n_tokens == r.max_new]
    tpot = [r.tpot_s for r in done if r.tpot_s is not None]
    out = {"attempted": len(requests),
           "failed": len(requests) - len(done)}
    if done:
        ttft = [r.ttft_from_due_s for r in done]
        late = [r.late_s for r in done]
        out.update(
            ttft_p50_ms=1e3 * median(ttft),
            ttft_p95_ms=1e3 * percentile(ttft, 95),
            late_p95_ms=1e3 * percentile(late, 95),
            tokens=sum(r.n_tokens for r in done))
    if tpot:
        out.update(tpot_p50_ms=1e3 * median(tpot),
                   tpot_p95_ms=1e3 * percentile(tpot, 95))
    return out


def check_sample(ctx, path: str, layers: list,
                 requests: list) -> tuple[float, int, int]:
    """Hold the tokens the window generated, for a seeded sample of
    its finished requests, against the plain reference.  Returns the
    worst gap (share of the logit spread), exact matches and tokens."""
    from znicz_tpu.export import read_bundle
    config = ctx.cell.config
    reference = discovery.load_module("reference", config["reference"])
    _manifest, params = read_bundle(path)
    params = {k: np.asarray(v, np.float32) for k, v in params.items()}
    done = [r for r in requests if r.ok]
    n = min(len(done), int(ctx.cell.traffic.get("check_requests", 8)))
    picks = np.random.default_rng([ctx.seed, 0xC4EC]).choice(
        len(done), size=n, replace=False)
    pad = int(ctx.cell.traffic.get("check_pad_tokens", 256))
    worst, exact, total = 0.0, 0, 0
    for req in (done[i] for i in sorted(picks)):
        seq = np.concatenate([req.prompt, req.tokens[:-1]])
        at = np.arange(req.prompt.size - 1, seq.size)
        # causal: zeros appended after the sequence change nothing
        # before them, and a few padded shapes compile a few times
        padded = np.zeros(-(-seq.size // pad) * pad, np.int32)
        padded[:seq.size] = seq
        rows = np.zeros(-(-at.size // 64) * 64, np.int64)
        rows[:at.size] = at
        logits = reference.next_token_logits(
            params, layers, padded, rows)[:at.size]
        best = logits.max(axis=-1)
        spread = best - np.median(logits, axis=-1) + 1e-9
        chosen = logits[np.arange(len(at)), req.tokens]
        worst = max(worst, float(((best - chosen) / spread).max()))
        exact += int((logits.argmax(axis=-1) == req.tokens).sum())
        total += len(at)
    return worst, exact, total


def set_up(ctx):
    layers = layer_table(ctx.cell.config)
    ctx.mark("imports done")
    path = export_bundle(ctx, layers)
    ctx.mark("bundle exported")
    engine = start_engine(ctx, path)
    ctx.mark(f"engine started ({engine.warmup_compiles} programs in "
             f"{engine.warmup_seconds:.1f}s)")
    return layers, path, engine


def warm_wave(ctx, engine, vocab: int) -> None:
    """A handful of requests through the whole path before the
    window (first dispatch of each family, allocator, callbacks)."""
    traffic = ctx.cell.traffic
    n = int(traffic.get("warm_requests", 8))
    wave = openloop.make_schedule(
        traffic, vocab, ctx.seed + 1,
        4.0 * n / float(traffic["arrivals"]["rate_per_s"]))[:n]
    for req in wave:
        req.due = 0.0
    if wave:
        openloop.run_open(wave, engine.submit, ctx.span)
    bad = [r.error for r in wave if not r.ok]
    if bad:
        raise discovery.BenchmarkError(f"warm-up wave failed: {bad[0]}")


def run(ctx) -> Outcome:
    config, traffic = ctx.cell.config, ctx.cell.traffic
    vocab = int(config["input"]["vocab"])
    layers, path, engine = set_up(ctx)
    try:
        warm_wave(ctx, engine, vocab)
        ctx.mark("warm wave served")
        seconds = ctx.seconds
        if ctx.trace:
            seconds = min(seconds, float(traffic.get("trace_seconds", 6)))
        schedule = openloop.make_schedule(traffic, vocab, ctx.seed,
                                          seconds)
        sampler = StatsSampler(engine) if ctx.trace else None
        ctx.open_window()
        if sampler:
            sampler.start()
        openloop.run_open(schedule, engine.submit, ctx.span)
        if sampler:
            sampler.stop()
        ctx.close_window()
        stats = engine.stats()
        summary = summarize(schedule)
    finally:
        engine.shutdown()
    worst, exact, total = check_sample(ctx, path, layers, schedule)
    built = int(ctx.counters["jax_programs"]
                + ctx.counters["znicz_xla_compiles_total"])
    tolerance = float(config["decode_gap_tolerance"])
    notes = [
        f"requests={summary['attempted']} failed={summary['failed']} "
        f"rate={traffic['arrivals']['rate_per_s']}/s "
        f"tokens={summary.get('tokens')} "
        f"ttft_p50_ms={summary.get('ttft_p50_ms')} "
        f"late_p95_ms={summary.get('late_p95_ms')} "
        f"drained_at={ctx.window_s:.2f}s "
        f"engine: served={stats['served']} rejected={stats['rejected']}"
        f" programs={stats['programs_live']} "
        f"warmup_s={stats['warmup_seconds']} "
        f"pages_used={stats['pages']['used']}/{stats['pages']['total']}",
        f"reference: {exact}/{total} tokens are its arg-max, worst gap "
        f"{worst:.2e} of the logit spread (tolerance {tolerance:g})"]
    problems = []
    if summary["failed"]:
        errors = sorted({r.error for r in schedule if r.error})[:3]
        problems.append(f"{summary['failed']} requests failed {errors}")
    if len(schedule) < int(traffic.get("min_requests", 200)) \
            and not ctx.trace:
        problems.append(f"only {len(schedule)} requests in the window")
    if built:
        problems.append(f"{built} programs built in the window")
    if not total:
        problems.append("no generated token was checked")
    if not worst <= tolerance:
        problems.append(f"a token trails the reference's best by "
                        f"{worst:.3g} of the logit spread")
    notes += [f"NOT CORRECT: {p}" for p in problems]
    late = [r.late_s for r in schedule if r.late_s is not None]
    return Outcome(
        correct=not problems,
        attempted=summary["attempted"], failed=summary["failed"],
        end_to_end={k: summary.get(k) for k in
                    ("ttft_p95_ms", "tpot_p50_ms", "tpot_p95_ms")},
        observations={
            "late_p95_ms": 1e3 * percentile(late, 95) if late else None,
            "samples": sampler.samples if sampler else [],
            "max_slots": int(traffic["engine"]["max_slots"])},
        notes=notes)


def sweep(ctx, rates: list[float]) -> None:
    """Offer each rate for ``--seconds`` in one process; print one
    JSON row per rate.  The knee is the highest rate whose backlog
    does not grow (queue empty at the end of the arrivals, TTFT flat)."""
    config, traffic = ctx.cell.config, ctx.cell.traffic
    vocab = int(config["input"]["vocab"])
    _layers, _path, engine = set_up(ctx)
    try:
        warm_wave(ctx, engine, vocab)
        for i, rate in enumerate(rates):
            schedule = openloop.make_schedule(
                traffic, vocab, ctx.seed + i, ctx.seconds, rate=rate)
            backlog = {}

            def probe(at: float) -> None:
                stats = engine.stats()
                backlog[at] = (stats["queued_prompts"],
                               stats["live_slots"])
            timers = [threading.Timer(ctx.seconds * f, probe, (f,))
                      for f in (0.5, 1.0)]
            for timer in timers:
                timer.start()
            t_open = time.monotonic()
            openloop.run_open(schedule, engine.submit, ctx.span,
                              drain_s=30.0)
            for timer in timers:
                timer.join()
            row = {"rate_per_s": rate, **summarize(schedule),
                   "queued_live_at_half": backlog.get(0.5),
                   "queued_live_at_end": backlog.get(1.0),
                   "drain_s": time.monotonic() - t_open - ctx.seconds,
                   "platform": ctx.devices[0].platform}
            print("sweep " + json.dumps(row), flush=True)
    finally:
        engine.shutdown()

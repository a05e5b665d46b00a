#!/usr/bin/env python3
"""Where do a training cell's SEGMENTS differ from one another?
(PERF.md §6, PR 31: ``olmo_hybrid_train_4of32``'s segments take one of
two times, 2.145 or 2.157 s, and ``throughput`` is the median segment.)

    chiprun --timeout 1200 -- python3 benchmarks/segment_levels.py \
        --workload olmo_hybrid_train_4of32 --seed <n> --segments 30 \
        --traced 12 --out chiprun_out/levels_<n>.json

Builds the cell as ``znbench/drivers/train.py`` does, warms up, then
runs ``--segments`` segments the way ``measure`` does (epochs, then the
fence) and keeps for each its wall time, what the operating system
charged the thread and the process, and the program's own spans by
name (``dispatch:<region>``, ``host_read:<vector>``, ``decision`` …:
sums, and each step's dispatch).  Then ``--traced`` more under the
profiler, and for those also what the DEVICE did: each execution of
the step program (the ``XLA Modules`` line) with the idle time before
it, and the self time of every operation inside it.  The segments are
split at the middle of their range (one above 1.2 × the median — the
SDC vote — is left out) and the two halves compared: which span, which
gap, which operation carries the difference.  One JSON object, to
``--out`` and its summary to the output.
"""

from __future__ import annotations

import argparse
import collections
import cProfile
import glob
import json
import os
import pstats
import re
import resource
import statistics
import sys
import tempfile
import time

T_START = time.perf_counter()
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def spans_since(mark: int) -> list:
    from znicz_tpu.observe import tracing
    return [ev for ev in tracing.TRACER.to_chrome_trace(
        since=mark)["traceEvents"] if ev.get("ph") == "X"]


def host_use() -> dict:
    """What the operating system has charged so far: CPU seconds of
    this thread and of the whole process, the thread's context switches
    (voluntary: it waited; involuntary: it was taken off its core) and
    page faults."""
    mine = resource.getrusage(resource.RUSAGE_THREAD)
    everyone = resource.getrusage(resource.RUSAGE_SELF)
    return {"thread_cpu_ms": (mine.ru_utime + mine.ru_stime) * 1e3,
            "thread_sys_ms": mine.ru_stime * 1e3,
            "thread_waits": mine.ru_nvcsw, "thread_preempted": mine.ru_nivcsw,
            "thread_faults": mine.ru_minflt,
            "process_cpu_ms": (everyone.ru_utime + everyone.ru_stime) * 1e3,
            "process_sys_ms": everyone.ru_stime * 1e3,
            "process_faults": everyone.ru_minflt}


def own_times(profile: cProfile.Profile, n: int = 40) -> dict:
    """The ``n`` functions with the most time of their own, ms."""
    rows = pstats.Stats(profile).stats.items()
    top = sorted(rows, key=lambda row: -row[1][2])[:n]
    return {f"{os.path.basename(file)}:{line}({name})": own * 1e3
            for (file, line, name), (_, _, own, _, _) in top}


def run_segment(trainer, per_segment: int, profile: bool = False) -> dict:
    from znicz_tpu.observe import tracing
    mark = tracing.TRACER.mark()
    profiler = cProfile.Profile() if profile else None
    before = host_use()
    t0 = time.perf_counter()
    if profiler:
        profiler.enable()
    for _ in range(per_segment):
        trainer.epoch()
    if profiler:
        profiler.disable()
    t_fence = time.perf_counter()
    trainer.fence()
    t1 = time.perf_counter()
    by_name = collections.Counter()
    dispatches = []
    for ev in spans_since(mark):
        # "epoch:7" → "epoch": a span that carries its count is one kind
        by_name[re.sub(r":\d+$", "", ev["name"])] += \
            ev.get("dur", 0.0) / 1e3
        if ev["name"].startswith("dispatch:"):
            dispatches.append(round(ev.get("dur", 0.0) / 1e3, 3))
    after = host_use()
    return {"t0": t0, "t1": t1, "wall_ms": (t1 - t0) * 1e3,
            "fence_ms": (t1 - t_fence) * 1e3,
            "host_use": {k: round(after[k] - before[k], 3)
                         for k in after},
            "own_ms": own_times(profiler) if profiler else {},
            "spans_ms": dict(by_name), "dispatch_ms": dispatches}


def device_side(xplane: str) -> list:
    """Each execution of a program on the device, in order: its name,
    start, length, the idle time since the one before it, and the self
    time of its operations by name (ns)."""
    from znbench import trace_reduce as tr
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane)
    plane = next((p for p in data.planes
                  if p.name.startswith(tr.DEVICE_PLANE_PREFIX)), None)
    if plane is None:             # off the chip: host spans only
        return []
    lines = {line.name: line for line in plane.lines}
    modules = sorted(
        (int(ev.start_ns), int(ev.start_ns) + int(ev.duration_ns),
         ev.name) for ev in lines["XLA Modules"].events)
    ops = tr.self_times(tr.Lane(tr._events(lines["XLA Ops"])))
    out, at, last_end = [], 0, None
    ops = sorted(ops, key=lambda row: row[0].start)
    for start, end, name in modules:
        inside = collections.Counter()
        while at < len(ops) and ops[at][0].start < end:
            event, own, _ = ops[at]
            if event.start >= start:
                inside[event.name] += own
            at += 1
        out.append({"name": name.split("(")[0], "start": start,
                    "dur_ns": end - start,
                    "gap_before_ns": None if last_end is None
                    else start - last_end,
                    "ops_ns": dict(inside)})
        last_end = end
    return out


def halves(segments: list, value=lambda s: s["wall_ms"]) -> tuple:
    """(fast, slow, the cut) of the segments that are no vote."""
    middle = statistics.median(value(s) for s in segments)
    kept = [s for s in segments if value(s) < 1.2 * middle]
    lo, hi = min(map(value, kept)), max(map(value, kept))
    cut = (lo + hi) / 2
    return ([s for s in kept if value(s) <= cut],
            [s for s in kept if value(s) > cut], cut)


def mean_of(segments: list, pick) -> dict:
    total = collections.Counter()
    for seg in segments:
        total.update(pick(seg))
    return {k: v / len(segments) for k, v in total.items()}


def difference(fast: list, slow: list, pick, n: int = 12) -> list:
    a, b = mean_of(fast, pick), mean_of(slow, pick)
    rows = [(b.get(k, 0.0) - a.get(k, 0.0), k, a.get(k, 0.0),
             b.get(k, 0.0)) for k in set(a) | set(b)]
    rows.sort(key=lambda r: -abs(r[0]))
    return [{"what": k, "fast": round(x, 4), "slow": round(y, 4),
             "slow_minus_fast": round(d, 4)} for d, k, x, y in rows[:n]]


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--segments", type=int, default=30)
    parser.add_argument("--traced", type=int, default=0)
    parser.add_argument("--profile", action="store_true",
                        help="cProfile each untraced segment: which "
                             "Python function holds the difference")
    parser.add_argument("--out", default=None)
    parser.add_argument("--toy", action="store_true")
    args = parser.parse_args()

    from znbench.harness import discovery, programs
    from znbench.harness.program import engine_options, layer_table
    from znbench.harness.window import Context
    import znbench.run as bench

    cell = discovery.find_cell(args.workload, toy=args.toy)
    devices = bench.take_devices(cell, args.toy)
    programs.listen()
    driver = discovery.load_module("drivers", cell.driver)
    train = getattr(driver, "train", driver)
    ctx = Context(cell, args.seed, 0.0, False, args.toy, devices,
                  T_START, tempfile.mkdtemp(prefix="znbench-"))
    traffic = cell.traffic
    per_segment = int(traffic["epochs_per_segment"])
    report: dict = {"workload": args.workload, "seed": args.seed,
                    "platform": devices[0].platform}
    with engine_options(traffic.get("engine", {})):
        wf, _ = driver.build(ctx, layer_table(cell.config))
        trainer = train.Trainer(ctx, wf)
        for _ in range(int(traffic.get("warmup_epochs", 2))):
            trainer.epoch()
        trainer.fence()
        ctx.mark("warmed up")
        plain = [run_segment(trainer, per_segment, args.profile)
                 for _ in range(args.segments)]
        ctx.mark(f"{len(plain)} segments")
        traced, executions = [], []
        if args.traced:
            import jax
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            trace_dir = os.path.join(ctx.scratch, "trace")
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            with jax.profiler.TraceAnnotation("levels.window"):
                traced = [run_segment(trainer, per_segment)
                          for _ in range(args.traced)]
            jax.profiler.stop_trace()
            ctx.mark(f"{len(traced)} traced segments")
            xplane = max(glob.glob(os.path.join(
                trace_dir, "**", "*.xplane.pb"), recursive=True),
                key=os.path.getmtime)
            executions = device_side(xplane)
            ctx.mark(f"{len(executions)} executions read")

    fast, slow, cut = halves(plain)
    report["untraced"] = {
        "wall_ms": [round(s["wall_ms"], 2) for s in plain],
        "cut_ms": round(cut, 2), "fast": len(fast), "slow": len(slow),
        "spans_ms": difference(fast, slow, lambda s: s["spans_ms"])
        if fast and slow else [],
        "host_use": difference(fast, slow, lambda s: s["host_use"])
        if fast and slow else [],
        "own_ms": difference(fast, slow, lambda s: s["own_ms"], 25)
        if fast and slow else [],
        "host_use_each": [s["host_use"] for s in plain],
        "dispatch_ms": [s["dispatch_ms"] for s in plain],
        "fence_ms": [round(s["fence_ms"], 3) for s in plain]}
    if traced and executions:
        # the step program is the one that ran most often; a segment's
        # executions are those that START inside it, host clock against
        # device clock matched at the first traced segment's first step
        steps_name = collections.Counter(
            e["name"] for e in executions).most_common(1)[0][0]
        steps = [e for e in executions if e["name"] == steps_name]
        per = len(steps) // len(traced)
        for i, seg in enumerate(traced):
            mine = steps[i * per:(i + 1) * per]
            seg["device"] = {
                "busy_ms": sum(e["dur_ns"] for e in mine) / 1e6,
                "step_ms": [round(e["dur_ns"] / 1e6, 3) for e in mine],
                "gap_ms": [None if e["gap_before_ns"] is None
                           else round(e["gap_before_ns"] / 1e6, 3)
                           for e in mine],
                "first_to_last_ms": (mine[-1]["start"] + mine[-1]["dur_ns"]
                                     - mine[0]["start"]) / 1e6}
            seg["ops_ms"] = {k: v / 1e6 for k, v in sum(
                (collections.Counter(e["ops_ns"]) for e in mine),
                collections.Counter()).items()}
        fast, slow, cut = halves(traced)
        report["traced"] = {
            "steps_program": steps_name, "executions": len(steps),
            "other_programs": sorted({e["name"] for e in executions}
                                     - {steps_name}),
            "wall_ms": [round(s["wall_ms"], 2) for s in traced],
            "device_busy_ms": [round(s["device"]["busy_ms"], 3)
                               for s in traced],
            "first_to_last_ms": [round(s["device"]["first_to_last_ms"], 3)
                                 for s in traced],
            "step_ms": [s["device"]["step_ms"] for s in traced],
            "gap_ms": [s["device"]["gap_ms"] for s in traced],
            "cut_ms": round(cut, 2), "fast": len(fast), "slow": len(slow),
            "spans_ms": difference(fast, slow, lambda s: s["spans_ms"])
            if fast and slow else [],
            "ops_ms": difference(fast, slow, lambda s: s["ops_ms"], 25)
            if fast and slow else [],
            # where a single segment holds both kinds of step
            "dispatch_ms": [s["dispatch_ms"] for s in traced]}
    text = json.dumps(report)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as fh:
            fh.write(text + "\n")
    for key in ("untraced", "traced"):
        part = report.get(key)
        if part:
            print(json.dumps({key: {k: part[k] for k in (
                "wall_ms", "cut_ms", "fast", "slow", "spans_ms",
                "host_use", "own_ms") if k in part}}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

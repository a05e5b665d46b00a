"""Summarize a jax.profiler trace: top device-time sinks by fusion.

Usage: ``python benchmarks/trace_top.py <profile_dir_or_trace.json.gz>
[n_steps] [--spans <host_spans.trace.json | dir>]`` — finds the
newest ``*.trace.json.gz`` under the directory, sums durations of
device-lane events by name, and prints the top entries (total ms,
ms/step when ``n_steps`` given, % of device total).  This is how
PERF.md's "named sinks" tables are made.

Collective ops (all-reduce / reduce-scatter / all-gather /
collective-permute/ppermute and their async start/done halves) are
additionally rolled into a **comms** bucket, printed as one
comm-vs-compute split line — the attribution needed to read the
ZeRO-1 (round 7) update-path traces: the reduce-scatter + all-gather
pair must show up as comm time halved against the replicated
all-reduce, not smeared into the fusion names.

``--requests`` (round 24) switches to the REQUEST-trace reader: the
input is a Chrome-trace JSON from ``/trace.json`` (or
``SpanTracer.export``), and the summary groups ``cat="request"``
spans by their ``trace_id`` — one parented span tree per request,
minted at ``submit()`` and threaded through every hop — then prints
the per-phase latency decomposition (queue vs prefill vs handoff vs
decode, p50/p95/p99), outcome counts, event counts (handoff drops,
breaker sheds, deadline evictions) and the slowest requests with
their per-phase breakdown.  This is how "where does my p99 live" is
read off a serving process.

``--spans`` (round 9) merges a HOST-span file — the
``host_spans.trace.json`` that :func:`znicz_tpu.observe.profile_window`
drops beside the device trace, or any Chrome-trace JSON from
``SpanTracer.export`` — into the summary: per-span totals (which
units/epochs/serve batches the host spent its time in) and a combined
comms-vs-compute-vs-host attribution line.  The merge is *aggregate*
(sums over the window): host perf_counter timestamps and device trace
timestamps share no epoch, so timestamp-level alignment is the job of
the profiler UI (TraceAnnotation puts the same spans on the profiler's
host lanes); this summary answers "where did the window's time go"
across both sources in one place.
"""

from __future__ import annotations

import collections
import glob
import gzip
import json
import os
import sys


def find_trace(path: str) -> str:
    if path.endswith(".json.gz"):
        return path
    hits = sorted(glob.glob(
        os.path.join(path, "**", "*.trace.json.gz"), recursive=True),
        key=os.path.getmtime)
    if not hits:
        raise SystemExit(f"no *.trace.json.gz under {path}")
    return hits[-1]


#: substrings classifying a device event as a cross-chip collective
#: (async halves included: "all-reduce-start"/"-done", fusion-wrapped
#: names keep the op substring)
_COMM_OPS = ("all-reduce", "reduce-scatter", "all-gather",
             "collective-permute", "ppermute", "all-to-all",
             "collective-broadcast", "partition-id", "replica-id")


def classify(name: str) -> str:
    low = name.lower()
    for op in _COMM_OPS:
        if op in low:
            return "comms"
    return "compute"


def parse_argv(argv: list) -> tuple:
    """``(positional_args, spans_path, requests_mode)`` — ``--spans``
    may appear anywhere; its value may be the span file or the profile
    dir ``profile_window`` wrote (``host_spans.trace.json`` inside).
    ``--requests`` flips to the request-trace reader (round 24)."""
    spans = None
    requests_mode = False
    rest: list = []
    i = 0
    while i < len(argv):
        if argv[i] == "--spans":
            if i + 1 >= len(argv):
                raise SystemExit("--spans requires a path")
            spans = argv[i + 1]
            i += 2
        elif argv[i] == "--requests":
            requests_mode = True
            i += 1
        else:
            rest.append(argv[i])
            i += 1
    return rest, spans, requests_mode


def load_host_spans(path: str) -> tuple:
    if os.path.isdir(path):
        cand = os.path.join(path, "host_spans.trace.json")
        if not os.path.exists(cand):
            raise SystemExit(f"no host_spans.trace.json under {path}")
        path = cand
    with open(path) as fh:
        data = json.load(fh)
    return path, [ev for ev in data.get("traceEvents", [])
                  if ev.get("ph") == "X"]


def print_span_merge(spans_path: str, device_total: float,
                     device_buckets: "collections.Counter",
                     n_steps: "int | None") -> None:
    """Host-span table + the combined attribution line."""
    spans_path, spans = load_host_spans(spans_path)
    print()
    print(f"host spans: {spans_path}")
    if not spans:
        print("  (no spans recorded — was engine.telemetry off?)")
        return
    by_name: collections.Counter = collections.Counter()
    n_by_name: collections.Counter = collections.Counter()
    for ev in spans:
        ms = ev.get("dur", 0) / 1e3
        by_name[ev["name"]] += ms
        n_by_name[ev["name"]] += 1
    # top-level spans only for the wall accounting: nested spans
    # (units inside a workflow span) would double-count; the
    # profile_window envelope span covers everything and is excluded
    # for the same reason
    top_ms = sum(ev.get("dur", 0) / 1e3 for ev in spans
                 if (ev.get("args") or {}).get("depth", 0) == 0
                 and ev.get("cat") != "profile")
    t0 = min(ev["ts"] for ev in spans) / 1e3
    t1 = max(ev["ts"] + ev.get("dur", 0) for ev in spans) / 1e3
    line = (f"host wall: {t1 - t0:.1f} ms, top-level spans "
            f"{top_ms:.1f} ms over {len(spans)} spans")
    if n_steps:
        line += f" ({(t1 - t0) / n_steps:.3f} ms/step)"
    print(line)
    for name, ms in by_name.most_common(15):
        row = f"{ms:9.1f} ms  {n_by_name[name]:6d}x"
        if n_steps:
            row += f"  {ms / n_steps:7.3f} ms/step"
        print(f"{row}  {name[:60]}")
    comms = device_buckets["comms"]
    compute = device_buckets["compute"]
    # aggregate merge: device busy time attributed by the device
    # trace; whatever host-span time the device cannot account for is
    # the host-side share (dispatch, batching, map/unmap, Python)
    host_gap = max(0.0, top_ms - device_total)
    covered = compute + comms + host_gap
    if covered:
        print(f"combined attribution: device compute {compute:.1f} ms "
              f"({100 * compute / covered:.1f}%) · device comms "
              f"{comms:.1f} ms ({100 * comms / covered:.1f}%) · "
              f"host-side {host_gap:.1f} ms "
              f"({100 * host_gap / covered:.1f}%)")


def _pctl(sorted_vals: list, q: float) -> float:
    """Nearest-rank percentile over an already-sorted list."""
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1,
              max(0, int(round(q / 100 * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


def load_request_trace(path: str) -> list:
    """``cat="request"`` events from a Chrome-trace JSON file (the
    ``/trace.json`` page saved to disk, or ``SpanTracer.export``
    output; a directory means its ``host_spans.trace.json``)."""
    if os.path.isdir(path):
        path = os.path.join(path, "host_spans.trace.json")
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as fh:
        data = json.load(fh)
    return [ev for ev in data.get("traceEvents", [])
            if (ev.get("args") or {}).get("trace_id")]


def summarize_requests(events: list, top: int = 5) -> dict:
    """Group request-scoped spans by trace_id → per-phase p50/p95/p99
    decomposition + outcome/event counts.  Returns the summary dict
    (also printed) so tests and dryruns can assert on it."""
    by_trace: dict = collections.defaultdict(
        lambda: {"phases": {}, "events": [], "outcome": None,
                 "total_ms": None, "name": None})
    phase_ms: dict = collections.defaultdict(list)
    outcomes: collections.Counter = collections.Counter()
    event_counts: collections.Counter = collections.Counter()
    for ev in events:
        args = ev.get("args") or {}
        tid = args["trace_id"]
        rec = by_trace[tid]
        dur_ms = ev.get("dur", 0) / 1e3
        if ev.get("ph") == "X" and int(args.get(
                "parent_span_id", -1)) == 0:
            rec["outcome"] = args.get("outcome", "?")
            rec["total_ms"] = dur_ms
            rec["name"] = ev.get("name")
            outcomes[rec["outcome"]] += 1
        elif ev.get("ph") == "X" and "phase" in args:
            phase = args["phase"]
            rec["phases"][phase] = (rec["phases"].get(phase, 0.0)
                                    + dur_ms)
            phase_ms[phase].append(dur_ms)
        elif ev.get("ph") in ("i", "I"):
            name = ev.get("name", "?")
            rec["events"].append(name)
            event_counts[name] += 1
    print(f"requests: {len(by_trace)} trace(s)  outcomes: "
          + (", ".join(f"{k}={v}" for k, v in sorted(outcomes.items()))
             or "-"))
    print(f"{'phase':>10s} {'count':>7s} {'p50 ms':>9s} "
          f"{'p95 ms':>9s} {'p99 ms':>9s} {'total ms':>10s}")
    decomposition: dict = {}
    for phase in sorted(phase_ms,
                        key=lambda p: -sum(phase_ms[p])):
        vals = sorted(phase_ms[phase])
        row = {"count": len(vals),
               "p50_ms": round(_pctl(vals, 50), 3),
               "p95_ms": round(_pctl(vals, 95), 3),
               "p99_ms": round(_pctl(vals, 99), 3),
               "total_ms": round(sum(vals), 3)}
        decomposition[phase] = row
        print(f"{phase:>10s} {row['count']:7d} {row['p50_ms']:9.3f} "
              f"{row['p95_ms']:9.3f} {row['p99_ms']:9.3f} "
              f"{row['total_ms']:10.3f}")
    for name, count in event_counts.most_common():
        print(f"    {count:6d}x  {name}")
    slowest = sorted(
        ((tid, rec) for tid, rec in by_trace.items()
         if rec["total_ms"] is not None),
        key=lambda kv: -kv[1]["total_ms"])[:top]
    for tid, rec in slowest:
        phases = "  ".join(f"{p}={ms:.2f}ms" for p, ms in
                           sorted(rec["phases"].items(),
                                  key=lambda kv: -kv[1]))
        print(f"  {tid}: {rec['total_ms']:.2f} ms "
              f"[{rec['outcome']}] {phases}"
              + (f"  events={rec['events']}" if rec["events"]
                 else ""))
    return {"requests": len(by_trace), "outcomes": dict(outcomes),
            "phases": decomposition, "events": dict(event_counts)}


def main() -> None:
    args, spans_path, requests_mode = parse_argv(sys.argv[1:])
    if not args:
        raise SystemExit(__doc__.split("\n\n")[1])
    if requests_mode:
        summarize_requests(load_request_trace(args[0]))
        return
    trace = find_trace(args[0])
    n_steps = int(args[1]) if len(args) > 1 else None
    with gzip.open(trace, "rt") as fh:
        data = json.load(fh)
    events = data["traceEvents"]
    # device lanes: pid whose process_name metadata contains TPU/device
    pid_names = {}
    tid_names = {}
    for ev in events:
        if ev.get("ph") == "M" and ev.get("name") == "process_name":
            pid_names[ev["pid"]] = ev["args"].get("name", "")
        if ev.get("ph") == "M" and ev.get("name") == "thread_name":
            tid_names[(ev["pid"], ev["tid"])] = ev["args"].get("name", "")
    device_pids = {pid for pid, name in pid_names.items()
                   if any(t in name.lower()
                          for t in ("tpu", "device", "/device"))}
    by_name: collections.Counter = collections.Counter()
    lane_total: collections.Counter = collections.Counter()
    info: dict = {}
    for ev in events:
        if ev.get("ph") != "X" or ev.get("pid") not in device_pids:
            continue
        lane = tid_names.get((ev["pid"], ev["tid"]), "")
        low = lane.lower()
        # the Modules lane is the program envelope — it double-counts
        # every op; keep only the per-op lane(s)
        if "step" in low or "module" in low:
            continue
        dur = ev.get("dur", 0) / 1e3  # us -> ms
        by_name[ev["name"]] += dur
        lane_total[lane] += dur
        args = ev.get("args") or {}
        if args and ev["name"] not in info:
            src = (args.get("source") or "").rsplit("/", 1)[-1]
            info[ev["name"]] = (
                float(args.get("model_flops") or 0),
                float(args.get("bytes_accessed") or 0),
                src, (args.get("tf_op") or "").strip(": "))
    total = sum(by_name.values())
    print(f"trace: {trace}")
    print(f"device busy: {total:.1f} ms"
          + (f" ({total / n_steps:.3f} ms/step)" if n_steps else ""))
    # comm-vs-compute attribution (the zero1/ring trace reader)
    buckets: collections.Counter = collections.Counter()
    comm_by_op: collections.Counter = collections.Counter()
    for name, ms in by_name.items():
        bucket = classify(name)
        buckets[bucket] += ms
        if bucket == "comms":
            low = name.lower()
            op = next(o for o in _COMM_OPS if o in low)
            comm_by_op[op] += ms
    comms = buckets["comms"]
    if total:
        line = (f"comms: {comms:.1f} ms ({100 * comms / total:.1f}%)  "
                f"compute: {buckets['compute']:.1f} ms "
                f"({100 * buckets['compute'] / total:.1f}%)")
        if n_steps:
            line += f"  [{comms / n_steps:.3f} comm ms/step]"
        print(line)
    for op, ms in comm_by_op.most_common():
        print(f"    {ms:9.1f} ms  {100 * ms / total:5.1f}%  {op}")
    n_events: collections.Counter = collections.Counter()
    for ev in events:
        if ev.get("ph") == "X" and ev.get("pid") in device_pids:
            n_events[ev["name"]] += 1
    for name, ms in by_name.most_common(25):
        line = f"{ms:9.1f} ms  {100 * ms / total:5.1f}%"
        if n_steps:
            line += f"  {ms / n_steps:7.3f} ms/step"
        flops, nbytes, src, tf_op = info.get(name, (0, 0, "", ""))
        count = n_events[name]
        sec = ms / 1e3 / max(count, 1)
        perf = ""
        if flops:
            perf += f"  {flops / sec / 1e12:6.1f} TF/s"
        if nbytes:
            perf += f"  {nbytes / sec / 1e9:6.0f} GB/s"
        print(f"{line}{perf}  {name[:40]:40s} {src:34s} {tf_op[:60]}")
    if spans_path:
        print_span_merge(spans_path, total, buckets, n_steps)


if __name__ == "__main__":
    main()

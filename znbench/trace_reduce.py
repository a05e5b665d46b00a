"""From a profiler trace (``.xplane.pb``) to numbers: device busy time
as the UNION of the intervals in which an operation ran, the idle
share, time per operation, collectives split into hidden and exposed,
and idle gaps attributed to the host span that covers them.

A library, tested on a recorded trace and on synthetic planes
(``znbench/tests/test_trace_reduce.py``).  How it differs from
``benchmarks/trace_top.py``, which it replaces as the yardstick:

- busy is a union of intervals per device, so operations that overlap
  on two lanes are not counted twice and an idle share exists (there:
  the sum of durations over every non-module lane);
- an operation that contains others (a ``while`` around a scanned
  chunk) is a container: only its self time counts, its children count
  for themselves (there: both were summed);
- host spans ride the profiler's own clock (``TraceAnnotation``) or
  are shifted onto it, so a gap is attributed to the span that covers
  it (there: host and device were merged as two aggregates);
- it reads the ``.xplane.pb`` with ``jax.profiler.ProfileData`` and
  returns values (there: a script that prints, reading perfetto JSON).

    python znbench/trace_reduce.py <file.xplane.pb> [--describe]
"""

from __future__ import annotations

import collections
import dataclasses
import sys

#: substrings that make a device operation a cross-chip collective
#: (async halves included; fusion-wrapped names keep the substring).
#: Copied from ``benchmarks/trace_top.py``.
COMM_OPS = ("all-reduce", "reduce-scatter", "all-gather",
            "collective-permute", "ppermute", "all-to-all",
            "collective-broadcast", "partition-id", "replica-id")

DEVICE_PLANE_PREFIX = "/device:TPU:"
#: device lines that hold operations; the others (modules, steps,
#: name scopes, source lines) are groupings of the same time
OP_LINES = ("XLA Ops",)
#: under ``--toy`` there is no device plane: the CPU client's
#: execution threads stand in, so that the reduction is rehearsed
TOY_HOST_PLANE = "/host:CPU"
TOY_OP_LINE_PREFIX = "tf_XLAPjRtCpuClient"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: int          # ns on the profiler's clock
    end: int
    #: the trace's own long name where ``name`` is a shortened one
    #: (a TPU operation is named by its whole HLO line)
    detail: str = ""

    @property
    def dur(self) -> int:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    #: device name → its operation lanes, each a list of events
    devices: dict
    #: host annotations and host-side events of every thread
    host: list

    def window(self, span_name: str) -> tuple[int, int] | None:
        """The interval of the (first) host span of that name."""
        for ev in self.host:
            if ev.name == span_name:
                return ev.start, ev.end
        return None


def is_comm(name: str) -> bool:
    low = name.lower()
    return any(op in low for op in COMM_OPS)


# ----------------------------------------------------------------------
# loading
# ----------------------------------------------------------------------
def short_name(name: str) -> tuple[str, str]:
    """``(name, detail)``: a TPU operation's event is named by its
    whole HLO line (``%fusion.486 = bf16[…] fusion(…), kind=…``); the
    instruction's name stands for it, a custom call keeps its target,
    the line stays as detail."""
    if not name.startswith("%") or " = " not in name:
        return name, ""
    head = name.split(" = ", 1)[0].lstrip("%")
    return head, name


def _events(line) -> list[Event]:
    out = []
    for ev in line.events:
        start = int(ev.start_ns)
        name, detail = short_name(ev.name)
        out.append(Event(name, start, start + int(ev.duration_ns),
                         detail))
    return out


def load(path: str, toy: bool = False) -> Trace:
    """Read an ``.xplane.pb``.  Device planes are ``/device:TPU:<n>``
    and their operation lines are ``OP_LINES``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: dict = {}
    host: list[Event] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            lanes = [Lane(_events(line)) for line in plane.lines
                     if line.name in OP_LINES]
            if lanes:
                devices[plane.name] = lanes
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = _events(line)
                if toy and plane.name == TOY_HOST_PLANE \
                        and line.name.startswith(TOY_OP_LINE_PREFIX):
                    devices.setdefault("toy:cpu", []).append(Lane(
                        e for e in events if e.dur > 0
                        and not e.name.startswith(("end: ",
                                                   "Threadpool"))))
                else:
                    host.extend(events)
    return Trace(devices=devices, host=host)


def describe(path: str, per_line: int = 12) -> str:
    """What a trace holds: planes, lines, event counts and a few
    events with their stats.  Look at one by hand before trusting a
    reduction of it."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    rows = []
    for plane in data.planes:
        rows.append(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            rows.append(f"  LINE {line.name!r} events={len(events)}")
            seen = set()
            for ev in events:
                key = ev.name.split(".")[0]
                if key in seen:
                    continue
                seen.add(key)
                stats = {str(k): str(v)[:100] for k, v in ev.stats}
                rows.append(f"    {ev.name!r} start={ev.start_ns:.0f} "
                            f"dur={ev.duration_ns:.0f} {stats}")
                if len(seen) >= per_line:
                    break
    return "\n".join(rows)


# ----------------------------------------------------------------------
# intervals
# ----------------------------------------------------------------------
def union(intervals) -> list[tuple[int, int]]:
    """Merge overlapping ``(start, end)`` intervals."""
    merged: list[list[int]] = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def total(intervals) -> int:
    return sum(b - a for a, b in intervals)


def clip(intervals, window: tuple[int, int]) -> list[tuple[int, int]]:
    lo, hi = window
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(intervals, holes) -> list[tuple[int, int]]:
    """The parts of merged, sorted ``intervals`` that no merged,
    sorted ``holes`` cover (one pass over both)."""
    out = []
    holes = list(holes)
    j = 0
    for a, b in intervals:
        while j < len(holes) and holes[j][1] <= a:
            j += 1
        cursor, k = a, j
        while k < len(holes) and holes[k][0] < b:
            if holes[k][0] > cursor:
                out.append((cursor, holes[k][0]))
            cursor = max(cursor, holes[k][1])
            k += 1
        if cursor < b:
            out.append((cursor, b))
    return out


class Lane(list):
    """One line of a device plane: a list of events that remembers
    its own reduction (a lane is reduced several times)."""

    reduced: list | None = None


def self_times(lane: list[Event]) -> list[tuple[Event, int, bool]]:
    """:func:`_self_times`, kept on the lane where it is a
    :class:`Lane`."""
    if not isinstance(lane, Lane):
        return _self_times(lane)
    if lane.reduced is None:
        lane.reduced = _self_times(lane)
    return lane.reduced


def _self_times(lane: list[Event]) -> list[tuple[Event, int, bool]]:
    """Per event of one lane: ``(event, self_ns, is_leaf)``.  Events
    of a lane nest (a ``while`` contains its body's operations); an
    event's self time is its duration minus its direct children's."""
    order = sorted(lane, key=lambda e: (e.start, -e.end))
    child_ns = [0] * len(order)
    has_child = [False] * len(order)
    stack: list[int] = []
    for i, ev in enumerate(order):
        while stack and order[stack[-1]].end <= ev.start:
            stack.pop()
        if stack and ev.end <= order[stack[-1]].end:
            parent = stack[-1]
            child_ns[parent] += ev.dur
            has_child[parent] = True
        stack.append(i)
    return [(ev, max(0, ev.dur - child_ns[i]), not has_child[i])
            for i, ev in enumerate(order)]


# ----------------------------------------------------------------------
# reductions
# ----------------------------------------------------------------------
def _leaves(lanes) -> list[Event]:
    return [ev for lane in lanes
            for ev, _self, leaf in self_times(lane) if leaf]


def busy_intervals(lanes, window=None) -> list[tuple[int, int]]:
    """Where an operation ran on one device: the union over its lanes
    of the leaf operations (containers only group their children)."""
    merged = union((e.start, e.end) for e in _leaves(lanes))
    return clip(merged, window) if window else merged


def busy(trace: Trace, window=None) -> dict:
    """Seconds busy per device, their mean, the window and the idle
    share (1 − mean busy ÷ window)."""
    window = window or trace_span(trace)
    per_device = {name: total(busy_intervals(lanes, window)) / 1e9
                  for name, lanes in trace.devices.items()}
    window_s = (window[1] - window[0]) / 1e9
    mean = (sum(per_device.values()) / len(per_device)
            if per_device else 0.0)
    return {"per_device_s": per_device, "busy_s": mean,
            "window_s": window_s,
            "idle_share": (1.0 - mean / window_s) if window_s else None}


def trace_span(trace: Trace) -> tuple[int, int]:
    """First start to last end of every device event."""
    events = [e for lanes in trace.devices.values()
              for lane in lanes for e in lane]
    if not events:
        return (0, 0)
    return min(e.start for e in events), max(e.end for e in events)


def op_seconds(trace: Trace, window=None) -> collections.Counter:
    """Self seconds per operation name, averaged over the devices."""
    out: collections.Counter = collections.Counter()
    n = max(1, len(trace.devices))
    for lanes in trace.devices.values():
        for lane in lanes:
            for ev, self_ns, _leaf in self_times(lane):
                if window and (ev.end <= window[0]
                               or ev.start >= window[1]):
                    continue
                out[ev.name] += self_ns / 1e9 / n
    return out


def top_ops(trace: Trace, n: int = 10, window=None) -> list:
    return [[name, seconds] for name, seconds
            in op_seconds(trace, window).most_common(n)]


def details(trace: Trace) -> dict:
    """Operation name → the trace's own long name (the HLO line)."""
    return {ev.name: ev.detail for lanes in trace.devices.values()
            for lane in lanes for ev in lane}


def matching_seconds(trace: Trace, predicate, window=None) -> float:
    """Self seconds of the operations whose ``(name, detail)``
    ``predicate`` accepts, averaged over the devices."""
    detail = details(trace)
    return sum(seconds for name, seconds
               in op_seconds(trace, window).items()
               if predicate(name, detail.get(name, "")))


def _comm_intervals(lane: list[Event]) -> list[tuple[int, int]]:
    """Collective intervals of one lane.  A synchronous collective is
    its own interval; an async pair runs from the start of
    ``<op>-start`` to the end of the ``<op>-done`` that follows it
    (first in, first out per kind of collective)."""
    out = []
    pending: dict[str, collections.deque] = collections.defaultdict(
        collections.deque)
    for ev, _self, leaf in self_times(lane):
        if not leaf or not is_comm(ev.name):
            continue
        low = ev.name.lower()
        kind = next(op for op in COMM_OPS if op in low)
        if "-start" in low:
            pending[kind].append(ev)
        elif "-done" in low and pending[kind]:
            out.append((pending[kind].popleft().start, ev.end))
        else:
            out.append((ev.start, ev.end))
    for queue in pending.values():      # a start the window cut off
        out.extend((ev.start, ev.end) for ev in queue)
    return out


def collectives(trace: Trace, window=None) -> dict:
    """Seconds of collectives per device (mean over devices), and the
    part of them during which no compute operation ran on that device
    (exposed); the rest is hidden behind compute."""
    totals, exposed = [], []
    for lanes in trace.devices.values():
        comm = union(i for lane in lanes for i in _comm_intervals(lane))
        compute = union((e.start, e.end) for e in _leaves(lanes)
                        if not is_comm(e.name))
        if window:
            comm, compute = clip(comm, window), clip(compute, window)
        totals.append(total(comm) / 1e9)
        exposed.append(total(subtract(comm, compute)) / 1e9)
    n = max(1, len(totals))
    comm_s, exposed_s = sum(totals) / n, sum(exposed) / n
    return {"comm_s": comm_s, "exposed_s": exposed_s,
            "hidden_s": comm_s - exposed_s,
            "exposed_share": exposed_s / comm_s if comm_s else 0.0}


#: gaps attributed one by one; the rest are lumped as "(short gaps)"
MAX_GAPS = 5000


def idle_gaps(trace: Trace, host_spans: list[Event], window=None,
              n: int = 10, ignore: tuple = ()) -> list:
    """The device's idle time by what the host was doing: every gap
    between busy intervals (of the first device) goes to the innermost
    host span that covers its middle, ``"(no span)"`` where none does.
    Returns ``[[span name, seconds], ...]``, the largest first."""
    import numpy as np
    if not trace.devices:
        return []
    window = window or trace_span(trace)
    lanes = next(iter(trace.devices.values()))
    gaps = sorted(subtract([window], busy_intervals(lanes, window)),
                  key=lambda g: g[0] - g[1])      # longest first
    spans = sorted((s for s in host_spans if s.name not in ignore),
                   key=lambda s: s.dur)          # innermost first
    starts = np.asarray([s.start for s in spans], np.int64)
    ends = np.asarray([s.end for s in spans], np.int64)
    by_name: collections.Counter = collections.Counter()
    for a, b in gaps[:MAX_GAPS]:
        middle = (a + b) // 2
        covers = np.flatnonzero((starts <= middle) & (middle < ends))
        owner = spans[covers[0]].name if covers.size else "(no span)"
        by_name[owner] += (b - a) / 1e9
    rest = sum(b - a for a, b in gaps[MAX_GAPS:]) / 1e9
    if rest:
        by_name["(short gaps)"] += rest
    return [[name, seconds] for name, seconds in by_name.most_common(n)]


def main(argv: list[str]) -> int:
    if not argv:
        print(__doc__)
        return 2
    if "--describe" in argv:
        print(describe(argv[0]))
        return 0
    trace = load(argv[0], toy="--toy" in argv)
    window = trace.window("znbench.window")
    print("devices:", list(trace.devices))
    print("busy:", busy(trace, window))
    print("collectives:", collectives(trace, window))
    for name, seconds in top_ops(trace, 20, window):
        print(f"  {seconds * 1e3:10.3f} ms  {name}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

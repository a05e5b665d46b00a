"""Where ``setup_s`` goes: the ``initialize:<workflow>`` spans, whole —
every unit's ``initialize``, so the parameters' draw on the host
(``setup_param_fill_s``), the copies up (``setup_upload_s``) and any
small program a unit jits while it initializes are IN this row, not
beside it.

This file also holds what the seven ``setup_*_s`` readers share: the
partition of ONE stretch of ONE clock, from the OS's start of the
process (``observe.tracing.process_start_us``) to the start of the
``znbench.window`` span, read off the program's own span ring.  Every
instant of the stretch goes into exactly one of five rows:

- ``preprogram``: before the first root ``initialize:<workflow>`` span
  opens — the interpreter, the imports, the runtime reaching the chip,
  and what the caller does before it initializes (the drivers draw
  their data there);
- ``initialize``: inside a root ``initialize:<workflow>`` span;
- ``compile_or_load``: after the first root opened, outside the roots,
  inside a ``jax:backend_compile`` span (a load from JAX's cache ends
  one too);
- ``trace_lower``: likewise inside a ``jax:trace`` or ``jax:lower``
  span and no backend compile;
- ``warmup``: the rest — first executions, the warm-up epochs with
  their epoch-end reads, the profiler's start in a traced run.

So the five sum to the stretch exactly, in whole nanoseconds, and the
stretch is the run's own ``setup_s`` plus the few tens of milliseconds
between the process's start and ``run.py``'s first line.  The rows are
unions of intervals, so a span that lies inside another of its row is
not counted twice.  ``param_fill`` and ``upload`` are sums of spans
that begin inside a root: parts of ``initialize``.

The readers read the WHOLE ring (``Context.program_spans`` hands over
only what came after the window opened) and return nothing where it
has wrapped, where the program records no ``initialize:`` span or does
not know its process's start (a program from before PR 48), or where
the run has no window span (untraced).
"""

import time

from znbench import trace_reduce
from znbench.harness.window import WINDOW_SPAN

ROWS = ("preprogram", "initialize", "trace_lower", "compile_or_load",
        "warmup")


def ring() -> tuple | None:
    """``(spans, process start)`` of the running process: every
    complete span of the ring as ``(name, args, t0, t1)`` in whole ns
    on this process's ``perf_counter``, or ``None`` where the ring is
    not whole or the program cannot say when its process began."""
    from znicz_tpu.observe import tracing
    start_us = getattr(tracing, "process_start_us", None)
    dropped = getattr(tracing.TRACER, "dropped", None)
    if start_us is None or dropped is None or dropped():
        return None
    shift_us = time.perf_counter() * 1e6 - tracing.now_us()
    spans = []
    for ev in tracing.TRACER.to_chrome_trace()["traceEvents"]:
        if ev.get("ph") != "X":
            continue
        t0 = ev["ts"] + shift_us
        spans.append((ev["name"], ev.get("args", {}),
                      round(t0 * 1e3),
                      round((t0 + ev.get("dur", 0.0)) * 1e3)))
    return spans, round((start_us() + shift_us) * 1e3)


def split(spans: list, started: int, opened: int) -> dict | None:
    """The rows in ns (and ``param_fill`` / ``upload``, the parts of
    ``initialize``) of a ring's spans, the process's start and the
    window's open, all in ns on one clock."""
    def named(*names) -> list:
        return [(t0, t1) for name, _a, t0, t1 in spans if name in names]

    roots = trace_reduce.union(
        (t0, t1) for name, args, t0, t1 in spans
        if name.startswith("initialize:") and t0 < opened
        and args.get("parent_span_id") == 0)
    if not roots:
        return None
    roots = trace_reduce.clip(roots, (started, opened))
    after = trace_reduce.subtract([(roots[0][0], opened)], roots)
    compiles = trace_reduce.union(named("jax:backend_compile"))
    made = trace_reduce.union(
        named("jax:trace", "jax:lower") + compiles)

    def inside(intervals) -> int:
        """ns of ``after`` that the merged ``intervals`` cover."""
        return trace_reduce.total(after) - trace_reduce.total(
            trace_reduce.subtract(after, intervals))

    def in_roots(prefix: str) -> int:
        return sum(t1 - t0 for name, _a, t0, t1 in spans
                   if name.startswith(prefix) and any(
                       a <= t0 < b for a, b in roots))

    out = {"preprogram": roots[0][0] - started,
           "initialize": trace_reduce.total(roots),
           "compile_or_load": inside(compiles)}
    out["trace_lower"] = inside(made) - out["compile_or_load"]
    out["warmup"] = trace_reduce.total(after) - inside(made)
    out["param_fill"] = in_roots("param_fill")
    out["upload"] = in_roots("upload:")
    return out


def partition(obs) -> dict | None:
    """The rows of this run in seconds (made once per observation)."""
    if "setup_partition" not in obs.__dict__:
        opened = next((t0 for name, t0, _t1 in obs.spans
                       if name == WINDOW_SPAN), None)
        whole = ring() if opened is not None else None
        rows = whole and split(whole[0], whole[1], round(opened * 1e9))
        obs.__dict__["setup_partition"] = rows and {
            row: ns / 1e9 for row, ns in rows.items()}
    return obs.__dict__["setup_partition"]


def row(obs, name: str):
    return (partition(obs) or {}).get(name)


def read(obs):
    return row(obs, "initialize")

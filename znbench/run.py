#!/usr/bin/env python3
"""Run one cell of the benchmark once and print its result line.

    python3 znbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: finds the cell's files by name (``BENCHMARK.json`` →
configuration, traffic mix, driver, per-layer readers), takes the TPU
(no TPU, or fewer chips than the cell asks for: exit 2 and no result
line), keeps JAX's compilation cache in the checkout's ``.jax_cache/``,
lets the driver set up, warm its own shapes, check correctness against
the plain reference and measure for ``--seconds``, and prints as its
LAST line one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` and, traced, ``breakdown``.  ``--trace 0`` gives
the cell's end-to-end metrics (profiler off), ``--trace 1`` its
per-layer metrics from a traced window.

For rehearsal and tests only: ``--toy`` runs the same cell from the toy
data files on whatever platform JAX is pinned to (every line says
which, and the result line carries ``"rehearsal": true``);
``--sweep r1,r2,…`` is the decode driver's knee sweep (a table, not a
result line); ``--keep-trace <dir>`` keeps the ``.xplane.pb``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()      # set-up is counted from here

import argparse                    # noqa: E402
import dataclasses                 # noqa: E402
import os                          # noqa: E402
import shutil                      # noqa: E402
import sys                         # noqa: E402
import tempfile                    # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

from znbench import trace_reduce                           # noqa: E402
from znbench.harness import discovery, programs, result    # noqa: E402
from znbench.harness.window import WINDOW_SPAN, Context    # noqa: E402


def parse(argv: list[str] | None) -> argparse.Namespace:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--toy", action="store_true")
    parser.add_argument("--sweep", default=None)
    parser.add_argument("--keep-trace", default=None)
    return parser.parse_args(argv)


def take_devices(cell, toy: bool) -> list:
    """The chips the cell asks for, or exit 2 without a result line."""
    import jax

    from znicz_tpu.backends import configure_compile_cache
    cache_dir = configure_compile_cache()
    # keep EVERY program, wherever the cache lives: JAX's default
    # floor keeps only programs that took over a second to compile,
    # and which those are changes from run to run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not toy:
        print(f"znbench: JAX found no TPU (platform={platform}); a "
              f"measurement never falls back.  --toy rehearses the "
              f"cell at toy size.", file=sys.stderr)
        raise SystemExit(2)
    if len(devices) < cell.chips:
        print(f"znbench: {cell.name} needs {cell.chips} chips, JAX "
              f"sees {len(devices)}", file=sys.stderr)
        raise SystemExit(2)
    print(f"znbench: workload={cell.name} platform={platform} "
          f"device_kind={devices[0].device_kind!r} "
          f"devices={len(devices)} uses={cell.chips} "
          f"compile_cache={cache_dir}", flush=True)
    return devices[:cell.chips]


@dataclasses.dataclass
class Observation:
    """What a per-layer reader may read: the cell, the window, the
    benchmark's and the program's spans, counter deltas over the
    window, the driver's observations, and the loaded trace."""
    cell: object
    chips: int
    window_s: float
    counters: dict
    spans: list                 # (name, t0, t1), benchmark's own
    program_spans: list         # dicts: name, cat, t0, t1, args
    observations: dict
    device: dict
    peaks: dict | None
    trace: object               # trace_reduce.Trace
    trace_window: tuple | None  # ns on the profiler's clock
    busy: dict


def host_spans_on_trace_clock(ctx: Context, program_spans: list,
                              trace, window) -> list:
    """The benchmark's annotations (already on the profiler's clock)
    and the program's own spans shifted onto it."""
    spans = [e for e in trace.host if e.name.startswith("znbench.")]
    opened = next((t0 for name, t0, _t1 in ctx.spans
                   if name == WINDOW_SPAN), None)
    if opened is None or window is None:
        return spans
    for span in program_spans:
        if span["cat"] in ("request", "epoch", "profile", "workflow"):
            continue            # whole-life spans cover every gap
        start = window[0] + int((span["t0"] - opened) * 1e9)
        end = window[0] + int((span["t1"] - opened) * 1e9)
        spans.append(trace_reduce.Event(span["name"], start, end))
    return spans


def per_layer(ctx: Context, outcome, device: dict) -> tuple:
    """Reduce the trace and run the cell's readers.  Returns
    ``(metrics, breakdown, busy)``."""
    cell = ctx.cell
    trace = trace_reduce.load(ctx.xplane, toy=ctx.toy)
    window = trace.window(WINDOW_SPAN)
    busy = trace_reduce.busy(trace, window)
    peaks = None
    if device["platform"] == "tpu":
        peaks = discovery.peaks_for(device["kind"])
    obs = Observation(
        cell=cell, chips=cell.chips, window_s=ctx.window_s,
        counters=ctx.counters, spans=ctx.spans,
        program_spans=ctx.program_spans(),
        observations=outcome.observations, device=device, peaks=peaks,
        trace=trace, trace_window=window, busy=busy)
    metrics = {}
    for entry in cell.per_layer:
        reader = discovery.load_module("layer_metrics", entry["name"])
        if reader is None:
            raise discovery.BenchmarkError(
                f"no reader znbench/layer_metrics/{entry['name']}.py")
        value = reader.read(obs)
        if value is not None:       # nothing to read: left out
            metrics[entry["name"]] = value
    host = host_spans_on_trace_clock(ctx, obs.program_spans, trace,
                                     window)
    breakdown = {
        "device_ops": trace_reduce.top_ops(trace, 10, window),
        "idle_gaps": trace_reduce.idle_gaps(
            trace, host, window, 10, ignore=(WINDOW_SPAN,)),
    }
    return metrics, breakdown, busy


def main(argv: list[str] | None = None) -> int:
    args = parse(argv)
    try:
        cell = discovery.find_cell(args.workload, toy=args.toy)
    except discovery.BenchmarkError as exc:
        print(f"znbench: {exc}", file=sys.stderr)
        return 2
    devices = take_devices(cell, args.toy)
    programs.listen()
    driver = discovery.load_module("drivers", cell.driver)
    if driver is None:
        print(f"znbench: no driver znbench/drivers/{cell.driver}.py",
              file=sys.stderr)
        return 2
    scratch = tempfile.mkdtemp(prefix="znbench-")
    ctx = Context(cell, args.seed, args.seconds, bool(args.trace),
                  args.toy, devices, T_START, scratch,
                  keep_trace=args.keep_trace)
    try:
        if args.sweep:
            rates = [float(r) for r in args.sweep.split(",")]
            driver.sweep(ctx, rates)
            return 0
        outcome = driver.run(ctx)
        for note in outcome.notes:
            print(f"znbench: {note}", flush=True)
        device = result.device_info(devices)
        units = {m["name"]: m["unit"]
                 for m in cell.end_to_end + cell.per_layer}
        breakdown = None
        if ctx.trace:
            metrics, breakdown, busy = per_layer(ctx, outcome, device)
            device["busy_s"] = busy["busy_s"]
            device["window_s"] = busy["window_s"]
        else:
            metrics = {m["name"]: outcome.end_to_end.get(m["name"])
                       for m in cell.end_to_end}
            metrics["setup_s"] = ctx.setup_s
        print(f"znbench: window_s={ctx.window_s:.3f} "
              f"setup_s={ctx.setup_s:.3f} programs={programs.programs()}"
              f" cache_hits={programs.cache_hits()} "
              f"built_in_window={ctx.counters.get('jax_programs')}",
              flush=True)
        extra = {"rehearsal": True} if args.toy else None
        print(result.result_line(outcome, metrics, units, device,
                                 breakdown, extra), flush=True)
        return 0
    finally:
        shutil.rmtree(scratch, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())

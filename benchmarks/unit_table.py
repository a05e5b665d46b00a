#!/usr/bin/env python3
"""Where a training cell's device time goes, unit by unit (PERF.md §5,
PR 33): one traced run of the cell exactly as ``znbench/run.py`` makes
it — its result line is printed — and then, from the same trace and
the program's own map (``znicz_tpu.observe.op_scopes()``), the table
the per-unit readers condense: family × (forward, backward, ``fused``:
one operation from both), ``update`` with its ``fingerprint`` part,
other, mixed, unattributed, in ms per step beside ``step_device_ms``; the dearest mixed, unattributed and
``other`` operations with their units; and what the map cost (seconds
to read each program's text back and to parse it, the text's size).

    chiprun --timeout 900 -- python3 benchmarks/unit_table.py \\
        --workload <cell> --seed <n> [--out chiprun_out/units_<cell>.json]
    python3 benchmarks/unit_table.py --workload <cell> --toy   # here
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                    # noqa: E402
import collections                 # noqa: E402
import json                        # noqa: E402
import logging                     # noqa: E402
import os                          # noqa: E402
import shutil                      # noqa: E402
import sys                         # noqa: E402
import tempfile                    # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


class Kept(logging.Handler):
    def __init__(self) -> None:
        super().__init__(logging.INFO)
        self.lines: list[str] = []

    def emit(self, record) -> None:
        self.lines.append(record.getMessage())


def dearest(seconds: dict, names: dict, helper, bucket: str, steps: int,
            n: int = 10) -> list:
    rows = [(value, name) for name, value in seconds.items()
            if helper.bucket_of(names.get(name))[0] == bucket]
    out = []
    for value, name in sorted(rows, reverse=True)[:n]:
        entry = names.get(name) or {}
        out.append([name, round(1e3 * value / steps, 3),
                    entry.get("units") or entry.get("unit")])
    return out


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--toy", action="store_true")
    parser.add_argument("--out", default=None)
    args = parser.parse_args()

    import znbench.run as bench
    from znbench import trace_reduce
    from znbench.harness import discovery, programs, result
    from znbench.harness.window import WINDOW_SPAN, Context
    from znicz_tpu import observe

    kept = Kept()
    log = logging.getLogger("znicz_tpu.observe.scopes")
    log.addHandler(kept)
    log.setLevel(logging.INFO)
    cell = discovery.find_cell(args.workload, toy=args.toy)
    devices = bench.take_devices(cell, args.toy)
    programs.listen()
    driver = discovery.load_module("drivers", cell.driver)
    scratch = tempfile.mkdtemp(prefix="znbench-")
    ctx = Context(cell, args.seed, args.seconds, True, args.toy, devices,
                  T_START, scratch)
    try:
        outcome = driver.run(ctx)
        for note in outcome.notes:
            print(f"znbench: {note}", flush=True)
        before = programs.programs()
        started = time.perf_counter()
        scopes = observe.op_scopes()
        asked_s = time.perf_counter() - started
        loaded = programs.programs() - before
        device = result.device_info(devices)
        metrics, breakdown, busy = bench.per_layer(ctx, outcome, device)
        device.update(busy_s=busy["busy_s"], window_s=busy["window_s"])
        units = {m["name"]: m["unit"]
                 for m in cell.end_to_end + cell.per_layer}
        print(result.result_line(outcome, metrics, units, device,
                                 breakdown), flush=True)

        helper = discovery.load_module("layer_metrics",
                                       "unit_attributed_share")
        names = helper.merged(scopes)
        trace = trace_reduce.load(ctx.xplane, toy=args.toy)
        window = trace.window(WINDOW_SPAN)
        seconds = trace_reduce.op_seconds(trace, window)
        steps = outcome.observations["steps"]
        table = helper.split(seconds, names)
        rows: dict = collections.defaultdict(dict)
        for (bucket, phase), value in sorted(table.items()):
            rows[bucket][phase or "all"] = round(1e3 * value / steps, 3)
        report = {
            "workload": args.workload, "seed": args.seed, "steps": steps,
            "ms_per_step": rows,
            "sum_ms_per_step": round(
                1e3 * sum(seconds.values()) / steps, 3),
            "step_device_ms": metrics.get("step_device_ms"),
            "unit_attributed_share": metrics.get("unit_attributed_share"),
            # in a bucket of its own, several units of one bucket too
            "rows_see_share": round(100 * sum(
                value for (bucket, _p), value in table.items()
                if bucket not in ("mixed", "unattributed"))
                / sum(table.values()), 2),
            "programs": {name: len(ops) for name, ops in scopes.items()},
            "op_scopes_s": round(asked_s, 3),
            "programs_built_or_loaded_by_asking": loaded,
            "op_scopes_log": kept.lines,
            "dearest": {bucket: dearest(seconds, names, helper, bucket,
                                        steps)
                        for bucket in ("mixed", "unattributed", "other",
                                       "update")},
        }
        print(json.dumps(report, indent=1), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as fh:
                json.dump(dict(report, op_scopes=scopes,
                               op_ms_per_step={
                                   name: 1e3 * value / steps
                                   for name, value in seconds.items()}),
                          fh)
        return 0
    finally:
        shutil.rmtree(scratch, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())

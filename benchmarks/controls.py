"""The runner of the cells' CONTROLS (``benchmarks/laguna_controls.py``,
``benchmarks/olmo_hybrid_controls.py``): do the limits of a cell's
comparison have teeth at the cell's sizes?

Builds the cell's workflow as its driver does, runs one epoch of steps,
and calls the driver's own ``check`` on it: once with the plain
reference (has to pass), then once per control, the reference replaced
by one that is wrong in a stated way (has to come out as not correct).
The exit code is 0 only if every one of these came out as it has to.

A READING is run and printed the same way and decides nothing: its
line carries ``"reading": true`` and no ``as_expected``, and the exit
code does not know it.  It is for a wrong reference the cell's limits
are known NOT to separate, so that the number is on record beside the
limit it passes under.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import tempfile
import time

T_START = time.perf_counter()


def arguments(doc: str = "") -> argparse.ArgumentParser:
    """``--seed`` and ``--toy``; a cell's script may add its own."""
    parser = argparse.ArgumentParser(description=doc.split("\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--toy", action="store_true")
    return parser


@contextlib.contextmanager
def trained(cell_name: str, args, epochs: int = 1, edit=None):
    """The cell's workflow as its driver builds it — from the layer
    table ``edit`` has changed in place, if given: a SYSTEM made wrong
    in a stated way — after ``epochs`` epochs of steps, under the
    traffic's engine options: ``(ctx, driver, workflow, layers,
    reference, devices)``."""
    from znbench.harness import discovery, programs
    from znbench.harness.program import engine_options, layer_table
    from znbench.harness.window import Context
    import znbench.run as bench

    cell = discovery.find_cell(cell_name, toy=args.toy)
    devices = bench.take_devices(cell, args.toy)
    programs.listen()
    driver = discovery.load_module("drivers", cell.driver)
    reference = discovery.load_module("reference",
                                      cell.config["reference"])
    scratch = tempfile.mkdtemp(prefix="znbench-")
    ctx = Context(cell, args.seed, 0.0, False, args.toy, devices,
                  T_START, scratch)
    layers = layer_table(cell.config)
    if edit is not None:
        edit(layers)
    with engine_options(cell.traffic.get("engine", {})):
        wf, _ = driver.build(ctx, layers)
        trainer = driver.train.Trainer(ctx, wf)
        for _ in range(epochs):
            trainer.epoch()
        trainer.fence()
        yield ctx, driver, wf, layers, reference, devices


def run_checks(cell_name: str, make_checks, make_readings=None,
               doc: str = "", args=None, make_plain=None) -> int:
    """``make_checks(reference, layers, workflow)`` and
    ``make_readings(…)`` give ``(name, module)`` pairs, ``module``
    standing where the driver loads the cell's reference; one JSON line
    each, ``ok`` last.  ``workflow`` is the cell's after its epoch of
    steps, for a control that needs a value no bundle holds (a
    selection bias).  ``args``: :func:`arguments`' as parsed, where the
    script has options of its own.  ``make_plain(…)``: the module of
    the FIRST check, the plain reference that has to pass — the cell's
    own where not given; a cell whose whole stack is minutes of the
    host's time a check gives one that stops where its controls do."""
    from znbench.harness import discovery

    if args is None:
        args = arguments(doc).parse_args()
    load_module = discovery.load_module
    ok = True
    with trained(cell_name, args) as (ctx, driver, wf, layers, reference,
                                      devices):
        cell = ctx.cell
        plain = make_plain(reference, layers, wf) if make_plain else None
        checks = [("reference", plain, False)] + [
            (name, module, False)
            for name, module in make_checks(reference, layers, wf)] + [
            (name, module, True) for name, module in
            (make_readings(reference, layers, wf)
             if make_readings else [])]
        for name, module, reading in checks:
            if module is not None:
                discovery.load_module = (
                    lambda kind, what, module=module: module
                    if kind == "reference" else load_module(kind, what))
            t0 = time.perf_counter()
            try:
                problems, notes = driver.check(ctx, wf, layers)
            finally:
                discovery.load_module = load_module
            said = next((n for n in notes if "worst layer" in n), "")
            line = {"check": name}
            if reading:
                line["reading"] = True
            else:
                good = (not problems) if name == "reference" else any(
                    "forward differs" in p for p in problems)
                ok = ok and good
                line["as_expected"] = good
            line.update({
                "correct": not problems, "problems": problems,
                "layers": said.split("reference: ", 1)[-1],
                "limit": cell.config["reference_tolerance"]["layers"],
                "seq_len": cell.traffic["seq_len"],
                "platform": devices[0].platform,
                "seconds": round(time.perf_counter() - t0, 1)})
            print(json.dumps(line), flush=True)
    print(json.dumps({"ok": ok}), flush=True)
    return 0 if ok else 1

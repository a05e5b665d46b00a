"""Find everything a cell needs by the names in ``BENCHMARK.json``.

A later PR adds a cell, a configuration, a traffic mix, a driver, a
reference or a per-layer metric by adding files and entries; nothing
here lists them:

- the cell is the entry of ``workloads`` with that ``name``;
- its configuration is ``configs[...].file`` (sizes + layer table);
- its traffic mix is ``<data root>/traffic/<traffic>.json`` and names
  its ``driver``;
- the driver is ``znbench/drivers/<driver>.py`` (``run(ctx)``);
- the plain reference is ``znbench/reference/<name>.py``, named by the
  configuration's ``reference`` key;
- a per-layer metric is ``znbench/layer_metrics/<metric>.py``
  (``read(obs)``).

``--toy`` swaps the data root (configuration and traffic files) for
``znbench/tests/data/toy``: the same cells and code at sizes the CPU
finishes in seconds.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(HERE)
TOY_ROOT = os.path.join(HERE, "tests", "data", "toy")


class BenchmarkError(Exception):
    """The benchmark's own files do not hold what a cell needs."""


def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_module(kind: str, name: str):
    """``znbench/<kind>/<name>.py`` as a module, or ``None`` where no
    such file exists."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location(
        f"znbench_{kind}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    why: str
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    #: metric entries of BENCHMARK.json that this cell reports
    end_to_end: list
    per_layer: list

    @property
    def driver(self) -> str:
        return self.traffic["driver"]


def _in_cell(metric: dict, cell_name: str) -> bool:
    cells = metric.get("workloads")
    return cells is None or cell_name in cells


def find_cell(workload: str, toy: bool = False) -> Cell:
    bench = load_json(os.path.join(REPO, "BENCHMARK.json"))
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise BenchmarkError(
            f"no workload {workload!r} in BENCHMARK.json "
            f"(has: {', '.join(sorted(cells))})")
    entry = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config_entry = configs[entry["config"]]
    config_file = os.path.join(REPO, config_entry["file"])
    traffic_file = os.path.join(HERE, "traffic",
                                f"{entry['traffic']}.json")
    if toy:
        config_file = os.path.join(
            TOY_ROOT, "configs", os.path.basename(config_file))
        traffic_file = os.path.join(
            TOY_ROOT, "traffic", os.path.basename(traffic_file))
    for path in (config_file, traffic_file):
        if not os.path.exists(path):
            raise BenchmarkError(f"{workload}: no file {path}")
    end_to_end = [m for m in bench["end_to_end"]
                  if _in_cell(m, workload)]
    per_layer = [m for m in bench["per_layer"]
                 if _in_cell(m, workload)]
    return Cell(name=workload, chips=int(entry["chips"]),
                why=entry["why"], config_name=entry["config"],
                traffic_name=entry["traffic"],
                config=load_json(config_file),
                traffic=load_json(traffic_file),
                end_to_end=end_to_end, per_layer=per_layer)


def peaks_for(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; a device the table does
    not know is an error, never a default."""
    table = load_json(os.path.join(HERE, "peaks.json"))["devices"]
    if device_kind not in table:
        raise BenchmarkError(
            f"no published peaks for device_kind {device_kind!r} in "
            f"znbench/peaks.json (has: {', '.join(sorted(table))}); "
            f"add it with its source")
    return table[device_kind]

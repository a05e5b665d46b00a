"""The seven ``setup_*_s`` readers of PR 48: the partition on hand-built
rings (exact, in whole ns), nothing to read where the ring is not
whole or the program spans no start-up, the entries of
``BENCHMARK.json`` by name, and a traced toy rehearsal of one image
cell and one LM cell."""

import json
import re
import types

import pytest

from znbench.harness import discovery
from znbench.harness.window import WINDOW_SPAN
from test_cells_toy import BENCH, CELLS, run

partition = discovery.load_module("layer_metrics", "setup_initialize_s")
READERS = {"setup_preprogram_s": "preprogram",
           "setup_initialize_s": "initialize",
           "setup_param_fill_s": "param_fill",
           "setup_upload_s": "upload",
           "setup_trace_lower_s": "trace_lower",
           "setup_compile_or_load_s": "compile_or_load",
           "setup_warmup_s": "warmup"}
MS = 1_000_000      # ns


def reader(name):
    return discovery.load_module("layer_metrics", name).read


def span(name, t0, t1, parent=0):
    return (name, {"parent_span_id": parent}, t0 * MS, t1 * MS)


def a_ring() -> list:
    """Set-up as the program spans it, in ms after the process began:
    imports to 1000, initialize 1000–3000 (a unit jits inside it), a
    region's making 3100–3900, warm-up, the window at 5000; then the
    window's own spans, which are not set-up's."""
    return [
        span("initialize:loader", 1000, 1400, parent=1),
        span("param_fill", 1500, 2100, parent=2),
        span("upload:fc.weights", 2100, 2300, parent=2),
        span("jax:trace", 2400, 2450, parent=2),       # inside a root:
        span("jax:backend_compile", 2450, 2500, parent=2),  # initialize
        span("initialize:fc", 1400, 3000, parent=1),
        span("initialize:wf", 1000, 3000),
        span("jax:trace", 3100, 3400, parent=9),
        span("jax:lower", 3400, 3500, parent=9),
        span("jax:cache_load", 3550, 3850, parent=8),  # in the compile
        span("jax:backend_compile", 3500, 3900, parent=9),
        span("compile:train_region", 3090, 4000, parent=7),
        span("upload:evaluator.epoch_loss", 4100, 4101, parent=7),
        span("workflow:wf", 3080, 4200),
        span("upload:evaluator.epoch_loss", 5100, 5101, parent=20),
        span("jax:trace", 5200, 5300),       # the check, after the open
    ]


# ----------------------------------------------------------------------
# the partition
# ----------------------------------------------------------------------
def test_the_five_rows_partition_the_stretch_to_the_nanosecond():
    started, opened = -7 * MS + 3, 5000 * MS + 11
    rows = partition.split(a_ring(), started, opened)
    assert rows == {
        "preprogram": 1000 * MS - started,
        "initialize": 2000 * MS,
        "trace_lower": 400 * MS,
        "compile_or_load": 400 * MS,
        "warmup": opened - 3000 * MS - 800 * MS,
        "param_fill": 600 * MS, "upload": 200 * MS}
    assert sum(rows[r] for r in partition.ROWS) == opened - started


def test_spans_inside_spans_of_a_row_are_counted_once():
    ring = a_ring() + [
        span("jax:trace", 3150, 3200, parent=9),    # inside the trace
        span("jax:lower", 3380, 3420, parent=9),    # across its edge
        span("jax:trace", 3600, 3700, parent=9),    # inside the compile
        span("jax:backend_compile", 3880, 3950),    # across its edge
    ]
    rows = partition.split(ring, 0, 5000 * MS)
    assert rows["trace_lower"] == 400 * MS
    assert rows["compile_or_load"] == 450 * MS
    assert sum(rows[r] for r in partition.ROWS) == 5000 * MS


def test_a_second_root_and_a_nested_workflow():
    ring = a_ring() + [
        span("initialize:inner", 1100, 1300, parent=5),   # nested
        span("initialize:second_wf", 4300, 4500),         # a root
        span("param_fill", 4350, 4400, parent=30),
        span("initialize:third_wf", 5500, 5600),          # the check's
    ]
    rows = partition.split(ring, 0, 5000 * MS)
    assert rows["initialize"] == 2200 * MS
    assert rows["param_fill"] == 650 * MS
    assert rows["preprogram"] == 1000 * MS
    assert sum(rows[r] for r in partition.ROWS) == 5000 * MS


def test_no_root_initialize_span_is_nothing_to_read():
    ring = [s for s in a_ring() if s[0] != "initialize:wf"]
    assert partition.split(ring, 0, 5000 * MS) is None
    # a root that opened after the window did is not set-up's
    assert partition.split([span("initialize:wf", 6000, 7000)],
                           0, 5000 * MS) is None


# ----------------------------------------------------------------------
# the readers on the process's own ring
# ----------------------------------------------------------------------
def observation(opened: float | None):
    spans = [] if opened is None else [(WINDOW_SPAN, opened, opened + 1)]
    return types.SimpleNamespace(spans=spans)


@pytest.fixture()
def tracing(monkeypatch):
    """``observe.tracing`` with a ring of its own."""
    from znicz_tpu.observe import tracing
    monkeypatch.setattr(tracing, "TRACER", tracing.SpanTracer(8))
    return tracing


def fill(tracing, initialize: bool) -> float:
    """Spans on the tracer's own clock; returns a window's open (s on
    ``perf_counter``) that lies after them."""
    import time
    with tracing.TRACER.span("initialize:wf" if initialize
                             else "workflow:wf", cat="setup"):
        with tracing.TRACER.span("param_fill", cat="setup"):
            time.sleep(0.002)
    with tracing.TRACER.span("compile:r", cat="compile"):
        time.sleep(0.001)
        t1 = tracing.now_us()
        tracing.TRACER.complete("jax:backend_compile", t1 - 500.0, t1,
                                cat="compile", nested=True)
    return time.perf_counter()


def test_the_readers_read_the_whole_ring_on_the_windows_clock(tracing):
    import time
    opened = fill(tracing, initialize=True)
    obs = observation(opened)
    rows = {name: reader(name)(obs) for name in READERS}
    assert all(value is not None and value >= 0
               for value in rows.values())
    stretch = opened - (time.perf_counter()
                        - (tracing.now_us()
                           - tracing.process_start_us()) / 1e6)
    assert sum(rows[name] for name, row in READERS.items()
               if row in partition.ROWS) == pytest.approx(stretch,
                                                          abs=1e-4)
    assert rows["setup_param_fill_s"] >= 0.002
    assert rows["setup_initialize_s"] >= rows["setup_param_fill_s"]
    assert rows["setup_compile_or_load_s"] == pytest.approx(
        500e-6, abs=2e-6)
    assert rows["setup_upload_s"] == 0.0


@pytest.mark.parametrize("name", READERS)
def test_a_reader_returns_nothing_where_there_is_nothing_to_read(
        name, tracing, monkeypatch):
    opened = fill(tracing, initialize=False)
    assert reader(name)(observation(opened)) is None     # no root span
    opened = fill(tracing, initialize=True)
    assert reader(name)(observation(None)) is None       # no window
    assert reader(name)(observation(opened)) is not None
    for i in range(8):                                   # wrapped
        tracing.TRACER.instant(f"filler{i}")
    assert tracing.TRACER.dropped() > 0
    assert reader(name)(observation(opened)) is None
    # a program from before PR 48: no start of the process, no count
    # of what the ring dropped
    monkeypatch.setattr(tracing, "TRACER", tracing.SpanTracer())
    opened = fill(tracing, initialize=True)
    monkeypatch.delattr(tracing, "process_start_us")
    assert reader(name)(observation(opened)) is None
    monkeypatch.undo()
    from znicz_tpu.observe import tracing as again
    monkeypatch.setattr(again, "TRACER", types.SimpleNamespace(
        to_chrome_trace=lambda: {"traceEvents": []}))
    assert reader(name)(observation(opened)) is None


# ----------------------------------------------------------------------
# BENCHMARK.json, by name
# ----------------------------------------------------------------------
def test_the_entries_are_in_the_benchmark_by_name():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    layers = {"setup_preprogram_s": "device",
              "setup_initialize_s": "units",
              "setup_param_fill_s": "units",
              "setup_upload_s": "device",
              "setup_trace_lower_s": "fused step",
              "setup_compile_or_load_s": "fused step",
              "setup_warmup_s": "training driver"}
    assert set(layers) == set(READERS)
    for name, layer in layers.items():
        assert entries[name] == {
            "name": name, "unit": "s", "better": "lower",
            "source": "program_span", "layer": layer,
            "moves": "setup_s", "workloads": CELLS}


# ----------------------------------------------------------------------
# the toy rehearsal
# ----------------------------------------------------------------------
@pytest.mark.parametrize("cell", ["alexnet_train_b768",
                                  "olmoe_train_t4096"])
def test_a_traced_rehearsal_reports_all_seven(cell):
    proc, lines = run(["--workload", cell, "--seed", "2147483777",
                       "--seconds", "2", "--trace", "1", "--toy"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    rows = {name: line["metrics"][name]["value"] for name in READERS}
    assert all(line["metrics"][name]["unit"] == "s" for name in READERS)
    assert all(value >= 0 for value in rows.values())
    assert min(rows[n] for n in ("setup_preprogram_s",
                                 "setup_initialize_s",
                                 "setup_param_fill_s", "setup_upload_s",
                                 "setup_trace_lower_s",
                                 "setup_compile_or_load_s")) > 0
    assert rows["setup_param_fill_s"] + rows["setup_upload_s"] \
        <= rows["setup_initialize_s"]
    # the five rows are the run's own setup_s and the interpreter's
    # start before run.py's first line
    said = next(l for l in lines if "setup_s=" in l)
    setup_s = float(re.search(r"setup_s=([0-9.]+)", said).group(1))
    total = sum(rows[name] for name, row in READERS.items()
                if row in partition.ROWS)
    assert 0 <= total - setup_s < 0.5
    # and set-up's spans are no window's: nothing is built in it
    assert line["metrics"]["programs_built_in_window"]["value"] == 0

"""Every driver end to end at toy size on the CPU (kernels
interpreted), the result line's shape, and the refusal to measure
without a TPU."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from znbench.harness import discovery

RUN = os.path.join(discovery.HERE, "run.py")
BENCH = discovery.load_json(os.path.join(discovery.REPO,
                                         "BENCHMARK.json"))
CELLS = [c["name"] for c in BENCH["workloads"]]


def run(args, cwd=None, script=RUN):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, script, *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    return proc, lines


def metric_names(kind, cell):
    return {m["name"] for m in BENCH[kind]
            if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("cell", CELLS)
def test_untraced_run_reports_the_end_to_end_metrics(cell):
    proc, lines = run(["--workload", cell, "--seed", "3", "--seconds",
                       "2", "--trace", "0", "--toy"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(lines[-1])
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "rehearsal"}
    assert line["rehearsal"] is True              # never a measurement
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == metric_names("end_to_end", cell)
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert "platform=cpu" in lines[0]


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_per_layer_metrics_and_a_breakdown(cell):
    proc, lines = run(["--workload", cell, "--seed", "4", "--seconds",
                       "2", "--trace", "1", "--toy"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(lines[-1])
    assert line["correct"] is True
    got = set(line["metrics"])
    assert got <= metric_names("per_layer", cell)
    assert not got & metric_names("end_to_end", cell)
    assert line["metrics"]["programs_built_in_window"]["value"] == 0
    assert line["device"]["busy_s"] > 0
    assert line["device"]["window_s"] >= line["device"]["busy_s"]
    assert 0 < len(line["breakdown"]["device_ops"]) <= 10
    assert len(line["breakdown"]["idle_gaps"]) <= 10
    if "dispatches_per_step" in metric_names("per_layer", cell):
        assert 0 < line["metrics"]["dispatches_per_step"]["value"] <= 1


def test_without_a_tpu_and_without_toy_there_is_no_result_line():
    proc, lines = run(["--workload", CELLS[0], "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    assert proc.returncode != 0
    assert not any(l.startswith("{") for l in lines)
    assert "no TPU" in proc.stderr


def test_a_traffic_mix_sets_engine_options_for_the_whole_run(
        monkeypatch):
    """How often the driver reads the guard's state back is the
    traffic mix's: ``run`` holds its ``engine`` options around set-up,
    window and check."""
    import types

    from znicz_tpu.utils.config import root
    train = discovery.load_module("drivers", "train")
    engine = root.common.engine
    # the program's default, set so that the block has a value to put
    # back and the test leaves the process as it found it
    monkeypatch.setitem(engine.__dict__, "anomaly_check_interval", 1)
    monkeypatch.setattr(train, "measure", lambda ctx: engine.get(
        "anomaly_check_interval"))
    cell = types.SimpleNamespace(
        traffic={"engine": {"anomaly_check_interval": 12}})
    assert train.run(types.SimpleNamespace(cell=cell)) == 12
    assert engine.get("anomaly_check_interval") == 1
    cell.traffic = {}                      # a mix that sets none
    assert train.run(types.SimpleNamespace(cell=cell)) == 1


def test_the_lm_cell_reads_the_guard_once_per_epoch():
    """The cell's point: one dispatch per step, one host wait per
    epoch.  The real-size file and its toy twin both say so."""
    for root_dir in (discovery.HERE, discovery.TOY_ROOT):
        traffic = discovery.load_json(os.path.join(
            root_dir, "traffic", "train_t2048_b32.json"))
        assert traffic["steps_per_dispatch"] == 1
        assert traffic["engine"]["anomaly_check_interval"] \
            == traffic["steps_per_epoch"]


def test_an_unknown_cell_is_refused():
    proc, lines = run(["--workload", "no_such_cell", "--toy"])
    assert proc.returncode != 0 and not lines


SERVING_CELL = {
    "name": "attn_lm_decode_open", "config": "attn_lm_base",
    "traffic": "decode_open", "chips": 1,
    "why": "open loop, Poisson, distinct prompts, greedy, paged engine"}
SERVING_END_TO_END = [("ttft_p95_ms", "ms"), ("tpot_p50_ms", "ms"),
                      ("tpot_p95_ms", "ms")]
SERVING_PER_LAYER = [
    ("decode_step_ms_p50", "ms", "lower", "tpot_p50_ms"),
    ("lanes_busy_share", "%", "higher", "tpot_p50_ms"),
    ("itl_p99_ms", "ms", "lower", "tpot_p95_ms"),
    ("queue_wait_ms_p50", "ms", "lower", "ttft_p95_ms"),
    ("prefill_ms_p50", "ms", "lower", "ttft_p95_ms"),
    ("generator_late_ms_p95", "ms", "lower", "ttft_p95_ms")]


def add_serving_cell(bench: dict, copy) -> None:
    """A serving cell the way a later PR adds one: a traffic file for
    the decode driver, and entries.  (PR 22 ships the driver, the
    generator and the readers, and no serving cell: PERF.md section 7.)"""
    toy = copy / "znbench" / "tests" / "data" / "toy" / "traffic"
    shutil.copy(toy / "decode_fixture.json", toy / "decode_open.json")
    # the real-size twin only has to exist for discovery
    shutil.copy(toy / "decode_fixture.json",
                copy / "znbench" / "traffic" / "decode_open.json")
    name = SERVING_CELL["name"]
    bench["workloads"].append(dict(SERVING_CELL))
    for metric, unit in SERVING_END_TO_END:
        bench["end_to_end"].append({
            "name": metric, "unit": unit, "better": "lower",
            "bound": 0.1, "source": "host_clock", "workloads": [name]})
    for metric, unit, better, moves in SERVING_PER_LAYER:
        bench["per_layer"].append({
            "name": metric, "unit": unit, "better": better,
            "source": "program_span", "layer": "decode serving",
            "moves": moves, "workloads": [name]})
    for metric in bench["per_layer"]:
        if metric["name"] in ("device_idle_share", "peak_hbm_gb"):
            metric["workloads"] = metric.get(
                "workloads", list(CELLS)) + [name]


def test_a_new_cell_config_traffic_and_metric_need_only_new_files(
        tmp_path):
    """Drop a configuration, two traffic mixes, a per-layer reader and
    their entries into a copy; no file that was there is edited.  One
    new cell trains a deeper model; the other is a serving cell on the
    decode driver, with its knee sweep."""
    copy = tmp_path / "checkout"
    copy.mkdir()
    shutil.copytree(discovery.HERE, copy / "znbench", ignore=(
        shutil.ignore_patterns("__pycache__", "out")))
    os.symlink(os.path.join(discovery.REPO, "znicz_tpu"),
               copy / "znicz_tpu")
    before = {p: p.read_bytes() for p in (copy / "znbench").rglob("*")
              if p.is_file()}
    toy = copy / "znbench" / "tests" / "data" / "toy"
    config = json.loads((toy / "configs" / "attn_lm_base.json")
                        .read_text())
    config["workflow"]["layers"][2]["repeat"] = 3      # a deeper model
    (toy / "configs" / "attn_lm_deep.json").write_text(
        json.dumps(config))
    traffic = json.loads((toy / "traffic" / "train_t2048_b32.json")
                         .read_text())
    traffic["seq_len"] = 16
    (toy / "traffic" / "train_t16.json").write_text(json.dumps(traffic))
    # the real-size twins only have to exist for discovery
    shutil.copy(toy / "configs" / "attn_lm_deep.json",
                copy / "znbench" / "configs" / "attn_lm_deep.json")
    shutil.copy(toy / "traffic" / "train_t16.json",
                copy / "znbench" / "traffic" / "train_t16.json")
    (copy / "znbench" / "layer_metrics" / "segments_run.py").write_text(
        '"""How many fenced segments the window held."""\n\n\n'
        "def read(obs):\n    return obs.observations['segments']\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({
        "name": "attn_lm_deep", "source": "a test",
        "file": "znbench/configs/attn_lm_deep.json", "reduced": [],
        "why": "a test"})
    bench["workloads"].append({
        "name": "attn_lm_deep_t16", "config": "attn_lm_deep",
        "traffic": "train_t16", "chips": 1, "why": "a test"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in metric and "attn_lm_train_t2048" \
                in metric["workloads"]:
            metric["workloads"].append("attn_lm_deep_t16")
    bench["per_layer"].append({
        "name": "segments_run", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "training driver",
        "moves": "throughput", "workloads": ["attn_lm_deep_t16"]})
    add_serving_cell(bench, copy)
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))
    script = str(copy / "znbench" / "run.py")

    proc, lines = run(["--workload", "attn_lm_deep_t16", "--seed", "2",
                       "--seconds", "1", "--trace", "1", "--toy"],
                      cwd=copy, script=script)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(lines[-1])
    assert line["correct"] is True
    assert line["metrics"]["segments_run"]["value"] >= 2
    assert "flash_ms_per_step" in line["metrics"]

    serving = SERVING_CELL["name"]
    proc, lines = run(["--workload", serving, "--seed", "3",
                       "--seconds", "2", "--trace", "0", "--toy"],
                      cwd=copy, script=script)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 20
    assert set(line["metrics"]) == {"setup_s"} | {
        name for name, _unit in SERVING_END_TO_END}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    checked = next(l for l in lines if "reference:" in l)
    assert " 0/" not in checked          # the window's own tokens

    proc, lines = run(["--workload", serving, "--seed", "4",
                       "--seconds", "2", "--trace", "1", "--toy"],
                      cwd=copy, script=script)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(lines[-1])
    assert line["correct"] is True
    assert {name for name, *_ in SERVING_PER_LAYER} \
        | {"device_idle_share", "programs_built_in_window"} \
        <= set(line["metrics"])
    assert line["metrics"]["programs_built_in_window"]["value"] == 0
    assert line["device"]["busy_s"] > 0

    # the knee sweep: a table, never a result line
    proc, lines = run(["--workload", serving, "--seed", "1",
                       "--seconds", "1", "--toy", "--sweep", "20,40"],
                      cwd=copy, script=script)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = [json.loads(l[6:]) for l in lines if l.startswith("sweep ")]
    assert [r["rate_per_s"] for r in rows] == [20.0, 40.0]
    assert all(r["platform"] == "cpu" and r["failed"] == 0
               for r in rows)
    assert not lines[-1].startswith("{")
    for path, content in before.items():
        assert path.read_bytes() == content, path     # nothing edited

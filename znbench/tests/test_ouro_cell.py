"""The Ouro cell (PR 35): its entries in ``BENCHMARK.json`` looked up BY
NAME (so that a later PR's entries do not move them), its configuration
against the catalog's, its traffic, ``flops_loop`` against a count by
hand, the three new readers on a synthetic trace / counter set, and a
``--toy`` rehearsal, traced and untraced."""

import json
import types

import pytest

from znbench import flops_loop, trace_reduce
from znbench.harness import discovery
from znbench.harness.program import layer_table
from znbench.tests.test_cells_toy import run

CELL = "ouro_train_loop4_t4096"
BENCH = discovery.load_json(discovery.REPO + "/BENCHMARK.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = {"loop_lm_train_mfu": ("units", "%", "higher", "host_clock"),
       "loop_grad_sum_ms_per_step": ("units", "ms", "lower",
                                     "device_trace"),
       "loop_applications_per_step": ("fused step", "count", "higher",
                                      "program_counter")}
APPENDED = [
    "dispatches_per_step", "step_device_ms", "input_wait_share",
    "device_idle_share", "peak_hbm_gb", "flash_fwd_ms_per_step",
    "flash_bwd_ms_per_step", "host_reads_per_step",
    "host_read_wait_ms_per_step", "host_busy_ms_per_step",
    "guard_skipped_steps", "unit_attributed_share", "update_ms_per_step",
    "fingerprint_ms_per_step", "attention_unit_ms_per_step",
    "gated_mlp_unit_ms_per_step", "dense_unit_ms_per_step",
    "other_units_ms_per_step", "dispatch_wait_ms_per_step"]


def per_layer(cell):
    return {m["name"] for m in BENCH["per_layer"]
            if cell in m.get("workloads", [cell])}


def reader(name):
    return discovery.load_module("layer_metrics", name)


def test_the_cell_and_its_entries():
    cell = next(c for c in BENCH["workloads"] if c["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ouro_2_6b", "train_lm_loop_ctx", 1)
    assert len(cell["why"]) <= 200
    config = next(c for c in BENCH["configs"] if c["name"] == "ouro_2_6b")
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert config["file"] == "znbench/configs/ouro_2_6b.json"
    assert len(config["why"]) <= 200
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name, (layer, unit, better, source) in NEW.items():
        assert by_name[name] == {
            "name": name, "unit": unit, "better": better,
            "source": source, "layer": layer, "moves": "throughput",
            "workloads": [CELL]}
        assert reader(name) is not None
    throughput = next(m for m in BENCH["end_to_end"]
                      if m["name"] == "throughput")
    assert CELL in throughput["workloads"]
    assert per_layer(CELL) == set(NEW) | set(APPENDED) \
        | {"programs_built_in_window"}
    for name in APPENDED:        # appended last, nothing else touched
        assert by_name[name]["workloads"][-1] == CELL
    # shares whose arithmetic reads a table entry once are left out
    assert not {"train_mfu", "lm_train_mfu", "flash_ms_per_step",
                "flash_roofline", "flash_dq_ms_per_step",
                "flash_dkv_ms_per_step"} & per_layer(CELL)
    for other in (c["name"] for c in BENCH["workloads"]
                  if c["name"] != CELL):
        assert not set(NEW) & per_layer(other)


def test_the_configuration_is_the_catalog_s_but_for_the_cut():
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "Ouro-2.6B")
    file = discovery.find_cell(CELL).config
    assert file["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in file["reduced"]:
            assert file["published"][key] == value
            assert file[key] < value
        else:
            assert file[key] == value, key
    assert file["num_hidden_layers"] >= 4 and file["vocab_size"] >= 6144
    assert file["reference"] == "ouro"
    assert discovery.load_module("reference", "ouro") is not None


def test_the_traffic_and_the_table():
    real = discovery.find_cell(CELL)
    assert real.driver == "train_lm"
    assert (real.traffic["seq_len"], real.traffic["batch_per_chip"],
            real.traffic["steps_per_dispatch"]) == (4096, 1, 1)
    assert real.traffic["engine"]["anomaly_check_interval"] \
        == real.traffic["steps_per_epoch"]
    assert real.traffic["min_segments"] == 10
    assert real.traffic["warmup_epochs"] == 2
    layers = layer_table(real.config)
    n = real.config["num_hidden_layers"]
    assert [l["type"] for l in layers] == (
        ["embedding"] + ["attention", "gated_mlp"] * n
        + ["rms_norm", "loop_exits"])
    assert [l.get("passes") for l in layers] == \
        [None] + [4] * (2 * n + 1) + [None]
    for spec in (l["->"] for l in layers if l["type"] == "attention"):
        assert spec["n_heads"] == 16 and spec["causal"]
        assert spec["pre_norm"] == spec["post_norm"] == "rms"
        assert spec["rope"] == {"theta": 1000000}
    for spec in (l["->"] for l in layers if l["type"] == "gated_mlp"):
        assert spec["width"] == 5632
    assert layers[0]["->"]["dim"] == 2048
    assert layers[-1]["->"]["output_sample_shape"] \
        == real.config["vocab_size"] == layers[0]["->"]["vocab_size"]
    toy = discovery.find_cell(CELL, toy=True)
    assert [l["type"] for l in layer_table(toy.config)][1:3] \
        == ["attention", "gated_mlp"]
    assert toy.traffic["driver"] == "train_lm"


# ----------------------------------------------------------------------
# the arithmetic
# ----------------------------------------------------------------------
def table(n_layers=8, vocab=8192, passes=4):
    block = [{"type": "attention", "passes": passes,
              "->": {"n_heads": 16, "causal": True}},
             {"type": "gated_mlp", "passes": passes,
              "->": {"width": 5632}}]
    return ([{"type": "embedding", "->": {"vocab_size": vocab,
                                          "dim": 2048}}]
            + block * n_layers
            + [{"type": "rms_norm", "passes": passes, "->": {}},
               {"type": "loop_exits",
                "->": {"output_sample_shape": vocab}}])


def test_model_flops_by_hand():
    """A layer application at T 4,096: 8 · 2048² = 33,554,432 of
    projections, 2 · 4096 · 2048 = 16,777,216 of causal-half scores,
    6 · 2048 · 5632 = 69,206,016 of MLP = 119,537,664 a token; 8 layers
    × 4 passes = 3,825,205,248; four exits of 2 · 2048 · 8192 + 2 · 2048
    = 134,234,112; × 3 × 4,096 tokens = 48.65 TFLOP a step (ISSUE 35's
    48.7)."""
    parts = flops_loop.forward_flops_per_token(table(), 4096)
    assert parts == {"projections": 32 * 33_554_432.0,
                     "scores": 32 * 16_777_216.0,
                     "mlps": 32 * 69_206_016.0,
                     "head": 4 * 33_554_432.0, "exit_gate": 4 * 4096.0}
    assert sum(parts.values()) == 3_825_205_248 + 134_234_112
    assert flops_loop.lm_train_flops(table(), 4096, 1) \
        == 3 * 4096 * 3_959_439_360
    assert flops_loop.lm_train_flops(table(), 4096, 1) / 1e12 \
        == pytest.approx(48.65, abs=0.01)
    assert flops_loop.lm_train_flops(table(6, 6144), 4096, 1) / 1e12 \
        == pytest.approx(36.49, abs=0.01)
    assert flops_loop.applications_per_step(table()) == 4 * 17
    # one pass of the same table is a quarter of the looped work, and a
    # plain head after a span reads its last state only
    once = flops_loop.forward_flops_per_token(table(passes=1), 4096)
    assert once["mlps"] * 4 == parts["mlps"]
    assert once["head"] * 4 == parts["head"]
    plain = table()[:-1] + [{"type": "softmax", "->": {
        "output_sample_shape": 8192, "per_position": True}}]
    assert flops_loop.forward_flops_per_token(plain, 4096)["head"] \
        == 33_554_432.0


def test_loop_lm_train_mfu_is_model_flops_over_peak():
    obs = types.SimpleNamespace(
        peaks={"bf16_flops_per_s": 197e12}, chips=1, window_s=6.0,
        observations={"layers": table(), "sample_shape": (4096,),
                      "batch": 1, "steps": 9})
    want = 100 * 3 * 4096 * 3_959_439_360 * (9 / 6.0) / 197e12
    assert reader("loop_lm_train_mfu").read(obs) == pytest.approx(want)
    assert 0 < want < 100
    obs.peaks = None                      # off a TPU: no share of a peak
    assert reader("loop_lm_train_mfu").read(obs) is None
    obs.peaks = {"bf16_flops_per_s": 197e12}
    obs.observations["layers"] = table()[:1] + [
        {"type": "softmax", "->": {"output_sample_shape": 8192}}]
    assert reader("loop_lm_train_mfu").read(obs) is None  # no looped span


# ----------------------------------------------------------------------
# the readers on a synthetic trace and counter set
# ----------------------------------------------------------------------
def unit(name, family, phase):
    return {"unit": name, "kind": name, "family": family, "phase": phase}


MS = {"fusion.1": 4, "fusion.2": 6, "fusion.3": 8, "fusion.4": 3}


def observation(monkeypatch, scopes, steps=2):
    from znicz_tpu import observe
    monkeypatch.setattr(observe, "op_scopes", lambda: scopes,
                        raising=False)
    events, at = [], 1_000_000
    for name, ms in MS.items():
        events.append(trace_reduce.Event(name, at, at + ms * 1_000_000))
        at += ms * 1_000_000
    trace = trace_reduce.Trace(
        devices={"/device:TPU:0": [trace_reduce.Lane(events)]}, host=[])
    return types.SimpleNamespace(
        trace=trace, trace_window=(1_000_000, at),
        observations={"steps": steps})


@pytest.fixture
def registry():
    """The process registry without the family, before and after."""
    from znicz_tpu.observe import metrics
    families = metrics.REGISTRY._families
    kept = families.pop("znicz_loop", None)
    yield metrics
    families.pop("znicz_loop", None)
    if kept is not None:
        families["znicz_loop"] = kept


def test_the_sum_s_time_is_the_operations_wholly_inside_its_scope(
        monkeypatch, registry):
    gauge = getattr(registry, "loop", None)
    if gauge is None:
        pytest.skip("a program from before PR 35")
    scopes = {"znicz_step__train_region": {
        "fusion.1": unit("GDMlp", "GatedMLP", "pass_sum"),
        "fusion.2": unit("GDAttn", "MultiHeadAttention", "pass_sum"),
        # an add fused into the update: the update's
        "fusion.3": unit("GDMlp", "GatedMLP", "update"),
        # two units' adds side by side: still the sum's
        "fusion.4": {"unit": None, "units": ["GDMlp", "GDAttn"],
                     "kinds": ["GDMlp", "GDAttn"],
                     "families": ["GatedMLP", "MultiHeadAttention"],
                     "phases": ["pass_sum", "pass_sum"]}}}
    read = reader("loop_grad_sum_ms_per_step").read
    # no looped span in the program (no gauge): nothing, whatever the map
    assert read(observation(monkeypatch, scopes)) is None
    gauge("pass_span_0", "passes").set(4)
    assert read(observation(monkeypatch, scopes)) \
        == pytest.approx((4 + 6 + 3) / 2)
    # the adds all folded away: 0 — the finding, not a gap
    scopes["znicz_step__train_region"] = {
        "fusion.1": {"unit": None, "units": ["GDMlp", "GDMlp"],
                     "kinds": ["GDMlp"] * 2, "families": ["GatedMLP"] * 2,
                     "phases": ["backward", "pass_sum"]},
        "fusion.3": unit("GDMlp", "GatedMLP", "update")}
    assert read(observation(monkeypatch, scopes)) == 0.0


@pytest.mark.parametrize("scopes", [None, {}])
def test_no_map_no_metric(monkeypatch, registry, scopes):
    """A program from before ``observe.op_scopes``, an empty map."""
    gauge = getattr(registry, "loop", None)
    if gauge is not None:
        gauge("pass_span_0", "passes").set(4)
    obs = observation(monkeypatch, scopes)
    if scopes is None:
        from znicz_tpu import observe
        monkeypatch.delattr(observe, "op_scopes", raising=False)
    assert reader("loop_grad_sum_ms_per_step").read(obs) is None


@pytest.fixture
def registry():
    """The process registry without the family, before and after."""
    from znicz_tpu.observe import metrics
    families = metrics.REGISTRY._families
    kept = families.pop("znicz_loop", None)
    yield metrics
    families.pop("znicz_loop", None)
    if kept is not None:
        families["znicz_loop"] = kept


def test_applications_per_step_reads_the_gauge(registry):
    read = reader("loop_applications_per_step").read
    assert read(None) is None             # the parent: no such family
    gauge = getattr(registry, "loop", None)
    if gauge is None:
        pytest.skip("a program from before PR 35")
    gauge("pass_span_0", "applications").set(68)      # static: not read
    assert read(None) is None             # no epoch has ended yet
    gauge("pass_span_0", "applications_per_step").set(68.0)
    assert read(None) == 68.0
    gauge("pass_span_1", "applications_per_step").set(6.0)
    assert read(None) == 74.0


# ----------------------------------------------------------------------
# the rehearsal
# ----------------------------------------------------------------------
def test_untraced_rehearsal():
    proc, lines = run(["--workload", CELL, "--seed", "3000000019",
                       "--seconds", "2", "--trace", "0", "--toy"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    assert set(line["metrics"]) == {"throughput", "setup_s"}
    log = "\n".join(lines)
    assert "6:loop_exits=" in log and "5:rms_norm=" in log


def test_traced_rehearsal_runs_every_pass_and_reads_once_per_epoch():
    proc, lines = run(["--workload", CELL, "--seed", "3000000021",
                       "--seconds", "2", "--trace", "1", "--toy"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(lines[-1])
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert line["correct"] is True
    # the toy table: 2 layers + the final norm, 4 passes
    assert metrics["loop_applications_per_step"] == 20
    assert metrics["dispatches_per_step"] == 1
    assert metrics["programs_built_in_window"] == 0
    assert metrics["guard_skipped_steps"] == 0
    # five reads an epoch of 4 steps, all at its end: the three of every
    # LM cell (error count, loss, guard), the exits' totals, the span's
    # count
    assert metrics["host_reads_per_step"] == pytest.approx(5 / 4)
    assert "unit_attributed_share" in metrics
    assert set(metrics) <= per_layer(CELL)

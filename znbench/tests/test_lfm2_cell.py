"""The LFM2 cell (PR 43): its entries in ``BENCHMARK.json`` looked up BY
NAME (so that a later PR's entries do not move them), its configuration
against the catalog's, its traffic, ``flops_conv`` against a count by
hand for one convolution layer, the attention layer and the held
experts, the six new readers on a synthetic trace / counter set, the
kernels' names, and a ``--toy`` rehearsal, traced and untraced."""

import json
import types

import pytest

from znbench import flops_conv, trace_reduce
from znbench.harness import discovery
from znbench.harness.program import layer_table
from znbench.tests.test_cells_toy import run

CELL = "lfm2_train_1of2"
BENCH = discovery.load_json(discovery.REPO + "/BENCHMARK.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = {"conv_lm_train_mfu": ("units", "%", "higher", "host_clock"),
       "short_conv_ms_per_step": ("kernels", "ms", "lower",
                                  "device_trace"),
       "short_conv_roofline": ("kernels", "%", "higher", "device_trace"),
       "short_conv_unit_ms_per_step": ("units", "ms", "lower",
                                       "device_trace"),
       "moe_held_gmm_roofline": ("kernels", "%", "higher",
                                 "device_trace"),
       "short_conv_kernel_layers": ("units", "count", "higher",
                                    "program_counter")}
APPENDED = [
    "dispatches_per_step", "step_device_ms", "input_wait_share",
    "device_idle_share", "peak_hbm_gb", "flash_fwd_ms_per_step",
    "flash_bwd_ms_per_step", "host_reads_per_step",
    "host_read_wait_ms_per_step", "host_busy_ms_per_step",
    "guard_skipped_steps", "moe_gmm_ms_per_step", "moe_gmm_overwork",
    "moe_load_imbalance", "moe_held_rows_per_expert",
    "moe_router_bias_ms_per_step", "unit_attributed_share",
    "update_ms_per_step", "fingerprint_ms_per_step",
    "attention_unit_ms_per_step", "moe_unit_ms_per_step",
    "gated_mlp_unit_ms_per_step", "dense_unit_ms_per_step",
    "other_units_ms_per_step", "dispatch_wait_ms_per_step"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def per_layer(cell):
    return {m["name"] for m in BENCH["per_layer"]
            if cell in m.get("workloads", [cell])}


def reader(name):
    return discovery.load_module("layer_metrics", name)


def test_the_cell_and_its_entries():
    cell = next(c for c in BENCH["workloads"] if c["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lfm2_8b_a1b", "train_lm_conv_ctx", 1)
    assert len(cell["why"]) <= 200
    config = next(c for c in BENCH["configs"]
                  if c["name"] == "lfm2_8b_a1b")
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["file"] == "znbench/configs/lfm2_8b_a1b.json"
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
    # the halves of the backward that read nothing since PR 30, and the
    # share that counts N·k rows over all E experts, are left out
    assert not {"flash_dq_ms_per_step", "flash_dkv_ms_per_step",
                "moe_gmm_roofline", "train_mfu", "lm_train_mfu",
                "band_lm_train_mfu", "flash_roofline",
                "delta_net_unit_ms_per_step",
                "conv_unit_ms_per_step"} & per_layer(CELL)
    for other in (c["name"] for c in BENCH["workloads"]
                  if c["name"] != CELL):
        assert not set(NEW) & per_layer(other)


def test_the_configuration_is_the_catalog_s_but_for_the_cut():
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "LFM2-8B-A1B")
    file = discovery.find_cell(CELL).config
    assert file["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in file["reduced"]:
            assert file["published"][key] == value
            assert file[key] < value
        else:
            assert file[key] == value, key
    # the guide's floors: a dense layer + one whole period of four, 8
    # experts, an eighth of the vocabulary
    assert file["num_hidden_layers"] >= 1 + 4
    assert file["num_experts"] >= 8
    assert file["vocab_size"] * 8 >= row["config"]["vocab_size"]
    assert file["reference"] == "lfm2"
    assert discovery.load_module("reference", "lfm2") is not None


def test_the_traffic_and_the_table():
    real = discovery.find_cell(CELL)
    assert real.driver == "train_lm"
    assert (real.traffic["batch_per_chip"], real.traffic["seq_len"],
            real.traffic["steps_per_dispatch"]) == (1, 4096, 1)
    assert real.traffic["engine"]["anomaly_check_interval"] \
        == real.traffic["steps_per_epoch"]
    assert real.traffic["min_segments"] == 10
    assert real.traffic["warmup_epochs"] == 2
    layers = layer_table(real.config)
    assert [l["type"] for l in layers] == (
        ["embedding", "short_conv", "gated_mlp", "attention", "moe"]
        + ["short_conv", "moe"] * 3 + ["rms_norm", "softmax"])
    assert layers[0]["->"]["dim"] == 2048
    assert layers[-1]["->"]["output_sample_shape"] \
        == real.config["vocab_size"] == layers[0]["->"]["vocab_size"]
    toy = discovery.find_cell(CELL, toy=True)
    assert [l["type"] for l in layer_table(toy.config)] \
        == [l["type"] for l in layers]
    assert toy.traffic["driver"] == "train_lm"


# ----------------------------------------------------------------------
# the arithmetic, by hand at the published widths
# ----------------------------------------------------------------------
EMB = {"type": "embedding", "->": {"vocab_size": 8192, "dim": 2048}}
CONV = {"type": "short_conv", "->": {"conv_kernel": 3}}
GQA = {"type": "attention", "->": {
    "n_heads": 32, "n_kv_heads": 8, "head_dim": 64, "causal": True,
    "qk_norm": "rms_head"}}
MOE = {"type": "moe", "->": {
    "n_experts": 32, "top_k": 4, "width": 1792,
    "held": list(range(16))}}


def test_one_convolution_layer_by_hand():
    """W_in 2·2048·6144 + W_out 2·2048·2048 = 33,554,432; the chain 2
    gate products + 3 products and 2 sums of the taps = 7 a channel."""
    parts = flops_conv.forward_flops_per_token([EMB, CONV], 4096)
    assert parts["conv_projections"] == 33_554_432
    assert parts["conv_chain"] == 7 * 2048
    assert sum(parts.values()) == 33_554_432 + 14_336
    assert flops_conv.conv_layers([EMB, CONV, GQA]) == [CONV["->"]]


def test_the_attention_layer_and_the_held_experts_by_hand():
    """q, k, v 2·2048·(32 + 16)·64 + out 2·2048·2048 = 20,971,520; the
    causal half 4·64·32 a pair over 4,097/2 pairs a row; 16 of 32 held,
    top 4: 2 rows a token here under uniform routing, 6·2048·1792 a
    row; the router 2·2048·32."""
    parts = flops_conv.forward_flops_per_token([EMB, GQA, MOE], 4096)
    assert parts["projections"] == 12_582_912 + 8_388_608
    assert parts["scores"] == 4 * 64 * 32 * 4097 / 2
    assert parts["routed"] == 2 * 22_020_096
    assert parts["router"] == 131_072
    assert parts["shared"] == 0
    seen = flops_conv.forward_flops_per_token([EMB, MOE], 4096, {1: 1.9})
    assert seen["routed"] == 1.9 * 22_020_096   # the rows computed here


def test_the_whole_cell_s_step():
    """Four convolution layers 4 × 33,568,768, the attention layer
    37,752,832, the dense MLP 88,080,384, four expert layers 4 ×
    44,171,264, the head 33,554,432 = 470.3 MFLOP a token forward (the
    issue reckoned 0.47 G): 5.78 TFLOP a step at T 4,096."""
    layers = layer_table(discovery.find_cell(CELL).config)
    per_token = sum(flops_conv.forward_flops_per_token(
        layers, 4096).values())
    assert per_token == 4 * 33_568_768 + 37_752_832 + 88_080_384 \
        + 4 * 44_171_264 + 33_554_432 == 470_347_776
    assert flops_conv.lm_train_flops(layers, 4096, 1) / 1e12 \
        == pytest.approx(5.7796, abs=1e-3)


def test_what_the_chain_s_kernels_are_given():
    """T × D = 8,388,608 elements a layer.  Forward: three f32 blocks
    read and y written at bf16, 14 bytes; backward: the projection
    again, y's cotangent (bf16) and the projection's cotangent, 26
    bytes: 335.5 MB a layer, 1.34 GB over four — 1.64 ms at the HBM
    peak; 22 FLOPs an element (7 forward, 15 backward) are 0.7 GFLOP:
    memory bounds it."""
    cost = flops_conv.short_conv_train_cost([EMB] + [CONV] * 4, 4096, 1)
    assert cost == {"flops": 4 * 8_388_608 * 22.0,
                    "bytes": 4 * 8_388_608 * 40.0}
    assert cost["bytes"] / 819e9 == pytest.approx(1.639e-3, rel=1e-3)
    assert cost["flops"] / 197e12 < cost["bytes"] / 819e9
    f32 = flops_conv.short_conv_train_cost([EMB, CONV], 4096, 1, 4)
    assert f32["bytes"] == 8_388_608 * 44.0


def test_what_the_grouped_matmuls_are_given_under_held():
    """8,192 rows a layer (2 a token) where ``flops_moe.gmm_train_cost``
    counts 16,384; slabs of the 16 experts HELD, not of all 32."""
    from znbench import flops_moe
    layers = [EMB, MOE]
    cost = flops_conv.held_gmm_train_cost(layers, {1: 2.0}, 4096)
    assert cost["flops"] == 18.0 * 8192 * 2048 * 1792
    whole = flops_moe.gmm_train_cost(layers, 4096, 2048)
    assert whole["flops"] == 2 * cost["flops"]
    assert cost["bytes"] < whole["bytes"]
    assert cost["flops"] / 197e12 > cost["bytes"] / 819e9     # compute
    assert flops_conv.held_gmm_train_cost(layers, {}, 4096) \
        == {"flops": 0.0, "bytes": 0.0}


# ----------------------------------------------------------------------
# the readers on a synthetic trace and counter set
# ----------------------------------------------------------------------
KERNELS = {"%jvp_znicz_short_conv_fwd_.3": 4,
           "%transpose_jvp_znicz_short_conv_bwd_.5": 6,
           "%znicz_qkv_prep_fwd.7": 11,      # the delta rule's: not ours
           "%gmm.9": 10, "%tgmm.11": 5,
           "fusion.1": 8, "fusion.2": 2, "fusion.3": 1}


def observation(monkeypatch, scopes=None, steps=2, layers=None):
    from znicz_tpu import observe
    monkeypatch.setattr(observe, "op_scopes", lambda: scopes or {},
                        raising=False)
    events, at = [], 1_000_000
    for name, ms in KERNELS.items():
        events.append(trace_reduce.Event(name, at, at + ms * 1_000_000))
        at += ms * 1_000_000
    trace = trace_reduce.Trace(
        devices={"/device:TPU:0": [trace_reduce.Lane(events)]}, host=[])
    return types.SimpleNamespace(
        trace=trace, trace_window=(1_000_000, at), peaks=PEAKS, chips=1,
        window_s=6.0, cell=discovery.find_cell(CELL),
        observations={"steps": steps, "batch": 1,
                      "sample_shape": (4096,), "model_dim": 2048,
                      "layers": layers or [EMB] + [CONV] * 4 + [GQA]})


def test_the_kernels_are_told_apart_by_name(monkeypatch):
    obs = observation(monkeypatch)
    assert reader("short_conv_ms_per_step").read(obs) \
        == pytest.approx((4 + 6) / 2)
    assert reader("moe_gmm_ms_per_step").read(obs) \
        == pytest.approx((10 + 5) / 2)
    for name in KERNELS:
        if "short_conv" in name:
            assert "qkv_prep" not in name and "flash" not in name


def test_the_roofline_is_the_bytes_over_the_peak_over_the_time(
        monkeypatch):
    obs = observation(monkeypatch)
    cost = flops_conv.short_conv_train_cost(
        obs.observations["layers"], 4096, 1, 2)
    assert reader("short_conv_roofline").read(obs) == pytest.approx(
        100 * (cost["bytes"] / 819e9) / 5e-3)
    obs.peaks = None                  # off a TPU: no share of a peak
    assert reader("short_conv_roofline").read(obs) is None


def test_the_held_roofline_reads_the_rows_computed(monkeypatch):
    from znicz_tpu.observe import metrics
    layers = [EMB, CONV, MOE]
    obs = observation(monkeypatch, layers=layers)
    obs.observations["moe_units"] = ["MoE_test_lfm2_cell"]
    read = reader("moe_held_gmm_roofline").read
    assert read(obs) is None                    # no gauge set: nothing
    metrics.moe_held("MoE_test_lfm2_cell", "rows_here").set(8000.0)
    cost = flops_conv.held_gmm_train_cost(
        layers, {2: 8000.0 / 4096}, 4096)
    assert read(obs) == pytest.approx(
        100 * (cost["flops"] / 197e12) / 7.5e-3)
    assert read(obs) < 105
    obs.peaks = None
    assert read(obs) is None


def test_no_kernel_no_metric(monkeypatch):
    """Interpret mode, the chain in jax.numpy, the parent of PR 43."""
    obs = observation(monkeypatch)
    obs.trace = trace_reduce.Trace(devices={"/device:TPU:0": [
        trace_reduce.Lane([trace_reduce.Event(
            "fusion.1", 1_000_000, 9_000_000)])]}, host=[])
    for name in ("short_conv_ms_per_step", "short_conv_roofline",
                 "moe_held_gmm_roofline", "short_conv_unit_ms_per_step"):
        assert reader(name).read(obs) is None


def test_conv_lm_train_mfu_is_model_flops_over_peak(monkeypatch):
    layers = layer_table(discovery.find_cell(CELL).config)
    obs = observation(monkeypatch, steps=40, layers=layers)
    obs.observations["moe_units"] = []
    want = 100 * flops_conv.lm_train_flops(layers, 4096, 1) \
        * (40 / 6.0) / 197e12
    assert reader("conv_lm_train_mfu").read(obs) == pytest.approx(want)
    assert 0 < want < 100
    obs.peaks = None
    assert reader("conv_lm_train_mfu").read(obs) is None
    obs.peaks = PEAKS
    obs.observations["layers"] = [EMB, GQA]     # no short convolution
    assert reader("conv_lm_train_mfu").read(obs) is None


def unit(name, family, phase):
    return {"unit": name, "kind": name, "family": family, "phase": phase}


def test_the_unit_s_time_is_read_by_its_family(monkeypatch):
    """Forward and backward of family ``ShortConv``, updates left out;
    an operation fused from two such units is theirs, one fused with
    another family's is not."""
    scopes = {"znicz_step__train_region": {
        "fusion.1": unit("ShortConv_1", "ShortConv", "forward"),
        "fusion.2": unit("GDShortConv_1", "ShortConv", "backward"),
        "fusion.3": unit("GDShortConv_1", "ShortConv", "update"),
        "%jvp_znicz_short_conv_fwd_.3": unit("ShortConv_1", "ShortConv",
                                             "forward"),
        "%gmm.9": unit("MoE_2", "MoE", "forward")}}
    read = reader("short_conv_unit_ms_per_step").read
    assert read(observation(monkeypatch, scopes)) \
        == pytest.approx((8 + 2 + 4) / 2)
    scopes["znicz_step__train_region"]["fusion.1"] = {
        "unit": None, "units": ["ShortConv_1", "ShortConv_5"],
        "kinds": ["ShortConv"] * 2, "families": ["ShortConv"] * 2,
        "phases": ["forward", "forward"]}
    assert read(observation(monkeypatch, scopes)) \
        == pytest.approx((8 + 2 + 4) / 2)
    scopes["znicz_step__train_region"]["fusion.1"]["families"] = [
        "ShortConv", "MoE"]
    assert read(observation(monkeypatch, scopes)) \
        == pytest.approx((2 + 4) / 2)
    # the accepted table files the family under ``other``
    share = reader("unit_attributed_share")
    assert share.bucket_of(unit("ShortConv_1", "ShortConv",
                                "forward")) == ("other", "forward")
    assert read(observation(monkeypatch, {})) is None


def test_the_layers_that_run_the_kernels_are_counted(monkeypatch):
    from znicz_tpu.observe import metrics
    read = reader("short_conv_kernel_layers").read
    family = metrics.REGISTRY.get("znicz_short_conv")
    before = read(None) or 0.0 if family is not None else 0.0
    for i, path in enumerate((1.0, 1.0, 0.0)):
        metrics.short_conv(f"ShortConv_cell_test_{i}", "path").set(path)
        metrics.short_conv(f"ShortConv_cell_test_{i}", "taps").set(3)
    assert read(None) == before + 2


# ----------------------------------------------------------------------
# the rehearsal
# ----------------------------------------------------------------------
def test_untraced_rehearsal():
    proc, lines = run(["--workload", CELL, "--seed", "3000000043",
                       "--seconds", "2", "--trace", "0", "--toy"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    assert set(line["metrics"]) == {"throughput", "setup_s"}
    log = "\n".join(lines)
    assert "1:short_conv=" in log and "3:attention=" in log
    assert "a bf16 router would read" in log


def test_traced_rehearsal_counts_the_kernel_layers():
    proc, lines = run(["--workload", CELL, "--seed", "3000000047",
                       "--seconds", "2", "--trace", "1", "--toy"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(lines[-1])
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert line["correct"] is True
    assert metrics["dispatches_per_step"] == 1
    assert metrics["programs_built_in_window"] == 0
    assert metrics["guard_skipped_steps"] == 0
    assert metrics["short_conv_kernel_layers"] == 4
    assert "moe_held_rows_per_expert" in metrics
    assert "short_conv_unit_ms_per_step" in metrics
    assert set(metrics) <= per_layer(CELL)
    # interpreted kernels leave no kernel to time
    assert not {"short_conv_ms_per_step", "short_conv_roofline",
                "moe_held_gmm_roofline"} & set(metrics)

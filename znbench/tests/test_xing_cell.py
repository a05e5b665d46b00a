"""The Xing4.0 cell (PR 46): its entries in ``BENCHMARK.json`` looked up
BY NAME (so that a later PR's entries do not move them; nothing here
pins a position or an exact list of another PR's), its configuration
against the catalog's, its traffic, ``flops_streams`` against a count
by hand at the published widths, the four new readers on a synthetic
trace / counter set, and a ``--toy`` rehearsal, traced and untraced."""

import json
import types

import pytest

from znbench import flops_streams, trace_reduce
from znbench.harness import discovery
from znbench.harness.program import layer_table
from znbench.tests.test_cells_toy import run

CELL = "xing_train_1of8"
CONFIG = "xing4_0_29b_a4b"
BENCH = discovery.load_json(discovery.REPO + "/BENCHMARK.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = {"stream_unit_ms_per_step": ("units", "ms", "lower",
                                   "device_trace"),
       "stream_roofline": ("kernels", "%", "higher", "device_trace"),
       "stream_sinkhorn_gap": ("units", "ratio", "lower",
                               "program_counter"),
       "streams_lm_train_mfu": ("units", "%", "higher", "host_clock")}
#: accepted metrics the cell reports (it may join more later)
JOINED = {"mla_flash_ms_per_step", "mla_flash_roofline",
          "moe_held_rows_per_expert", "moe_held_fit_step_share",
          "unit_attributed_share", "attention_unit_ms_per_step",
          "gated_mlp_unit_ms_per_step", "moe_unit_ms_per_step",
          "moe_gmm_ms_per_step", "peak_hbm_gb", "device_idle_share",
          "host_reads_per_step", "dispatches_per_step"}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def per_layer(cell):
    return {m["name"] for m in BENCH["per_layer"]
            if cell in m.get("workloads", [cell])}


def reader(name):
    return discovery.load_module("layer_metrics", name)


def test_the_cell_and_its_entries():
    cell = next(c for c in BENCH["workloads"] if c["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "train_lm_streams_ctx", 1)
    assert len(cell["why"]) <= 200
    config = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "num_nextn_predict_layers"]
    assert config["file"] == f"znbench/configs/{CONFIG}.json"
    assert len(config["why"]) <= 200
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name, (layer, unit, better, source) in NEW.items():
        entry = by_name[name]
        assert {k: entry[k] for k in ("unit", "better", "source",
                                      "layer", "moves")} == {
            "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "throughput"}
        assert CELL in entry["workloads"]
        assert reader(name) is not None
    throughput = next(m for m in BENCH["end_to_end"]
                      if m["name"] == "throughput")
    assert CELL in throughput["workloads"]
    assert set(NEW) | JOINED | {"programs_built_in_window"} \
        <= per_layer(CELL)
    # the halves of the backward that read nothing since PR 30, other
    # families' kernels and other cells' shares of the peak are left out
    assert not {"flash_dq_ms_per_step", "flash_dkv_ms_per_step",
                "latent_lm_train_mfu", "kda_ms_per_step",
                "delta_net_unit_ms_per_step", "short_conv_ms_per_step",
                "conv_unit_ms_per_step"} & per_layer(CELL)


def test_the_configuration_is_the_catalog_s_but_for_the_cut():
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "Xing4.0-29B-A4B")
    file = discovery.find_cell(CELL).config
    assert file["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in file["reduced"]:
            assert file["published"][key] == value
            assert file[key] < value
        else:
            assert file[key] == value, key
    # the guide's floors: a dense layer + four layers after it, 8
    # experts, an eighth of the vocabulary
    assert file["num_hidden_layers"] >= 1 + 4
    assert file["n_routed_experts"] >= 8
    assert file["vocab_size"] * 8 >= row["config"]["vocab_size"]
    assert file["reference"] == "xing"
    assert discovery.load_module("reference", "xing") is not None


def test_the_traffic_and_the_table():
    real = discovery.find_cell(CELL)
    assert real.driver == "train_lm"
    assert (real.traffic["batch_per_chip"],
            real.traffic["steps_per_dispatch"]) == (1, 1)
    assert real.traffic["seq_len"] in (2048, 4096)
    assert real.traffic["engine"]["anomaly_check_interval"] \
        == real.traffic["steps_per_epoch"]
    assert real.traffic["engine"]["keep_written_leaves"] is True
    assert real.traffic["min_segments"] == 10
    assert real.traffic["warmup_epochs"] == 2
    layers = layer_table(real.config)
    kinds = [l["type"] for l in layers]
    assert kinds.count("stream_read") == kinds.count("stream_write") == 10
    assert kinds.count("latent_attention") == 5
    assert kinds.count("moe") == 4 and kinds.count("gated_mlp") == 1
    assert layers[0]["->"]["dim"] == 3584
    assert layers[-1]["->"]["output_sample_shape"] \
        == real.config["vocab_size"] == layers[0]["->"]["vocab_size"]
    toy = discovery.find_cell(CELL, toy=True)
    assert set(l["type"] for l in layer_table(toy.config)) == set(kinds)
    assert toy.traffic["driver"] == "train_lm"


# ----------------------------------------------------------------------
# the arithmetic, by hand at the published widths
# ----------------------------------------------------------------------
EMB = {"type": "embedding", "->": {"vocab_size": 16384, "dim": 3584}}
READ = {"type": "stream_read", "->": {"n_streams": 4}}
WRITE = {"type": "stream_write", "->": {"n_streams": 4}}
OPEN = {"type": "stream_open", "->": {"n_streams": 4}}
CLOSE = {"type": "stream_close", "->": {"n_streams": 4}}
MLA = {"type": "latent_attention", "->": {
    "n_heads": 32, "q_latent": 768, "kv_latent": 512, "qk_nope": 128,
    "qk_rope": 64, "v_head_dim": 128}}
MOE = {"type": "moe", "->": {
    "n_experts": 64, "top_k": 4, "width": 1024, "shared_width": 1024,
    "held": list(range(8))}}


def test_one_sublayer_s_maps_by_hand():
    """x~ phi 2·14,336·24 = 688,128 and the n² + 2n = 24 mixes of D,
    2·3584·24 = 172,032: 860,160 FLOPs a token and sublayer."""
    parts = flops_streams.forward_flops_per_token(
        [EMB, OPEN, READ, WRITE, CLOSE], 2048)
    assert parts["stream_maps"] == 688_128 + 172_032 == 860_160
    assert sum(parts.values()) == 860_160
    assert flops_streams.stream_reads([EMB, READ, MLA]) == [READ["->"]]


def test_the_latent_layer_with_its_query_latent_by_hand():
    """Down 2·3584·(768 + 512 + 64) = 9,633,792; the queries' up
    2·768·6,144 = 9,437,184; the K/V up 2·512·8,192 = 8,388,608; out
    2·4,096·3584 = 29,360,128; the causal half (2·192 + 2·128)·32 a
    pair over (T + 1)/2 pairs a row.  Without the query latent the
    queries are columns of the one projection, as ``flops_latent``."""
    parts = flops_streams.forward_flops_per_token([EMB, MLA], 2048)
    assert parts["mla_projections"] == 9_633_792 + 9_437_184 \
        + 8_388_608 + 29_360_128 == 56_819_712
    assert parts["mla_scores"] == 640 * 32 * 2049 / 2
    from znbench import flops_latent
    fused = {"type": "latent_attention", "->": {
        k: v for k, v in MLA["->"].items() if k != "q_latent"}}
    assert flops_streams.forward_flops_per_token([EMB, fused], 2048)[
        "mla_projections"] == flops_latent.forward_flops_per_token(
            [EMB, fused], 2048)["mla_projections"]


def test_the_held_experts_by_hand():
    """8 of 64 held, top 4: half a row a token here under uniform
    routing, 6·3584·1024 = 22,020,096 a row; the shared expert a whole
    row; the router 2·3584·64."""
    parts = flops_streams.forward_flops_per_token([EMB, MOE], 2048)
    assert parts["routed"] == 0.5 * 22_020_096
    assert parts["shared"] == 22_020_096
    assert parts["router"] == 458_752
    seen = flops_streams.forward_flops_per_token([EMB, MOE], 2048,
                                                 {1: 0.47})
    assert seen["routed"] == 0.47 * 22_020_096  # the rows computed here


def test_the_whole_cell_s_step():
    """Five mixers 5 × (56,819,712 + scores), ten sublayers' maps
    8,601,600, the dense MLP 198,180,864, four expert layers 4 ×
    33,488,896, the head 117,440,512."""
    real = discovery.find_cell(CELL)
    layers, t = layer_table(real.config), real.traffic["seq_len"]
    per_token = sum(flops_streams.forward_flops_per_token(
        layers, t).values())
    scores = 640 * 32 * (t + 1) / 2
    assert per_token == 5 * (56_819_712 + scores) + 8_601_600 \
        + 198_180_864 + 4 * 33_488_896 + 117_440_512
    assert flops_streams.lm_train_flops(layers, t, 1) \
        == 3 * t * per_token
    if t == 2048:
        assert per_token == 847_185_920
        assert flops_streams.lm_train_flops(layers, t, 1) / 1e12 \
            == pytest.approx(5.2051, abs=1e-3)


def test_what_the_stream_units_must_move():
    """8·n·D + 5·D = 132,608 elements a token and sublayer, the open
    and the close 2 × 2 × (14,336 + 3,584) = 71,680: 1,397,760 f32 a
    token over ten sublayers, 11.45 GB a step at T 2,048 — 13.98 ms at
    the HBM peak, where the maps' 3 × 8.6 MFLOP a token are 0.27 ms of
    the bf16 peak: memory bounds it."""
    layers = [EMB, OPEN] + [READ, MLA, WRITE] * 10 + [CLOSE]
    cost = flops_streams.stream_train_cost(layers, 2048, 1)
    assert cost["bytes"] == 4.0 * 2048 * (10 * 132_608 + 71_680) \
        == 4.0 * 2048 * 1_397_760
    assert cost["flops"] == 3.0 * 2048 * 8_601_600
    assert cost["bytes"] / 819e9 == pytest.approx(13.98e-3, rel=1e-3)
    assert cost["flops"] / 197e12 < cost["bytes"] / 819e9 / 40


# ----------------------------------------------------------------------
# the readers on a synthetic trace and counter set
# ----------------------------------------------------------------------
OPS = {"fusion.1": 8, "fusion.2": 2, "fusion.3": 1, "fusion.4": 5,
       "%znicz_flash_fwd_mla.3": 4, "%gmm.9": 10}


def observation(monkeypatch, scopes=None, steps=2, layers=None):
    from znicz_tpu import observe
    monkeypatch.setattr(observe, "op_scopes", lambda: scopes or {},
                        raising=False)
    events, at = [], 1_000_000
    for name, ms in OPS.items():
        events.append(trace_reduce.Event(name, at, at + ms * 1_000_000))
        at += ms * 1_000_000
    trace = trace_reduce.Trace(
        devices={"/device:TPU:0": [trace_reduce.Lane(events)]}, host=[])
    return types.SimpleNamespace(
        trace=trace, trace_window=(1_000_000, at), peaks=PEAKS, chips=1,
        window_s=6.0, cell=discovery.find_cell(CELL),
        observations={"steps": steps, "batch": 1,
                      "sample_shape": (2048,), "model_dim": 3584,
                      "moe_units": [],
                      "layers": layers or [EMB, OPEN]
                      + [READ, MLA, WRITE] * 10 + [CLOSE]})


def unit(name, phase, family="Streams"):
    return {"unit": name, "kind": name, "family": family, "phase": phase}


SCOPES = {"znicz_step__train_region": {
    "fusion.1": unit("StreamRead_1", "forward"),
    "fusion.2": unit("GDStreamWrite_1", "backward"),
    "fusion.3": unit("GDStreamRead_1", "update"),
    "fusion.4": {"unit": None,
                 "units": ["StreamWrite_1", "StreamRead_2"],
                 "kinds": ["StreamWrite", "StreamRead"],
                 "families": ["Streams"] * 2,
                 "phases": ["forward", "forward"]},
    "%znicz_flash_fwd_mla.3": unit("MultiHeadAttention_1", "forward",
                                   "MultiHeadAttention"),
    "%gmm.9": unit("MoE_2", "forward", "MoE")}}


def test_the_units_time_is_read_by_their_family(monkeypatch):
    """Forward and backward of family ``Streams`` — the open, the
    READs, the WRITEs and the close are ONE family —, updates left
    out; an operation fused from two stream units is theirs, one fused
    with another family's is not."""
    read = reader("stream_unit_ms_per_step").read
    assert read(observation(monkeypatch, SCOPES)) \
        == pytest.approx((8 + 2 + 5) / 2)
    mixed = json.loads(json.dumps(SCOPES))
    mixed["znicz_step__train_region"]["fusion.4"]["families"] = [
        "Streams", "MultiHeadAttention"]
    assert read(observation(monkeypatch, mixed)) \
        == pytest.approx((8 + 2) / 2)
    # the accepted table files the family under ``other``
    share = reader("unit_attributed_share")
    assert share.bucket_of(unit("StreamRead_1", "forward")) \
        == ("other", "forward")
    assert read(observation(monkeypatch, {})) is None


def test_the_roofline_is_the_bytes_over_the_peak_over_the_time(
        monkeypatch):
    obs = observation(monkeypatch, SCOPES)
    cost = flops_streams.stream_train_cost(
        obs.observations["layers"], 2048, 1)
    want = 100 * (cost["bytes"] / 819e9) / 7.5e-3
    assert reader("stream_roofline").read(obs) == pytest.approx(want)
    assert 100 < want < 200     # 7.5 ms is faster than the chip can be
    obs.peaks = None                  # off a TPU: no share of a peak
    assert reader("stream_roofline").read(obs) is None
    assert reader("stream_roofline").read(
        observation(monkeypatch, {})) is None     # the parent: no map


def test_the_gap_is_the_worse_of_rows_and_columns():
    from znicz_tpu.observe import metrics
    read = reader("stream_sinkhorn_gap").read
    metrics.stream_maps("StreamOpen_cell_test", "row_gap").set(0.5)
    metrics.stream_maps("StreamOpen_cell_test", "col_gap").set(2e-6)
    metrics.stream_maps("StreamOpen_cell_test", "clamped").set(99.0)
    assert read(None) == 0.5
    metrics.stream_maps("StreamOpen_cell_test", "row_gap").set(0.0)
    metrics.stream_maps("StreamOpen_cell_test", "col_gap").set(0.0)


def test_streams_lm_train_mfu_is_model_flops_over_peak(monkeypatch):
    real = discovery.find_cell(CELL)
    layers, t = layer_table(real.config), real.traffic["seq_len"]
    obs = observation(monkeypatch, steps=40, layers=layers)
    obs.observations["sample_shape"] = (t,)
    want = 100 * flops_streams.lm_train_flops(layers, t, 1) \
        * (40 / 6.0) / 197e12
    assert reader("streams_lm_train_mfu").read(obs) == pytest.approx(want)
    assert 0 < want < 100
    obs.peaks = None
    assert reader("streams_lm_train_mfu").read(obs) is None
    obs.peaks = PEAKS
    obs.observations["layers"] = [EMB, MLA]     # one residual stream
    assert reader("streams_lm_train_mfu").read(obs) is None


# ----------------------------------------------------------------------
# the rehearsal
# ----------------------------------------------------------------------
def test_untraced_rehearsal():
    proc, lines = run(["--workload", CELL, "--seed", "3000000046",
                       "--seconds", "2", "--trace", "0", "--toy"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    assert set(line["metrics"]) == {"throughput", "setup_s"}
    log = "\n".join(lines)
    for word in ("1:stream_open=", "2:stream_read=",
                 "3:latent_attention=", "4:stream_write=",
                 "14:stream_close=", "a bf16 router would read"):
        assert word in log, word


def test_traced_rehearsal_reads_what_sinkhorn_reached():
    proc, lines = run(["--workload", CELL, "--seed", "3000000049",
                       "--seconds", "2", "--trace", "1", "--toy"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(lines[-1])
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert line["correct"] is True
    assert metrics["dispatches_per_step"] == 1
    assert metrics["programs_built_in_window"] == 0
    assert metrics["guard_skipped_steps"] == 0
    assert 0 < metrics["stream_sinkhorn_gap"] < 0.2
    assert "moe_held_rows_per_expert" in metrics
    assert set(metrics) <= per_layer(CELL)
    # off a TPU: no share of a peak; interpreted kernels leave no
    # kernel to time
    assert not {"stream_roofline", "streams_lm_train_mfu",
                "mla_flash_ms_per_step", "mla_flash_roofline"} \
        & set(metrics)

"""The Ling cell (PR 37): its entries in ``BENCHMARK.json`` looked up BY
NAME (so that a later PR's entries do not move them), its configuration
against the catalog's, its traffic, ``flops_latent`` against a count by
hand for one linear layer, the latent-attention layer and the held
experts, the six new readers on a synthetic trace / counter set, the
kernels' names, and a ``--toy`` rehearsal, traced and untraced."""

import json
import types

import pytest

from znbench import flops_latent, trace_reduce
from znbench.harness import discovery
from znbench.harness.program import layer_table
from znbench.tests.test_cells_toy import run

CELL = "ling_train_1of64"
BENCH = discovery.load_json(discovery.REPO + "/BENCHMARK.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = {"kda_ms_per_step": ("kernels", "ms", "lower", "device_trace"),
       "kda_roofline": ("kernels", "%", "higher", "device_trace"),
       "mla_flash_ms_per_step": ("kernels", "ms", "lower",
                                 "device_trace"),
       "mla_flash_roofline": ("kernels", "%", "higher", "device_trace"),
       "latent_lm_train_mfu": ("units", "%", "higher", "host_clock"),
       "moe_router_bias_ms_per_step": ("units", "ms", "lower",
                                       "device_trace")}
APPENDED = [
    "dispatches_per_step", "step_device_ms", "input_wait_share",
    "device_idle_share", "peak_hbm_gb", "flash_fwd_ms_per_step",
    "flash_bwd_ms_per_step", "host_reads_per_step",
    "host_read_wait_ms_per_step", "host_busy_ms_per_step",
    "guard_skipped_steps", "moe_gmm_ms_per_step", "moe_gmm_overwork",
    "moe_load_imbalance", "moe_held_rows_per_expert",
    "unit_attributed_share", "update_ms_per_step",
    "fingerprint_ms_per_step", "attention_unit_ms_per_step",
    "moe_unit_ms_per_step", "gated_mlp_unit_ms_per_step",
    "delta_net_unit_ms_per_step", "dense_unit_ms_per_step",
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
        "ling_3_0_flash", "train_lm_latent_ctx", 1)
    assert len(cell["why"]) <= 200
    config = next(c for c in BENCH["configs"]
                  if c["name"] == "ling_3_0_flash")
    assert config["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size",
        "num_nextn_predict_layers"]
    assert config["file"] == "znbench/configs/ling_3_0_flash.json"
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
    # shares whose arithmetic knows one head width, a scalar decay and
    # no latent are left out
    assert not {"train_mfu", "lm_train_mfu", "band_lm_train_mfu",
                "hybrid_lm_train_mfu", "flash_ms_per_step",
                "flash_roofline", "flash_dq_ms_per_step",
                "flash_dkv_ms_per_step", "moe_gmm_roofline",
                "delta_ms_per_step", "delta_chunk_ms_per_step",
                "delta_roofline", "delta_pad_overwork"} & per_layer(CELL)
    for other in (c["name"] for c in BENCH["workloads"]
                  if c["name"] != CELL):
        assert not set(NEW) & per_layer(other)


def test_the_configuration_is_the_catalog_s_but_for_the_cut():
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "Ling-3.0-flash")
    file = discovery.find_cell(CELL).config
    assert file["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in file["reduced"]:
            assert file["published"][key] == value
            assert file[key] < value
        else:
            assert file[key] == value, key
    # the guide's floors: a dense layer + one whole period, 8 experts,
    # an eighth of the vocabulary
    assert file["num_hidden_layers"] >= 1 + file["layer_group_size"]
    assert file["num_experts"] >= 8
    assert file["vocab_size"] * 8 >= row["config"]["vocab_size"]
    assert file["reference"] == "ling"
    assert discovery.load_module("reference", "ling") is not None


def test_the_traffic_and_the_table():
    real = discovery.find_cell(CELL)
    assert real.driver == "train_lm"
    assert (real.traffic["batch_per_chip"],
            real.traffic["steps_per_dispatch"]) == (1, 1)
    assert real.traffic["seq_len"] in (4096, 8192)
    assert real.traffic["engine"]["anomaly_check_interval"] \
        == real.traffic["steps_per_epoch"]
    assert real.traffic["min_segments"] == 10
    assert real.traffic["warmup_epochs"] == 2
    layers = layer_table(real.config)
    assert [l["type"] for l in layers] == (
        ["embedding", "gated_delta_net", "gated_mlp"]
        + ["gated_delta_net", "moe"] * 3 + ["latent_attention", "moe"]
        + ["gated_delta_net", "moe"] * 2 + ["rms_norm", "softmax"])
    assert layers[0]["->"]["dim"] == 2560
    assert layers[-1]["->"]["output_sample_shape"] \
        == real.config["vocab_size"] == layers[0]["->"]["vocab_size"]
    toy = discovery.find_cell(CELL, toy=True)
    assert [l["type"] for l in layer_table(toy.config)] \
        == [l["type"] for l in layers]
    assert toy.traffic["driver"] == "train_lm"


# ----------------------------------------------------------------------
# the arithmetic, by hand at the published widths
# ----------------------------------------------------------------------
EMB = {"type": "embedding", "->": {"vocab_size": 19648, "dim": 2560}}
KDA = {"type": "gated_delta_net", "->": {
    "n_heads": 32, "key_dim": 128, "value_dim": 128, "conv_kernel": 4,
    "decay": "channel", "lower_bound": -5.0}}
MLA = {"type": "latent_attention", "->": {
    "n_heads": 32, "causal": True, "head_gate": True, "kv_latent": 512,
    "qk_nope": 128, "qk_rope": 64, "v_head_dim": 128}}
MOE = {"type": "moe", "->": {
    "n_experts": 512, "top_k": 8, "width": 768, "shared_width": 768,
    "held": list(range(8))}}


def test_one_linear_layer_by_hand():
    """q ‖ k ‖ v 2·2560·12288, the output gate 2·2560·4096, the write
    gate's and the per-channel decay's logits 2·2560·(32 + 4096), the
    out-projection 2·4096·2560 = 125,992,960; 4 taps over 12,288
    channels 98,304; the chunked rule a chunk and head: five C²·128
    products of 1,048,576, the inverse by halves 174,592, three
    64·128·128 products with the state 6,291,456 = 11,708,928, ÷ 64
    positions × 32 heads = 5,854,464 a token."""
    parts = flops_latent.forward_flops_per_token([EMB, KDA], 4096)
    assert parts["kda_projections"] == 125_992_960
    assert parts["kda_conv"] == 98_304
    assert parts["kda_rule"] == 5_854_464
    assert sum(parts.values()) == 131_945_728
    # a scalar decay's logits are 2·D·2H: the layer is told apart
    scalar = {"type": "gated_delta_net",
              "->": {**KDA["->"], "decay": "head"}}
    assert flops_latent.forward_flops_per_token(
        [EMB, scalar], 4096)["kda_projections"] \
        == 125_992_960 - 2 * 2560 * 32 * 127
    assert flops_latent.kda_layers([EMB, scalar, KDA]) == [KDA["->"]]


def test_the_latent_attention_layer_by_hand():
    """The fused down-projection 2·2560·(32·192 + 512 + 64), the
    up-projection 2·512·32·256, the head gate 2·2560·32, the
    out-projection 2·4096·2560 = 63,930,368; over the causal half
    (4,096·4,097/2 pairs) 2·192 + 2·128 a pair and head."""
    parts = flops_latent.forward_flops_per_token([EMB, MLA], 4096)
    assert parts["mla_projections"] == 34_406_400 + 8_388_608 \
        + 163_840 + 20_971_520 == 63_930_368
    assert parts["mla_scores"] == 640 * 32 * 4097 / 2 == 41_953_280
    cost = flops_latent.mla_flash_train_cost([EMB, MLA], 4096, 1)
    pairs = 4096 * 4097 // 2
    assert cost["flops"] == 32 * pairs * (640 + 3 * 384 + 2 * 256)
    assert cost["flops"] / 197e12 > cost["bytes"] / 819e9   # compute
    assert flops_latent.latent_layers([EMB, MLA, KDA]) == [MLA["->"]]


def test_the_held_experts_by_hand():
    """8 of 512 held, top 8: 8 · 8 / 512 = 0.125 rows a token here
    under uniform routing, 6·2560·768 a row; the shared expert a whole
    row; the router 2·2560·512."""
    parts = flops_latent.forward_flops_per_token([EMB, MOE], 4096)
    assert parts["routed"] == 0.125 * 11_796_480 == 1_474_560
    assert parts["shared"] == 11_796_480
    assert parts["router"] == 2_621_440
    seen = flops_latent.forward_flops_per_token([EMB, MOE], 4096, {1: 0.5})
    assert seen["routed"] == 0.5 * 11_796_480   # the rows computed here


def test_the_whole_cell_s_step():
    """Six linear layers 6 × 131,945,728, the latent layer 105,883,648,
    the dense MLP 94,371,840, six expert layers 6 × 15,892,480, the
    head 100,597,760 = 1,187,882,496 a token forward: 3.56 GFLOP a
    token trained, 14.6 TFLOP a step at T 4,096 (ISSUE 37 reckoned
    ≈ 3.9 and 16, with 0.45 for the chunked rule where the program's
    algebra needs 0.105)."""
    layers = layer_table(discovery.find_cell(CELL).config)
    per_token = sum(flops_latent.forward_flops_per_token(
        layers, 4096).values())
    assert per_token == 1_187_882_496
    assert 3 * per_token / 1e9 == pytest.approx(3.5636, abs=1e-3)
    assert flops_latent.lm_train_flops(layers, 4096, 1) / 1e12 \
        == pytest.approx(14.597, abs=0.005)


def test_what_the_four_kernels_are_given():
    """A chunk of one head: forward M, W, U, P at 1,048,576 each + the
    inverse's five levels of two whole 64³ products 5,242,880 =
    9,437,184; backward M again + ten products + two 64³ = 10,485,760;
    the walk 4 · 64·128·128 forward and 8 backward.  Bytes at the
    stored widths (f32, but V, W, K̂, V′ as the backward keeps them and
    the per-chunk states and their cotangent at bf16): 295,680 +
    410,624 + 164,352 + 246,784 = 1,117,440 (all f32: 1,281,280).  64
    chunks × 32 heads × 6 layers = 12,288 of them a step: 0.40 TFLOP
    against 13.7 GB — memory bounds the roofline at 16.8 ms."""
    flops = flops_latent.kda_kernel_flops(128, 128)
    assert flops == {"chunk_fwd": 9_437_184, "chunk_bwd": 10_485_760,
                     "state_fwd": 4_194_304, "state_bwd": 8_388_608}
    bytes_ = flops_latent.kda_kernel_bytes(128, 128)
    assert bytes_ == {"chunk_fwd": 295_680, "chunk_bwd": 410_624,
                      "state_fwd": 164_352, "state_bwd": 246_784}
    cost = flops_latent.kda_train_cost([EMB] + [KDA] * 6, 4096, 1)
    assert cost == {"flops": 12_288 * 32_505_856.0,
                    "bytes": 12_288 * 1_117_440.0}
    assert cost["bytes"] / 819e9 == pytest.approx(16.77e-3, rel=5e-3)
    assert cost["flops"] / 197e12 < cost["bytes"] / 819e9


# ----------------------------------------------------------------------
# the readers on a synthetic trace and counter set
# ----------------------------------------------------------------------
KERNELS = {"%jvp_znicz_kda_chunk_fwd_.3": 4,
           "%transpose_jvp_znicz_kda_chunk_bwd_.5": 6,
           "%jvp_znicz_kda_state_fwd_.7": 2,
           "%transpose_jvp_znicz_kda_state_bwd_.9": 3,
           "%jvp_znicz_flash_fwd_mla.11": 5,
           "%znicz_flash_bwd_mla_dq.13": 7,
           "%znicz_flash_bwd_mla_dkv.15": 9,
           # a scalar-decay layer's and a one-width call's: not theirs
           "%jvp_znicz_gdr_chunk_fwd_.17": 11,
           "%znicz_delta_state_fwd.19": 13,
           "%znicz_flash_fwd.21": 15,
           "fusion.1": 8, "fusion.2": 1}


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
        window_s=6.0,
        observations={"steps": steps, "batch": 1,
                      "sample_shape": (4096,),
                      "layers": layers or [EMB] + [KDA] * 6 + [MLA]})


def test_the_kernels_are_told_apart_by_name(monkeypatch):
    obs = observation(monkeypatch)
    assert reader("kda_ms_per_step").read(obs) \
        == pytest.approx((4 + 6 + 2 + 3) / 2)
    assert reader("mla_flash_ms_per_step").read(obs) \
        == pytest.approx((5 + 7 + 9) / 2)
    # the accepted readers: the scalar kernels' hold no per-channel
    # time, the flash forward's and backward's count the two-width
    # calls by substring
    assert reader("delta_chunk_ms_per_step").read(obs) \
        == pytest.approx(11 / 2)
    assert reader("delta_ms_per_step").read(obs) == pytest.approx(13 / 2)
    assert reader("flash_fwd_ms_per_step").read(obs) \
        == pytest.approx((5 + 15) / 2)
    assert reader("flash_bwd_ms_per_step").read(obs) \
        == pytest.approx((7 + 9) / 2)
    for name in KERNELS:
        if "kda" in name:
            assert "znicz_gdr_chunk" not in name
            assert "znicz_delta_state" not in name


def test_the_rooflines_are_the_count_over_the_peak_over_the_time(
        monkeypatch):
    obs = observation(monkeypatch)
    kda = flops_latent.kda_train_cost(obs.observations["layers"], 4096, 1)
    assert reader("kda_roofline").read(obs) == pytest.approx(
        100 * (kda["bytes"] / 819e9) / (7.5e-3))
    mla = flops_latent.mla_flash_train_cost(
        obs.observations["layers"], 4096, 1)
    assert reader("mla_flash_roofline").read(obs) == pytest.approx(
        100 * (mla["flops"] / 197e12) / (10.5e-3))
    obs.peaks = None                  # off a TPU: no share of a peak
    assert reader("kda_roofline").read(obs) is None
    assert reader("mla_flash_roofline").read(obs) is None


def test_no_kernel_no_metric(monkeypatch):
    """Interpret mode, the plain paths, a program from before PR 37."""
    obs = observation(monkeypatch)
    obs.trace = trace_reduce.Trace(devices={"/device:TPU:0": [
        trace_reduce.Lane([trace_reduce.Event(
            "fusion.1", 1_000_000, 9_000_000)])]}, host=[])
    for name in ("kda_ms_per_step", "kda_roofline",
                 "mla_flash_ms_per_step", "mla_flash_roofline"):
        assert reader(name).read(obs) is None


def test_latent_lm_train_mfu_is_model_flops_over_peak(monkeypatch):
    layers = layer_table(discovery.find_cell(CELL).config)
    obs = observation(monkeypatch, steps=18, layers=layers)
    obs.observations["moe_units"] = []
    want = 100 * flops_latent.lm_train_flops(layers, 4096, 1) \
        * (18 / 6.0) / 197e12
    assert reader("latent_lm_train_mfu").read(obs) == pytest.approx(want)
    assert 0 < want < 100
    obs.peaks = None
    assert reader("latent_lm_train_mfu").read(obs) is None
    obs.peaks = PEAKS
    obs.observations["layers"] = [EMB, {"type": "softmax", "->": {
        "output_sample_shape": 19648}}]
    assert reader("latent_lm_train_mfu").read(obs) is None


def unit(name, family, phase):
    return {"unit": name, "kind": name, "family": family, "phase": phase}


def test_the_bias_s_rule_is_timed_under_its_own_phase(monkeypatch):
    scopes = {"znicz_step__train_region": {
        "fusion.1": unit("GDMoE_4", "MoE", "router_bias"),
        "fusion.2": unit("GDMoE_4", "MoE", "update")}}
    read = reader("moe_router_bias_ms_per_step").read
    assert read(observation(monkeypatch, scopes)) == pytest.approx(8 / 2)
    # the rule fused into a neighbour: the neighbour's, and 0 here
    scopes["znicz_step__train_region"]["fusion.1"] = {
        "unit": None, "units": ["GDMoE_4", "GDMoE_4"],
        "kinds": ["GDMoE"] * 2, "families": ["MoE"] * 2,
        "phases": ["update", "router_bias"]}
    assert read(observation(monkeypatch, scopes)) == 0.0
    # no map: nothing
    assert read(observation(monkeypatch, {})) is None


# ----------------------------------------------------------------------
# the rehearsal
# ----------------------------------------------------------------------
def test_untraced_rehearsal():
    proc, lines = run(["--workload", CELL, "--seed", "3000000037",
                       "--seconds", "2", "--trace", "0", "--toy"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    assert set(line["metrics"]) == {"throughput", "setup_s"}
    log = "\n".join(lines)
    assert "9:latent_attention=" in log and "13:gated_delta_net=" in log
    assert "a bf16 router would read" in log


def test_traced_rehearsal_moves_the_bias_inside_the_one_dispatch():
    proc, lines = run(["--workload", CELL, "--seed", "3000000039",
                       "--seconds", "2", "--trace", "1", "--toy"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(lines[-1])
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert line["correct"] is True
    assert metrics["dispatches_per_step"] == 1
    assert metrics["programs_built_in_window"] == 0
    assert metrics["guard_skipped_steps"] == 0
    # nine reads an epoch of 4 steps, all at its end: the three of every
    # LM cell (error count, loss, guard) and the six expert layers'
    # totals, the bias's among them
    assert metrics["host_reads_per_step"] == pytest.approx(9 / 4)
    assert "moe_router_bias_ms_per_step" in metrics
    assert "moe_held_rows_per_expert" in metrics
    assert "unit_attributed_share" in metrics
    assert set(metrics) <= per_layer(CELL)
    # interpreted kernels leave no kernel to time
    assert not {"kda_ms_per_step", "mla_flash_ms_per_step"} \
        & set(metrics)

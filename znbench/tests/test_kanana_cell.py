"""The kanana-2 cell (PR 52): its entries in ``BENCHMARK.json`` looked
up BY NAME (so that a later PR's entries do not move them; nothing here
pins a position or an exact list of another PR's), its configuration
against the catalog's key by key, its traffic, ``flops_latent`` against
a count by hand at the published widths, the two new readers on a
synthetic map, and ``--toy`` rehearsals through ``run.py`` — traced,
untraced, and with the SYSTEM's shared expert at ONE expert's width,
which has to read ``correct: false``."""

import json
import types

import pytest

from znbench import flops_band, flops_latent, trace_reduce
from znbench.harness import discovery
from znbench.harness.program import layer_table
from znbench.tests.test_cells_toy import RUN, run

CELL = "kanana2_train_1of8"
CONFIG = "kanana_2_30b_a3b"
BENCH = discovery.load_json(discovery.REPO + "/BENCHMARK.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("mla_project_ms_per_step", "mla_rotate_norm_ms_per_step")
#: accepted metrics the cell joined (it may join more later)
JOINED = {
    "dispatches_per_step", "programs_built_in_window", "step_device_ms",
    "input_wait_share", "device_idle_share", "peak_hbm_gb",
    "host_reads_per_step", "host_read_wait_ms_per_step",
    "host_busy_ms_per_step", "dispatch_wait_ms_per_step",
    "guard_skipped_steps", "unit_attributed_share", "update_ms_per_step",
    "fingerprint_ms_per_step", "attention_unit_ms_per_step",
    "moe_unit_ms_per_step", "gated_mlp_unit_ms_per_step",
    "dense_unit_ms_per_step", "other_units_ms_per_step",
    "setup_preprogram_s", "setup_initialize_s", "setup_param_fill_s",
    "setup_upload_s", "setup_trace_lower_s", "setup_compile_or_load_s",
    "setup_warmup_s", "latent_lm_train_mfu", "mla_flash_ms_per_step",
    "mla_flash_roofline", "flash_fwd_ms_per_step", "flash_bwd_ms_per_step",
    "moe_gmm_ms_per_step", "moe_gmm_overwork", "moe_load_imbalance",
    "moe_held_rows_per_expert", "moe_held_fit_step_share",
    "moe_router_bias_ms_per_step", "moe_combine_ms_per_step",
    "moe_route_ms_per_step"}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def per_layer(cell):
    return {m["name"] for m in BENCH["per_layer"]
            if cell in m.get("workloads", [cell])}


def reader(name):
    return discovery.load_module("layer_metrics", name)


def test_the_cell_and_its_entries():
    cell = next(c for c in BENCH["workloads"] if c["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "train_lm_mla_ctx", 1)
    assert len(cell["why"]) <= 200
    for word in ("T 16384", "batch 1", "768 rows", "1/8"):
        assert word in cell["why"], word
    config = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert config["file"] == f"znbench/configs/{CONFIG}.json"
    assert len(config["why"]) <= 200 and len(config["source"]) <= 200
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW:
        entry = by_name[name]
        assert {k: entry[k] for k in ("unit", "better", "source",
                                      "layer", "moves")} == {
            "unit": "ms", "better": "lower", "source": "device_trace",
            "layer": "units", "moves": "throughput"}
        # the three cells with a latent-K/V layer
        assert set(entry["workloads"]) >= {
            CELL, "ling_train_1of64", "xing_train_1of8"}
        assert reader(name) is not None
    throughput = next(m for m in BENCH["end_to_end"]
                      if m["name"] == "throughput")
    assert CELL in throughput["workloads"]
    assert set(NEW) | JOINED <= per_layer(CELL)
    # the halves of the backward that read nothing on a two-width
    # kernel, other families' kernels and other cells' shares of the
    # peak are left out
    assert not {"flash_dq_ms_per_step", "flash_dkv_ms_per_step",
                "kda_ms_per_step", "delta_net_unit_ms_per_step",
                "short_conv_ms_per_step", "conv_unit_ms_per_step",
                "stream_unit_ms_per_step", "streams_lm_train_mfu",
                "band_lm_train_mfu", "lm_train_mfu"} & per_layer(CELL)


def test_the_configuration_is_the_catalog_s_but_for_the_cut():
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "kanana-2-30b-a3b-instruct-2601")
    file = discovery.find_cell(CELL).config
    assert file["source"] == row["source_url"]
    assert file["catalog_name"] == row["name"]
    for key, value in row["config"].items():
        if key in file["reduced"]:
            assert file["published"][key] == value
            assert file[key] < value
        else:
            assert file[key] == value, key
    assert set(file["reduced"]) <= set(row["config"])
    # the guide's floors: the dense layer + four layers after it, 8
    # experts, an eighth of the vocabulary
    assert file["num_hidden_layers"] >= 1 + 4
    assert file["n_routed_experts"] == 16 >= 8
    assert file["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert file["reference"] == "kanana"
    assert discovery.load_module("reference", "kanana") is not None
    for key in ("reduced_why", "assumed", "deployment",
                "reference_tolerance_why"):
        assert file[key], key


def test_the_traffic_and_the_table():
    real = discovery.find_cell(CELL)
    assert real.driver == "train_lm"
    assert (real.traffic["batch_per_chip"],
            real.traffic["steps_per_dispatch"]) == (1, 1)
    assert real.traffic["seq_len"] in (8192, 16384)      # never 4,096
    assert real.traffic["engine"]["anomaly_check_interval"] \
        == real.traffic["steps_per_epoch"]
    assert real.traffic["engine"]["keep_written_leaves"] is True
    assert (real.traffic["min_segments"], real.traffic["warmup_epochs"],
            real.traffic["trace_seconds"]) == (10, 2, 6)
    layers = layer_table(real.config)
    kinds = [layer["type"] for layer in layers]
    blocks = kinds.count("latent_attention")
    assert blocks == {16384: 5, 8192: 6}[real.traffic["seq_len"]]
    assert kinds.count("moe") == blocks - 1
    assert kinds.count("gated_mlp") == 1 and kinds[2] == "gated_mlp"
    assert layers[0]["->"]["dim"] == 2048
    assert layers[-1]["->"]["output_sample_shape"] \
        == real.config["vocab_size"] == layers[0]["->"]["vocab_size"] \
        == real.config["input"]["vocab"] == 16032
    toy = discovery.find_cell(CELL, toy=True)
    assert set(layer["type"] for layer in layer_table(toy.config)) \
        == set(kinds)
    assert toy.traffic["driver"] == "train_lm"


# ----------------------------------------------------------------------
# the arithmetic, by hand at the published widths
# ----------------------------------------------------------------------
EMB = {"type": "embedding", "->": {"vocab_size": 16032, "dim": 2048}}
MLA = {"type": "latent_attention", "->": {
    "n_heads": 32, "kv_latent": 512, "qk_nope": 128, "qk_rope": 64,
    "v_head_dim": 128}}
DENSE = {"type": "gated_mlp", "->": {"width": 6144}}
MOE = {"type": "moe", "->": {
    "n_experts": 128, "top_k": 6, "width": 768, "shared_width": 1536,
    "held": list(range(16))}}
HEAD = {"type": "softmax", "->": {"output_sample_shape": 16032}}


def stack(blocks: int) -> list:
    return [EMB, MLA, DENSE] + [MLA, MOE] * (blocks - 1) + [HEAD]


def test_one_block_by_hand():
    """The fused projection 2·2048·6,720 = 27,525,120, the K/V up
    2·512·8,192 = 8,388,608, out 2·4,096·2048 = 16,777,216; the causal
    half (2·192 + 2·128)·32 a pair over (T + 1)/2 pairs a row; the
    dense MLP 6·2048·6,144; 16 of 128 held at top 6 is 0.75 of a row a
    token at 6·2048·768, the shared pair 6·2048·1,536, the router
    2·2048·128; the head 2·2048·16,032."""
    parts = flops_latent.forward_flops_per_token(stack(2), 16384)
    assert parts["mla_projections"] == 2 * (27_525_120 + 8_388_608
                                            + 16_777_216) == 105_381_888
    assert parts["mla_scores"] == 2 * 640 * 32 * 16385 / 2
    assert parts["dense"] == 75_497_472
    assert parts["routed"] == 0.75 * 9_437_184 == 7_077_888
    assert parts["shared"] == 18_874_368
    assert parts["router"] == 524_288
    assert parts["head"] == 65_667_072
    assert parts["kda_projections"] == parts["kda_rule"] == 0
    seen = flops_latent.forward_flops_per_token(stack(2), 16384, {4: 0.7})
    assert seen["routed"] == 0.7 * 9_437_184    # the rows computed here


@pytest.mark.parametrize("t,blocks,step,kernels", [
    (16384, 5, 66.3, 49.5), (8192, 6, 26.9, 14.9)])
def test_the_two_candidate_steps_by_hand(t, blocks, step, kernels):
    """A step at (16,384, 5 blocks) is 66.3 TFLOP of which the
    two-width kernels are given 49.5; at (8,192, 6 blocks) 26.9 and
    14.9."""
    layers = stack(blocks)
    per_token = blocks * (52_690_944 + 10_240 * (t + 1)) + 75_497_472 \
        + (blocks - 1) * (524_288 + 18_874_368 + 7_077_888) + 65_667_072
    assert sum(flops_latent.forward_flops_per_token(
        layers, t).values()) == per_token
    total = flops_latent.lm_train_flops(layers, t, 1)
    assert total == 3 * t * per_token
    assert total / 1e12 == pytest.approx(step, abs=0.05)
    pairs = t * (t + 1) // 2
    assert flops_band.visible_pairs(t) == pairs
    cost = flops_latent.mla_flash_train_cost(layers, t, 1)
    # forward 2·192 + 2·128; backward the scores again, dq, dk at 192
    # and dp, dv at 128
    assert cost["flops"] == blocks * 32 * pairs * (640 + 3 * 384 + 2 * 256)
    assert cost["flops"] / 1e12 == pytest.approx(kernels, abs=0.06)
    # compute bounds them by far
    assert cost["flops"] / 197e12 > 8 * cost["bytes"] / 819e9


def test_the_cell_s_own_table_is_one_of_the_two():
    real = discovery.find_cell(CELL)
    layers, t = layer_table(real.config), real.traffic["seq_len"]
    blocks = sum(layer["type"] == "latent_attention" for layer in layers)
    assert flops_latent.lm_train_flops(layers, t, 1) \
        == flops_latent.lm_train_flops(stack(blocks), t, 1)
    assert len(flops_latent.latent_layers(layers)) == blocks
    assert not flops_latent.kda_layers(layers)


# ----------------------------------------------------------------------
# the two new readers on a synthetic map
# ----------------------------------------------------------------------
OPS = {"fusion.1": 8, "fusion.2": 2, "fusion.3": 1, "fusion.4": 5,
       "fusion.5": 3, "%znicz_flash_fwd_mla.3": 4, "%gmm.9": 10}


def observation(monkeypatch, scopes=None, steps=2):
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
                      "sample_shape": (16384,), "model_dim": 2048,
                      "moe_units": [], "layers": stack(5)})


def unit(name, phase, family="MultiHeadAttention"):
    return {"unit": name, "kind": name, "family": family, "phase": phase}


SCOPES = {"znicz_step__train_region": {
    "fusion.1": unit("MultiHeadAttention_1", "project"),
    "fusion.2": unit("GDMultiHeadAttention_1", "project"),
    "fusion.3": unit("MultiHeadAttention_1", "rotate_norm"),
    # fused across both scopes: the unit's forward, in neither phase
    "fusion.4": unit("MultiHeadAttention_1", "forward"),
    # another family's operation under a scope of the same name
    "fusion.5": unit("MoE_2", "project", "MoE"),
    "%znicz_flash_fwd_mla.3": unit("MultiHeadAttention_1", "forward"),
    "%gmm.9": unit("MoE_2", "forward", "MoE")}}


def test_the_two_phases_are_read_from_the_attention_family(monkeypatch):
    """``project`` and ``rotate_norm`` of family MultiHeadAttention ÷
    steps; an operation in neither phase, the kernels and another
    family's stay out; the unit's row holds all of them."""
    obs = observation(monkeypatch, SCOPES)
    assert reader("mla_project_ms_per_step").read(obs) \
        == pytest.approx((8 + 2) / 2)
    assert reader("mla_rotate_norm_ms_per_step").read(obs) \
        == pytest.approx(1 / 2)
    assert reader("attention_unit_ms_per_step").read(obs) \
        == pytest.approx((8 + 2 + 1 + 5 + 4) / 2)
    assert reader("mla_flash_ms_per_step").read(obs) \
        == pytest.approx(4 / 2)
    for name in NEW:                  # no map: nothing
        assert reader(name).read(observation(monkeypatch, {})) is None


def test_a_program_that_knows_no_such_phase_reads_nothing(monkeypatch):
    """The parent of PR 52: ``observe.scopes`` names no ``project`` —
    the readers return ``None`` and do not raise, the line leaves the
    metrics out."""
    from znicz_tpu.observe import scopes
    obs = observation(monkeypatch, SCOPES)
    monkeypatch.setattr(scopes, "UNIT_PHASES", ("route", "combine"))
    for name in NEW:
        assert reader(name).read(obs) is None
    monkeypatch.delattr(scopes, "UNIT_PHASES")
    for name in NEW:
        assert reader(name).read(obs) is None


def test_the_roofline_and_the_mfu_take_the_cell(monkeypatch):
    obs = observation(monkeypatch, SCOPES, steps=6)
    cost = flops_latent.mla_flash_train_cost(stack(5), 16384, 1)
    per_step_s = 4e-3 / 6
    assert reader("mla_flash_roofline").read(obs) == pytest.approx(
        100 * (cost["flops"] / 197e12) / per_step_s)
    want = 100 * flops_latent.lm_train_flops(stack(5), 16384, 1) \
        * (6 / 6.0) / 197e12
    assert reader("latent_lm_train_mfu").read(obs) == pytest.approx(want)
    assert 30 < want < 40             # a step a second is 33.7%
    obs.peaks = None                  # off a TPU: no share of a peak
    assert reader("mla_flash_roofline").read(obs) is None
    assert reader("latent_lm_train_mfu").read(obs) is None


# ----------------------------------------------------------------------
# the rehearsals
# ----------------------------------------------------------------------
def test_untraced_rehearsal():
    proc, lines = run(["--workload", CELL, "--seed", "3000000052",
                       "--seconds", "2", "--trace", "0", "--toy"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    assert set(line["metrics"]) == {"throughput", "setup_s"}
    log = "\n".join(lines)
    for word in ("1:latent_attention=", "2:gated_mlp=",
                 "3:latent_attention=", "4:moe=", "6:softmax=",
                 "a bf16 router would read", "a bf16 table would read"):
        assert word in log, word


def test_traced_rehearsal_reads_the_new_phases():
    proc, lines = run(["--workload", CELL, "--seed", "3000000053",
                       "--seconds", "2", "--trace", "1", "--toy"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(lines[-1])
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert line["correct"] is True
    assert metrics["dispatches_per_step"] == 1
    assert metrics["programs_built_in_window"] == 0
    assert metrics["guard_skipped_steps"] == 0
    assert metrics["moe_held_rows_per_expert"] > 0
    assert 0 <= metrics["moe_held_fit_step_share"] <= 100
    for name in NEW:
        assert metrics[name] >= 0
    assert set(metrics) <= per_layer(CELL)
    # off a TPU: no share of a peak; interpreted kernels leave no
    # kernel to time
    assert not {"mla_flash_roofline", "mla_flash_ms_per_step",
                "latent_lm_train_mfu"} & set(metrics)


#: the toy's shared expert is 64 wide, two experts of 32 side by side:
#: the SYSTEM made to compute one of them
ONE_SHARED_EXPERT = (
    "from znicz_tpu.ops import moe\n"
    "real = moe.gated_mlp\n"
    "def one(xp, dot, m, w_gate, w_up, w_down, act='silu'):\n"
    "    if w_gate.shape[1] == 64:\n"
    "        w_gate, w_up, w_down = w_gate[:, :32], w_up[:, :32], "
    "w_down[:32]\n"
    "    return real(xp, dot, m, w_gate, w_up, w_down, act)\n"
    "moe.gated_mlp = one\n")


def test_a_system_with_one_shared_expert_reads_not_correct(tmp_path):
    """The SYSTEM — not the reference — with its shared expert at ONE
    routed expert's width, under the same configuration through the
    same driver: ``correct: false``, by the layers' limit."""
    toy = discovery.find_cell(CELL, toy=True)
    spec = next(layer["->"] for layer in layer_table(toy.config)
                if layer["type"] == "moe")
    assert (spec["shared_width"], spec["width"]) == (64, 32)
    script = tmp_path / "wrong_run.py"
    script.write_text(
        "import runpy, sys\n"
        f"sys.path.insert(0, {discovery.REPO!r})\n"
        + ONE_SHARED_EXPERT
        + f"runpy.run_path({RUN!r}, run_name='__main__')\n")
    proc, lines = run(["--workload", CELL, "--seed", "3000000054",
                       "--seconds", "2", "--trace", "0", "--toy"],
                      script=str(script))
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(lines[-1])
    assert line["correct"] is False
    assert "forward differs from the reference" in "\n".join(lines)

"""The Laguna cell's entries in ``BENCHMARK.json`` (looked up by name,
so that a later PR's entries do not move them), its configuration
against the catalog's, its traffic and its readers."""

import json
import os

import pytest

from znbench.harness import discovery
from znbench.harness.program import layer_table

CELL = "laguna_train_1of32"
BENCH = discovery.load_json(discovery.REPO + "/BENCHMARK.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = {"flash_win_ms_per_step": "kernels", "flash_win_roofline": "kernels",
       "flash_band_overwork": "kernels",
       "moe_held_rows_per_expert": "units", "band_lm_train_mfu": "units"}


def per_layer(cell):
    return {m["name"] for m in BENCH["per_layer"]
            if cell in m.get("workloads", [cell])}


def test_the_cell_and_its_entries():
    cell = next(c for c in BENCH["workloads"] if c["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "laguna_s_2_1", "train_lm_pretrain_ctx", 1)
    assert len(cell["why"]) <= 200
    config = next(c for c in BENCH["configs"]
                  if c["name"] == "laguna_s_2_1")
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["file"] == "znbench/configs/laguna_s_2_1.json"
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name, layer in NEW.items():
        entry = by_name[name]
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "throughput" and entry["layer"] == layer
        assert discovery.load_module("layer_metrics", name) is not None
    reported = per_layer(CELL)
    assert set(NEW) <= reported
    assert {"flash_fwd_ms_per_step", "flash_dq_ms_per_step",
            "flash_dkv_ms_per_step", "step_device_ms", "peak_hbm_gb",
            "moe_gmm_ms_per_step", "moe_load_imbalance",
            "host_reads_per_step", "guard_skipped_steps"} <= reported
    # shares whose arithmetic counts another model's work are left out
    assert not {"moe_gmm_roofline", "lm_train_mfu", "train_mfu",
                "flash_ms_per_step", "flash_roofline",
                "sdc_vote_ms"} & reported
    # no other cell reports what this PR added
    for other in (c["name"] for c in BENCH["workloads"]
                  if c["name"] != CELL):
        assert not set(NEW) & per_layer(other)


def test_the_traffic_and_the_share():
    real = discovery.find_cell(CELL)
    assert real.driver == "train_lm"
    assert real.traffic["seq_len"] in (8192, 4096)
    assert real.traffic["batch_per_chip"] == 1
    assert real.traffic["steps_per_dispatch"] == 1
    assert real.traffic["engine"]["anomaly_check_interval"] \
        == real.traffic["steps_per_epoch"]
    assert real.traffic["min_segments"] == 10
    layers = layer_table(real.config)
    assert [l["type"] for l in layers] == [
        "embedding", "attention", "gated_mlp", "attention", "moe",
        "attention", "moe", "attention", "moe", "attention", "moe",
        "rms_norm", "softmax"]
    heads = [(l["->"]["n_heads"], l["->"].get("window"))
             for l in layers if l["type"] == "attention"]
    assert heads == [(48, None), (72, 512), (72, 512), (72, 512),
                     (48, None)]
    for spec in (l["->"] for l in layers if l["type"] == "attention"):
        assert (spec["n_kv_heads"], spec["head_dim"]) == (8, 128)
        assert spec["head_gate"] and spec["pre_norm"] == "rms"
        rope = spec["rope"]
        assert (rope["theta"], rope.get("rotary_dim")) == (
            (10000, None) if spec.get("window") else (500000, 64))
        assert bool(rope.get("yarn")) == (not spec.get("window"))
    for spec in (l["->"] for l in layers if l["type"] == "moe"):
        assert (spec["n_experts"], spec["top_k"], spec["width"]) == (
            256, 10, 1024)
        assert spec["held"] == list(range(8))
        assert (spec["score"], spec["routed_scale"],
                spec["shared_width"], spec["norm_topk"]) == (
            "sigmoid", 2.5, 1024, True)
    assert layers[2]["->"]["width"] == 12288
    assert layers[0]["->"] == {"vocab_size": 12544, "dim": 3072}
    assert real.config["input"]["vocab"] == 12544 == 100352 // 8
    assert real.config["published"] == {
        "num_hidden_layers": 48, "num_experts": 256,
        "vocab_size": 100352}
    for key in ("reduced_why", "assumed", "deployment",
                "reference_tolerance_why"):
        assert real.config[key]


def test_every_number_of_the_catalog_s_config_is_in_the_file():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guides here")
    with open(CATALOG) as fh:
        row = next(json.loads(line) for line in fh
                   if '"Laguna-S-2.1"' in line)
    config = discovery.find_cell(CELL).config
    assert config["source"] == row["source_url"]
    changed = {"num_hidden_layers": 5, "num_experts": 8,
               "vocab_size": 12544}
    for key, value in row["config"].items():
        assert config[key] == changed.get(key, value), key


def test_the_toy_twin_has_the_same_table_in_small():
    toy = discovery.find_cell(CELL, toy=True)
    real = discovery.find_cell(CELL)
    small, big = layer_table(toy.config), layer_table(real.config)
    assert [l["type"] for l in small] == [l["type"] for l in big]
    for a, b in zip(small, big):
        assert set(a["->"]) == set(b["->"])
    assert toy.config["reference"] == real.config["reference"] == "laguna"
    assert toy.config["reference_tolerance"] \
        == real.config["reference_tolerance"]

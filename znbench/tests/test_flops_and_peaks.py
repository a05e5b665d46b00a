"""The copied FLOPs arithmetic against its originals on the old
shapes, and the table of peaks."""

import json
import os

import pytest

from znbench import flops
from znbench.harness import discovery

HERE = os.path.dirname(os.path.abspath(__file__))
ZNBENCH = os.path.dirname(HERE)


def test_alexnet_flops_match_bench_train_step_flops():
    import bench
    from znicz_tpu.backends import NumpyDevice
    from znicz_tpu.models.samples import alexnet

    wf = alexnet.build(minibatch_size=4, image_size=67, n_classes=10,
                       n_train_samples=8, n_valid_samples=0)
    wf.initialize(device=NumpyDevice())
    cfg = {"learning_rate": 0.01, "gradient_moment": 0.9,
           "weights_decay": 0.0005, "dropout": 0.5, "n_classes": 10}
    mine = flops.train_step_flops(alexnet.layers(cfg), (67, 67, 3), 4)
    assert mine == pytest.approx(bench.train_step_flops(wf), rel=1e-12)


def test_published_alexnet_is_about_two_gflops_forward_per_image():
    config = discovery.load_json(
        os.path.join(ZNBENCH, "configs", "alexnet.json"))
    from znbench.harness.program import layer_table
    forward = flops.forward_flops(layer_table(config), (227, 227, 3), 1)
    assert 2.0e9 < forward < 2.4e9        # 1.1 G MACs, one tower


@pytest.mark.parametrize("causal", [False, True])
def test_sequence_flops_match_seq_bench(causal, monkeypatch):
    from benchmarks import seq_bench
    monkeypatch.setattr(seq_bench, "CAUSAL", causal)
    layers = [{"type": "attention",
               "->": {"n_heads": seq_bench.HEADS, "causal": causal}},
              {"type": "layer_norm", "->": {}},
              {"type": "softmax", "->": {"output_sample_shape": 8}}]
    mine = flops.train_step_flops(
        layers, (seq_bench.SEQ_LEN, seq_bench.DIM), seq_bench.BATCH)
    assert mine == pytest.approx(seq_bench.attn_train_flops(),
                                 rel=1e-12)


def test_repeat_expands_and_the_lm_head_sees_one_position():
    config = discovery.load_json(
        os.path.join(ZNBENCH, "configs", "attn_lm_base.json"))
    layers = flops.expand(config["workflow"]["layers"])
    assert [l["type"] for l in layers].count("attention") == 6
    t, d, v, b = 2048, 512, 32768, 64
    per_layer = 4 * 2.0 * b * t * d * d + 0.5 * 4.0 * b * t * t * d
    assert flops.forward_flops(layers, (t,), b) == pytest.approx(
        6 * per_layer + 2.0 * b * d * v)
    cost = flops.flash_train_cost(layers, t, d, b)
    assert cost["flops"] == pytest.approx(6 * 0.5 * 14.0 * b * t * t * d)
    least, bound = flops.roofline_seconds(
        cost, discovery.peaks_for("TPU v5 lite"))
    assert bound == "compute" and least == pytest.approx(
        cost["flops"] / 197e12)


def test_peaks_know_the_v5e_and_refuse_the_unknown():
    v5e = discovery.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["ici_bits_per_s"] == 1600e9
    assert "Google Cloud" in v5e["source"]
    with pytest.raises(discovery.BenchmarkError):
        discovery.peaks_for("TPU v9 imaginary")
    with pytest.raises(discovery.BenchmarkError):
        discovery.peaks_for("cpu")


def test_benchmark_json_names_only_files_that_exist():
    bench = json.load(open(os.path.join(discovery.REPO,
                                        "BENCHMARK.json")))
    for cell in bench["workloads"]:
        found = discovery.find_cell(cell["name"])
        assert discovery.load_module("drivers", found.driver)
        assert discovery.load_module("reference",
                                     found.config["reference"])
        assert discovery.find_cell(cell["name"], toy=True).config["toy"]
    for metric in bench["per_layer"]:
        assert discovery.load_module("layer_metrics", metric["name"]), \
            metric["name"]
        assert metric["moves"] in {m["name"]
                                   for m in bench["end_to_end"]}

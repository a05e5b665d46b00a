"""The Olmo-Hybrid cell's own arithmetic (``znbench/flops_delta.py``)
pinned by hand-computed values at the published widths, and its readers
on what a program without the new gauge and kernels leaves (nothing,
never an error)."""

import types

import pytest

from znbench import flops_band, flops_delta
from znbench.harness import discovery
from znbench.harness.program import layer_table

CONFIG = discovery.load_json(
    discovery.REPO + "/znbench/configs/olmo_hybrid_7b.json")
LAYERS = layer_table(CONFIG)
D, H, DK, DV, F, V = 3840, 30, 96, 192, 11008, 12544


def test_the_layer_table_is_one_period_at_the_published_widths():
    assert [layer["type"] for layer in LAYERS] == [
        "embedding", "gated_delta_net", "gated_mlp", "gated_delta_net",
        "gated_mlp", "gated_delta_net", "gated_mlp", "attention",
        "gated_mlp", "rms_norm", "softmax"]
    assert flops_delta.delta_shape(LAYERS[1]["->"]) == (H, DK, DV, 4)


def test_inverse_by_halves():
    assert flops_delta.inverse_flops(2) == 0
    assert flops_delta.inverse_flops(4) == 2 * 2 * 2 ** 3
    # sizes 2, 4, 8, 16, 32: C/2s pairs of two s³ matmuls
    assert flops_delta.inverse_flops(64) == sum(
        (64 // (2 * s)) * 4 * s ** 3 for s in (2, 4, 8, 16, 32)) \
        == 174592


def test_chunk_flops_by_part():
    parts = flops_delta.chunk_flops(DK, DV)
    assert parts["k_kt"] == parts["q_kt"] == parts["w"] \
        == 2 * 64 * 64 * 96
    assert parts["u"] == parts["p_v"] == 2 * 64 * 64 * 192
    assert parts["state"] == 3 * 2 * 64 * 96 * 192
    # 12.8 MFLOP a chunk and head: 0.2 MFLOP a token and head
    assert sum(parts.values()) == pytest.approx(12.76e6, rel=1e-3)


def test_train_flops_at_the_published_widths():
    """The linear layers' parts by hand; the whole against every part
    written out by hand (the attention layer, the MLPs and the head are
    ``flops_band``'s count: the sum has to be what the four rules give
    together)."""
    t = 4096
    parts = flops_delta.delta_flops_per_token(LAYERS)
    wide = H * (2 * DK + DV)
    assert parts["delta_projections"] == 3 * (
        2 * D * wide + 2 * D * H * DV + 2 * D * 2 * H + 2 * H * DV * D)
    assert parts["delta_conv"] == 3 * 2 * 4 * wide
    assert parts["delta_rule"] == pytest.approx(
        3 * H * sum(flops_delta.chunk_flops(DK, DV).values()) / 64)
    assert parts["delta_rule"] == pytest.approx(3 * 5.98e6, rel=2e-3)
    rest = {"attn_projections": 8 * D * 30 * 128,
            "scores": 4 * 128 * 30 * (t + 1) / 2,
            "dense": 4 * 6 * D * F, "head": 2 * D * V}
    assert sum(flops_band.forward_flops_per_token(LAYERS, t).values()) \
        == pytest.approx(sum(rest.values()), rel=1e-12)
    # ISSUE 31: 5.45 GFLOP a token in training, 22 TFLOP a step at
    # T 4,096; the linear mixers' rule about one hundredth of it
    per_token = 3 * (sum(parts.values()) + sum(rest.values()))
    assert per_token == pytest.approx(5.44e9, rel=3e-3)
    assert flops_delta.lm_train_flops(LAYERS, t, 1) \
        == pytest.approx(per_token * t, rel=1e-12)
    assert flops_delta.lm_train_flops(LAYERS, t, 2) \
        == pytest.approx(22.3e12 * 2, rel=3e-3)
    assert 0.008 < 3 * parts["delta_rule"] / per_token < 0.012
    assert 0.05 < 3 * rest["head"] / per_token < 0.06
    # a table without a linear layer is flops_band's count alone
    laguna = layer_table(discovery.load_json(
        discovery.REPO + "/znbench/configs/laguna_s_2_1.json"))
    assert flops_delta.lm_train_flops(laguna, t, 1) \
        == flops_band.lm_train_flops(laguna, t, 1)


def test_state_kernels_cost():
    cost = flops_delta.delta_train_cost(LAYERS, 4096, 1)
    chunk_heads = 3 * H * 64
    assert cost["flops"] == chunk_heads * 12 * 64 * DK * DV
    key, value, state, row = 64 * DK, 64 * DV, DK * DV, DV
    forward = 2 * key + 2 * value + state + row
    backward = 4 * key + 3 * value + 2 * state + 2 * row
    assert cost["bytes"] == chunk_heads * (forward + backward) * 4
    # 21 FLOP a byte forward: the walk is bound by memory
    assert 4 * 64 * DK * DV / (forward * 4) == pytest.approx(21.3,
                                                            abs=0.1)
    # a sequence of broken chunks walks the padded ones too
    assert flops_delta.delta_train_cost(LAYERS, 4033, 1) == cost
    # the other configurations have no such layer
    olmoe = layer_table(discovery.load_json(
        discovery.REPO + "/znbench/configs/olmoe_1b_7b.json"))
    assert flops_delta.delta_train_cost(olmoe, 4096, 1) \
        == {"flops": 0.0, "bytes": 0.0}


def _obs(**over):
    trace = types.SimpleNamespace(devices=[])
    obs = types.SimpleNamespace(
        observations={"steps": 10, "layers": LAYERS, "batch": 1,
                      "sample_shape": (4096,)},
        trace=trace, trace_window=None, peaks=None, window_s=2.0,
        chips=1)
    for key, value in over.items():
        setattr(obs, key, value)
    return obs


@pytest.mark.parametrize("metric", [
    "delta_ms_per_step", "delta_roofline", "delta_pad_overwork",
    "hybrid_lm_train_mfu"])
def test_readers_return_nothing_where_there_is_nothing_to_read(metric):
    """The parent's program has neither gauge nor kernel: a reader
    returns ``None`` there and does not raise."""
    from znicz_tpu.observe import metrics
    metrics.REGISTRY.clear()
    reader = discovery.load_module("layer_metrics", metric)
    assert reader.read(_obs()) is None


def test_the_utilisation_reader_counts_the_model_s_work():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    reader = discovery.load_module("layer_metrics",
                                   "hybrid_lm_train_mfu")
    got = reader.read(_obs(peaks=peaks))
    want = 100 * flops_delta.lm_train_flops(LAYERS, 4096, 1) * 5 / 197e12
    assert got == pytest.approx(want)
    # a model without a recurrent layer is not this reader's
    laguna = layer_table(discovery.load_json(
        discovery.REPO + "/znbench/configs/laguna_s_2_1.json"))
    obs = _obs(peaks=peaks)
    obs.observations["layers"] = laguna
    assert reader.read(obs) is None


def test_the_overwork_reader_reads_the_scan_gauge():
    from znicz_tpu.observe import metrics
    metrics.REGISTRY.clear()
    for unit, path, share in (("a", 1, 1.78), ("b", 1, 1.0),
                              ("c", 0, 1.0)):
        metrics.delta_scan(unit, "path").set(path)
        metrics.delta_scan(unit, "padded_share").set(share)
    reader = discovery.load_module("layer_metrics", "delta_pad_overwork")
    assert reader.read(_obs()) == pytest.approx(1.39)

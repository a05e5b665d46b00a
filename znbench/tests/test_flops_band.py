"""The Laguna cell's own arithmetic (``znbench/flops_band.py``) pinned
by hand-computed values at the published widths, and its readers on
what a program without the new gauges and kernels leaves (nothing,
never an error)."""

import types

import pytest

from znbench import flops_band
from znbench.harness import discovery
from znbench.harness.program import layer_table

CONFIG = discovery.load_json(
    discovery.REPO + "/znbench/configs/laguna_s_2_1.json")
LAYERS = layer_table(CONFIG)


def test_visible_pairs():
    assert flops_band.visible_pairs(8, None) == 36          # 8·9/2
    assert flops_band.visible_pairs(8, 8) == 36
    assert flops_band.visible_pairs(8, 100) == 36
    assert flops_band.visible_pairs(8, 3) == 1 + 2 + 6 * 3
    assert flops_band.visible_pairs(8, 1) == 8
    assert flops_band.visible_pairs(4096, 512) == 512 * 513 / 2 \
        + 3584 * 512


def test_forward_flops_per_token_at_the_published_widths():
    t = 4096
    parts = flops_band.forward_flops_per_token(LAYERS, t)
    d, dh = 3072, 128
    full = 2 * d * (48 + 16) * dh + 2 * 48 * dh * d
    sliding = 2 * d * (72 + 16) * dh + 2 * 72 * dh * d
    assert parts["projections"] == 2 * full + 3 * sliding
    assert parts["gate"] == 2 * d * (2 * 48 + 3 * 72)
    assert parts["scores"] == pytest.approx(
        (2 * 4 * dh * 48 * (t * (t + 1) / 2)
         + 3 * 4 * dh * 72 * (512 * 513 / 2 + 3584 * 512)) / t)
    assert parts["dense"] == 6 * d * 12288
    assert parts["shared"] == 4 * 6 * d * 1024
    assert parts["router"] == 4 * 2 * d * 256
    # 10 of 256 chosen, 8 of 256 held: 10·8/256 rows a token a layer
    assert parts["routed"] == pytest.approx(4 * 6 * d * 1024 * 10 * 8 / 256)
    assert parts["head"] == 2 * d * 12544
    # ISSUE 29's estimate: 13.8 TFLOP a step at T 4096, 29–30 at 8192
    assert flops_band.lm_train_flops(LAYERS, 4096, 1) \
        == pytest.approx(13.74e12, rel=2e-3)
    assert flops_band.lm_train_flops(LAYERS, 8192, 1) \
        == pytest.approx(30.0e12, rel=2e-3)
    # the rows the chip really computed replace the expectation
    moe_at = [i for i, l in enumerate(LAYERS) if l["type"] == "moe"]
    more = flops_band.forward_flops_per_token(
        LAYERS, t, {i: 1.0 for i in moe_at})
    assert more["routed"] == 4 * 6 * d * 1024


def test_windowed_kernel_cost():
    cost = flops_band.flash_win_train_cost(LAYERS, 4096, 1)
    pairs = 512 * 513 / 2 + 3584 * 512
    assert cost["flops"] == 3 * 14 * 128 * 72 * pairs
    assert cost["bytes"] == 3 * 6 * 4096 * (72 + 8) * 128 * 2
    # a window that covers the sequence is no windowed kernel
    assert flops_band.flash_win_train_cost(LAYERS, 512, 1) \
        == {"flops": 0.0, "bytes": 0.0}
    # the other configurations have no windowed layer
    olmoe = layer_table(discovery.load_json(
        discovery.REPO + "/znbench/configs/olmoe_1b_7b.json"))
    assert flops_band.flash_win_train_cost(olmoe, 4096, 1)["flops"] == 0


def _obs(**over):
    trace = types.SimpleNamespace(devices=[])
    obs = types.SimpleNamespace(
        observations={"steps": 10, "layers": LAYERS, "batch": 1,
                      "sample_shape": (4096,), "moe_units": []},
        trace=trace, trace_window=None, peaks=None, window_s=2.0,
        chips=1)
    for key, value in over.items():
        setattr(obs, key, value)
    return obs


@pytest.mark.parametrize("metric", [
    "flash_win_ms_per_step", "flash_win_roofline", "flash_band_overwork",
    "moe_held_rows_per_expert", "band_lm_train_mfu"])
def test_readers_return_nothing_where_there_is_nothing_to_read(metric):
    """The parent's program has neither gauge nor kernel: a reader
    returns ``None`` there and does not raise."""
    from znicz_tpu.observe import metrics
    metrics.REGISTRY.clear()
    reader = discovery.load_module("layer_metrics", metric)
    assert reader.read(_obs()) is None


def test_the_utilisation_reader_counts_the_model_s_work():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    reader = discovery.load_module("layer_metrics", "band_lm_train_mfu")
    got = reader.read(_obs(peaks=peaks))
    want = 100 * flops_band.lm_train_flops(LAYERS, 4096, 1) * 5 / 197e12
    assert got == pytest.approx(want)


def test_the_overwork_reader_reads_the_band_gauge():
    from znicz_tpu.observe import metrics
    metrics.REGISTRY.clear()
    for unit, executed in (("a", 0.2), ("b", 0.3)):
        metrics.flash_band(unit, "window").set(512)
        metrics.flash_band(unit, "band_share").set(0.1)
        metrics.flash_band(unit, "executed_share").set(executed)
    reader = discovery.load_module("layer_metrics", "flash_band_overwork")
    assert reader.read(_obs()) == pytest.approx(2.5)
    held = discovery.load_module("layer_metrics",
                                 "moe_held_rows_per_expert")
    metrics.moe_held("m", "held").set(8)
    metrics.moe_held("m", "rows_here").set(1280)
    obs = _obs()
    obs.observations["moe_units"] = ["m"]
    assert held.read(obs) == 160

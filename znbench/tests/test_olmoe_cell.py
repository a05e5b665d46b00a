"""What PR 25 adds to the benchmark: the expert model's arithmetic
pinned against a hand count, its four readers on hand-built
observations, the plain reference's routing hand-over, the entries of
``BENCHMARK.json``, and the traced toy rehearsal's exact read count."""

import json
import types

import numpy as np
import pytest

from znbench import flops, flops_moe
from znbench.harness import discovery
from znbench.trace_reduce import Event, Trace
from test_cells_toy import metric_names, run

MS = 1_000_000   # ns
CELL = "olmoe_train_t4096"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def reader(name):
    return discovery.load_module("layer_metrics", name).read


def real_layers() -> list:
    return discovery.find_cell(CELL).config["workflow"]["layers"]


# ----------------------------------------------------------------------
# the arithmetic, against a hand count at the published widths
# ----------------------------------------------------------------------
def test_model_flops_per_token_by_hand():
    parts = flops_moe.forward_flops_per_token(real_layers(), 4096)
    assert parts == {
        "projections": 2 * 8 * 2048 ** 2,            # 2 × 33,554,432
        "scores": 2 * 2 * 4096 * 2048,               # 2 × 16,777,216
        "experts": 2 * 6 * 8 * 2048 * 1024,          # 2 × 100,663,296
        "router": 2 * 2 * 2048 * 64,                 # 2 × 262,144
        "head": 2 * 2048 * 50304}                    # 206,045,184
    assert sum(parts.values()) == 508_559_360
    # the head's share after the depth cut (ISSUE 25: 41%; 8% at 16)
    assert parts["head"] / sum(parts.values()) == pytest.approx(
        0.405, abs=1e-3)
    assert flops_moe.lm_train_flops(real_layers(), 4096, 1) == \
        3 * 4096 * 508_559_360                       # 6.25 TFLOP a step


def test_grouped_matmul_cost_by_hand():
    cost = flops_moe.gmm_train_cost(real_layers(), 4096, 2048)
    rows, d, f, e = 4096 * 8, 2048, 1024, 64
    assert cost["flops"] == 2 * 18 * rows * d * f    # 2 layers
    slabs = e * d * f
    one_layer = 0
    for k, n in ((d, f), (d, f), (f, d)):
        one_layer += (rows * k * 2 + slabs * 2 + rows * n * 4)      # fwd
        one_layer += (rows * n * 2 + slabs * 2 + rows * k * 4)      # rows
        one_layer += (rows * (k + n) * 2 + slabs * 4)               # slabs
    assert cost["bytes"] == 2 * one_layer
    # at 512 rows an expert the slabs' traffic bounds it (each weight
    # is read twice in bf16 and its gradient written in f32 for 512
    # rows of work); at 1,024 rows — 8,192 tokens a step — compute does
    least, bound = flops.roofline_seconds(cost, PEAKS)
    assert bound == "memory"
    assert least == pytest.approx(cost["bytes"] / 819e9)
    assert cost["flops"] / 197e12 == pytest.approx(12.56e-3, rel=1e-3)
    assert cost["bytes"] / 819e9 == pytest.approx(13.9e-3, rel=2e-2)
    twice = flops_moe.gmm_train_cost(real_layers(), 8192, 2048)
    assert flops.roofline_seconds(twice, PEAKS)[1] == "compute"


# ----------------------------------------------------------------------
# the readers
# ----------------------------------------------------------------------
def kernel_trace():
    """Two steps of one expert layer as the TPU names them: the Pallas
    grouped matmuls (``gmm``, ``tgmm``), a flash kernel, and a fusion
    whose HLO LINE mentions a grouped matmul."""
    lane, t = [Event("while.1", 0, 200 * MS)], 0
    for step in range(2):
        for name, dur in (("gmm", 3), ("gmm", 3), ("gmm", 4),
                          ("gmm", 3), ("tgmm", 5),
                          ("jvp_znicz_flash_fwd_", 7)):
            n = 10 * step + len(lane)
            lane.append(Event(f"{name}.{n}", t * MS, (t + dur) * MS,
                              f"%{name}.{n} = f32[] custom-call()"))
            t += dur
        lane.append(Event(f"fusion.{step}", t * MS, (t + 2) * MS,
                          f"%fusion.{step} = f32[] fusion(%gmm.3)"))
        t += 2
    return Trace(devices={"/device:TPU:0": [lane]}, host=[])


def observation(**kwargs):
    base = dict(
        trace=Trace(devices={}, host=[]), trace_window=None,
        peaks=PEAKS, chips=1, window_s=2.0,
        observations={"steps": 2, "batch": 1, "sample_shape": (4096,),
                      "model_dim": 2048, "layers": real_layers(),
                      "moe_units": []})
    base.update(kwargs)
    return types.SimpleNamespace(**base)


def test_grouped_matmuls_are_read_by_name_and_their_roofline():
    obs = observation(trace=kernel_trace())
    assert reader("moe_gmm_ms_per_step")(obs) == pytest.approx(18.0)
    cost = flops_moe.gmm_train_cost(real_layers(), 4096, 2048)
    assert reader("moe_gmm_roofline")(obs) == pytest.approx(
        100 * cost["bytes"] / 819e9 / 18e-3)
    # a program without an expert layer, a CPU rehearsal, no peaks
    bare = Trace(devices={"d": [[Event("fusion.1", 0, MS, "%fusion.1")]]},
                 host=[])
    for trace in (bare, Trace(devices={}, host=[])):
        assert reader("moe_gmm_ms_per_step")(
            observation(trace=trace)) is None
        assert reader("moe_gmm_roofline")(
            observation(trace=trace)) is None
    assert reader("moe_gmm_roofline")(
        observation(trace=kernel_trace(), peaks=None)) is None


def test_load_imbalance_reads_the_gauges():
    from znicz_tpu.observe import metrics
    for unit, fullest in (("cell_moe_a", 1200.0), ("cell_moe_b", 1100.0)):
        metrics.moe_expert_tokens(unit, "max").set(fullest)
        metrics.moe_expert_tokens(unit, "mean").set(1000.0)
    obs = observation()
    obs.observations["moe_units"] = ["cell_moe_a", "cell_moe_b"]
    assert reader("moe_load_imbalance")(obs) == pytest.approx(1.15)
    obs.observations["moe_units"] = ["cell_moe_never_ran"]
    assert reader("moe_load_imbalance")(obs) is None
    assert reader("moe_load_imbalance")(observation()) is None


def test_lm_train_mfu_is_model_flops_over_peak():
    obs = observation()           # 2 steps of 4096 tokens in 2 s
    want = 100 * 3 * 4096 * 508_559_360 / 197e12
    assert reader("lm_train_mfu")(obs) == pytest.approx(want)
    assert reader("lm_train_mfu")(observation(peaks=None)) is None


# ----------------------------------------------------------------------
# the plain reference: its own choice, and a choice handed in
# ----------------------------------------------------------------------
def test_reference_takes_the_routing_it_is_given():
    reference = discovery.load_module("reference", "olmoe")
    layers = discovery.find_cell(CELL, toy=True).config[
        "workflow"]["layers"]
    rng = np.random.default_rng(0)
    params, d, e, f, v = {}, 64, 8, 32, 97
    for i, layer in enumerate(layers):
        kind = layer["type"]
        if kind == "embedding":
            params[f"layer{i}_weights"] = rng.normal(0, 1, (v, d))
        elif kind == "attention":
            params[f"layer{i}_weights"] = rng.normal(0, .1, (d, 3 * d))
            params[f"layer{i}_weights_out"] = rng.normal(0, .1, (d, d))
            for gain in ("norm", "q", "k"):
                params[f"layer{i}_gain_{gain}"] = np.ones(d)
        elif kind == "moe":
            params[f"layer{i}_weights"] = rng.normal(0, .5, (d, e))
            params[f"layer{i}_weights_gate"] = rng.normal(0, .1, (e, d, f))
            params[f"layer{i}_weights_up"] = rng.normal(0, .1, (e, d, f))
            params[f"layer{i}_weights_down"] = rng.normal(0, .1, (e, f, d))
            params[f"layer{i}_gain_norm"] = np.ones(d)
        elif kind == "rms_norm":
            params[f"layer{i}_weights"] = np.ones(d)
        else:
            params[f"layer{i}_weights"] = rng.normal(0, .1, (d, v))
    tokens = rng.integers(0, v, (2, 16))
    outs, router = reference.run(params, layers, tokens)
    assert len(outs) == len(layers) and outs[-1].shape == (2, 16, v)
    np.testing.assert_allclose(np.asarray(outs[-1]).sum(-1), 1.0,
                               rtol=1e-5)
    moe_at = [i for i, l in enumerate(layers) if l["type"] == "moe"]
    assert sorted(router["chosen"]) == moe_at
    for i in moe_at:              # top 2 of 8, the larger first
        logits = np.asarray(router["logits"][i])
        np.testing.assert_array_equal(
            router["chosen"][i], np.argsort(-logits, -1)[:, :2])
        lb, z = router["aux"][i]
        assert 2.0 <= float(lb) < 8.0 and float(z) > 0
    # handed its own choice back, nothing moves; handed another, the
    # expert layers' outputs do
    same = reference.forward(params, layers, tokens, router["chosen"])
    for a, b in zip(same, outs):
        np.testing.assert_allclose(a, np.asarray(b), rtol=1e-6, atol=1e-7)
    other = {i: (router["chosen"][i] + 1) % e for i in moe_at}
    moved = reference.forward(params, layers, tokens, other)
    assert np.abs(moved[moe_at[0]] - np.asarray(outs[moe_at[0]])).max() \
        > 1e-3
    # a loss and a gradient for every parameter
    value, grads = reference.loss_and_grads(params, layers, tokens,
                                            rng.integers(0, v, (2, 16)))
    assert np.isfinite(value) and set(grads) == set(params)
    assert all(np.abs(g).max() > 0 for g in grads.values())


# ----------------------------------------------------------------------
# the entries, and the traced rehearsal
# ----------------------------------------------------------------------
def test_the_cell_and_its_entries():
    bench = discovery.load_json(discovery.REPO + "/BENCHMARK.json")
    cell = next(c for c in bench["workloads"] if c["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "olmoe_1b_7b", "train_lm_t4096", 1)
    assert len(cell["why"]) <= 200
    config = next(c for c in bench["configs"]
                  if c["name"] == "olmoe_1b_7b")
    assert config["reduced"] == ["num_hidden_layers"]
    new = {m["name"]: m for m in bench["per_layer"][-4:]}
    assert list(new) == ["moe_gmm_ms_per_step", "moe_gmm_roofline",
                         "moe_load_imbalance", "lm_train_mfu"]
    for name, entry in new.items():
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "throughput"
        assert entry["layer"] == ("kernels" if "gmm" in name
                                  else "units")
        assert discovery.load_module("layer_metrics", name) is not None
    reported = metric_names("per_layer", CELL)
    assert {"flash_fwd_ms_per_step", "flash_dq_ms_per_step",
            "flash_dkv_ms_per_step", "step_device_ms", "peak_hbm_gb",
            "host_reads_per_step", "guard_skipped_steps"} <= reported
    # the old flash reader counts EVERY custom call, the grouped
    # matmuls too: the cell does not report it (PERF.md §7)
    assert not {"flash_ms_per_step", "flash_roofline", "train_mfu",
                "sdc_vote_ms"} & reported
    assert metric_names("end_to_end", CELL) == {"throughput", "setup_s"}
    real = discovery.find_cell(CELL)
    assert real.driver == "train_lm"
    assert real.traffic["seq_len"] == 4096
    assert real.traffic["batch_per_chip"] in (4, 2, 1)
    assert real.traffic["steps_per_dispatch"] == 1
    assert real.traffic["engine"]["anomaly_check_interval"] \
        == real.traffic["steps_per_epoch"] == 12
    # the catalog's config, key for key; only the depth differs
    catalog = {"attention_bias": False, "clip_qkv": None,
               "hidden_act": "silu", "hidden_size": 2048,
               "intermediate_size": 1024,
               "max_position_embeddings": 4096, "model_type": "olmoe",
               "norm_topk_prob": False, "num_attention_heads": 16,
               "num_experts": 64, "num_experts_per_tok": 8,
               "num_hidden_layers": 16, "num_key_value_heads": 16,
               "rms_norm_eps": 1e-05, "rope_scaling": None,
               "rope_theta": 10000, "tie_word_embeddings": False,
               "vocab_size": 50304}
    differs = [k for k, v in catalog.items() if real.config[k] != v]
    assert differs == real.config["reduced"] == ["num_hidden_layers"]
    table = real.config["workflow"]["layers"]
    assert [l["type"] for l in table] == [
        "embedding", "attention", "moe", "attention", "moe",
        "rms_norm", "softmax"]
    assert table[2]["->"]["n_experts"] == 64 \
        and table[2]["->"]["top_k"] == 8 \
        and table[2]["->"]["width"] == 1024 \
        and table[1]["->"]["n_heads"] == 16


def test_traced_rehearsal_reads_the_experts_once_per_epoch():
    """3 epoch-end reads (two accumulators, the guard) + one per
    expert layer, and a vote's 1 + 23 where one falls in the window:
    no read per step."""
    proc, lines = run(["--workload", CELL, "--seed", "2999999999",
                       "--seconds", "2", "--trace", "1", "--toy"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["failed"] == 0
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    steps = line["attempted"]
    epochs = steps // 4
    warm = 8                      # two warm-up epochs of four ticks
    votes = (warm + steps) // 50 - warm // 50
    assert metrics["host_reads_per_step"] * steps == pytest.approx(
        5 * epochs + votes * (1 + 23))
    assert metrics["moe_load_imbalance"] >= 1.0
    assert metrics["guard_skipped_steps"] == 0
    assert metrics["programs_built_in_window"] == 0
    assert "moe_gmm_ms_per_step" not in metrics     # interpreted
    checked = [l for l in lines if "reference:" in l]
    assert any("bf16 router would read" in l for l in checked)
    assert any("bf16 table would read" in l for l in checked)

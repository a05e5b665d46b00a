"""The SmallThinker cell (PR 50): its entries in ``BENCHMARK.json``
looked up BY NAME (so that a later PR's entries do not move them;
nothing here pins a position or an exact list of another PR's), its
configuration against the catalog's key by key, its traffic and its
driver, ``flops_band`` / ``flops_gqa`` against a count by hand at the
published widths, the four new readers on a synthetic trace and counter
set, and ``--toy`` rehearsals through ``run.py`` — traced, untraced,
and with the SYSTEM made wrong in two stated ways, each of which has to
read ``correct: false``."""

import json
import os
import types

import pytest

from znbench import flops, flops_band, flops_gqa, trace_reduce
from znbench.harness import discovery
from znbench.harness.program import layer_table
from znbench.tests.test_cells_toy import RUN, run

CELL = "smallthinker_train_1of8"
CONFIG = "smallthinker_21b_a3b"
TRAFFIC = "train_lm_long_ctx"
BENCH = discovery.load_json(discovery.REPO + "/BENCHMARK.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = {"moe_route_ms_per_step": ("units", "ms", "lower", "device_trace"),
       "moe_hidden_live_share": ("units", "%", "lower",
                                 "program_counter"),
       "flash_causal_ms_per_step": ("kernels", "ms", "lower",
                                    "device_trace"),
       "flash_causal_roofline": ("kernels", "%", "higher",
                                 "device_trace")}
#: accepted metrics the cell reports (it may join more later)
JOINED = {
    "dispatches_per_step", "step_device_ms", "input_wait_share",
    "device_idle_share", "peak_hbm_gb", "host_reads_per_step",
    "host_read_wait_ms_per_step", "host_busy_ms_per_step",
    "dispatch_wait_ms_per_step", "guard_skipped_steps",
    "unit_attributed_share", "update_ms_per_step",
    "fingerprint_ms_per_step", "attention_unit_ms_per_step",
    "moe_unit_ms_per_step", "dense_unit_ms_per_step",
    "other_units_ms_per_step", "setup_preprogram_s",
    "setup_initialize_s", "setup_param_fill_s", "setup_upload_s",
    "setup_trace_lower_s", "setup_compile_or_load_s", "setup_warmup_s",
    "band_lm_train_mfu", "flash_win_ms_per_step", "flash_win_roofline",
    "flash_band_overwork", "flash_fwd_ms_per_step",
    "flash_dq_ms_per_step", "flash_dkv_ms_per_step",
    "moe_gmm_ms_per_step", "moe_gmm_overwork", "moe_load_imbalance",
    "moe_held_rows_per_expert", "moe_held_fit_step_share",
    "programs_built_in_window"}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def per_layer(cell):
    return {m["name"] for m in BENCH["per_layer"]
            if cell in m.get("workloads", [cell])}


def reader(name):
    return discovery.load_module("layer_metrics", name)


def test_the_cell_and_its_entries():
    cell = next(c for c in BENCH["workloads"] if c["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    assert len(cell["why"]) <= 200
    config = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == [
        "num_hidden_layers", "moe_num_primary_experts", "vocab_size"]
    assert config["file"] == f"znbench/configs/{CONFIG}.json"
    assert len(config["why"]) <= 200 and len(config["source"]) <= 200
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name, (layer, unit, better, source) in NEW.items():
        entry = by_name[name]
        assert {k: entry[k] for k in ("unit", "better", "source",
                                      "layer", "moves")} == {
            "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "throughput"}
        assert entry["workloads"] == [CELL]
        assert reader(name) is not None
    throughput = next(m for m in BENCH["end_to_end"]
                      if m["name"] == "throughput")
    assert CELL in throughput["workloads"]
    assert set(NEW) | JOINED <= per_layer(CELL)
    # a list for the one accepted metric that had none: every cell
    assert by_name["programs_built_in_window"]["workloads"] \
        == [c["name"] for c in BENCH["workloads"]][:10]
    # no one-pass backward (its full layer's K grid is too deep), no
    # dense gated MLP, no second share of the whole step's peak, no
    # other family's kernels
    assert not {"flash_bwd_ms_per_step", "gated_mlp_unit_ms_per_step",
                "lm_train_mfu", "latent_lm_train_mfu",
                "mla_flash_ms_per_step", "kda_ms_per_step",
                "delta_net_unit_ms_per_step", "short_conv_ms_per_step",
                "moe_router_bias_ms_per_step",
                "conv_unit_ms_per_step"} & per_layer(CELL)


def test_the_configuration_is_the_catalog_s_but_for_the_cut():
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "SmallThinker-21BA3B-Instruct")
    file = discovery.find_cell(CELL).config
    assert file["source"] == row["source_url"]
    assert file["catalog_name"] == row["name"]
    for key, value in row["config"].items():
        if key in file["reduced"]:
            assert file["published"][key] == value
            assert file[key] < value
        else:
            assert file[key] == value, key
    assert file["published"] == {
        "num_hidden_layers": 52, "moe_num_primary_experts": 64,
        "vocab_size": 151936}
    # the guide's floors: a whole period of four, 8 experts, an eighth
    # of the vocabulary
    assert file["num_hidden_layers"] == 4
    assert file["rope_layout"][:4] == [0, 1, 1, 1] \
        == file["sliding_window_layout"][:4]
    assert file["moe_num_primary_experts"] == 8
    assert file["vocab_size"] * 8 == row["config"]["vocab_size"]
    for key in ("reduced_why", "assumed", "deployment", "precision",
                "reference_tolerance", "reference_tolerance_why"):
        assert file[key], key
    assert {"router_input", "window", "rotary", "aux_loss", "optimizer",
            "init"} <= set(file["assumed"])
    assert file["reference"] == "smallthinker"
    assert discovery.load_module("reference", "smallthinker") is not None


def test_the_traffic_the_driver_and_the_table():
    real = discovery.find_cell(CELL)
    assert real.driver == "train_lm_early_router"
    assert discovery.load_module("drivers", real.driver).run
    assert (real.traffic["batch_per_chip"],
            real.traffic["steps_per_dispatch"]) == (1, 1)
    assert real.traffic["seq_len"] in (8192, 16384)      # never 4,096
    assert real.traffic["seq_len"] > real.config["sliding_window_size"]
    assert real.traffic["engine"]["anomaly_check_interval"] \
        == real.traffic["steps_per_epoch"]
    assert real.traffic["engine"]["keep_written_leaves"] is True
    assert real.traffic["min_segments"] == 10
    assert real.traffic["warmup_epochs"] == 2
    layers = layer_table(real.config)
    assert [l["type"] for l in layers] == ["embedding"] \
        + ["attention", "moe"] * 4 + ["rms_norm", "softmax"]
    attention = [l["->"] for l in layers if l["type"] == "attention"]
    assert [bool(a.get("rope")) for a in attention] \
        == [False, True, True, True]
    assert [a.get("window") for a in attention] \
        == [None, 4096, 4096, 4096]
    for a in attention:
        assert (a["n_heads"], a["n_kv_heads"], a["head_dim"]) \
            == (28, 4, 128)
        assert a["causal"] and a["residual"] and a["pre_norm"] == "rms"
        assert a["norm_eps"] == 1e-6
        assert not a.get("rope") or a["rope"] == {"theta": 1500000}
    for m in (l["->"] for l in layers if l["type"] == "moe"):
        assert (m["n_experts"], m["top_k"], m["width"]) == (64, 6, 768)
        assert m["held"] == list(range(8)) and m["norm_topk"] is True
        assert (m["score"], m["act"], m["route_from"]) \
            == ("softmax", "relu", "block_input")
        assert not m.get("aux_loss_weight") and not m.get("shared_width")
    assert layers[0]["->"]["dim"] == 2560
    assert layers[-1]["->"]["output_sample_shape"] \
        == real.config["vocab_size"] == layers[0]["->"]["vocab_size"] \
        == real.config["input"]["vocab"] == 18992
    toy = discovery.find_cell(CELL, toy=True)
    toy_layers = layer_table(toy.config)
    assert [l["type"] for l in toy_layers] == [l["type"] for l in layers]
    assert toy.traffic["driver"] == real.driver
    # the toy's sequence is longer than the toy's window: the band cuts
    window = next(l["->"]["window"] for l in toy_layers
                  if l["->"].get("window"))
    assert toy.traffic["seq_len"] > window
    assert [l["->"].get("route_from") for l in toy_layers
            if l["type"] == "moe"] == ["block_input"] * 4


def test_the_accepted_driver_is_run_not_copied():
    """``train_lm_early_router`` loads ``train_lm`` by name and holds
    nothing of ``check`` itself; a layer without ``route_from`` goes
    through ``train_lm``'s function untouched, one with it is read from
    the Vector the unit names, un-normed."""
    driver = discovery.load_module("drivers", "train_lm_early_router")
    assert driver.train_lm.check_router is driver.check_router
    assert (driver.build, driver.check) == (driver.train_lm.build,
                                            driver.train_lm.check)
    text = open(os.path.join(discovery.HERE, "drivers",
                             "train_lm_early_router.py")).read()
    for word in ("def check(", "def measure(", "def build(",
                 "bf16_router", "np.sort"):
        assert word not in text, word
    assert "return train_lm.run(ctx)" in text
    # the program at hand knows every option of the cell's table; one
    # without ``route_from`` and ``act`` is refused by their names
    real = discovery.find_cell(CELL)
    assert driver.unknown_options(layer_table(real.config)) == []
    import znicz_tpu.models.standard_workflow as sw
    known = sw.layer_type("moe")
    sw.register_layer_type("moe", type("MoE", (), {
        "__init__": lambda self, workflow, n_experts, top_k, width,
        norm_topk=False, score="softmax", held=None, pre_norm=None,
        residual=False, norm_eps=1e-5, **kwargs: None}))
    try:
        unknown = driver.unknown_options(layer_table(real.config))
        ctx = types.SimpleNamespace(cell=real)
        with pytest.raises(discovery.BenchmarkError,
                           match="knows no option 'act' of layer 2"):
            driver.run(ctx)
    finally:
        sw.register_layer_type("moe", known)
    assert {(kind, option) for _i, kind, option in unknown} \
        == {("moe", "act"), ("moe", "route_from")}
    seen = {}

    def spy(reference, params, layers, wf, i, n):
        seen.update(unit=wf.forwards[i], spec=layers[i]["->"])
        return {"logits": 0.0}

    driver._check_router, old = spy, driver._check_router
    try:
        unit = types.SimpleNamespace(
            input="normed side", route_input="block input",
            router_logits="logits", last_choice="choice")
        wf = types.SimpleNamespace(forwards=[None, unit])
        spec = {"pre_norm": "rms", "route_from": "block_input",
                "top_k": 6}
        driver.check_router(None, {}, [{}, {"->": spec}], wf, 1, 1)
        assert seen["unit"].input == "block input"
        assert seen["unit"].router_logits == "logits"
        assert seen["spec"] == {"route_from": "block_input", "top_k": 6}
        driver.check_router(None, {}, [{}, {"->": {"pre_norm": "rms"}}],
                            wf, 1, 1)
        assert seen["unit"] is unit and seen["spec"]["pre_norm"] == "rms"
    finally:
        driver._check_router = old


# ----------------------------------------------------------------------
# the arithmetic, by hand at the published widths (ISSUE 50's figures)
# ----------------------------------------------------------------------
def test_a_step_by_flops_band_by_hand():
    """Per token and forward: projections 4 × (2·2560·(28 + 8)·128 +
    2·3584·2560) = 4 × 41,943,040 = 167.8 M; head 2·2560·18,992 =
    97.2 M; routed rows 6·2560·768 × 6·8/64 × 4 = 35.4 M; router
    4 × 2·2560·64 = 1.3 M; scores 4·128·28 a visible pair — the causal
    half in the one full layer (117.4 M a token at T 16,384), the band
    of 4,096 in the three others (154.1 M)."""
    real = discovery.find_cell(CELL)
    layers = layer_table(real.config)
    for t, total, share in ((16384, 28.2, 0.47), (8192, 12.1, 0.39)):
        parts = flops_band.forward_flops_per_token(layers, t)
        assert parts["projections"] == 4 * 41_943_040
        assert parts["head"] == 2 * 2560 * 18992 == 97_239_040
        assert parts["routed"] == 4 * 6 * 2560 * 768 * 0.75 == 35_389_440
        assert parts["router"] == 4 * 327_680
        assert parts["gate"] == parts["dense"] == parts["shared"] == 0
        full = 4 * 128 * 28 * (t + 1) / 2
        band = 4 * 128 * 28 * (4096 * 4097 / 2 + (t - 4096) * 4096) / t
        assert parts["scores"] == pytest.approx(full + 3 * band)
        step = flops_band.lm_train_flops(layers, t, 1)
        assert step == pytest.approx(3 * t * sum(parts.values()))
        assert step / 1e12 == pytest.approx(total, abs=0.06)
        assert parts["scores"] / sum(parts.values()) \
            == pytest.approx(share, abs=0.006)
    parts = flops_band.forward_flops_per_token(layers, 16384)
    assert 4 * 128 * 28 * 16385 / 2 == pytest.approx(117.4e6, rel=1e-3)
    assert parts["scores"] - 117_447_680 == pytest.approx(154.1e6,
                                                          rel=1e-3)
    # the rows an expert sees: T·6/64, an eighth of a deployment's
    assert 16384 * 6 / 64 == 1536 and 8192 * 6 / 64 == 768


def test_the_kernels_costs_by_hand():
    """The full layer: 14·128·28 = 50,176 FLOPs a pair of the causal
    half; bytes 6 × T × (28 + 4) × 128 × 2.  The three window layers:
    the same a pair of the band.  Compute bounds both."""
    real = discovery.find_cell(CELL)
    layers = layer_table(real.config)
    t = 16384
    assert len(flops_gqa.causal_layers(layers, t)) == 1
    assert len(flops_gqa.causal_layers(layers, 4096)) == 4  # no cut
    causal = flops_gqa.flash_causal_train_cost(layers, t, 1)
    assert causal["flops"] == 50_176 * t * (t + 1) / 2
    assert causal["bytes"] == 6 * t * 32 * 128 * 2
    assert causal["flops"] / 1e12 == pytest.approx(6.735, abs=2e-3)
    band = flops_band.flash_win_train_cost(layers, t, 1)
    assert band["flops"] == 3 * 50_176 * (
        4096 * 4097 / 2 + (t - 4096) * 4096)
    assert band["bytes"] == 3 * causal["bytes"]
    for cost in (causal, band):
        least, bound = flops.roofline_seconds(cost, PEAKS)
        assert bound == "compute"
    assert flops.roofline_seconds(causal, PEAKS)[0] \
        == pytest.approx(34.19e-3, rel=1e-3)
    assert flops.roofline_seconds(band, PEAKS)[0] \
        == pytest.approx(44.85e-3, rel=1e-3)
    # a latent layer's and a windowed layer's kernels are not these
    other = [layers[0], {"type": "attention", "->": {
        "n_heads": 4, "causal": True, "kv_latent": 64}},
        {"type": "attention", "->": {"n_heads": 4, "causal": False}}]
    assert flops_gqa.flash_causal_train_cost(other, t, 1) \
        == {"flops": 0.0, "bytes": 0.0}


# ----------------------------------------------------------------------
# the readers on a synthetic trace and counter set
# ----------------------------------------------------------------------
OPS = {"%jvp_znicz_flash_fwd_.3": 4, "%znicz_flash_dq.5": 6,
       "%znicz_flash_dkv.7": 8, "%znicz_flash_fwd_win.9": 3,
       "%znicz_flash_dq_win.2": 5, "%znicz_flash_fwd_mla.4": 7,
       "%znicz_flash_bwd.8": 2, "fusion.1": 9, "fusion.2": 1,
       "fusion.3": 2, "sort.4": 3}


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
    real = discovery.find_cell(CELL)
    return types.SimpleNamespace(
        trace=trace, trace_window=(1_000_000, at), peaks=PEAKS, chips=1,
        window_s=6.0, cell=real,
        observations={"steps": steps, "batch": 1,
                      "sample_shape": (16384,), "model_dim": 2560,
                      "moe_units": [], "layers": layer_table(real.config)})


def unit(name, phase, family="MoE"):
    return {"unit": name, "kind": name, "family": family, "phase": phase}


SCOPES = {"znicz_step__train_region": {
    "fusion.1": unit("MoE_2", "forward"),
    "fusion.2": unit("MoE_2", "route"),
    "fusion.3": unit("GDMoE_2", "route"),
    "sort.4": {"unit": None, "units": ["MoE_2", "MoE_4"],
               "kinds": ["MoE"] * 2, "families": ["MoE"] * 2,
               "phases": ["route", "route"]}}}


def test_the_un_windowed_kernels_are_told_from_their_neighbours(
        monkeypatch):
    obs = observation(monkeypatch)
    read = reader("flash_causal_ms_per_step").read
    assert read(obs) == pytest.approx((4 + 6 + 8 + 2) / 2)
    # the windowed and the latent kernels are other rows'
    assert reader("flash_win_ms_per_step").read(obs) \
        == pytest.approx((3 + 5) / 2)
    obs.trace = trace_reduce.Trace(devices={}, host=[])
    assert read(obs) is None


def test_the_causal_roofline_is_the_least_time_over_the_time(
        monkeypatch):
    obs = observation(monkeypatch)
    want = 100 * 34.19e-3 / 10e-3
    assert reader("flash_causal_roofline").read(obs) \
        == pytest.approx(want, rel=1e-3)
    assert want > 100      # 10 ms is faster than the chip can be
    obs.peaks = None                  # off a TPU: no share of a peak
    assert reader("flash_causal_roofline").read(obs) is None


def test_the_route_phase_is_read_by_family_and_phase(monkeypatch):
    """Forward and pullback of phase ``route`` in family ``MoE``; an
    operation fused from two expert layers' routes is theirs; the
    family's row counts the phase too (no two rows' sums overlap)."""
    read = reader("moe_route_ms_per_step").read
    assert read(observation(monkeypatch, SCOPES)) \
        == pytest.approx((1 + 2 + 3) / 2)
    assert reader("moe_unit_ms_per_step").read(
        observation(monkeypatch, SCOPES)) == pytest.approx(
            (9 + 1 + 2 + 3) / 2)
    assert read(observation(monkeypatch, {})) is None


def test_the_live_share_is_the_mean_over_the_layers(monkeypatch):
    from znicz_tpu.observe import metrics
    read = reader("moe_hidden_live_share").read
    family = {}

    def gauge(unit, stat, value):
        family[unit, stat] = types.SimpleNamespace(value=value)

    monkeypatch.setattr(metrics.REGISTRY, "get", lambda name: (
        family if name == "znicz_moe_hidden" else None))
    for name, live in (("MoE_a", 40.0), ("MoE_b", 60.0)):
        gauge(name, "live", live)
        gauge(name, "total", 100.0)
    gauge("MoE_c", "live", 0.0)
    gauge("MoE_c", "total", 0.0)                     # no step yet
    assert read(None) == pytest.approx(50.0)
    for name in ("MoE_a", "MoE_b"):
        gauge(name, "total", 0.0)
    assert read(None) is None
    # the real gauge is such a family
    metrics.moe_hidden("MoE_cell_test", "live").set(1.0)
    monkeypatch.undo()
    assert ("MoE_cell_test", "live") in dict(
        metrics.REGISTRY.get("znicz_moe_hidden").items())


def test_the_route_scope_in_an_op_name():
    from znicz_tpu.observe import scopes
    names = ["MoE_2", "GDMoE_2"]
    for op_name, want in (
            ("jit(step)/MoE_2/jvp(route)/dot_general", True),
            ("jit(step)/GDMoE_2/transpose(jvp(route))/mul", True),
            ("jit(step)/MoE_2/route/sort", True),
            ("jit(step)/MoE_2/jvp()/reroute_x/mul", False),
            ("jit(step)/MoE_2/jvp(router_bias)/add", False)):
        assert (scopes._ROUTE.search(op_name) is not None) == want, op_name
        assert scopes.scope_of(op_name, names) is not None


# ----------------------------------------------------------------------
# the rehearsals
# ----------------------------------------------------------------------
def test_untraced_rehearsal():
    proc, lines = run(["--workload", CELL, "--seed", "3000000050",
                       "--seconds", "2", "--trace", "0", "--toy"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    assert set(line["metrics"]) == {"throughput", "setup_s"}
    log = "\n".join(lines)
    for word in ("1:attention=", "2:moe=", "8:moe=", "10:softmax=",
                 "a bf16 router would read", "a bf16 table would read"):
        assert word in log, word


def test_traced_rehearsal_reads_the_new_counters():
    proc, lines = run(["--workload", CELL, "--seed", "3000000051",
                       "--seconds", "2", "--trace", "1", "--toy"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(lines[-1])
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert line["correct"] is True
    assert metrics["dispatches_per_step"] == 1
    assert metrics["programs_built_in_window"] == 0
    assert metrics["guard_skipped_steps"] == 0
    assert 35 < metrics["moe_hidden_live_share"] < 65
    assert metrics["moe_route_ms_per_step"] >= 0
    assert metrics["moe_held_rows_per_expert"] > 0
    assert set(metrics) <= per_layer(CELL)
    # off a TPU: no share of a peak; interpreted kernels leave no
    # kernel to time
    assert not {"flash_causal_roofline", "flash_causal_ms_per_step",
                "flash_win_roofline", "band_lm_train_mfu"} & set(metrics)


WRONG = {
    "router_after_attention": (
        "from znicz_tpu.models.standard_workflow import StandardWorkflow\n"
        "StandardWorkflow._link_route = lambda self, index, unit, prev: "
        "unit.link_attrs(prev, ('route_input', 'output'))\n"),
    "silu_experts": (
        "from znicz_tpu.ops import activations_math\n"
        "activations_math.GATES['relu'] = activations_math.GATES['silu']\n"),
}


@pytest.mark.parametrize("what", list(WRONG))
def test_a_system_made_wrong_reads_not_correct(what, tmp_path):
    """The SYSTEM — not the reference — wired with its router after
    attention (the usual place), or with SiLU experts, under the same
    configuration through the same driver: ``correct: false``."""
    script = tmp_path / "wrong_run.py"
    script.write_text(
        "import runpy, sys\n"
        f"sys.path.insert(0, {discovery.REPO!r})\n"
        + WRONG[what]
        + f"runpy.run_path({RUN!r}, run_name='__main__')\n")
    proc, lines = run(["--workload", CELL, "--seed", "3000000052",
                       "--seconds", "2", "--trace", "0", "--toy"],
                      script=str(script))
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(lines[-1])
    assert line["correct"] is False
    assert "forward differs from the reference" in "\n".join(lines)

"""The per-unit device-time readers (PR 33): the bucket arithmetic on a
synthetic trace and map, the entries in ``BENCHMARK.json`` (looked up
by name, so that a later PR's entries do not move them), the
``dispatch_wait_ms_per_step`` reader, and a ``--toy --trace 1``
rehearsal of one conv cell and one LM cell."""

import json
import types

import pytest

from znbench import trace_reduce
from znbench.harness import discovery
from znbench.harness.window import WINDOW_SPAN
from znbench.tests.test_cells_toy import run

BENCH = discovery.load_json(discovery.REPO + "/BENCHMARK.json")
ALL = [c["name"] for c in BENCH["workloads"]]
LMS = ["attn_lm_train_t2048", "olmoe_train_t4096", "laguna_train_1of32",
       "olmo_hybrid_train_4of32"]
#: entry → (layer, cells, bucket and phase it reads)
ROWS = {
    "unit_attributed_share": ("fused step", ALL, None),
    "update_ms_per_step": ("units", ALL, ("update",)),
    "fingerprint_ms_per_step": ("units", ALL, ("update", "fingerprint")),
    "attention_unit_ms_per_step": ("units", LMS, ("attention",)),
    "moe_unit_ms_per_step": (
        "units", ["olmoe_train_t4096", "laguna_train_1of32"], ("moe",)),
    "gated_mlp_unit_ms_per_step": (
        "units", ["laguna_train_1of32", "olmo_hybrid_train_4of32"],
        ("gated_mlp",)),
    "delta_net_unit_ms_per_step": (
        "units", ["olmo_hybrid_train_4of32"], ("delta_net",)),
    "dense_unit_ms_per_step": ("units", ALL, ("dense",)),
    "conv_unit_ms_per_step": ("units", ["alexnet_train_b768"],
                              ("conv",)),
    "other_units_ms_per_step": ("units", ALL, ("other",)),
}
helper = discovery.load_module("layer_metrics", "unit_attributed_share")


def reader(name):
    return discovery.load_module("layer_metrics", name)


# ----------------------------------------------------------------------
# the entries
# ----------------------------------------------------------------------
@pytest.mark.parametrize("name", [*ROWS, "dispatch_wait_ms_per_step"])
def test_each_entry_by_name(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    layer, cells, _ = ROWS.get(name, ("training driver", ALL, None))
    assert entry["workloads"] == cells
    assert entry["layer"] == layer and entry["moves"] == "throughput"
    assert entry["source"] == ("program_span" if layer ==
                               "training driver" else "device_trace")
    assert (entry["unit"], entry["better"]) == (
        ("%", "higher") if name == "unit_attributed_share"
        else ("ms", "lower"))
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert reader(name) is not None


def test_the_entries_are_appended_and_touch_nothing_else():
    names = [m["name"] for m in BENCH["per_layer"]]
    first = names.index("unit_attributed_share")
    assert names[first:first + 11] == [*ROWS,
                                       "dispatch_wait_ms_per_step"]
    assert len(set(names)) == len(names)
    assert {m["layer"] for m in BENCH["per_layer"][first:first + 11]} \
        <= {m["layer"] for m in BENCH["per_layer"][:first]}


# ----------------------------------------------------------------------
# the bucket arithmetic
# ----------------------------------------------------------------------
def unit(name, family, phase, kind=None):
    return {"unit": name, "kind": kind or name, "family": family,
            "phase": phase}


def mixed(*parts):
    units, families, phases = zip(*parts)
    return {"unit": None, "units": list(units), "kinds": list(units),
            "families": list(families), "phases": list(phases)}


SCOPES = {
    "znicz_step__train_region": {
        "fusion.1": unit("Attn_1", "MultiHeadAttention", "forward"),
        "znicz_flash_bwd.2": unit("GDAttn_1", "MultiHeadAttention",
                                  "backward"),
        "fusion.3": unit("GDAttn_1", "MultiHeadAttention", "update"),
        "gather.4": unit("GDAttn_1", "MultiHeadAttention",
                         "fingerprint"),
        "gmm.5": unit("MoE_2", "MoE", "forward"),
        "fusion.6": unit("GDMLP_3", "GatedMLP", "backward"),
        "znicz_delta_state_fwd.7": unit("Mixer_4", "GatedDeltaNet",
                                        "forward"),
        "fusion.8": unit("GDSoftmax", "All2AllSoftmax", "backward"),
        "convolution.9": unit("GDRELUConv", "ConvRELU", "backward"),
        "fusion.10": unit("evaluator", "EvaluatorSoftmax", "forward"),
        "fusion.11": mixed(("Attn_1", "MultiHeadAttention", "forward"),
                           ("MoE_2", "MoE", "forward")),
        # several units, ONE bucket: a layer's weight gradient with the
        # forward's cast fused in; two units' updates side by side; a
        # map that names the units only says nothing of their buckets
        "fusion.14": mixed(("MoE_2", "MoE", "forward"),
                           ("GDMoE_2", "MoE", "backward")),
        "fusion.15": mixed(("GDAttn_1", "MultiHeadAttention", "update"),
                           ("GDMoE_2", "MoE", "fingerprint")),
        "fusion.16": {"unit": None, "units": ["MoE_2", "GDMoE_2"]},
        "fusion.12": unit("Attn_1", "MultiHeadAttention", "forward"),
        "fusion.13": unit("Attn_1", "MultiHeadAttention", "forward"),
    },
    # the eval variant: ``fusion.12`` is another unit's there, and
    # ``fusion.13`` the same entry in both
    "znicz_step__train_region#2": {
        "fusion.12": unit("evaluator", "EvaluatorSoftmax", "forward"),
        "fusion.13": unit("Attn_1", "MultiHeadAttention", "forward"),
    },
}
#: operation → ms in the window (one lane, back to back); ``copy.99``
#: is in no map
MS = {"fusion.1": 1, "znicz_flash_bwd.2": 2, "fusion.3": 3,
      "gather.4": 4, "gmm.5": 5, "fusion.6": 6,
      "znicz_delta_state_fwd.7": 7, "fusion.8": 8, "convolution.9": 9,
      "fusion.10": 10, "fusion.11": 11, "fusion.12": 12,
      "fusion.13": 13, "copy.99": 14, "fusion.14": 15, "fusion.15": 16,
      "fusion.16": 17}
STEPS = 2


def observation(monkeypatch, scopes=SCOPES, steps=STEPS):
    """A trace of one lane holding ``MS`` inside a window, and one
    event before it that no reader may count."""
    from znicz_tpu import observe
    monkeypatch.setattr(observe, "op_scopes", lambda: scopes,
                        raising=False)
    events, at = [trace_reduce.Event("fusion.1", 0, 500_000)], 1_000_000
    for name, ms in MS.items():
        events.append(trace_reduce.Event(name, at, at + ms * 1_000_000))
        at += ms * 1_000_000
    trace = trace_reduce.Trace(
        devices={"/device:TPU:0": [trace_reduce.Lane(events)]}, host=[])
    return types.SimpleNamespace(
        trace=trace, trace_window=(1_000_000, at),
        observations={"steps": steps})


def test_every_operation_lands_in_one_bucket(monkeypatch):
    obs = observation(monkeypatch)
    want = {"attention_unit_ms_per_step": (1 + 2 + 13) / STEPS,
            "update_ms_per_step": (3 + 4 + 16) / STEPS,
            "fingerprint_ms_per_step": 4 / STEPS,
            "moe_unit_ms_per_step": (5 + 15) / STEPS,
            "gated_mlp_unit_ms_per_step": 6 / STEPS,
            "delta_net_unit_ms_per_step": 7 / STEPS,
            "dense_unit_ms_per_step": 8 / STEPS,
            "conv_unit_ms_per_step": 9 / STEPS,
            "other_units_ms_per_step": 10 / STEPS}
    got = {name: reader(name).read(obs) for name in want}
    assert got == pytest.approx(want)
    table = helper.table(obs)
    assert table[("mixed", "")] == pytest.approx(0.011 + 0.017)
    assert table[("moe", "fused")] == pytest.approx(0.015)
    assert table[("update", "fused")] == pytest.approx(0.016)
    # a name two programs give to different units, and a name in no
    # map; the same entry in both programs stays attributed
    assert table[("unattributed", "")] == pytest.approx(0.012 + 0.014)
    assert table[("attention", "forward")] == pytest.approx(0.014)
    assert table[("attention", "backward")] == pytest.approx(0.002)
    total = sum(MS.values())
    # the share is of operations in exactly ONE unit, whatever bucket
    # a fused one lands in
    assert reader("unit_attributed_share").read(obs) == pytest.approx(
        100 * (total - 11 - 12 - 14 - 15 - 16 - 17) / total)


def test_the_identity(monkeypatch):
    """families + update + other + mixed + unattributed = the
    window's summed self time ÷ steps; fingerprint is inside update."""
    obs = observation(monkeypatch)
    rows = [name for name, (_l, _c, reads) in ROWS.items()
            if reads and reads != ("update", "fingerprint")]
    table = helper.table(obs)
    rest = 1e3 * (table[("mixed", "")]
                  + table[("unattributed", "")]) / STEPS
    whole = 1e3 * sum(trace_reduce.op_seconds(
        obs.trace, obs.trace_window).values()) / STEPS
    assert sum(reader(n).read(obs) for n in rows) + rest \
        == pytest.approx(whole) == pytest.approx(sum(MS.values()) / STEPS)
    assert reader("fingerprint_ms_per_step").read(obs) \
        <= reader("update_ms_per_step").read(obs)


@pytest.mark.parametrize("scopes", [None, {}, {"znicz_step__r": {}}])
def test_no_map_no_metric(monkeypatch, scopes):
    """A program from before ``observe.op_scopes`` (the parent of
    PR 33), or one that remembered no program: every reader returns
    nothing and raises nothing."""
    from znicz_tpu import observe
    obs = observation(monkeypatch)
    if scopes is None:
        monkeypatch.delattr(observe, "op_scopes")
    else:
        monkeypatch.setattr(observe, "op_scopes", lambda: scopes)
    assert [reader(name).read(obs) for name in ROWS] == [None] * len(ROWS)


def test_dispatch_wait_counts_the_region_spans_in_the_window():
    def span(name, cat, t0, t1):
        return {"name": name, "cat": cat, "t0": t0, "t1": t1, "args": {}}
    obs = types.SimpleNamespace(
        observations={"steps": 4},
        spans=[(WINDOW_SPAN, 10.0, 20.0)],
        program_spans=[
            span("dispatch:train_region", "region", 9.0, 9.5),  # before
            span("dispatch:train_region", "region", 11.0, 11.25),
            span("chunk:train_region", "region", 12.0, 12.5),
            span("accum:train_region", "region", 13.0, 13.25),
            span("jit_region", "unit", 10.5, 13.5),       # the parent
            span("host_read:x", "transfer", 14.0, 15.0),
            span("dispatch:train_region", "region", 19.9, 20.1)])
    assert reader("dispatch_wait_ms_per_step").read(obs) \
        == pytest.approx(1e3 * (0.25 + 0.5 + 0.25) / 4)
    obs.program_spans = obs.program_spans[4:6]
    assert reader("dispatch_wait_ms_per_step").read(obs) is None


# ----------------------------------------------------------------------
# a rehearsal: the CPU's thunks are named after the instructions, so
# the readers read — next to nothing, the CPU client runs most thunks
# where the toy lane does not see them
# ----------------------------------------------------------------------
@pytest.mark.parametrize("cell", ["alexnet_train_b768",
                                  "olmoe_train_t4096"])
def test_a_toy_rehearsal_prints_the_new_metrics(cell):
    proc, lines = run(["--workload", cell, "--seed", "5", "--seconds",
                       "2", "--trace", "1", "--toy"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    metrics = json.loads(lines[-1])["metrics"]
    mine = {name for name, (_l, cells, _r) in ROWS.items()
            if cell in cells} | {"dispatch_wait_ms_per_step"}
    print({name: metrics[name]["value"] for name in sorted(mine)})
    assert mine <= set(metrics)
    assert 0 < metrics["unit_attributed_share"]["value"] <= 100
    assert metrics["fingerprint_ms_per_step"]["value"] \
        <= metrics["update_ms_per_step"]["value"]
    assert metrics["dispatch_wait_ms_per_step"]["value"] > 0

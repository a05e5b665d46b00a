"""``delta_chunk_ms_per_step`` (PR 32): the chunk-local kernels of the
gated delta rule read by their name and kept apart from the walk's;
its entry in ``BENCHMARK.json`` looked up BY NAME, so that a later PR's
entries do not move it."""

import types

import pytest

from znbench.harness import discovery
from znbench.harness.window import WINDOW_SPAN
from znbench.trace_reduce import Event, Trace

NAME = "delta_chunk_ms_per_step"
BENCH = discovery.load_json(discovery.REPO + "/BENCHMARK.json")
MS = 1_000_000   # ns


def reader(name):
    return discovery.load_module("layer_metrics", name).read


def observation(lane, steps=2):
    return types.SimpleNamespace(
        program_spans=[], spans=[(WINDOW_SPAN, 0.0, 10.0)],
        observations={"steps": steps}, counters={},
        trace=Trace(devices={"/device:TPU:0": [lane]} if lane else {},
                    host=[]),
        trace_window=None)


def mixer(kernels):
    """Two steps of a linear layer as the TPU names its operations:
    ``(name, ms)`` each, then a consumer whose HLO LINE mentions the
    last of them."""
    lane, t = [], 0
    for step in range(2):
        for kernel, dur in kernels:
            lane.append(Event(f"{kernel}.{step}", t * MS, (t + dur) * MS,
                              f"%{kernel}.{step} = f32[] custom-call()"))
            t += dur
        lane.append(Event(f"fusion.{step}", t * MS, (t + 3) * MS,
                          f"%fusion.{step} = f32[] fusion(%{kernel}.{step})"))
        t += 3
    return lane


CHANGE = [("jvp_znicz_gdr_chunk_fwd_", 4), ("jvp_znicz_delta_state_fwd_", 2),
          ("transpose_jvp_znicz_delta_state_bwd_", 3),
          ("transpose_jvp_znicz_gdr_chunk_bwd_", 5)]
PARENT = [("broadcast_multiply_fusion", 7), ("jvp_znicz_delta_state_fwd_", 2),
          ("transpose_jvp_znicz_delta_state_bwd_", 3)]


def test_the_entry_by_name():
    """A later cell may be appended to its ``workloads``; nothing else
    of it may change."""
    entry, = (m for m in BENCH["per_layer"] if m["name"] == NAME)
    cells = entry.pop("workloads")
    assert entry == {
        "name": NAME, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "throughput"}
    assert cells[0] == "olmo_hybrid_train_4of32"
    assert set(cells) <= {c["name"] for c in BENCH["workloads"]}
    # the layer and the end-to-end metric as the walk's entry has them
    walk, = (m for m in BENCH["per_layer"]
             if m["name"] == "delta_ms_per_step")
    assert (walk["layer"], walk["moves"]) == (entry["layer"],
                                              entry["moves"])


def test_the_two_pairs_of_kernels_are_read_apart():
    obs = observation(mixer(CHANGE))
    assert reader(NAME)(obs) == pytest.approx(9.0)
    assert reader("delta_ms_per_step")(obs) == pytest.approx(5.0)


@pytest.mark.parametrize("lane", [mixer(PARENT), None],
                         ids=["the_parent_s_program", "no_device"])
def test_it_reads_nothing_where_a_chunk_is_plain_xla(lane):
    assert reader(NAME)(observation(lane)) is None

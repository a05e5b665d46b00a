"""``flash_bwd_ms_per_step``: the one-pass flash backward, read by its
kernel's name; its entry in ``BENCHMARK.json`` looked up BY NAME, so
that a later PR's entries do not move it."""

import types

import pytest

from znbench.harness import discovery
from znbench.harness.window import WINDOW_SPAN
from znbench.trace_reduce import Event, Trace

NAME = "flash_bwd_ms_per_step"
BENCH = discovery.load_json(discovery.REPO + "/BENCHMARK.json")
LM_CELLS = ["attn_lm_train_t2048", "olmoe_train_t4096",
            "laguna_train_1of32"]
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


def kernels(backward):
    """Two steps of a layer as the TPU names them: the forward, then
    the backward's kernels as ``(name, ms)``, then a consumer whose HLO
    LINE mentions the last of them."""
    lane, t = [], 0
    for step in range(2):
        for kernel, dur in [("jvp_znicz_flash_fwd_", 4)] + backward:
            lane.append(Event(f"{kernel}.{step}", t * MS, (t + dur) * MS,
                              f"%{kernel}.{step} = bf16[] custom-call()"))
            t += dur
        lane.append(Event(f"fusion.{step}", t * MS, (t + 3) * MS,
                          f"%fusion.{step} = f32[] fusion(%{kernel}.{step})"))
        t += 3
    return lane


def test_the_entry_by_name():
    entries = [m for m in BENCH["per_layer"] if m["name"] == NAME]
    assert entries == [{
        "name": NAME, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "throughput", "workloads": LM_CELLS}]
    cells = {c["name"] for c in BENCH["workloads"]}
    assert set(LM_CELLS) <= cells
    # the kernels layer is named as the entries before this one name it
    assert "kernels" in {m["layer"] for m in BENCH["per_layer"]
                         if m["name"] == "flash_fwd_ms_per_step"}


def test_the_one_pass_kernel_is_read_by_its_name():
    one = observation(kernels([("transpose_jvp_znicz_flash_bwd__", 9)]))
    assert reader(NAME)(one) == pytest.approx(9.0)
    assert reader("flash_fwd_ms_per_step")(one) == pytest.approx(4.0)
    # its name holds neither of the two kernels it replaces: they read
    # nothing there and are left out of the line, not counted twice
    assert reader("flash_dq_ms_per_step")(one) is None
    assert reader("flash_dkv_ms_per_step")(one) is None
    assert reader("flash_ms_per_step")(one) == pytest.approx(13.0)


@pytest.mark.parametrize("lane", [
    kernels([("transpose_jvp_znicz_flash_dq__", 5),
             ("transpose_jvp_znicz_flash_dkv__", 6)]),     # the parent
    kernels([("transpose_jvp_znicz_flash_dq_win__", 5),
             ("transpose_jvp_znicz_flash_dkv_win__", 6)]),  # a window
    [Event("jvp__.3", 0, 4 * MS, "%jvp__.3 = f32[] custom-call()")],
    None,                                                   # no device
], ids=["two_kernels", "windowed", "unnamed", "no_device"])
def test_it_reads_nothing_where_no_backward_takes_the_one_pass(lane):
    assert reader(NAME)(observation(lane)) is None


def test_a_mixed_program_splits_by_name():
    """Laguna's shape of things, should its full layers engage: the
    windowed layers' two kernels beside a one-pass call."""
    lane = kernels([("transpose_jvp_znicz_flash_dq_win__", 5),
                    ("transpose_jvp_znicz_flash_dkv_win__", 6),
                    ("transpose_jvp_znicz_flash_bwd__", 9)])
    obs = observation(lane)
    assert reader(NAME)(obs) == pytest.approx(9.0)
    assert reader("flash_dq_ms_per_step")(obs) == pytest.approx(5.0)
    assert reader("flash_dkv_ms_per_step")(obs) == pytest.approx(6.0)

"""The reduction from trace to numbers, pinned on synthetic planes and
on a small recorded trace."""

import os

import pytest

from znbench import trace_reduce as tr
from znbench.trace_reduce import Event, Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
US = 1000   # ns


def lanes(*lanes_):
    return Trace(devices={"/device:TPU:0": [list(l) for l in lanes_]},
                 host=[])


def test_busy_is_a_union_not_a_sum():
    # two lanes overlap from 40 to 60 us: busy is 80 us, not 100
    trace = lanes([Event("fusion.1", 0, 60 * US)],
                  [Event("copy.2", 40 * US, 80 * US)])
    busy = tr.busy(trace, (0, 100 * US))
    assert busy["busy_s"] == pytest.approx(80e-6)
    assert busy["idle_share"] == pytest.approx(0.2)
    assert sum(tr.op_seconds(trace).values()) == pytest.approx(100e-6)


def test_busy_is_clipped_to_the_window_and_averaged_over_devices():
    trace = Trace(devices={
        "/device:TPU:0": [[Event("a", 0, 100 * US)]],
        "/device:TPU:1": [[Event("a", 50 * US, 150 * US)]]}, host=[])
    busy = tr.busy(trace, (0, 100 * US))
    assert busy["per_device_s"]["/device:TPU:1"] == pytest.approx(50e-6)
    assert busy["busy_s"] == pytest.approx(75e-6)
    assert busy["idle_share"] == pytest.approx(0.25)


def test_a_container_counts_its_self_time_only():
    # a while op around a scanned chunk: two children, a hole between
    lane = [Event("while.1", 0, 100 * US),
            Event("fusion.1", 10 * US, 40 * US),
            Event("fusion.2", 50 * US, 90 * US)]
    trace = lanes(lane)
    ops = tr.op_seconds(trace)
    assert ops["while.1"] == pytest.approx(30e-6)
    assert ops["fusion.2"] == pytest.approx(40e-6)
    # busy: the leaves only — the holes inside the while are idle
    assert tr.busy(trace, (0, 100 * US))["busy_s"] == \
        pytest.approx(70e-6)


def test_collective_split_into_hidden_and_exposed():
    # an async all-reduce from 10 to 70 us; compute covers 20..50
    lane = [Event("all-reduce-start.1", 10 * US, 12 * US),
            Event("fusion.7", 20 * US, 50 * US),
            Event("all-reduce-done.1", 60 * US, 70 * US)]
    comm = tr.collectives(lanes(lane), (0, 100 * US))
    assert comm["comm_s"] == pytest.approx(60e-6)
    assert comm["hidden_s"] == pytest.approx(30e-6)
    assert comm["exposed_s"] == pytest.approx(30e-6)
    assert comm["exposed_share"] == pytest.approx(0.5)


def test_a_synchronous_collective_with_no_compute_is_all_exposed():
    lane = [Event("fusion.1", 0, 10 * US),
            Event("all-gather.3", 10 * US, 30 * US)]
    comm = tr.collectives(lanes(lane), (0, 30 * US))
    assert comm["comm_s"] == pytest.approx(20e-6)
    assert comm["exposed_share"] == pytest.approx(1.0)
    assert tr.is_comm("fused-reduce-scatter.2")
    assert not tr.is_comm("fusion.9")


def test_a_gap_goes_to_the_innermost_host_span_that_covers_it():
    trace = lanes([Event("fusion.1", 0, 10 * US),
                   Event("fusion.2", 40 * US, 50 * US),
                   Event("fusion.3", 90 * US, 100 * US)])
    host = [Event("znbench.window", 0, 100 * US),
            Event("znbench.segment", 0, 100 * US - 1),
            Event("sample+d2h", 12 * US, 38 * US),
            Event("wait_for_request", 55 * US, 85 * US)]
    gaps = dict(tr.idle_gaps(trace, host, (0, 100 * US),
                             ignore=("znbench.window",)))
    assert gaps["sample+d2h"] == pytest.approx(30e-6)
    assert gaps["wait_for_request"] == pytest.approx(40e-6)
    assert "znbench.segment" not in gaps
    # without the inner spans the outer one takes them
    outer = dict(tr.idle_gaps(trace, host[:2], (0, 100 * US),
                              ignore=("znbench.window",)))
    assert outer["znbench.segment"] == pytest.approx(70e-6)
    assert dict(tr.idle_gaps(trace, [], (0, 100 * US))) == {
        "(no span)": pytest.approx(70e-6)}


def test_interval_arithmetic():
    assert tr.union([(5, 9), (0, 3), (2, 4)]) == [(0, 4), (5, 9)]
    assert tr.subtract([(0, 10), (20, 30)], [(2, 3), (5, 25)]) == \
        [(0, 2), (3, 5), (25, 30)]
    assert tr.clip([(0, 10), (20, 30)], (5, 22)) == [(5, 10), (20, 22)]


def test_the_recorded_trace_loads():
    """A 7 KB trace recorded on the CPU (three jitted matmuls under
    ``znbench.window`` / ``znbench.dispatch`` annotations): host
    annotations are found; under ``toy`` the CPU client's threads
    stand in for device lanes."""
    path = os.path.join(DATA, "cpu_small.xplane.pb")
    trace = tr.load(path)
    assert trace.devices == {}            # no TPU plane: nothing made up
    window = trace.window("znbench.window")
    assert window is not None and window[1] > window[0]
    assert sum(e.name == "znbench.dispatch" for e in trace.host) == 3
    toy = tr.load(path, toy=True)
    busy = tr.busy(toy, window)
    assert 0 < busy["busy_s"] < busy["window_s"]
    assert any(name.startswith("dot_general")
               for name, _s in tr.top_ops(toy, 5, window))
    assert "PLANE /host:CPU" in tr.describe(path)

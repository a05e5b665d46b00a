"""The eight readers PR 23 adds: the three flash kernels by name, what
the training host waits for, what it must do, the SDC vote and the
guard's skipped steps — on hand-built observations, then in the traced
toy rehearsal of both training cells with the toy's exact counts."""

import json
import types

import pytest

from znbench.harness import discovery
from znbench.harness.window import WINDOW_SPAN
from znbench.trace_reduce import Event, Trace
from test_cells_toy import CELLS, metric_names, run

MS = 1_000_000   # ns


def reader(name):
    return discovery.load_module("layer_metrics", name).read


def span(name, cat, t0, t1, span_id, parent, **args):
    return {"name": name, "cat": cat, "t0": t0, "t1": t1,
            "args": {**args, "span_id": span_id,
                     "parent_span_id": parent}}


def observation(program_spans=(), trace=None, steps=4, counters=None,
                window=(0.0, 10.0)):
    return types.SimpleNamespace(
        program_spans=list(program_spans),
        spans=[(WINDOW_SPAN, *window)],
        observations={"steps": steps},
        counters=counters if counters is not None
        else {"znicz_step_anomalies_total": 0.0},
        trace=trace or Trace(devices={}, host=[]),
        trace_window=None)


# ----------------------------------------------------------------------
# the kernels, by name
# ----------------------------------------------------------------------
def named_kernel_trace():
    """Two steps of a two-layer model as the TPU names them: the
    kernel's name inside the HLO instruction's, consumers whose HLO
    LINE mentions a kernel, and a container around the lot."""
    lane = [Event("while.1", 0, 100 * MS)]
    t = 0
    for step in range(2):
        for layer in range(2):
            n = 2 * step + layer
            for kernel, dur in (("jvp_znicz_flash_fwd_", 4),
                                ("transpose_jvp_znicz_flash_dq__", 5),
                                ("transpose_jvp_znicz_flash_dkv__", 6)):
                lane.append(Event(f"{kernel}.{n}", t * MS,
                                  (t + dur) * MS,
                                  f"%{kernel}.{n} = f32[] custom-call()"))
                t += dur
            lane.append(Event(
                f"fusion.{n}", t * MS, (t + 3) * MS,
                f"%fusion.{n} = f32[] fusion(%jvp_znicz_flash_fwd_.{n})"))
            t += 3
    return Trace(devices={"/device:TPU:0": [lane]}, host=[])


def test_each_flash_kernel_is_read_by_its_name_and_they_sum():
    obs = observation(trace=named_kernel_trace(), steps=2)
    fwd = reader("flash_fwd_ms_per_step")(obs)
    dq = reader("flash_dq_ms_per_step")(obs)
    dkv = reader("flash_dkv_ms_per_step")(obs)
    assert (fwd, dq, dkv) == pytest.approx((8.0, 10.0, 12.0))
    # the old reader (every custom call) sees the same three kernels
    assert reader("flash_ms_per_step")(obs) == pytest.approx(
        fwd + dq + dkv)


def test_flash_readers_return_nothing_without_named_kernels():
    """Interpret mode (the CPU rehearsal) and a program that does not
    name its kernels leave no operation of that name."""
    lane = [Event("jvp__.3", 0, 4 * MS, "%jvp__.3 = f32[] custom-call()"),
            Event("fusion.1", 4 * MS, 6 * MS, "%fusion.1 = fusion()")]
    obs = observation(trace=Trace(devices={"d": [lane]}, host=[]))
    for name in ("flash_fwd_ms_per_step", "flash_dq_ms_per_step",
                 "flash_dkv_ms_per_step"):
        assert reader(name)(obs) is None
        assert reader(name)(observation()) is None     # no device


# ----------------------------------------------------------------------
# the host
# ----------------------------------------------------------------------
def four_step_epoch():
    """``wf.run()`` over four steps: loader, region and decision under
    the root; the last decision holds the epoch-end read, the guard
    read and a vote with two parameter reads inside it.  Times in
    seconds; every unit span is 10 ms long."""
    spans = [span("workflow:lm", "workflow", 1.0, 2.0, 1, 0)]
    next_id = 2
    for step in range(4):
        t = 1.0 + 0.1 * step
        spans.append(span("loader", "unit", t, t + 0.010, next_id, 1,
                          kind="ArrayLoader"))
        spans.append(span("train_region", "unit", t + 0.010, t + 0.020,
                          next_id + 1, 1, kind="RegionUnit"))
        next_id += 2
    # steps 0..2: a bare decision; step 3: reads and a vote inside
    for step in range(3):
        t = 1.02 + 0.1 * step
        spans.append(span("decision", "unit", t, t + 0.010, next_id, 1,
                          kind="DecisionGD"))
        next_id += 1
    decision = next_id
    spans += [
        span("decision", "unit", 1.32, 1.62, decision, 1,
             kind="DecisionGD"),
        span("host_read:evaluator.epoch_n_err", "transfer", 1.32, 1.42,
             decision + 1, decision, bytes=12),
        span("sdc_vote", "resilience", 1.43, 1.57, decision + 2,
             decision, tick=50),
        span("host_read:fc.weights", "transfer", 1.44, 1.47,
             decision + 3, decision + 2, bytes=4096),
        span("host_read:fc.bias", "transfer", 1.50, 1.52,
             decision + 4, decision + 2, bytes=64),
        span("host_read:anomaly_guard.anomaly_state", "transfer", 1.58,
             1.60, decision + 5, decision, bytes=12),
        span("epoch:0", "epoch", 1.0, 1.62, decision + 6, 0),
    ]
    return spans


def test_host_reads_are_counted_once_wherever_they_nest():
    obs = observation(four_step_epoch())
    assert reader("host_reads_per_step")(obs) == pytest.approx(4 / 4)
    # 100 + 30 + 20 + 20 ms of waiting over four steps
    assert reader("host_read_wait_ms_per_step")(obs) == pytest.approx(
        170.0 / 4)
    assert reader("sdc_vote_ms")(obs) == pytest.approx(140.0)


def test_host_busy_is_self_time_without_reads_and_votes():
    obs = observation(four_step_epoch())
    # 4 loaders + 4 regions + 3 bare decisions at 10 ms, and the last
    # decision's own 300 − (100 read + 140 vote + 20 guard read) = 40;
    # the vote's inner reads are the vote's children, not counted again
    assert reader("host_busy_ms_per_step")(obs) == pytest.approx(
        (11 * 10.0 + 40.0) / 4)


def test_the_call_that_hands_over_a_program_is_not_host_work():
    """A ``jit`` call past the runtime's in-flight limit blocks until
    the device finishes a program: ``dispatch:`` inside the region
    unit's fire, ``chunk:`` beside the units."""
    spans = [
        span("workflow:alexnet", "workflow", 1.0, 2.0, 1, 0),
        span("loader", "unit", 1.0, 1.004, 2, 1, kind="ArrayLoader"),
        span("chunk:train_region", "region", 1.004, 1.010, 3, 1,
             steps=16),
        span("decision", "unit", 1.010, 1.030, 4, 1, kind="DecisionGD"),
        span("host_read:evaluator.epoch_loss", "transfer", 1.011, 1.029,
             5, 4, bytes=12),
        # a per-step region fire: 2 ms of preparation around a call
        # that blocked for 140 ms
        span("train_region", "unit", 1.100, 1.242, 6, 1,
             kind="RegionUnit"),
        span("dispatch:train_region", "region", 1.101, 1.241, 7, 6),
        # a serving request's spans keep ids of their own (trace_id)
        {"name": "req.prefill", "cat": "request", "t0": 1.0, "t1": 1.5,
         "args": {"trace_id": "ab-000001", "span_id": 2,
                  "parent_span_id": 1}},
    ]
    obs = observation(spans, steps=16)
    assert reader("host_busy_ms_per_step")(obs) == pytest.approx(
        (4.0 + 2.0 + 2.0) / 16)
    assert reader("host_reads_per_step")(obs) == pytest.approx(1 / 16)


def test_spans_of_the_check_after_the_window_are_not_the_windows():
    """The ring is read after the correctness check has read every
    parameter back."""
    spans = four_step_epoch() + [
        span("host_read:fc.weights", "transfer", 10.5, 10.6, 90, 0,
             bytes=4096),
        span("late_unit", "unit", 10.7, 10.9, 91, 0, kind="Unit")]
    obs = observation(spans)
    assert reader("host_reads_per_step")(obs) == pytest.approx(1.0)
    assert reader("host_busy_ms_per_step")(obs) == pytest.approx(37.5)


def test_a_program_without_these_spans_reports_nothing():
    """The parent commit's ring: unit spans with a depth and no ids,
    no ``host_read``, no ``sdc_vote``.  Nothing is read, nothing
    raises."""
    spans = [{"name": "decision", "cat": "unit", "t0": 1.0, "t1": 1.3,
              "args": {"kind": "DecisionGD", "depth": 1}},
             {"name": "chunk:train_region", "cat": "region", "t0": 1.3,
              "t1": 1.31, "args": {"steps": 16, "depth": 0}}]
    obs = observation(spans)
    for name in ("host_reads_per_step", "host_read_wait_ms_per_step",
                 "host_busy_ms_per_step", "sdc_vote_ms"):
        assert reader(name)(obs) is None


def test_guard_skipped_steps_is_the_counters_growth():
    assert reader("guard_skipped_steps")(observation()) == 0.0
    obs = observation(counters={"znicz_step_anomalies_total": 3.0})
    assert reader("guard_skipped_steps")(obs) == 3.0
    assert reader("guard_skipped_steps")(observation(counters={})) is None


def test_the_new_entries_name_their_cells_and_layers():
    bench = discovery.load_json(discovery.REPO + "/BENCHMARK.json")
    new = {m["name"]: m for m in bench["per_layer"][-8:]}
    assert list(new) == [
        "flash_fwd_ms_per_step", "flash_dq_ms_per_step",
        "flash_dkv_ms_per_step", "host_reads_per_step",
        "host_read_wait_ms_per_step", "host_busy_ms_per_step",
        "sdc_vote_ms", "guard_skipped_steps"]
    for name, entry in new.items():
        assert entry["moves"] == "throughput"
        # the three kernels run in the LM cell alone, and no vote
        # falls in a traced AlexNet window (one per 50 dispatches)
        lm_only = name.startswith("flash_") or name == "sdc_vote_ms"
        assert entry["workloads"] == (
            ["attn_lm_train_t2048"] if lm_only else CELLS)
        assert entry["layer"] == (
            "kernels" if name.startswith("flash_") else "fused step"
            if name == "guard_skipped_steps" else "training driver")
        assert discovery.load_module("layer_metrics", name) is not None


# ----------------------------------------------------------------------
# the traced toy rehearsal of both cells
# ----------------------------------------------------------------------
#: per toy cell: steps per decision tick, blocking reads per epoch (the
#: two epoch-end accumulators and the guard's state), parameter
#: tensors the vote reads back after the fingerprint, warm-up ticks
TOY = {"alexnet_train_b768": (2, 3, 16, 2),
       "attn_lm_train_t2048": (1, 3, 11, 8)}
VOTE_INTERVAL = 50            # engine.sdc_vote_interval's default


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_reports_the_host_metrics_with_toy_counts(cell):
    proc, lines = run(["--workload", cell, "--seed", "5", "--seconds",
                       "2", "--trace", "1", "--toy"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(lines[-1])
    assert line["correct"] is True
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    steps = line["attempted"]
    traffic = discovery.load_json(
        f"{discovery.TOY_ROOT}/traffic/"
        f"{discovery.find_cell(cell, toy=True).traffic_name}.json")
    steps_per_tick, per_epoch, vote_params, warm_ticks = TOY[cell]
    epochs = steps // traffic["steps_per_epoch"]
    ticks = steps // steps_per_tick
    votes = (warm_ticks + ticks) // VOTE_INTERVAL \
        - warm_ticks // VOTE_INTERVAL
    reads = per_epoch * epochs + votes * (1 + vote_params)
    assert metrics["host_reads_per_step"] * steps == pytest.approx(reads)
    assert metrics["guard_skipped_steps"] == 0
    assert metrics["host_read_wait_ms_per_step"] > 0
    assert metrics["host_busy_ms_per_step"] > 0
    assert ("sdc_vote_ms" in metrics) == (
        votes > 0 and "sdc_vote_ms" in metric_names("per_layer", cell))
    # interpret mode leaves no kernel to time
    assert not {"flash_fwd_ms_per_step", "flash_dq_ms_per_step",
                "flash_dkv_ms_per_step"} & set(metrics)
    assert set(metrics) <= metric_names("per_layer", cell)
    # the gaps now lie under the program's own spans
    owners = {name for name, _s in line["breakdown"]["idle_gaps"]}
    assert any(name.startswith("host_read:") for name in owners)

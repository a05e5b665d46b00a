"""What the training host waits for, and which kernel runs (PR 23).

- ``Vector.map_read`` of a device-authoritative buffer is the one
  blocking device→host read of the training path: one
  ``host_read:<name>`` span, one count, the span's duration in the
  wait sum; a second ``map_read`` reads nothing;
- every span names its parent (``span_id`` / ``parent_span_id``),
  across an exception unwind and per thread;
- ``run_chunked``, ``run_accumulated`` and ``run_pipelined`` record
  the spans ``run`` records (root, loader, decision), one loader span
  per dispatch; under ``run`` the program's call is a span inside the
  region unit's;
- ``engine.anomaly_check_interval`` sets how often the guard is read;
- ``sdc_vote`` and ``sdc_audit`` spans at the sentinel's cadence;
- a ``TrivialUnit``'s fire records nothing;
- the kernels and the step programs carry names;
- telemetry off records nothing at any of these sites;
- the ring's spans, shifted the way the benchmark shifts them, land on
  their own profiler annotations.
"""

from __future__ import annotations

import collections
import statistics
import threading
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_blobs
from znicz_tpu.observe import metrics as obs_metrics
from znicz_tpu.observe import tracing as obs_tracing
from znicz_tpu.observe.tracing import SpanTracer
from znicz_tpu.utils.config import root


def _spans(tracer, since: int = 0) -> list[dict]:
    return [ev for ev in tracer.to_chrome_trace(since=since)["traceEvents"]
            if ev.get("ph") == "X"]


def _toy_workflow(name: str, epochs: int = 2, steps_per_epoch: int = 8):
    from znicz_tpu.backends import XLADevice
    from znicz_tpu.loader.fullbatch import ArrayLoader
    from znicz_tpu.models.standard_workflow import StandardWorkflow

    batch = 12
    data, labels = make_blobs(batch * steps_per_epoch // 3, 3, 10)
    wf = StandardWorkflow(
        name=name,
        loader_factory=lambda w: ArrayLoader(
            w, train_data=data, train_labels=labels,
            minibatch_size=batch),
        layers=[{"type": "all2all_tanh",
                 "->": {"output_sample_shape": 16},
                 "<-": {"learning_rate": 0.05}},
                {"type": "softmax", "->": {"output_sample_shape": 3},
                 "<-": {"learning_rate": 0.05}}],
        decision_config={"max_epochs": epochs})
    wf._max_fires = 100_000
    wf.initialize(device=XLADevice())
    return wf


def _trained_spans(name: str, drive=lambda wf: wf.run(), **kw) -> tuple:
    wf = _toy_workflow(name, **kw)
    mark = obs_tracing.TRACER.mark()
    drive(wf)
    return wf, _spans(obs_tracing.TRACER, mark)


# ----------------------------------------------------------------------
# host_read
# ----------------------------------------------------------------------
def test_map_read_from_device_is_one_host_read_span_and_count():
    from znicz_tpu.backends import XLADevice
    from znicz_tpu.memory import Vector

    reads = obs_metrics.host_reads()
    waited = obs_metrics.host_read_wait_seconds()
    arr = np.arange(64, dtype=np.float32).reshape(8, 8)
    vec = Vector(arr, name="read_probe")
    vec.initialize(XLADevice())
    vec.devmem = vec.devmem + 1.0        # device-authoritative now
    base_n, base_s = reads.value, waited.value
    mark = obs_tracing.TRACER.mark()
    vec.map_read()
    spans = _spans(obs_tracing.TRACER, mark)
    assert [s["name"] for s in spans] == ["host_read:read_probe"]
    assert spans[0]["cat"] == "transfer"
    assert spans[0]["args"]["bytes"] == arr.nbytes
    assert reads.value == base_n + 1
    assert waited.value - base_s == pytest.approx(
        spans[0]["dur"] / 1e6, abs=1e-9)
    np.testing.assert_array_equal(vec.mem, arr + 1.0)
    vec.map_read()                       # SYNCED: nothing to wait for
    assert _spans(obs_tracing.TRACER, mark) == spans
    assert reads.value == base_n + 1


# ----------------------------------------------------------------------
# parents
# ----------------------------------------------------------------------
def test_parent_span_id_is_the_enclosing_span():
    tracer = SpanTracer()
    with tracer.span("outer"):
        with tracer.span("mid"):
            with tracer.span("inner"):
                pass
        with tracer.span("mid2"):
            pass
    by_name = {s["name"]: s["args"] for s in _spans(tracer)}
    assert by_name["outer"]["parent_span_id"] == 0
    assert by_name["mid"]["parent_span_id"] == by_name["outer"]["span_id"]
    assert by_name["mid2"]["parent_span_id"] == by_name["outer"]["span_id"]
    assert by_name["inner"]["parent_span_id"] == by_name["mid"]["span_id"]
    ids = [a["span_id"] for a in by_name.values()]
    assert len(set(ids)) == 4 and 0 not in ids
    # a retroactive span is a root with an id of its own
    tracer.complete("late", 0.0, 1.0)
    late = _spans(tracer)[-1]["args"]
    assert late["parent_span_id"] == 0 and late["span_id"] not in ids


def test_parent_span_id_across_an_exception_unwind():
    tracer = SpanTracer()
    with pytest.raises(RuntimeError):
        with tracer.span("outer"):
            with tracer.span("boom"):
                raise RuntimeError("x")
    with tracer.span("after"):
        with tracer.span("child"):
            pass
    by_name = {s["name"]: s["args"] for s in _spans(tracer)}
    assert by_name["boom"]["parent_span_id"] == by_name["outer"]["span_id"]
    assert by_name["after"]["parent_span_id"] == 0  # the stack unwound
    assert by_name["child"]["parent_span_id"] == by_name["after"]["span_id"]


def test_parent_span_id_is_per_thread():
    tracer = SpanTracer()
    both_open = threading.Barrier(2)

    def work(tag: str) -> None:
        with tracer.span(f"outer_{tag}"):
            both_open.wait(timeout=10)   # both outers open at once
            with tracer.span(f"inner_{tag}"):
                both_open.wait(timeout=10)

    threads = [threading.Thread(target=work, args=(t,)) for t in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    by_name = {s["name"]: s for s in _spans(tracer)}
    for tag in "ab":
        inner, outer = by_name[f"inner_{tag}"], by_name[f"outer_{tag}"]
        assert inner["args"]["parent_span_id"] == outer["args"]["span_id"]
        assert outer["args"]["parent_span_id"] == 0
        assert inner["tid"] == outer["tid"]
    assert by_name["inner_a"]["tid"] != by_name["inner_b"]["tid"]


# ----------------------------------------------------------------------
# the drivers
# ----------------------------------------------------------------------
#: driver → (the call, ``engine.grad_accum``, its dispatches over
#: 2 epochs × 8 minibatches, the name its dispatch spans begin with;
#: the pipeline executor dispatches per stage and microbatch and has
#: no span of its own)
DRIVERS = {
    "run_chunked": (lambda wf: wf.run_chunked(4), 1, 4, "chunk:"),
    "run_accumulated": (lambda wf: wf.run_accumulated(), 2, 8, "accum:"),
    "run_pipelined": (lambda wf: wf.run_pipelined(2), 2, 8, None),
}


@pytest.mark.parametrize("driver", DRIVERS)
def test_run_chunked_records_the_spans_run_records(driver):
    drive, grad_accum, fires, dispatch = DRIVERS[driver]
    wf_a, per_step = _trained_spans("spans_per_step")
    root.common.engine.grad_accum = grad_accum   # read at initialize
    wf_b, driven = _trained_spans(f"spans_{driver}", drive)

    def unit_spans(spans) -> collections.Counter:
        return collections.Counter(
            (s["name"], s["args"]["kind"]) for s in spans
            if s["cat"] == "unit" and s["args"]["kind"] in (
                "ArrayLoader", "DecisionGD"))

    a, b = unit_spans(per_step), unit_spans(driven)
    assert set(a) == set(b) == {(wf_a.loader.name, "ArrayLoader"),
                                (wf_a.decision.name, "DecisionGD")}
    assert set(a.values()) == {16}       # 2 epochs × 8 steps
    assert set(b.values()) == {fires}    # ONE loader span per
    #                                      dispatch, not one per step
    roots = [s for s in driven if s["cat"] == "workflow"]
    assert [s["name"] for s in roots] == [f"workflow:spans_{driver}"]
    root_id = roots[0]["args"]["span_id"]
    for s in driven:
        if s["name"].startswith("jax:"):
            continue        # JAX's stamps: children of compile:<region>
        if s["cat"] in ("unit", "region", "compile"):
            assert s["args"]["parent_span_id"] == root_id
    if dispatch is not None:
        dispatches = [s for s in driven
                      if s["name"].startswith((dispatch, "compile:"))]
        assert len(dispatches) == fires
    # the epoch-end reads and the guard read sit inside the decision
    decisions = {s["args"]["span_id"] for s in driven
                 if s["name"] == wf_b.decision.name}
    reads = [s for s in driven if s["name"].startswith("host_read:")]
    assert reads and all(
        s["args"]["parent_span_id"] in decisions for s in reads)
    assert wf_b.loader.run_count == fires
    assert wf_b.decision.run_count == fires


def test_the_dispatch_call_is_a_child_of_the_region_units_fire():
    """Under ``run()`` the warmed program's call — which blocks past
    the runtime's in-flight limit — is ``dispatch:<region>`` inside
    the region unit's span, so the unit's self time is its own work."""
    wf, spans = _trained_spans("dispatch_child")
    region = wf._region_unit.region
    fires = {s["args"]["span_id"] for s in spans
             if s["cat"] == "unit" and s["args"]["kind"] == "RegionUnit"}
    calls = [s for s in spans if s["name"] == f"dispatch:{region.name}"]
    compiles = [s for s in spans if s["name"] == f"compile:{region.name}"]
    assert len(fires) == 16 and len(calls) + len(compiles) == 16
    assert {s["cat"] for s in calls} == {"region"}
    assert all(s["args"]["parent_span_id"] in fires
               for s in calls + compiles)


def test_anomaly_check_interval_sets_the_guard_reads_per_step():
    def guard_reads(interval: int) -> int:
        root.common.engine.anomaly_check_interval = interval
        _wf, spans = _trained_spans(f"guard_every_{interval}")
        return sum(1 for s in spans
                   if s["name"].startswith("host_read:")
                   and "anomaly_state" in s["name"])

    assert guard_reads(1) == 16          # 16 steps: one read each
    assert guard_reads(4) == 4           # one per 4 decision ticks


@pytest.mark.parametrize("name, interval, ticks", [
    ("sdc_vote", 5, [5, 10, 15]), ("sdc_audit", 4, [4, 8, 12, 16])])
def test_sentinel_span_once_per_interval(name, interval, ticks):
    setattr(root.common.engine, f"{name}_interval", interval)
    wf, spans = _trained_spans(f"{name}_every_{interval}")
    assert wf.integrity is not None
    found = [s for s in spans if s["name"] == name]
    assert [s["args"]["tick"] for s in found] == ticks
    assert {s["cat"] for s in found} == {"resilience"}
    decisions = {s["args"]["span_id"] for s in spans
                 if s["name"] == wf.decision.name}
    assert all(s["args"]["parent_span_id"] in decisions for s in found)
    # its parameter reads are its children, one span each
    ids = {s["args"]["span_id"] for s in found}
    inside = [s["name"] for s in spans
              if s["name"].startswith("host_read:")
              and s["args"]["parent_span_id"] in ids]
    assert any("weights" in read for read in inside)


def test_trivial_unit_fire_records_no_span():
    from znicz_tpu.units import EndPoint, Repeater, StartPoint, Unit

    mark = obs_tracing.TRACER.mark()
    for cls in (Repeater, StartPoint, EndPoint):
        unit = cls(None, name=f"trivial_{cls.__name__}")
        unit._fire()
        assert unit.run_count == 1       # the fire itself is counted
        fam = obs_metrics.REGISTRY.get("znicz_unit_run_seconds")
        assert (unit.name,) not in dict(fam.items() if fam else ())
    assert obs_tracing.TRACER.mark() == mark
    Unit(None, name="plain_unit")._fire()
    assert [s["name"] for s in _spans(obs_tracing.TRACER, mark)] \
        == ["plain_unit"]
    _wf, spans = _trained_spans("no_repeater_span", epochs=1)
    kinds = {s["args"].get("kind") for s in spans}
    assert not kinds & {"Repeater", "StartPoint", "EndPoint"}


def test_telemetry_off_records_nothing_at_the_new_sites():
    root.common.engine.telemetry = False
    root.common.engine.sdc_vote_interval = 2
    reads = obs_metrics.host_reads()
    waited = obs_metrics.host_read_wait_seconds()
    base = (obs_tracing.TRACER.mark(), reads.value, waited.value)
    wf = _toy_workflow("telemetry_off", epochs=1)
    wf.run_chunked(4)
    wf.decision.evaluator.epoch_n_err.map_read()
    assert wf.integrity._tick == 2       # the vote itself still ran
    assert (obs_tracing.TRACER.mark(), reads.value, waited.value) == base


# ----------------------------------------------------------------------
# names on device work
# ----------------------------------------------------------------------
def _pallas_names(jaxpr, out: list) -> list:
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                if hasattr(sub, "jaxpr"):
                    _pallas_names(sub.jaxpr, out)
                elif hasattr(sub, "eqns"):
                    _pallas_names(sub, out)
    return out


def test_flash_pallas_calls_carry_their_names():
    from znicz_tpu.ops.pallas_attention import flash_attention

    q = jnp.ones((1, 256, 2, 64), jnp.float32)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               interpret=True).sum()

    forward = jax.make_jaxpr(loss)(q, q, q)
    assert _pallas_names(forward.jaxpr, []) == ["znicz_flash_fwd"]
    # a causal call's backward is ONE kernel — T 256 is one K tile, and
    # under two the first Q tile's dq waits in VMEM (PR 55) —, a
    # non-causal call's a dq and a dk/dv kernel
    backward = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q)
    assert _pallas_names(backward.jaxpr, []) == [
        "znicz_flash_fwd", "znicz_flash_bwd"]
    for causal, kernels in ((True, ["znicz_flash_bwd"]),
                            (False, ["znicz_flash_dq", "znicz_flash_dkv"])):
        backward = jax.make_jaxpr(jax.grad(
            lambda *a: flash_attention(*a, causal=causal, block_k=128,
                                       interpret=True).sum(),
            argnums=(0, 1, 2)))(q, q, q)
        assert _pallas_names(backward.jaxpr, []) == [
            "znicz_flash_fwd"] + kernels


def test_row_kernels_carry_their_names():
    from znicz_tpu.ops import pallas_kernels as pk

    x = jnp.ones((16, 128), jnp.float32)
    g = jnp.ones((128,), jnp.float32)
    calls = {
        "znicz_layer_norm_fwd": lambda: pk.layer_norm_forward(
            x, g, g, 1e-5, interpret=True),
        "znicz_layer_norm_bwd": lambda: pk.layer_norm_backward(
            x, x, g, 1e-5, interpret=True),
    }
    for name, call in calls.items():
        assert _pallas_names(jax.make_jaxpr(call)().jaxpr, []) == [name]


def test_region_programs_are_named_after_region_and_variant():
    wf = _toy_workflow("named_programs", epochs=1)
    wf.run_chunked(4)
    region = wf._region_unit.region
    assert region.program_name("step") == f"znicz_step__{region.name}"
    names = {getattr(fn, "__name__", None)
             for fn in region._cache.values()}
    assert names == {f"znicz_chunk4__{region.name}"}
    skips = tuple(bool(u.gate_skip) for u in region.units)
    assert region.build_callable(skips).__name__ \
        == f"znicz_step__{region.name}"
    assert region.build_callable(
        skips, accum_phase=("apply", 2)).__name__ \
        == f"znicz_apply_micro__{region.name}"
    module = jax.jit(region.build_callable(skips)).lower(
        *[v._devmem for v in region._vectors]).as_text()
    assert f"module @jit_znicz_step__{region.name}" in module


# ----------------------------------------------------------------------
# the clock
# ----------------------------------------------------------------------
def _clock_gaps_us(tmp_path, monkeypatch) -> list[float]:
    """Record ring spans with their annotations riding inside a
    profiler window opened the way the benchmark opens it, shift the
    ring's copies with the benchmark's own arithmetic, and return how
    far each start and end lies from its annotation, in µs."""
    from znbench import run as znbench_run
    from znbench import trace_reduce
    from znbench.harness.window import WINDOW_SPAN, Context

    cell = types.SimpleNamespace(name="clock")
    ctx = Context(cell, seed=0, seconds=1.0, trace=True, toy=True,
                  devices=[], t_start=time.perf_counter(),
                  scratch=str(tmp_path))
    names = [f"clock_probe_{i}" for i in range(10)]
    ctx.open_window()
    try:
        # what profile_window sets while its device trace is open
        monkeypatch.setattr(obs_tracing, "_DEVICE_TRACE_OPEN", True)
        for name in names:
            with obs_tracing.TRACER.span(name, cat="unit"):
                time.sleep(0.003)
            time.sleep(0.001)
    finally:
        monkeypatch.setattr(obs_tracing, "_DEVICE_TRACE_OPEN", False)
        ctx.close_window()
    trace = trace_reduce.load(ctx.xplane, toy=True)
    window = trace.window(WINDOW_SPAN)
    assert window is not None
    annotated = {ev.name: ev for ev in trace.host if ev.name in names}
    shifted = {ev.name: ev for ev in znbench_run.host_spans_on_trace_clock(
        ctx, ctx.program_spans(), trace, window) if ev.name in names}
    assert set(annotated) == set(shifted) == set(names)
    gaps = []
    for name in names:
        assert annotated[name].dur >= 3_000_000      # it slept 3 ms
        gaps.append(abs(shifted[name].start - annotated[name].start) / 1e3)
        gaps.append(abs(shifted[name].end - annotated[name].end) / 1e3)
    return gaps


def test_profile_window_starts_the_profiler_with_the_python_tracer_off(
        tmp_path, monkeypatch):
    from znicz_tpu.observe import profile_window
    started = {}

    def start_trace(outdir, profiler_options=None):
        started["options"] = profiler_options
        started["riding"] = obs_tracing._DEVICE_TRACE_OPEN

    monkeypatch.setattr(jax.profiler, "start_trace", start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    with profile_window(str(tmp_path / "win"), tracer=SpanTracer()):
        assert obs_tracing._DEVICE_TRACE_OPEN   # annotations ride
    assert started["options"].python_tracer_level == 0
    assert started["riding"] is False and \
        obs_tracing._DEVICE_TRACE_OPEN is False


def test_shifted_ring_spans_land_on_their_annotations(tmp_path,
                                                      monkeypatch):
    """The benchmark puts the ring's spans (``perf_counter``) on the
    profiler's clock by the offset of the ``znbench.window``
    annotation.  Both copies of a span must agree within 200 µs, an
    order below the shortest gap the attribution is used for (3 ms).
    One attempt decides, on the MEDIAN of the twenty edges: a span's
    two clocks are read a few instructions apart, and a worker
    descheduled between two of them (tier-1 runs six) moves that one
    edge, while clocks that drift apart move them all."""
    gaps = _clock_gaps_us(tmp_path, monkeypatch)
    assert statistics.median(gaps) < 200.0, sorted(gaps)

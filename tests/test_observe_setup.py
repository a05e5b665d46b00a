"""Where a process's start-up goes (PR 48).

- ``Workflow.initialize`` is a span (``initialize:<workflow>``) with
  one ``initialize:<unit>`` child per call of a unit's ``initialize``,
  ``deferred`` where the unit asked for a later pass; a workflow nested
  as a unit comes out as a child of its unit span;
- ``param_fill`` and ``upload:<vector>`` are children of the unit that
  asked, with ``bytes``; a tensor drawn in chunks by the pool of host
  threads is still ONE ``param_fill`` span (``chunks``, ``workers``),
  and ``znicz_param_fill_bytes_total{path}`` says which way the bytes
  went;
- a region's first dispatch records ``jax:trace``, ``jax:lower`` and
  ``jax:backend_compile`` once each under ``compile:<region>`` (a
  jitted function traced inside the step's trace is not a span of its
  own), a second dispatch none;
- ``znicz_setup_seconds{phase}`` is the spans' sums (``initialize``:
  of their self time);
- the process's start lies before the tracer's epoch;
- telemetry off records nothing; the ``jax.monitoring`` listeners are
  registered once;
- the ring counts what it drops and a retroactive span can name the
  span open on its thread as its parent.
"""

from __future__ import annotations

import collections
import os
import time

import jax
import numpy as np
import pytest

from conftest import make_blobs
from znicz_tpu.observe import metrics as obs_metrics
from znicz_tpu.observe import tracing as obs_tracing
from znicz_tpu.observe.tracing import SpanTracer
from znicz_tpu.units import Unit
from znicz_tpu.utils.config import root
from znicz_tpu.workflow import Workflow

PHASES = ("initialize", "param_fill", "upload", "trace", "lower",
          "backend_compile", "cache_load")
JAX_SPANS = {"jax:trace": "trace", "jax:lower": "lower",
             "jax:backend_compile": "backend_compile",
             "jax:cache_load": "cache_load"}


def _spans(tracer, since: int = 0) -> list[dict]:
    return [ev for ev in tracer.to_chrome_trace(since=since)["traceEvents"]
            if ev.get("ph") == "X"]


def _phases() -> dict:
    return {p: obs_metrics.setup_seconds(p).value for p in PHASES}


def _toy_workflow(name: str):
    from znicz_tpu.backends import XLADevice
    from znicz_tpu.loader.fullbatch import ArrayLoader
    from znicz_tpu.models.standard_workflow import StandardWorkflow

    data, labels = make_blobs(16, 3, 10)
    wf = StandardWorkflow(
        name=name,
        loader_factory=lambda w: ArrayLoader(
            w, train_data=data, train_labels=labels, minibatch_size=12),
        layers=[{"type": "all2all_tanh",
                 "->": {"output_sample_shape": 16},
                 "<-": {"learning_rate": 0.05}},
                {"type": "softmax", "->": {"output_sample_shape": 3},
                 "<-": {"learning_rate": 0.05}}],
        decision_config={"max_epochs": 1})
    wf._max_fires = 100_000
    wf.initialize(device=XLADevice())
    return wf


@pytest.fixture(scope="module")
def started():
    """One toy workflow initialized and run for an epoch, with the
    spans and the counter's growth of each half."""
    before = _phases()
    mark = obs_tracing.TRACER.mark()
    wf = _toy_workflow("setup_probe")
    init_spans = _spans(obs_tracing.TRACER, mark)
    mark = obs_tracing.TRACER.mark()
    wf.run()
    run_spans = _spans(obs_tracing.TRACER, mark)
    after = _phases()
    return {"wf": wf, "init": init_spans, "run": run_spans,
            "counted": {p: after[p] - before[p] for p in PHASES}}


def _children(spans: list, parent: dict) -> list:
    return [s for s in spans
            if s["args"]["parent_span_id"] == parent["args"]["span_id"]]


# ----------------------------------------------------------------------
# initialize
# ----------------------------------------------------------------------
def test_initialize_is_a_root_span_with_a_child_per_unit(started):
    wf, spans = started["wf"], started["init"]
    roots = [s for s in spans if s["name"] == "initialize:setup_probe"]
    assert len(roots) == 1 and roots[0]["cat"] == "setup"
    assert roots[0]["args"]["parent_span_id"] == 0
    units = [s for s in _children(spans, roots[0])
             if s["name"].startswith("initialize:")]
    # the region unit is made after the passes, outside the span
    expected = {u.name: type(u).__name__ for u in wf.units
                if u is not wf._region_unit}
    assert {s["name"][len("initialize:"):]: s["args"]["kind"]
            for s in units} == expected
    assert len(units) == len(expected)
    assert all(s["cat"] == "setup" and "deferred" not in s["args"]
               for s in units)
    assert sum(s["dur"] for s in units) <= roots[0]["dur"]


class _Late(Unit):
    """Needs what ``_Early`` makes, and is initialized before it."""

    def initialize(self, **kwargs) -> None:
        self.taken = self.workflow.early.made   # AttributeError: defer
        super().initialize(**kwargs)


class _Early(Unit):
    def initialize(self, **kwargs) -> None:
        self.made = 1
        super().initialize(**kwargs)


def test_a_unit_that_asks_for_a_later_pass_is_deferred_once():
    wf = Workflow(name="two_pass")
    _Late(wf, name="late")
    wf.early = _Early(wf, name="early")
    mark = obs_tracing.TRACER.mark()
    wf.initialize()
    spans = [s for s in _spans(obs_tracing.TRACER, mark)
             if s["name"] == "initialize:late"]
    assert [s["args"].get("deferred", False) for s in spans] \
        == [True, False]
    assert wf.is_initialized


def test_a_nested_workflow_is_a_child_of_its_unit_span():
    outer = Workflow(name="outer_wf")
    inner = Workflow(outer, name="inner_wf")
    _Early(inner, name="leaf")
    mark = obs_tracing.TRACER.mark()
    outer.initialize()
    by_name = collections.defaultdict(list)
    for s in _spans(obs_tracing.TRACER, mark):
        by_name[s["name"]].append(s["args"])
    root_id = by_name["initialize:outer_wf"][0]["span_id"]
    unit = next(a for a in by_name["initialize:inner_wf"]
                if a.get("kind") == "Workflow")
    nested = next(a for a in by_name["initialize:inner_wf"]
                  if "kind" not in a)
    assert unit["parent_span_id"] == root_id
    assert nested["parent_span_id"] == unit["span_id"]
    assert by_name["initialize:leaf"][0]["parent_span_id"] \
        == nested["span_id"]


# ----------------------------------------------------------------------
# param_fill, upload
# ----------------------------------------------------------------------
def test_fill_and_upload_are_children_of_the_unit_that_asked(started):
    wf, spans = started["wf"], started["init"]
    unit = wf.forwards[0]
    parent = next(s for s in spans
                  if s["name"] == f"initialize:{unit.name}")
    kids = _children(spans, parent)
    fills = [s for s in kids if s["name"] == "param_fill"]
    assert sorted(s["args"]["bytes"] for s in fills) == sorted(
        [unit.weights.devmem.nbytes, unit.bias.devmem.nbytes])
    assert all(s["cat"] == "setup" and s["args"]["chunks"] == 1
               for s in fills)
    uploads = {s["name"]: s for s in kids
               if s["name"].startswith("upload:")}
    up = uploads[f"upload:{unit.weights.name}"]
    assert up["cat"] == "transfer"
    assert up["args"]["bytes"] == unit.weights.devmem.nbytes
    # every fill and every upload of set-up has a unit for a parent
    unit_ids = {s["args"]["span_id"] for s in spans
                if s["name"].startswith("initialize:")}
    assert all(s["args"]["parent_span_id"] in unit_ids for s in spans
               if s["name"] == "param_fill"
               or s["name"].startswith("upload:"))


class _Filler(Unit):
    """Draws one tensor above a chunk and one below, as a unit's
    ``initialize`` does."""

    def initialize(self, **kwargs) -> None:
        from znicz_tpu.utils import prng
        gen = prng.get()
        self.large = gen.fill_normal((2, prng.CHUNK // 2 + 7), 0.0, 0.02)
        self.small = gen.fill_uniform((5, 3), -1.0, 1.0)
        super().initialize(**kwargs)


def test_a_chunked_fill_is_still_one_span_of_the_unit_that_asked():
    paths = ("stream", "chunked")
    before = {p: obs_metrics.param_fill_bytes(p).value for p in paths}
    secs = obs_metrics.setup_seconds("param_fill").value
    wf = Workflow(name="chunked_fill")
    unit = _Filler(wf, name="filler")
    mark = obs_tracing.TRACER.mark()
    wf.initialize()
    spans = _spans(obs_tracing.TRACER, mark)
    parent = next(s for s in spans if s["name"] == "initialize:filler")
    fills = [s for s in spans if s["name"].startswith("param_fill")]
    # one span a tensor, on the calling thread: the pool's threads
    # open none (the benchmark sums ``param_fill*`` by prefix)
    assert [s["name"] for s in fills] == ["param_fill", "param_fill"]
    assert all(s["args"]["parent_span_id"] == parent["args"]["span_id"]
               and s["cat"] == "setup" and s["tid"] == parent["tid"]
               for s in fills)
    large, small = fills
    assert large["args"]["bytes"] == unit.large.nbytes
    assert large["args"]["chunks"] == 2
    assert 1 <= large["args"]["workers"] <= 8
    assert (small["args"]["bytes"], small["args"]["chunks"],
            small["args"]["workers"]) == (unit.small.nbytes, 1, 1)
    grown = {p: obs_metrics.param_fill_bytes(p).value - before[p]
             for p in paths}
    assert grown == {"stream": unit.small.nbytes,
                     "chunked": unit.large.nbytes}
    assert obs_metrics.setup_seconds("param_fill").value - secs \
        == pytest.approx(sum(s["dur"] for s in fills) / 1e6, abs=1e-9)
    text = obs_metrics.REGISTRY.to_prometheus()
    assert 'znicz_param_fill_bytes_total{path="chunked"}' in text
    assert 'znicz_param_fill_bytes_total{path="stream"}' in text


def test_a_host_write_that_reaches_the_device_is_one_upload_span():
    from znicz_tpu.backends import XLADevice
    from znicz_tpu.memory import Vector

    vec = Vector(np.zeros((4, 4), np.float32), name="write_probe")
    vec.initialize(XLADevice())
    sent = obs_metrics.transfer_bytes("h2d")
    base_b = sent.value
    base_s = obs_metrics.setup_seconds("upload").value
    mark = obs_tracing.TRACER.mark()
    vec.map_write()
    vec.mem[...] = 1.0
    vec.unmap()
    vec.unmap()                          # DEVICE: nothing to send
    spans = _spans(obs_tracing.TRACER, mark)
    assert [s["name"] for s in spans] == ["upload:write_probe"]
    assert spans[0]["args"]["bytes"] == 64
    assert sent.value == base_b + 64
    assert obs_metrics.setup_seconds("upload").value - base_s \
        == pytest.approx(spans[0]["dur"] / 1e6, abs=1e-9)
    np.testing.assert_array_equal(np.asarray(vec.devmem), 1.0)


# ----------------------------------------------------------------------
# the step program's making
# ----------------------------------------------------------------------
def test_first_dispatch_splits_its_compile_span_by_jax_stamps(started):
    spans = started["run"]
    compiles = [s for s in spans if s["name"] == "compile:train_region"]
    assert len(compiles) == 1
    kids = _children(spans, compiles[0])
    names = collections.Counter(s["name"] for s in kids)
    # ONE of each: the jitted functions the step's trace meets are
    # traced inside it and are no spans of their own
    assert names["jax:trace"] == names["jax:lower"] \
        == names["jax:backend_compile"] == 1
    assert set(names) <= set(JAX_SPANS)
    parts = [s for s in kids if s["name"] != "jax:cache_load"]
    assert all(s["cat"] == "compile" and s["args"]["depth"]
               == compiles[0]["args"]["depth"] + 1 for s in parts)
    assert all("train_region" in s["args"]["fun_name"] for s in parts)
    assert sum(s["dur"] for s in parts) <= compiles[0]["dur"]
    t0, t1 = compiles[0]["ts"], compiles[0]["ts"] + compiles[0]["dur"]
    assert all(t0 <= s["ts"] and s["ts"] + s["dur"] <= t1 + 1.0
               for s in parts)
    order = sorted(parts, key=lambda s: s["ts"])
    assert [s["name"] for s in order] \
        == ["jax:trace", "jax:lower", "jax:backend_compile"]


def test_a_warmed_dispatch_records_no_jax_span(started):
    wf = started["wf"]
    wf.decision.max_epochs += 1
    wf.decision.complete.value = False
    before = _phases()
    mark = obs_tracing.TRACER.mark()
    wf.run()
    spans = _spans(obs_tracing.TRACER, mark)
    assert any(s["name"] == "dispatch:train_region" for s in spans)
    assert not [s for s in spans if s["cat"] in ("compile", "setup")]
    # an epoch's edges write anew from the host (the loader's next
    # permutation and cursor, the evaluator's sums), no step does: the
    # one phase a warmed run adds to
    after = _phases()
    uploads = [s for s in spans if s["name"].startswith("upload:")]
    assert sorted(s["name"] for s in uploads) == [
        "upload:ArrayLoader.sched_cursor", "upload:ArrayLoader.sched_perm",
        "upload:evaluator.epoch_loss", "upload:evaluator.epoch_n_err"]
    assert after.pop("upload") - before.pop("upload") == pytest.approx(
        sum(s["dur"] for s in uploads) / 1e6, abs=1e-9)
    assert after == before


# ----------------------------------------------------------------------
# the counter family
# ----------------------------------------------------------------------
def test_setup_seconds_are_the_spans_sums(started):
    spans = started["init"] + started["run"]
    want = dict.fromkeys(PHASES, 0.0)
    for s in spans:
        if s["name"] == "param_fill":
            want["param_fill"] += s["dur"]
        elif s["name"].startswith("upload:"):
            want["upload"] += s["dur"]
        elif s["name"] in JAX_SPANS:
            want[JAX_SPANS[s["name"]]] += s["dur"]
        elif s["name"].startswith("initialize:"):
            want["initialize"] += s["dur"] - sum(
                k["dur"] for k in _children(spans, s))
    counted = started["counted"]
    for phase in PHASES:
        assert counted[phase] == pytest.approx(
            want[phase] / 1e6, abs=1e-6), phase
    assert min(counted[p] for p in
               ("initialize", "param_fill", "upload", "trace", "lower",
                "backend_compile")) > 0
    # initialize's self time leaves the other phases out: together
    # they are the root span and the compile span's stamped part
    root_span = next(s for s in started["init"]
                     if s["name"] == "initialize:setup_probe")
    inside = sum(s["dur"] for s in started["init"]
                 if s["name"] in JAX_SPANS
                 and s["name"] != "jax:cache_load"
                 or s["name"] == "param_fill"
                 or s["name"].startswith(("upload:", "initialize:")))
    assert inside >= root_span["dur"]
    text = obs_metrics.REGISTRY.to_prometheus()
    assert 'znicz_setup_seconds{phase="initialize"}' in text


# ----------------------------------------------------------------------
# the process's start
# ----------------------------------------------------------------------
def test_process_start_lies_before_the_epoch_and_is_the_os_own(started):
    start_us = obs_tracing.process_start_us()
    assert start_us < 0
    assert obs_tracing.process_start_us() == start_us   # read once
    gauge = obs_metrics.process_start_time_seconds().value
    assert gauge == pytest.approx(
        time.time() - (obs_tracing.now_us() - start_us) / 1e6, abs=0.05)
    # the kernel's own account, in whole seconds of boot time
    with open("/proc/self/stat") as fh:
        ticks = int(fh.read().rpartition(")")[2].split()[19])
    with open("/proc/stat") as fh:
        btime = next(int(line.split()[1]) for line in fh
                     if line.startswith("btime"))
    assert gauge == pytest.approx(
        btime + ticks / os.sysconf("SC_CLK_TCK"), abs=1.5)
    assert "process_start_time_seconds " in \
        obs_metrics.REGISTRY.to_prometheus()


# ----------------------------------------------------------------------
# off, and once
# ----------------------------------------------------------------------
def test_telemetry_off_records_nothing_and_costs_upload_nothing(
        monkeypatch):
    from znicz_tpu.backends import XLADevice
    from znicz_tpu.memory import Vector

    root.common.engine.telemetry = False
    before = _phases()
    sent = obs_metrics.transfer_bytes("h2d").value
    mark = obs_tracing.TRACER.mark()
    wf = _toy_workflow("setup_off")
    wf.run()
    # no span is asked for, no counter looked up
    monkeypatch.setattr(obs_tracing.TRACER, "span", None)
    monkeypatch.setattr(obs_metrics, "setup_seconds", None)
    vec = Vector(np.zeros(8, np.float32), name="off_probe")
    vec.initialize(XLADevice())
    monkeypatch.undo()
    root.common.engine.telemetry = True
    assert obs_tracing.TRACER.mark() == mark
    assert _phases() == before
    assert obs_metrics.transfer_bytes("h2d").value == sent


def test_the_jax_listeners_are_registered_once():
    for i in range(3):
        _toy_workflow(f"listen_{i}")
    obs_tracing.watch_startup()
    from jax._src import monitoring
    assert monitoring.get_event_duration_listeners().count(
        obs_tracing._on_jax_duration) == 1
    assert monitoring.get_scalar_listeners().count(
        obs_tracing._on_jax_scalar) == 1
    # a jitted function traced inside another's trace: the outer
    # trace is the span
    inner = jax.jit(lambda x: x * 2.0)
    outer = jax.jit(lambda x: inner(x) + inner(x + 1.0))
    mark = obs_tracing.TRACER.mark()
    with obs_tracing.TRACER.span("probe") as span:
        outer(np.ones(3, np.float32))
    traced = [s for s in _spans(obs_tracing.TRACER, mark)
              if s["name"] == "jax:trace"]
    assert len(traced) == 1
    assert traced[0]["args"]["parent_span_id"] \
        == _spans(obs_tracing.TRACER, mark)[-1]["args"]["span_id"]
    assert span.self_us < span.dur_us


# ----------------------------------------------------------------------
# the ring
# ----------------------------------------------------------------------
def test_the_ring_counts_what_it_drops():
    tracer = SpanTracer(max_events=4)
    for i in range(4):
        tracer.instant(f"e{i}")
    assert tracer.dropped() == 0
    assert tracer.to_chrome_trace()["dropped"] == 0
    for i in range(3):
        with tracer.span(f"s{i}"):
            pass
    assert tracer.dropped() == 3 and len(tracer) == 4
    assert tracer.to_chrome_trace()["dropped"] == 3
    tracer.clear()
    assert tracer.dropped() == 3         # cleared is not dropped


def test_a_nested_retroactive_span_takes_the_open_span_as_parent():
    tracer = SpanTracer()
    with tracer.span("outer") as outer:
        t = obs_tracing.now_us()
        tracer.complete("stamped", t - 50.0, t, nested=True, who="jax")
        tracer.complete("epoch_like", t - 50.0, t)
        with tracer.span("inner"):
            pass
    tracer.complete("alone", 0.0, 1.0, nested=True)
    by_name = {s["name"]: s for s in _spans(tracer)}
    outer_id = by_name["outer"]["args"]["span_id"]
    stamped = by_name["stamped"]["args"]
    assert stamped["parent_span_id"] == outer_id
    assert stamped["depth"] == 1 and stamped["who"] == "jax"
    assert by_name["epoch_like"]["args"]["parent_span_id"] == 0
    assert by_name["alone"]["args"]["parent_span_id"] == 0
    assert outer.self_us == pytest.approx(
        outer.dur_us - 50.0 - by_name["inner"]["dur"], abs=1e-6)

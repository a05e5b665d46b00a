"""Process-local metrics registry: counters, gauges, histograms.

The reference framework's observability was live *introspection* —
plotters and the web status page read whatever attributes a workflow
happened to expose (``veles/web_status.py``).  This module is the
modern equivalent's measurement half: a thread-safe, process-local
registry of named metric families in the Prometheus data model

- **counter** — monotone accumulator (``znicz_xla_compiles_total``),
- **gauge** — set-to-current value (``znicz_serving_queue_rows``),
- **histogram** — fixed-bucket distribution with cumulative
  ``le``-bucket counts (``znicz_unit_run_seconds``),

each optionally split by a small, fixed set of labels.  Two
expositions: :meth:`MetricsRegistry.to_prometheus` (text format 0.0.4,
what ``WebStatusServer`` serves at ``/metrics``) and
:meth:`MetricsRegistry.to_json` (the machine-readable feed).

Design constraints, in order:

1. **Near-zero overhead when telemetry is off** — every hot-path
   instrumentation site checks :func:`enabled`
   (``root.common.engine.telemetry``, default on) before doing any
   work; a disabled gate costs one dict lookup.
2. **Thread safety** — the serving scheduler thread, the web-status
   handler threads and the training loop all touch the registry; one
   registry-level lock guards family creation and every child update
   (contention is negligible: host-side events are O(kHz)).
3. **Bounded cardinality** — labels are unit/bucket/direction-shaped
   (dozens of children), never per-request.

Canonical series used across the framework live here as helper
constructors (:func:`xla_compiles`, :func:`unit_run_seconds`, …) so
instrumentation sites and tests agree on names by construction.
"""

from __future__ import annotations

import bisect
import math
import threading
from collections import OrderedDict
from typing import Callable, Iterable

from znicz_tpu.utils.config import root


def enabled() -> bool:
    """The telemetry master gate: ``root.common.engine.telemetry``
    (default on).  Hot-path instrumentation (per-unit spans/timing,
    transfer byte counts) short-circuits on this; rare-event counters
    (compiles, snapshots) and the serving engine's own stats are
    always recorded — they are functional state, not overhead."""
    return bool(root.common.engine.get("telemetry", True))


#: default histogram bounds (seconds): log-ish ladder from 0.1 ms to
#: 30 s — covers unit fires, serve latencies and snapshot writes
DEFAULT_BUCKETS = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
    0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0)


def _fmt(value: float) -> str:
    """Prometheus sample-value formatting: integral floats print as
    integers, +Inf spelled the Prometheus way."""
    if value == math.inf:
        return "+Inf"
    if value == -math.inf:
        return "-Inf"
    f = float(value)
    if f.is_integer() and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


def _escape_label(value: str) -> str:
    return (str(value).replace("\\", r"\\").replace('"', r'\"')
            .replace("\n", r"\n"))


class Counter:
    """Monotone accumulator child."""

    __slots__ = ("_lock", "_value")

    def __init__(self, lock: threading.RLock) -> None:
        self._lock = lock
        self._value = 0.0

    def inc(self, amount: float = 1.0) -> None:
        if amount < 0:
            raise ValueError(f"counter increment must be >= 0, "
                             f"got {amount}")
        with self._lock:
            self._value += amount

    @property
    def value(self) -> float:
        return self._value


class Gauge:
    """Set-to-current child.  ``set_function`` turns it into a
    callback gauge read at collect time (live queue depths)."""

    __slots__ = ("_lock", "_value", "_fn")

    def __init__(self, lock: threading.RLock) -> None:
        self._lock = lock
        self._value = 0.0
        self._fn: Callable[[], float] | None = None

    def set(self, value: float) -> None:
        with self._lock:
            self._value = float(value)
            self._fn = None

    def inc(self, amount: float = 1.0) -> None:
        with self._lock:
            self._value += amount

    def dec(self, amount: float = 1.0) -> None:
        self.inc(-amount)

    def set_function(self, fn: Callable[[], float]) -> None:
        with self._lock:
            self._fn = fn

    @property
    def value(self) -> float:
        fn = self._fn
        if fn is not None:
            try:
                return float(fn())
            except Exception:  # noqa: BLE001 — a dead callback reads 0
                return 0.0
        return self._value


class Histogram:
    """Fixed-bucket distribution child with Prometheus ``le``
    semantics (cumulative counts of observations <= bound)."""

    __slots__ = ("_lock", "bounds", "counts", "sum", "count", "_max")

    def __init__(self, lock: threading.RLock,
                 bounds: tuple[float, ...]) -> None:
        self._lock = lock
        self.bounds = bounds
        self.counts = [0] * (len(bounds) + 1)  # +1: the +Inf bucket
        self.sum = 0.0
        self.count = 0
        self._max = -math.inf

    def observe(self, value: float) -> None:
        value = float(value)
        idx = bisect.bisect_left(self.bounds, value)
        with self._lock:
            self.counts[idx] += 1
            self.sum += value
            self.count += 1
            if value > self._max:
                self._max = value

    def percentile(self, q: float) -> float:
        """Bucket-interpolated percentile estimate (error bounded by
        the width of the bucket the true quantile lands in — the
        classic Prometheus ``histogram_quantile`` math)."""
        with self._lock:
            total = self.count
            if not total:
                return 0.0
            rank = q / 100.0 * total
            cum = 0
            for i, n in enumerate(self.counts):
                if not n:
                    continue
                lo_cum = cum
                cum += n
                if cum >= rank:
                    lo = self.bounds[i - 1] if i > 0 else 0.0
                    hi = (self.bounds[i] if i < len(self.bounds)
                          else max(self._max, lo))
                    frac = (rank - lo_cum) / n
                    return lo + (hi - lo) * frac
            return max(self._max, 0.0)


class MetricFamily:
    """One named metric + its labeled children."""

    KINDS = ("counter", "gauge", "histogram")
    _CHILD = {"counter": Counter, "gauge": Gauge, "histogram": Histogram}

    def __init__(self, name: str, kind: str, help_: str,
                 labelnames: tuple[str, ...],
                 lock: threading.RLock,
                 buckets: tuple[float, ...] = DEFAULT_BUCKETS) -> None:
        if kind not in self.KINDS:
            raise ValueError(f"unknown metric kind '{kind}'")
        self.name = name
        self.kind = kind
        self.help = help_
        self.labelnames = tuple(labelnames)
        self.buckets = tuple(sorted(buckets))
        self._lock = lock
        self._children: "OrderedDict[tuple, object]" = OrderedDict()

    def labels(self, **labelvalues):
        """The child for this label combination, created on first
        use.  Label names must match the family declaration exactly."""
        if tuple(sorted(labelvalues)) != tuple(sorted(self.labelnames)):
            raise ValueError(
                f"metric '{self.name}' declares labels "
                f"{self.labelnames}, got {tuple(sorted(labelvalues))}")
        key = tuple(str(labelvalues[n]) for n in self.labelnames)
        with self._lock:
            child = self._children.get(key)
            if child is None:
                if self.kind == "histogram":
                    child = Histogram(self._lock, self.buckets)
                else:
                    child = self._CHILD[self.kind](self._lock)
                self._children[key] = child
            return child

    # label-less convenience: the family IS its single child ---------
    def _solo(self):
        if self.labelnames:
            raise ValueError(
                f"metric '{self.name}' has labels {self.labelnames} — "
                f"address a child via .labels(...)")
        return self.labels()

    def inc(self, amount: float = 1.0) -> None:
        self._solo().inc(amount)

    def dec(self, amount: float = 1.0) -> None:
        self._solo().dec(amount)

    def set_function(self, fn: Callable[[], float]) -> None:
        self._solo().set_function(fn)

    def set(self, value: float) -> None:
        self._solo().set(value)

    def observe(self, value: float) -> None:
        self._solo().observe(value)

    @property
    def value(self) -> float:
        return self._solo().value

    def items(self) -> list[tuple[tuple, object]]:
        with self._lock:
            return list(self._children.items())


class MetricsRegistry:
    """Thread-safe, process-local registry of metric families."""

    def __init__(self) -> None:
        self._lock = threading.RLock()
        self._families: "OrderedDict[str, MetricFamily]" = OrderedDict()

    # ------------------------------------------------------------------
    # declaration (idempotent: re-declaring the same family returns it)
    # ------------------------------------------------------------------
    def _declare(self, name: str, kind: str, help_: str,
                 labels: Iterable[str],
                 buckets: tuple[float, ...] = DEFAULT_BUCKETS
                 ) -> MetricFamily:
        labels = tuple(labels)
        with self._lock:
            fam = self._families.get(name)
            if fam is not None:
                if fam.kind != kind or fam.labelnames != labels:
                    raise ValueError(
                        f"metric '{name}' already registered as "
                        f"{fam.kind}{fam.labelnames}, cannot re-declare "
                        f"as {kind}{labels}")
                return fam
            fam = MetricFamily(name, kind, help_, labels, self._lock,
                               buckets=buckets)
            self._families[name] = fam
            return fam

    def counter(self, name: str, help_: str = "",
                labels: Iterable[str] = ()) -> MetricFamily:
        return self._declare(name, "counter", help_, labels)

    def gauge(self, name: str, help_: str = "",
              labels: Iterable[str] = ()) -> MetricFamily:
        return self._declare(name, "gauge", help_, labels)

    def histogram(self, name: str, help_: str = "",
                  labels: Iterable[str] = (),
                  buckets: tuple[float, ...] = DEFAULT_BUCKETS
                  ) -> MetricFamily:
        return self._declare(name, "histogram", help_, labels,
                             buckets=buckets)

    def get(self, name: str) -> MetricFamily | None:
        with self._lock:
            return self._families.get(name)

    def clear(self) -> None:
        """Drop every family (tests)."""
        with self._lock:
            self._families.clear()

    # ------------------------------------------------------------------
    # exposition
    # ------------------------------------------------------------------
    def to_json(self) -> dict:
        out: dict = {}
        with self._lock:
            families = list(self._families.values())
        for fam in families:
            rows = []
            for key, child in fam.items():
                labels = dict(zip(fam.labelnames, key))
                if fam.kind == "histogram":
                    rows.append({
                        "labels": labels,
                        "buckets": {_fmt(b): c for b, c in zip(
                            fam.buckets + (math.inf,), child.counts)},
                        "sum": child.sum, "count": child.count})
                else:
                    rows.append({"labels": labels,
                                 "value": child.value})
            out[fam.name] = {"type": fam.kind, "help": fam.help,
                             "values": rows}
        return out

    def to_prometheus(self) -> str:
        """Text exposition format 0.0.4."""
        lines: list[str] = []
        with self._lock:
            families = list(self._families.values())
        for fam in families:
            if fam.help:
                lines.append(f"# HELP {fam.name} {fam.help}")
            lines.append(f"# TYPE {fam.name} {fam.kind}")
            for key, child in fam.items():
                pairs = [f'{n}="{_escape_label(v)}"'
                         for n, v in zip(fam.labelnames, key)]
                base = ",".join(pairs)
                if fam.kind == "histogram":
                    cum = 0
                    for bound, n in zip(fam.buckets + (math.inf,),
                                        child.counts):
                        cum += n
                        le = ([f'le="{_fmt(bound)}"'] if not base
                              else pairs + [f'le="{_fmt(bound)}"'])
                        lines.append(
                            f"{fam.name}_bucket{{{','.join(le)}}} {cum}")
                    suffix = f"{{{base}}}" if base else ""
                    lines.append(
                        f"{fam.name}_sum{suffix} {_fmt(child.sum)}")
                    lines.append(
                        f"{fam.name}_count{suffix} {child.count}")
                else:
                    suffix = f"{{{base}}}" if base else ""
                    lines.append(
                        f"{fam.name}{suffix} {_fmt(child.value)}")
        return "\n".join(lines) + ("\n" if lines else "")


#: the process-global registry every framework series registers on
REGISTRY = MetricsRegistry()


# ----------------------------------------------------------------------
# canonical framework series — single home for the names so
# instrumentation sites, the dryrun attestation and the tests agree
# ----------------------------------------------------------------------
def xla_compiles(site: str) -> Counter:
    """XLA trace+compile events: jit-region variants, scan chunks and
    serving AOT programs, labeled by site.  The steady-state retrace
    guard asserts this stays flat on warmed paths."""
    return REGISTRY.counter(
        "znicz_xla_compiles_total",
        "XLA program compiles (jit-region variants, scan chunks, "
        "serving AOT buckets)", labels=("site",)).labels(site=site)


def aot_cache_events(site: str, outcome: str) -> Counter:
    """Persisted-AOT-cache verdicts by compile site: ``hit`` (an
    executable deserialized instead of compiled — must NOT move
    :func:`xla_compiles`), ``miss`` (no entry; the site traced as it
    always did) and ``corrupt`` (digest/deserialize failure — entry
    quarantined, site fell back to tracing, paired with a
    ``recoveries{kind="aotcache_fallback"}`` increment).  The coldstart
    bench asserts ``hit>0`` with ``znicz_xla_compiles_total`` flat on
    its warm arm."""
    return REGISTRY.counter(
        "znicz_aot_cache_total",
        "Persisted AOT executable cache lookups by site and outcome "
        "(hit=deserialized, miss=traced, corrupt=quarantined+traced)",
        labels=("site", "outcome")).labels(site=site, outcome=outcome)


def aot_cache_bytes(cache: str = "local") -> Gauge:
    """Resident bytes of the persisted AOT executable store (payloads
    only; sidecars/metadata excluded).  Bounded by
    ``engine.aot_cache_bytes`` — the store evicts oldest-first past
    it."""
    return REGISTRY.gauge(
        "znicz_aot_cache_bytes",
        "Bytes of serialized executables resident in the AOT cache",
        labels=("cache",)).labels(cache=cache)


def unit_run_seconds(unit: str) -> Histogram:
    """Per-unit ``run()`` wall time (host control plane)."""
    return REGISTRY.histogram(
        "znicz_unit_run_seconds",
        "Unit.run wall time by unit name",
        labels=("unit",)).labels(unit=unit)


def transfer_bytes(direction: str) -> Counter:
    """Host<->device transfer volume through the Vector map/unmap
    protocol (``h2d`` uploads, ``d2h`` fetches)."""
    return REGISTRY.counter(
        "znicz_device_transfer_bytes_total",
        "Vector host<->device transfer bytes by direction",
        labels=("direction",)).labels(direction=direction)


def host_reads() -> Counter:
    """Blocking device→host reads (``Vector.map_read`` of a
    device-authoritative buffer): each makes the host wait for every
    step dispatched before it, whatever its size.  Over
    ``znicz_region_steps_total`` it is blocking reads per step: at
    ≥ 1 the driver waits for every step (raise
    ``engine.anomaly_check_interval``)."""
    return REGISTRY.counter(
        "znicz_host_reads_total",
        "Blocking device-to-host reads through Vector.map_read").labels()


def host_read_wait_seconds() -> Counter:
    """Seconds the host spent inside those reads — the sum of the
    ``host_read:<vector>`` spans' durations, mostly time waiting for
    the device to finish what was dispatched."""
    return REGISTRY.counter(
        "znicz_host_read_wait_seconds",
        "Seconds blocked in device-to-host reads (Vector.map_read)"
    ).labels()


def setup_seconds(phase: str) -> Counter:
    """Host seconds of the process's own start-up by phase, added to
    as each start-up span closes (``observe.tracing``): ``initialize``
    (the ``initialize:<workflow>`` / ``initialize:<unit>`` spans' SELF
    time, so the phases beside it are not in it), ``param_fill``
    (``param_fill``: one span per tensor around the whole draw, so the
    WALL time of the thread that asked and not the CPU time of the
    pool that draws a large tensor's chunks — ``utils.prng``),
    ``upload`` (``upload:<vector>``), ``trace``,
    ``lower``, ``backend_compile`` and ``cache_load`` (the ``jax:*``
    spans; a load from JAX's cache is inside a backend compile and in
    both).  It outlives the span ring, and it should stand still once
    every shape is warmed: growth in steady state is a recompile or a
    host write that reaches the device."""
    return REGISTRY.counter(
        "znicz_setup_seconds",
        "Host seconds of start-up work by phase (initialize, "
        "param_fill, upload, trace, lower, backend_compile, "
        "cache_load)", labels=("phase",)).labels(phase=phase)


def param_fill_bytes(path: str) -> Counter:
    """Bytes of random parameters drawn on the host
    (``RandomGenerator.fill_normal`` / ``fill_uniform``) by how:
    ``stream`` (a tensor of one chunk, from the generator's own
    stream) or ``chunked`` (a larger one, a seed per chunk, drawn by
    the pool of host threads).  The two shares say how much of a
    model's parameters took the pool."""
    return REGISTRY.counter(
        "znicz_param_fill_bytes_total",
        "Bytes of random parameters drawn on the host by path "
        "(stream, chunked)", labels=("path",)).labels(path=path)


def process_start_time_seconds() -> Gauge:
    """When the OS started this process, in Unix seconds (the
    conventional Prometheus name): ``time()`` less it is the process's
    age, and the zero the start-up spans count from."""
    return REGISTRY.gauge(
        "process_start_time_seconds",
        "Start time of the process since the Unix epoch in seconds"
    ).labels()


def input_wait_seconds(loader: str) -> Histogram:
    """Host time a training step spent BLOCKED on the input pipeline
    (prefetch miss, empty prefetch queue).  A fully hidden input plane
    keeps this ≈ 0 while :func:`input_stage_seconds` keeps accruing —
    the ratio of the two sums is the input-overlap attestation the
    dryrun and ``stream_bench`` report as ``input_hidden``."""
    return REGISTRY.histogram(
        "znicz_input_wait_seconds",
        "Step time blocked waiting for the input pipeline",
        labels=("loader",)).labels(loader=loader)


def input_stage_seconds(loader: str) -> Histogram:
    """Producer-side cost of one minibatch (shard read/decode +
    staging) — the work the prefetch must hide under the device
    step."""
    return REGISTRY.histogram(
        "znicz_input_stage_seconds",
        "Producer time to read+stage one minibatch",
        labels=("loader",)).labels(loader=loader)


def prefetch_depth(loader: str) -> Gauge:
    """Configured prefetch depth (in-flight device batches) of a
    streaming/double-buffered loader."""
    return REGISTRY.gauge(
        "znicz_prefetch_depth",
        "Loader prefetch depth (0 = synchronous input)",
        labels=("loader",)).labels(loader=loader)


def loader_prefetch(loader: str, event: str) -> Counter:
    """Loader prefetch lifecycle counters: ``hit`` (step served from
    an in-flight prefetch), ``miss`` (synchronous fallback),
    ``epoch_cross`` (prefetch legally spanned an epoch boundary via
    the counter-based shuffle — each one is a recovered stall)."""
    return REGISTRY.counter(
        "znicz_loader_prefetch_total",
        "Loader prefetch events (hit/miss/epoch_cross)",
        labels=("loader", "event")).labels(loader=loader, event=event)


def partition_rules(workflow: str) -> Gauge:
    """Size of a workflow's declarative partition-rule table (unit
    overrides + default tail) — the dryrun tail attests
    ``partition=rules, specs=N`` from this pair of gauges."""
    return REGISTRY.gauge(
        "znicz_partition_rules",
        "Partition-rule table size (overrides + default tail)",
        labels=("workflow",)).labels(workflow=workflow)


def partition_leaves(workflow: str) -> Gauge:
    """Vector leaves bound (resolved) through a workflow's partition
    table — every placed buffer the rule engine decided."""
    return REGISTRY.gauge(
        "znicz_partition_leaves",
        "Vector leaves resolved through the partition-rule table",
        labels=("workflow",)).labels(workflow=workflow)


def pipeline_stages(workflow: str) -> Gauge:
    """Pipeline-parallel stage count K the workflow's unit chain was
    split into (round 20) — 0/absent means unstaged execution."""
    return REGISTRY.gauge(
        "znicz_pipeline_stages",
        "Pipeline-parallel stages the forward/backward chain spans",
        labels=("workflow",)).labels(workflow=workflow)


def pipeline_bubble_seconds(workflow: str) -> Counter:
    """Cumulative pipeline bubble time: per optimizer step, the sum
    over stages of (schedule makespan − that stage's busy time).  With
    the 1F1B schedule the steady-state fraction is (K−1)/(M+K−1);
    divide by wall time to read the realized fraction from /metrics."""
    return REGISTRY.counter(
        "znicz_pipeline_bubble_seconds_total",
        "Stage idle (bubble) seconds summed over pipeline stages",
        labels=("workflow",)).labels(workflow=workflow)


def grad_accum_microbatches(workflow: str) -> Gauge:
    """Microbatches accumulated on device per optimizer step
    (``engine.grad_accum``; round 20) — 1 means fused batches."""
    return REGISTRY.gauge(
        "znicz_grad_accum_microbatches",
        "Gradient-accumulation microbatches per optimizer step",
        labels=("workflow",)).labels(workflow=workflow)


def flash_band(unit: str, stat: str) -> Gauge:
    """A windowed attention unit's band (``stat`` = ``window``: the
    positions a row sees; ``band_share``: Σ_r min(r + 1, window) ÷ T²,
    what the mask leaves of the square; ``executed_share``: sub-tiles
    the kernels run ÷ all — its excess over ``band_share`` is what the
    tiling computes and masks away).  Static per program, set once at
    ``initialize``; a layer without a window has no series."""
    return REGISTRY.gauge(
        "znicz_flash_band",
        "Window of a flash-attention layer, the share of T x T inside "
        "its band and the share its tiles execute",
        labels=("unit", "stat")).labels(unit=unit, stat=stat)


def flash_backward(unit: str, stat: str) -> Gauge:
    """The form of a flash-attention unit's backward, as its plan holds
    it (``pallas_attention.FlashPlan``; ``stat`` = ``passes``: 1 — ONE
    ``znicz_flash_bwd`` / ``_bwd_win`` call, a score sub-tile computed
    once — or 2, ``znicz_flash_dq`` + ``znicz_flash_dkv``,
    ``pallas_attention.backward_passes``; ``resident_dq_bytes``: the f32
    VMEM in which a one-pass backward keeps its unfinished dq tiles
    from K tile to K tile, what the call asks for beyond a call's own —
    0 where the K side is one tile and under two passes).  Static per
    program, set once at ``initialize`` by every unit whose core the
    kernels run."""
    return REGISTRY.gauge(
        "znicz_flash_backward",
        "Passes over the score tiles in a flash-attention layer's "
        "backward and the bytes of dq it keeps resident in VMEM",
        labels=("unit", "stat")).labels(unit=unit, stat=stat)


def moe_expert_tokens(unit: str, stat: str) -> Gauge:
    """Rows (token, expert) pairs an expert of a ``MoE`` unit computed
    per step, over the last epoch: ``stat`` = ``max`` / ``min`` (the
    fullest / emptiest expert of a step, averaged over the steps) or
    ``mean`` (N·k ÷ E).  max ÷ mean is the load imbalance a dropless
    layer pays in its grouped matmul's longest group.  Fed from
    totals the unit keeps on the device, read once per epoch."""
    return REGISTRY.gauge(
        "znicz_moe_expert_tokens",
        "Rows per expert and step of a dropless MoE layer over the "
        "last epoch (max, mean, min)",
        labels=("unit", "stat")).labels(unit=unit, stat=stat)


def moe_held(unit: str, stat: str) -> Gauge:
    """One chip's share of an expert-parallel ``MoE`` layer, per step
    over the last epoch (``stat`` = ``held``: experts whose weights
    live here; ``of``: experts the router chooses among; ``rows_here``:
    (token, expert) pairs routed to the held experts, which this chip
    computes; ``rows_routed``: all N·k pairs; ``capacity``: rows the
    step's buffers hold at most, ``fit``: rows they hold in a step
    whose pairs here fit that many; ``rows_over``: pairs beyond the
    capacity, which poison the step so that the guard refuses it;
    ``fit_steps`` of the epoch's ``steps``: the steps that ran at the
    fit size — a count, not a mean: the others ran the whole capacity
    and made their forward again in the backward).  A layer that
    holds every expert has no series.  Fed from totals the unit keeps
    on the device, read once per epoch."""
    return REGISTRY.gauge(
        "znicz_moe_held",
        "Experts held on this chip, experts routed over, and rows "
        "routed here and in all per step, of a MoE layer",
        labels=("unit", "stat")).labels(unit=unit, stat=stat)


def moe_gmm_rows(unit: str, stat: str) -> Gauge:
    """What the grids of a ``MoE`` layer's grouped-matmul kernels did,
    per call and step over the last epoch (``stat`` = ``visited``: the
    rows their visits cover, Σ over the non-empty groups of the row
    tiles a group touches × the tile — a tile that straddles a group
    boundary is visited once per group; ``real``: the rows routed).
    ``visited`` ÷ ``real`` is the kernels' overwork, 1 when every group
    ends on a tile edge.  No series on the XLA path (``ragged_dot``).
    Fed from totals the unit keeps on the device, read once per
    epoch."""
    return REGISTRY.gauge(
        "znicz_moe_gmm_rows",
        "Rows the grouped-matmul kernels' visits cover and rows that "
        "are real, per call and step, of a MoE layer",
        labels=("unit", "stat")).labels(unit=unit, stat=stat)


def moe_guard_sum(unit: str, stat: str) -> Gauge:
    """Where the anomaly guard's Σ g² of a ``MoE`` layer's gradients
    came from (``stat`` = ``from_kernel``: of the layer's parameter
    tensors, those whose sum ``znicz_tgmm`` made beside the gradient
    and the update read instead of the tensor — 3, the expert slabs,
    where the kernels engage and the gradient reaches the update as
    the kernel wrote it; 0 on the ``ragged_dot`` path, under
    accumulation, with no guard linked).  Static per program, set when
    the layer's backward is traced."""
    return REGISTRY.gauge(
        "znicz_moe_guard_sum",
        "Parameter tensors of a MoE layer whose gradient's sum of "
        "squares, which the anomaly guard reads, came from the kernel "
        "that made the gradient",
        labels=("unit", "stat")).labels(unit=unit, stat=stat)


def moe_slab_copy(unit: str, stat: str) -> Gauge:
    """A ``MoE`` layer's expert slabs in the matmuls' dtype, kept as
    leaves the update writes (``stat`` = ``slabs``: slabs of the layer
    whose grouped matmuls read that leaf in the program last traced,
    so that no step casts them — 3, or 0 where the matmuls run in the
    slabs' own dtype; ``refreshed``: times a copy was made again from
    a slab the HOST wrote, since ``initialize`` — a restored snapshot,
    a seeded flip, any ``map_write``; 0 while a run only trains)."""
    return REGISTRY.gauge(
        "znicz_moe_slab_copy",
        "Expert slabs of a MoE layer read from their copy in the "
        "matmuls' dtype, and how often the host's writes had it made "
        "again",
        labels=("unit", "stat")).labels(unit=unit, stat=stat)


def delta_scan(unit: str, stat: str) -> Gauge:
    """A ``GatedDeltaNet`` unit's chunked state scan (``stat`` =
    ``chunk``: positions per chunk; ``chunks``: ⌈T / chunk⌉, the length
    of the sequential walk; ``key_dim`` / ``value_dim``: the state's
    d_k × d_v; ``padded_share``: elements of the 128-lane tiles a
    d_k × d_v product occupies in the kernels ÷ d_k · d_v — 1.0 when
    nothing is padded or no kernel runs, 1.78 at 96 × 192;
    ``state_mb``: MB of per-chunk states kept for the backward;
    ``path``: 1 the ``znicz_delta_state_*`` kernels, 0 the plain
    scan; ``chunk_path``: 1 the ``znicz_gdr_chunk_*`` kernels for what
    is local to a chunk — Γ, the triangular inverse, W, U, K̂, Qc, P —
    0 ``jax.numpy`` under autodiff; ``prep_path``: 1 the
    ``znicz_qkv_prep_fwd`` / ``_bwd`` kernels from the q ‖ k ‖ v
    projection where it lies to head-major q, k, v — the taps, the
    SiLU, the L2 norms; the kernels path AND heads whose d_k and d_v
    are whole 128-lane tiles —, 0 ``jax.numpy`` and a move;
    ``exact_products`` / ``mask_products``: what the chunk kernels'
    bodies multiply a chunk, forward + backward
    (``pallas_delta.chunk_products``) — products of two real f32
    factors at the highest precision, six bf16 passes each, and
    products with a 0 / ±1 matrix built from indices, the f32 factor's
    three bf16 parts in one contraction — 0 without the kernels;
    ``decay_channels``: decays a head,
    1 or d_k — with d_k the kernels are ``znicz_kda_chunk_*`` /
    ``znicz_kda_state_*``; ``sub_block``: positions whose decays are
    exponentiated against one reference point, the chunk itself under
    a decay per head).  Static per program, set once at
    ``initialize``."""
    return REGISTRY.gauge(
        "znicz_delta_scan",
        "Chunked state scan of a gated-delta-rule layer: chunk length, "
        "chunks walked, state size, the kernels' tile padding, MB of "
        "states kept for the backward, kernels (1) or plain scan (0) "
        "for the walk, for what is local to a chunk and for the taps, "
        "SiLU and norms before them",
        labels=("unit", "stat")).labels(unit=unit, stat=stat)


def short_conv(unit: str, stat: str) -> Gauge:
    """The form a ``ShortConv`` unit's chain took (``stat`` = ``path``:
    1 the ``znicz_short_conv_fwd`` / ``_bwd`` kernels from the
    projection where it lies to W_out's input, 0 both gates and the
    taps in ``jax.numpy``; ``taps``: J; ``channels``: D).  Static per
    program, set once at ``initialize``."""
    return REGISTRY.gauge(
        "znicz_short_conv",
        "Gated short-convolution mixer: kernels (1) or jax.numpy (0) "
        "for the chain between its projections, taps, channels",
        labels=("unit", "stat")).labels(unit=unit, stat=stat)


def stream_maps(unit: str, stat: str) -> Gauge:
    """What the maps of a residual path of n streams did
    (``ops/streams.py``; ``unit`` is the ``StreamOpen`` unit, which
    keeps the totals of every READ after it; ``stat`` = ``row_gap`` /
    ``col_gap``: the worst |row sum − 1| and |column sum − 1| of H_res
    over the last epoch's tokens and sublayers — how near the doubly
    stochastic matrices Sinkhorn's iterations came; ``clamped``: the
    entries of M⁰'s logits the clamp touched, per step; ``sublayers``:
    the READ / WRITE pairs; ``streams``: n).  Fed from totals kept on
    the device, read once per epoch."""
    return REGISTRY.gauge(
        "znicz_stream_maps",
        "Sinkhorn's worst row and column gap of H_res, clamped logits "
        "per step, sublayers and streams of a residual path of n streams",
        labels=("unit", "stat")).labels(unit=unit, stat=stat)


def moe_router(unit: str, stat: str) -> Gauge:
    """The choice of a ``MoE`` unit with ``select_bias`` (``stat`` =
    ``groups_kept``: groups a token's top k are taken among, 0 without
    a group limit — static; ``bias_abs_max``: the largest |b_e| of the
    selection bias; ``bias_steps``: steps in which the step program's
    own rule moved b — it is gated by the anomaly guard — since the
    start).  The last two are read with the unit's epoch-end reads."""
    return REGISTRY.gauge(
        "znicz_moe_router",
        "Group limit, selection-bias extreme and the steps its rule "
        "ran, of a MoE layer that chooses by score + bias",
        labels=("unit", "stat")).labels(unit=unit, stat=stat)


def moe_hidden(unit: str, stat: str) -> Gauge:
    """What a ``MoE`` unit of ReLU experts (``act="relu"``) found in
    its hidden, ``relu(W_gate m) ⊙ W_up m`` over the rows of the experts
    it holds, counted on the device beside ``moe_stats`` and summed over
    the steps since the last epoch-end read (``stat`` = ``live``:
    elements that are not zero; ``total``: elements there are — of a
    held share, over the steps that ran at the fit size).  ``live`` /
    ``total`` ≈ 0.5 at initialisation: the half a ReLU leaves is the
    work a sparse down-projection would skip, which the layer counts
    and does not exploit."""
    return REGISTRY.gauge(
        "znicz_moe_hidden",
        "Non-zero and all elements of a ReLU expert layer's hidden "
        "since the last epoch-end read",
        labels=("unit", "stat")).labels(unit=unit, stat=stat)


def moe_combine(unit: str, form: str) -> Gauge:
    """How a ``MoE`` layer's rows go back to their tokens (``form`` =
    ``gather``: by gathers in both directions — every dropless layer,
    and a held share whose N · k pairs are at most ``HELD_GATHER``
    times its fit size; ``scatter``: a held share below that, by a
    gather and a scatter-add over its buffer's rows): 1 for the form
    the layer's programs hold, 0 for the other.  Set once at
    ``initialize``."""
    return REGISTRY.gauge(
        "znicz_moe_combine",
        "Whether a MoE layer's rows go back to their tokens by gathers "
        "or by a scatter-add",
        labels=("unit", "form")).labels(unit=unit, form=form)


def attention_latent(unit: str, stat: str) -> Gauge:
    """The static sizes of a latent-K/V attention unit
    (``MultiHeadAttention`` with ``kv_latent``; ``stat`` = ``latent``:
    the compressed K/V's width; ``qk_nope`` / ``qk_rope``: a key's
    per-head part and the rotary part all heads share; ``v``: a
    value's width; ``q_latent``: the query latent's, where there is
    one; ``backward_passes``, where the two-width kernels run: 1 —
    ``znicz_flash_bwd_mla``, a score tile computed once — or 2,
    ``pallas_mla.backward_passes``).  Set once at ``initialize``.  Where such a layer's
    device time goes is no counter's: its matmuls outside the kernels
    and the element-wise passes around them are the phases ``project``
    and ``rotate_norm`` of ``observe.op_scopes()`` (``observe/scopes.py``)."""
    return REGISTRY.gauge(
        "znicz_attention_latent",
        "Latent width, per-head and shared rotary key widths and value "
        "width of a latent-K/V attention layer",
        labels=("unit", "stat")).labels(unit=unit, stat=stat)


def loop(group: str, stat: str) -> Gauge:
    """A looped span of the layer table (``znicz_tpu.pass_span``;
    ``stat`` = ``passes``: R; ``layers``: its members; ``applications``:
    R × members, what one step should apply — the three static, set at
    ``initialize``; ``applications_per_step``: member applications the
    step program ran per step over the last epoch, counted on the
    device and read once per epoch)."""
    return REGISTRY.gauge(
        "znicz_loop",
        "Passes, layers and member applications per step of a looped "
        "span of the layer table",
        labels=("group", "stat")).labels(group=group, stat=stat)


def loop_exit(unit: str, exit_index: int, stat: str) -> Gauge:
    """One exit of a ``All2AllExits`` head over the last epoch (``stat`` =
    ``loss``: mean cross-entropy of that exit's prediction; ``mass``:
    mean exit probability q_r; exit ``entropy``'s ``value``: mean
    entropy of the exit distribution).  Fed from totals the evaluator
    keeps on the device, read once per epoch."""
    return REGISTRY.gauge(
        "znicz_loop_exit",
        "Mean cross-entropy and exit mass per exit, and mean entropy of "
        "the exit distribution, of a multi-exit head over the last epoch",
        labels=("unit", "exit", "stat")).labels(
            unit=unit, exit=str(exit_index), stat=stat)


def moe_aux_loss(unit: str, kind: str) -> Gauge:
    """A ``MoE`` unit's auxiliary router losses, mean per step over
    the last epoch, unweighted (``kind`` = ``load_balance``: top_k
    under uniform routing; ``z``: mean squared log-partition)."""
    return REGISTRY.gauge(
        "znicz_moe_aux_loss",
        "Auxiliary router losses of a MoE layer, mean per step over "
        "the last epoch (load_balance, z)",
        labels=("unit", "kind")).labels(unit=unit, kind=kind)


def snapshot_seconds(op: str) -> Histogram:
    return REGISTRY.histogram(
        "znicz_snapshot_seconds",
        "Snapshot state-tree save/load duration",
        labels=("op",)).labels(op=op)


def epochs_total(workflow: str) -> Counter:
    return REGISTRY.counter(
        "znicz_epochs_total", "Training epochs completed",
        labels=("workflow",)).labels(workflow=workflow)


def region_steps(region: str) -> Counter:
    return REGISTRY.counter(
        "znicz_region_steps_total",
        "Jit-region device steps dispatched (scan chunks count each "
        "inner step)", labels=("region",)).labels(region=region)


def backend_info(backend: str, platform: str) -> Gauge:
    return REGISTRY.gauge(
        "znicz_backend_info",
        "Active device backend (value is always 1; read the labels)",
        labels=("backend", "platform")).labels(
            backend=backend, platform=platform)


def serving_requests(engine: str, event: str) -> Counter:
    return REGISTRY.counter(
        "znicz_serving_requests_total",
        "Serving requests by lifecycle event "
        "(submitted/served/rejected)",
        labels=("engine", "event")).labels(engine=engine, event=event)


def serving_latency_seconds(engine: str) -> Histogram:
    return REGISTRY.histogram(
        "znicz_serving_latency_seconds",
        "Serving enqueue->reply latency",
        labels=("engine",)).labels(engine=engine)


def serving_queue_rows(engine: str) -> Gauge:
    return REGISTRY.gauge(
        "znicz_serving_queue_rows",
        "Rows pending in the continuous batcher's bounded queue",
        labels=("engine",)).labels(engine=engine)


def serving_bucket_batches(engine: str, bucket: int) -> Counter:
    return REGISTRY.counter(
        "znicz_serving_bucket_batches_total",
        "Coalesced batches dispatched per bucket size",
        labels=("engine", "bucket")).labels(engine=engine,
                                            bucket=bucket)


def serving_bucket_rows(engine: str, bucket: int) -> Counter:
    return REGISTRY.counter(
        "znicz_serving_bucket_rows_total",
        "Real (non-padded) rows served per bucket size",
        labels=("engine", "bucket")).labels(engine=engine,
                                            bucket=bucket)


def serving_ttft_seconds(engine: str) -> Histogram:
    """Time-to-first-token: prompt submit → first generated token
    available (queue wait + prefill + first sample).  The interactive
    half of decode latency — kept as its OWN canonical series beside
    :func:`serving_token_seconds` because the two move independently
    (admission policy moves TTFT, cache locality moves per-token)."""
    return REGISTRY.histogram(
        "znicz_serving_ttft_seconds",
        "Decode time-to-first-token (submit -> first token)",
        labels=("engine",)).labels(engine=engine)


def serving_token_seconds(engine: str) -> Histogram:
    """Per-token decode latency: one observation per generated token
    after the first (the steady-state token cadence a streaming client
    sees)."""
    return REGISTRY.histogram(
        "znicz_serving_token_seconds",
        "Decode per-token latency (inter-token cadence after the "
        "first token)", labels=("engine",)).labels(engine=engine)


def serving_tokens(engine: str, kind: str) -> Counter:
    """Token throughput counters: ``prompt`` (prefilled positions)
    vs ``generated`` (sampled tokens) — tokens/s on a dashboard is
    ``rate(generated)``."""
    return REGISTRY.counter(
        "znicz_serving_tokens_total",
        "Decode tokens by kind (prompt=prefilled, generated=sampled)",
        labels=("engine", "kind")).labels(engine=engine, kind=kind)


def serving_decode_slots(engine: str) -> Gauge:
    """Live decode slots (sequences mid-generation) — occupancy of
    the preallocated KV-cache pages."""
    return REGISTRY.gauge(
        "znicz_serving_decode_slots",
        "Sequences currently occupying KV-cache decode slots",
        labels=("engine",)).labels(engine=engine)


def kv_pages_total(engine: str) -> Gauge:
    """Pages in the decode engine's paged KV pool (fixed at start —
    the token-capacity bound: ``pages × kv_page_tokens`` tokens)."""
    return REGISTRY.gauge(
        "znicz_kv_pages_total",
        "KV-cache pages in the paged decode pool",
        labels=("engine",)).labels(engine=engine)


def kv_pages_used(engine: str) -> Gauge:
    """Pages currently held by live sequences or the prefix cache —
    the page-table occupancy series ROADMAP item 3 names; a live
    callback gauge, so /metrics always reads the current pool state."""
    return REGISTRY.gauge(
        "znicz_kv_pages_used",
        "KV-cache pages held by live sequences + the prefix cache",
        labels=("engine",)).labels(engine=engine)


def kv_bytes_per_lane(engine: str) -> Gauge:
    """KV-cache bytes reserved per decode lane (pool bytes —
    including the per-block scale pools of int8 pages — over
    ``max_slots``).  Cache bytes bound decode concurrency, so this is
    the direct denominator of the round-21 quantization lanes win."""
    return REGISTRY.gauge(
        "znicz_kv_bytes_per_lane",
        "KV-cache bytes reserved per decode lane",
        labels=("engine",)).labels(engine=engine)


def kv_page_migrations(engine: str, direction: str) -> Counter:
    """KV pages moved between tiers/pools (round 22): ``spill`` (HBM
    → host-DRAM tier, a cold prefix block demoted under pool
    pressure), ``restore`` (host → HBM through the staging ring — a
    spilled block matched again), ``handoff`` (prefill pool → decode
    pool, one count per page carried by a prefill→decode transfer).
    Spill traffic trending up at a flat hit rate means the working
    set outgrew HBM and the tier is absorbing it — the intended
    shape; restores outpacing spills means thrash (tier too small)."""
    return REGISTRY.counter(
        "znicz_kv_page_migrations_total",
        "KV pages moved between cache tiers / serving pools",
        labels=("engine", "direction")).labels(engine=engine,
                                               direction=direction)


def kv_spill_pages(engine: str) -> Gauge:
    """Host-DRAM tier occupancy (live callback gauge): KV pages
    currently spilled out of the HBM pool.  With
    ``znicz_kv_pages_used`` this is the two-tier residency picture —
    total cached prefix capacity is the sum."""
    return REGISTRY.gauge(
        "znicz_kv_spill_pages",
        "KV pages resident in the host-DRAM spill tier",
        labels=("engine",)).labels(engine=engine)


def prefix_cache_events(engine: str, event: str) -> Counter:
    """Prefix-sharing admissions: ``hit`` (≥1 full block of the
    prompt reused from the radix cache), ``miss`` (prefilled from
    scratch), ``evicted`` (a cached block released under pool
    pressure).  Hit *tokens* ride ``znicz_prefix_tokens_total``."""
    return REGISTRY.counter(
        "znicz_prefix_cache_total",
        "Prefix-cache admission events (hit/miss/evicted)",
        labels=("engine", "event")).labels(engine=engine, event=event)


def prefix_tokens(engine: str, kind: str) -> Counter:
    """Prompt tokens by prefix-cache outcome: ``shared`` positions
    skipped prefill entirely (their K/V pages were reused),
    ``computed`` positions paid the prefill forward."""
    return REGISTRY.counter(
        "znicz_prefix_tokens_total",
        "Prompt tokens by prefix-cache outcome (shared/computed)",
        labels=("engine", "kind")).labels(engine=engine, kind=kind)


def spec_tokens(engine: str, verdict: str) -> Counter:
    """Speculative-decoding drafter proposals by verifier verdict
    (``accepted`` / ``rejected``) — acceptance rate is
    ``accepted / (accepted + rejected)``."""
    return REGISTRY.counter(
        "znicz_spec_tokens_total",
        "Drafted tokens by verification verdict (accepted/rejected)",
        labels=("engine", "verdict")).labels(engine=engine,
                                             verdict=verdict)


def swap_pause_seconds(engine: str) -> Counter:
    """Cumulative wall time decode admission was paused for swap
    drains.  TTFT deadline clocks stamp from admission-ELIGIBLE time
    (submit time + any overlapping pause), so this series is the
    audit trail for what the serving SLO histograms exclude."""
    return REGISTRY.counter(
        "znicz_swap_pause_seconds_total",
        "Decode admission pause time accumulated by swap drains",
        labels=("engine",)).labels(engine=engine)


def serving_warmup_seconds(engine: str) -> Gauge:
    return REGISTRY.gauge(
        "znicz_serving_warmup_seconds",
        "Wall time spent AOT-compiling the bucket ladder at start()",
        labels=("engine",)).labels(engine=engine)


# ----------------------------------------------------------------------
# resilience series (round 11): every fault, skip, retry, quarantine,
# rollback and breaker transition is a scrapeable counter so the chaos
# dryrun attests recovery from the same /metrics feed Prometheus reads
# ----------------------------------------------------------------------
def faults_injected(site: str) -> Counter:
    """Deterministic fault-injection events by named site (one event
    per transient firing; a persistent fault counts once)."""
    return REGISTRY.counter(
        "znicz_faults_injected_total",
        "Injected fault events by site (resilience.faults)",
        labels=("site",)).labels(site=site)


def recoveries(kind: str) -> Counter:
    """Recovery events: the system absorbed a fault and kept going
    (anomaly_step, rollback, shard_retry, shard_quarantine,
    reader_restart, serving_retry, snapshot_write,
    snapshot_fallback)."""
    return REGISTRY.counter(
        "znicz_recoveries_total",
        "Faults absorbed without failing the run, by recovery kind",
        labels=("kind",)).labels(kind=kind)


def step_anomalies(workflow: str, kind: str) -> Counter:
    """Training steps whose loss (kind=loss) or gradients (kind=grad)
    went non-finite; the guard skipped their optimizer update."""
    return REGISTRY.counter(
        "znicz_step_anomalies_total",
        "Non-finite training steps by kind (update skipped)",
        labels=("workflow", "kind")).labels(workflow=workflow, kind=kind)


def anomaly_rollbacks(workflow: str) -> Counter:
    return REGISTRY.counter(
        "znicz_anomaly_rollbacks_total",
        "Rollbacks to the last good snapshot after K consecutive "
        "anomalous steps", labels=("workflow",)).labels(workflow=workflow)


def loader_read_retries(loader: str) -> Counter:
    return REGISTRY.counter(
        "znicz_loader_read_retries_total",
        "Shard read attempts that failed and were retried",
        labels=("loader",)).labels(loader=loader)


def loader_shards_quarantined(loader: str) -> Counter:
    return REGISTRY.counter(
        "znicz_loader_shards_quarantined_total",
        "Shards quarantined after exhausting read retries (their rows "
        "deliver zeros for the rest of the run)",
        labels=("loader",)).labels(loader=loader)


def loader_pipeline_restarts(loader: str) -> Counter:
    return REGISTRY.counter(
        "znicz_loader_pipeline_restarts_total",
        "Streaming pipelines rebuilt after a producer/uploader thread "
        "died", labels=("loader",)).labels(loader=loader)


def snapshot_failures(op: str) -> Counter:
    return REGISTRY.counter(
        "znicz_snapshot_failures_total",
        "Snapshot operations that failed and were absorbed "
        "(op=write: training continued on the last good snapshot; "
        "op=load: a corrupt file fell back to an older snapshot)",
        labels=("op",)).labels(op=op)


def serving_breaker_state(engine: str) -> Gauge:
    """0 = closed (healthy), 1 = half-open (probing), 2 = open
    (shedding load with fast Overloaded replies)."""
    return REGISTRY.gauge(
        "znicz_serving_breaker_state",
        "Circuit-breaker state (0 closed, 1 half-open, 2 open)",
        labels=("engine",)).labels(engine=engine)


def serving_breaker_transitions(engine: str, to: str) -> Counter:
    return REGISTRY.counter(
        "znicz_serving_breaker_transitions_total",
        "Circuit-breaker state transitions by target state",
        labels=("engine", "to")).labels(engine=engine, to=to)


def serving_queue_age_seconds(engine: str, pool: str = "all") -> Gauge:
    """Age of the oldest pending request (live callback gauge) — the
    breaker's stall signal, a /readyz input, and the autoscalers'
    scale-up trigger.  ``pool`` (round 22) splits the series for
    disaggregated serving: ``prefill`` and ``decode`` queues age
    independently (a prompt burst must scale the prefill pool without
    touching decode residency), while monolithic engines keep the
    single ``all`` child."""
    return REGISTRY.gauge(
        "znicz_serving_queue_age_seconds",
        "Age of the oldest request pending in the serving queue",
        labels=("engine", "pool")).labels(engine=engine, pool=pool)


def last_step_timestamp(workflow: str) -> Gauge:
    """Unix time of the last completed training step — /readyz turns
    this into last-step staleness for external supervisors."""
    return REGISTRY.gauge(
        "znicz_last_step_timestamp_seconds",
        "Unix timestamp of the workflow's last completed step",
        labels=("workflow",)).labels(workflow=workflow)


# ----------------------------------------------------------------------
# continuous-learning series (round 13): the train-to-serve handoff —
# every publish, swap verdict and live model version is a scrapeable
# series so the soak harness and the chaos dryrun attest the
# publish→verify→canary→promote→rollback pipeline from /metrics
# ----------------------------------------------------------------------
def swaps_total(engine: str, outcome: str) -> Counter:
    """Weight hot-swap verdicts per serving engine: ``promoted`` (the
    candidate went live), ``rejected`` (the canary gate refused it —
    the incumbent kept serving), ``rolled_back`` (a promoted model
    tripped probation and the prior version was restored)."""
    return REGISTRY.counter(
        "znicz_swaps_total",
        "Weight hot-swap outcomes (promoted/rejected/rolled_back)",
        labels=("engine", "outcome")).labels(engine=engine,
                                             outcome=outcome)


def quant_canary(engine: str, outcome: str) -> Counter:
    """Canary verdicts for QUANTIZED candidates only (round 21):
    ``promoted`` / ``rejected`` / ``rolled_back``, a sub-ledger of
    ``znicz_swaps_total`` — the int8 publisher arm's health is a
    separate question from ordinary weight refreshes (a mis-scaled
    calibration must show up here as ``rejected``)."""
    return REGISTRY.counter(
        "znicz_quant_canary_total",
        "Canary outcomes for int8-quantized swap candidates",
        labels=("engine", "outcome")).labels(engine=engine,
                                             outcome=outcome)


def model_version(engine: str) -> Gauge:
    """The monotonic published-model version an engine is currently
    serving (0 = the bundle it started from, before any promote)."""
    return REGISTRY.gauge(
        "znicz_model_version",
        "Published model version currently live on the engine",
        labels=("engine",)).labels(engine=engine)


def swap_duration_seconds(engine: str) -> Histogram:
    """End-to-end hot-swap duration: stage (host→device upload of the
    candidate weights, off the dispatch path) + drain (decode engines
    let old-model generations finish) + the atomic publish flip."""
    return REGISTRY.histogram(
        "znicz_swap_duration_seconds",
        "Weight hot-swap duration (stage + drain + atomic flip)",
        labels=("engine",)).labels(engine=engine)


def snapshot_age_seconds(source: str) -> Gauge:
    """Seconds since ``source`` (a Snapshotter prefix or a publisher
    directory) last wrote a GOOD artifact — a live callback gauge, so
    /readyz sees a stalled trainer as staleness without any writer
    heartbeat code (threshold: ``engine.ready_max_snapshot_age_s``)."""
    return REGISTRY.gauge(
        "znicz_snapshot_age_seconds",
        "Time since the last good snapshot/publish by source",
        labels=("source",)).labels(source=source)


# ----------------------------------------------------------------------
# population series (round 14): K-replica evolution as a mesh workload —
# per-member fitness, generation and exploit/explore progress are
# scrapeable so the population dryrun and pop_bench attest the engine
# from the same /metrics feed as everything else
# ----------------------------------------------------------------------
def population_members(engine: str) -> Gauge:
    """Members (stacked model replicas) in the population run."""
    return REGISTRY.gauge(
        "znicz_population_members",
        "Model replicas trained by the population engine",
        labels=("engine",)).labels(engine=engine)


def population_fitness(engine: str, member: int) -> Gauge:
    """Per-member fitness (higher is better; classification runs
    report ``-validation_err_pt``), updated at every epoch boundary."""
    return REGISTRY.gauge(
        "znicz_population_fitness",
        "Per-member population fitness (latest epoch; higher=better)",
        labels=("engine", "member")).labels(engine=engine,
                                            member=member)


def population_best_fitness(engine: str) -> Gauge:
    """Best fitness any member has reached so far in the run — the
    single number the dryrun tail and dashboards read."""
    return REGISTRY.gauge(
        "znicz_population_best_fitness",
        "Best member fitness seen so far in the population run",
        labels=("engine",)).labels(engine=engine)


def population_generations(engine: str) -> Counter:
    return REGISTRY.counter(
        "znicz_population_generations_total",
        "Evolution generations applied to the stacked population",
        labels=("engine",)).labels(engine=engine)


def population_evolution(engine: str, op: str) -> Counter:
    """Evolution-op counters: ``exploit`` (a truncated member copied a
    winner's weights+hypers), ``explore`` (its hypers were perturbed),
    ``crossover`` (a slot was refilled by arithmetic weight blending),
    ``mutate`` (its hypers were mutated)."""
    return REGISTRY.counter(
        "znicz_population_evolution_total",
        "Population evolution ops (exploit/explore/crossover/mutate)",
        labels=("engine", "op")).labels(engine=engine, op=op)


def publishes_total(source: str) -> Counter:
    """Snapshot bundles published for serving pickup (the training
    side of the handoff; the watcher's digest verdicts ride
    ``znicz_snapshot_failures_total{op=publish}``)."""
    return REGISTRY.counter(
        "znicz_publishes_total",
        "Model bundles published to the serving handoff directory",
        labels=("source",)).labels(source=source)


# ----------------------------------------------------------------------
# round 16: multi-tenant fleet series — the isolation proof is read
# from exactly these (the bench and the dryrun attest per-tenant p99,
# shed attribution and replica counts from a live /metrics scrape)
# ----------------------------------------------------------------------
def fleet_requests(fleet: str, tenant: str, event: str) -> Counter:
    """Per-tenant request lifecycle on one fleet: ``submitted``,
    ``served``, ``shed`` (rate-limit/preemption/breaker), ``expired``
    (deadline), ``failed``.  ``shed`` attribution per tenant is the
    overload proof: under a low-priority flood ONLY the flooding
    tenant's child moves."""
    return REGISTRY.counter(
        "znicz_fleet_requests_total",
        "Fleet requests by tenant and lifecycle event",
        labels=("fleet", "tenant", "event")).labels(
        fleet=fleet, tenant=tenant, event=event)


def fleet_latency_seconds(fleet: str, tenant: str) -> Histogram:
    """Per-tenant SLO-latency distribution: submit→reply for one-shot
    scoring, submit→first-token (TTFT) for generation — the
    scheduling-bound metric in both cases (a generation's completion
    time is proportional to the tokens requested; its cadence rides
    ``znicz_serving_token_seconds``)."""
    return REGISTRY.histogram(
        "znicz_fleet_latency_seconds",
        "Fleet SLO latency by tenant (reply for one-shot, TTFT for "
        "generation)",
        labels=("fleet", "tenant")).labels(fleet=fleet, tenant=tenant)


def fleet_latency_p99_seconds(fleet: str, tenant: str) -> Gauge:
    """Exact windowed per-tenant p99 exported as a summary-style
    gauge (callback over the fleet's sliding window) — the SLO bound
    the isolation attestation reads from the scrape, immune to
    histogram-bucket interpolation error."""
    return REGISTRY.gauge(
        "znicz_fleet_latency_p99_seconds",
        "Exact windowed p99 fleet latency by tenant",
        labels=("fleet", "tenant")).labels(fleet=fleet, tenant=tenant)


def fleet_breaker_state(fleet: str, tenant: str) -> Gauge:
    """Per-TENANT circuit breaker (0=closed, 1=half-open, 2=open):
    one tenant's breaker opening sheds only that tenant."""
    return REGISTRY.gauge(
        "znicz_fleet_breaker_state",
        "Per-tenant fleet breaker state (0 closed, 1 half-open, "
        "2 open)",
        labels=("fleet", "tenant")).labels(fleet=fleet, tenant=tenant)


def fleet_tenant_tokens(fleet: str, tenant: str) -> Gauge:
    """Live token-bucket level per tenant (callback gauge)."""
    return REGISTRY.gauge(
        "znicz_fleet_tenant_tokens",
        "Fleet admission token-bucket level by tenant",
        labels=("fleet", "tenant")).labels(fleet=fleet, tenant=tenant)


def fleet_models(fleet: str) -> Gauge:
    """Resident models on one fleet (the dryrun tail's ``fleet=N
    models``)."""
    return REGISTRY.gauge(
        "znicz_fleet_models",
        "Models resident in the fleet",
        labels=("fleet",)).labels(fleet=fleet)


def quantized_models(fleet: str) -> Gauge:
    """Resident models serving from int8-quantized bundles (round
    21) — with ``znicz_fleet_models`` this is the fleet's quantization
    rollout fraction, the residency dividend of halved weight
    bytes."""
    return REGISTRY.gauge(
        "znicz_quantized_models",
        "Resident fleet models serving int8-quantized bundles",
        labels=("fleet",)).labels(fleet=fleet)


def fleet_replicas(fleet: str, model: str) -> Gauge:
    """Live replica count per model (the autoscaler moves this; a
    ``fleet.replica_loss`` injection dips it until repair)."""
    return REGISTRY.gauge(
        "znicz_fleet_replicas",
        "Live serving replicas per fleet model",
        labels=("fleet", "model")).labels(fleet=fleet, model=model)


def fleet_scale_events(fleet: str, model: str, op: str) -> Counter:
    """Autoscaler verdicts per model: ``up``, ``down``, ``repair``
    (replica-loss respawn)."""
    return REGISTRY.counter(
        "znicz_fleet_scale_events_total",
        "Fleet autoscaler scale events per model",
        labels=("fleet", "model", "op")).labels(
        fleet=fleet, model=model, op=op)


def fleet_traffic_weight(fleet: str, model: str, version: str) -> Gauge:
    """Configured A/B traffic fraction per model version (weighted
    routing generalizing the round-13 two-version canary)."""
    return REGISTRY.gauge(
        "znicz_fleet_traffic_weight",
        "Configured traffic fraction per fleet model version",
        labels=("fleet", "model", "version")).labels(
        fleet=fleet, model=model, version=version)


def fleet_ladder_evictions(fleet: str, model: str) -> Counter:
    """Bucket programs dropped by the SHARED ladder budget under
    memory pressure — pressure lands on the lowest-priority model's
    ladder first."""
    return REGISTRY.counter(
        "znicz_fleet_ladder_evictions_total",
        "Bucket programs evicted by the shared fleet ladder budget",
        labels=("fleet", "model")).labels(fleet=fleet, model=model)


# ----------------------------------------------------------------------
# round 19: silent-data-corruption sentinel — fingerprint votes,
# redundant-compute audits and quarantine verdicts are scrapeable so
# the sdc dryrun attests detection from the same /metrics feed
# ----------------------------------------------------------------------
def sdc_votes(workflow: str, verdict: str) -> Counter:
    """Cross-replica fingerprint votes by verdict: ``clean`` (every
    process's post-update param fingerprint agreed) vs ``divergent``
    (at least one chip/host computed different params — the silent-
    data-corruption signature none of the isfinite/digest layers can
    see)."""
    return REGISTRY.counter(
        "znicz_sdc_votes_total",
        "Cross-replica fingerprint votes (clean/divergent)",
        labels=("workflow", "verdict")).labels(workflow=workflow,
                                               verdict=verdict)


def sdc_audits(workflow: str, verdict: str) -> Counter:
    """Redundant-compute audits by verdict: the last microbatch's step
    replayed on the shadow oracle either ``match``ed the device's
    post-update fingerprints or caught a ``mismatch``."""
    return REGISTRY.counter(
        "znicz_sdc_audits_total",
        "Redundant-compute shadow audits (match/mismatch)",
        labels=("workflow", "verdict")).labels(workflow=workflow,
                                               verdict=verdict)


def sdc_detected(kind: str) -> Counter:
    """Confirmed silent-data-corruption detections by detector:
    ``vote`` (cross-replica fingerprint compare), ``audit``
    (redundant-compute replay), ``serving`` (sampled shadow re-score
    of live replies)."""
    return REGISTRY.counter(
        "znicz_sdc_detected_total",
        "Confirmed SDC detections by detector (vote/audit/serving)",
        labels=("kind",)).labels(kind=kind)


def sdc_suspects(process, device: str) -> Counter:
    """SDC suspicion events attributed to a process/device pair —
    ``device`` is ``-`` for host-level attributions (training votes /
    audits) or the serving replica id for shadow-audit catches."""
    return REGISTRY.counter(
        "znicz_sdc_suspect_total",
        "SDC suspicion events by process and device/replica",
        labels=("process", "device")).labels(process=process,
                                             device=device)


def sdc_quarantined(kind: str) -> Counter:
    """Corrupt compute units removed from service: ``host`` (elastic
    gang restarted without the culprit, blocklisted) or ``replica``
    (serving replica removed via the ReplicaGroup repair path)."""
    return REGISTRY.counter(
        "znicz_sdc_quarantined_total",
        "Corrupt hosts/replicas quarantined after confirmed SDC",
        labels=("kind",)).labels(kind=kind)


def loader_rows_quarantined(loader: str) -> Counter:
    """Minibatch rows served as ZEROS because their shard is
    quarantined — the silent-data-loss that used to be invisible:
    ``_gather_retry`` kept the run alive but nothing counted the
    zero-filled rows.  Report-only on /readyz."""
    return REGISTRY.counter(
        "znicz_loader_rows_quarantined_total",
        "Rows zero-filled from quarantined shards (silent data loss, "
        "now loud)", labels=("loader",)).labels(loader=loader)


#: the currently-live build_info child's label key (previous children
#: are zeroed when richer info arrives, so scrapes read the ==1 row)
_build_info_live: tuple | None = None


def set_build_info(*, platform: str = "?", mesh: str = "?",
                   processes: str = "?", fallback: bool = False) -> None:
    """Register/refresh the ``znicz_build_info`` gauge: package
    version, jax version, platform, mesh shape and process count as
    labels, value 1 — fleet debugging can tell which build a scrape
    came from.  Called from device creation (full info) and from
    ``WebStatusServer`` (``fallback=True`` — registers only when
    nothing richer did, so supervisor-only processes export it too).
    Richer info supersedes: the previous child is zeroed so exactly
    one row reads 1."""
    global _build_info_live
    if fallback and _build_info_live is not None:
        return
    import jax

    import znicz_tpu
    fam = REGISTRY.gauge(
        "znicz_build_info",
        "Build identity (value 1; read the labels): package version, "
        "jax version, platform, mesh shape, process count",
        labels=("version", "jax", "platform", "mesh", "processes"))
    key = {"version": znicz_tpu.__version__, "jax": jax.__version__,
           "platform": str(platform), "mesh": str(mesh),
           "processes": str(processes)}
    key_t = tuple(sorted(key.items()))
    if _build_info_live == key_t:
        return
    if _build_info_live is not None:
        fam.labels(**dict(_build_info_live)).set(0)
    fam.labels(**key).set(1)
    _build_info_live = key_t


# -- elastic multi-host supervision (round 18) -------------------------
def heartbeat_age_seconds(process) -> Gauge:
    """Seconds since process ``process`` last beat into the heartbeat
    channel (callback gauge fed by the coordinator-side
    ``HeartbeatMonitor`` — /metrics and /readyz read peer liveness
    from the same series).  ``inf`` renders as ``+Inf`` when a peer
    has never beaten."""
    return REGISTRY.gauge(
        "znicz_heartbeat_age_seconds",
        "Seconds since each process's last heartbeat",
        labels=("process",)).labels(process=process)


def host_losses(kind: str) -> Counter:
    """Processes the elastic supervisor declared gone, by kind:
    ``loss`` (died / heartbeat stale), ``stall`` (wall-clock beats
    flow, step counter frozen — hung collective), ``preempt``
    (checkpoint-on-signal drain + EXIT_PREEMPTED), ``sdc`` (round 19:
    a confirmed silent-data-corruption culprit exited EXIT_SDC and is
    blocklisted — the restart resumes from the PRE-divergence
    snapshot, not the newest one)."""
    return REGISTRY.counter(
        "znicz_host_losses_total",
        "Hosts lost to the elastic supervisor by kind",
        labels=("kind",)).labels(kind=kind)


def elastic_restarts() -> Counter:
    """Gang relaunches onto the surviving host set (each one implies a
    reshard-resume from the newest digest-verified snapshot)."""
    return REGISTRY.counter(
        "znicz_elastic_restarts_total",
        "Elastic gang restarts onto the surviving mesh")._solo()


def checkpoint_on_signal() -> Counter:
    """Barriered preemption checkpoints completed (worker-side; the
    gang supervisor folds worker heartbeat attestations into its own
    registry under the same name)."""
    return REGISTRY.counter(
        "znicz_checkpoint_on_signal_total",
        "Preemption-triggered barriered checkpoints")._solo()


# ----------------------------------------------------------------------
# round 24: correlated observability — exact windowed percentiles as
# canonical gauges (the number SERVE_BENCH rows print and /metrics
# exports must be the SAME number), flight-recorder health, and the
# federated gang-level series the supervisor/fleet scrape loops write
# ----------------------------------------------------------------------
def _percentile(sorted_vals: list, q: float) -> float:
    """Nearest-rank percentile of an already-sorted list."""
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1,
              max(0, int(round(q / 100.0 * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


def window_p99(win, n0: int = 0) -> float:
    """p99 of a latency window's tail, skipping the first ``n0``
    samples.

    The per-pass slice the serve bench and the dryruns use to compare
    warmed passes: snapshot ``len(win)`` before a pass, then take the
    p99 of only the observations that pass appended, so cold-start and
    earlier-pass samples never pollute the comparison.  ``win`` is any
    iterable of latencies (typically an engine's bounded phase deque).
    Promoted here (round 24) from ``serving.engine`` so the bench-side
    helper and the :func:`phase_p99_seconds` callback gauges are one
    implementation."""
    tail = sorted(list(win)[n0:])
    return _percentile(tail, 99.0)


def phase_p99_seconds(engine: str, phase: str) -> Gauge:
    """Exact windowed p99 of one serving phase (``queue`` /
    ``prefill`` / ``handoff`` / ``decode`` / ``ttft`` / ``token``) as
    a live callback gauge over the engine's bounded phase window —
    the per-phase decomposition of tail latency the disagg split is
    justified by, readable from ONE scrape instead of a bench
    stopwatch."""
    return REGISTRY.gauge(
        "znicz_phase_p99_seconds",
        "Exact windowed p99 latency per serving phase",
        labels=("engine", "phase")).labels(engine=engine, phase=phase)


def trace_requests(engine: str, outcome: str) -> Counter:
    """Request traces closed per engine by outcome (``ok`` / ``shed``
    / ``expired`` / ``failed``) — the denominator for /trace.json
    request-tree sampling (the span ring is bounded; this counter is
    not)."""
    return REGISTRY.counter(
        "znicz_trace_requests_total",
        "Request-scoped traces finished, by outcome",
        labels=("engine", "outcome")).labels(engine=engine,
                                             outcome=outcome)


def flightrecord_events(kind: str) -> Counter:
    """Ops events journaled by the flight recorder, by kind (swap,
    canary, breaker, restart, quarantine, autoscale, ...)."""
    return REGISTRY.counter(
        "znicz_flightrecord_events_total",
        "Flight-recorder events journaled, by kind",
        labels=("kind",)).labels(kind=kind)


def flightrecord_dropped() -> Counter:
    """Flight-recorder events DROPPED because the journal write
    stalled or failed (disk full, torn device, injected
    ``observe.recorder_stall``) — telemetry degrades to counting
    here and never blocks a dispatch or a swap."""
    return REGISTRY.counter(
        "znicz_flightrecord_dropped_total",
        "Flight-recorder events dropped on journal write "
        "stall/failure")._solo()


def fed_sources(gang: str) -> Gauge:
    """Child sources (worker /metrics endpoints, in-process child
    registries, heartbeat channels) a federator folds per scrape."""
    return REGISTRY.gauge(
        "znicz_fed_sources",
        "Sources folded into the federated gang-level scrape",
        labels=("gang",)).labels(gang=gang)


def fed_scrape_age_seconds(gang: str, source: str) -> Gauge:
    """Seconds since ``source`` was last folded successfully (live
    callback gauge) — the federated view's staleness bound: a child
    whose exporter died shows up HERE, not as silently frozen
    numbers."""
    return REGISTRY.gauge(
        "znicz_fed_scrape_age_seconds",
        "Staleness of each federated source's last successful fold",
        labels=("gang", "source")).labels(gang=gang, source=source)


def fed_queue_age_seconds(gang: str, process: str, pool: str) -> Gauge:
    """Federated copy of each child's oldest-pending-request age,
    labeled by process AND pool — one scrape answers 'which pool is
    backed up on which host'."""
    return REGISTRY.gauge(
        "znicz_fed_queue_age_seconds",
        "Federated per-child serving queue age by process and pool",
        labels=("gang", "process", "pool")).labels(
        gang=gang, process=process, pool=pool)


def fed_requests(gang: str, process: str, event: str) -> Gauge:
    """Federated snapshot of each child's request lifecycle counters
    (summed over that child's engines) — a gauge, not a counter: the
    federator republishes the child's last-seen totals."""
    return REGISTRY.gauge(
        "znicz_fed_requests",
        "Federated per-child serving request totals by event",
        labels=("gang", "process", "event")).labels(
        gang=gang, process=process, event=event)


def fed_heartbeat_age_seconds(gang: str, process: str) -> Gauge:
    """Federated heartbeat staleness per gang member (fed from the
    supervisor's heartbeat channel fold)."""
    return REGISTRY.gauge(
        "znicz_fed_heartbeat_age_seconds",
        "Federated seconds since each gang member's last heartbeat",
        labels=("gang", "process")).labels(gang=gang, process=process)


def fed_step(gang: str, process: str) -> Gauge:
    """Federated per-member step counter — 'which host is slow' read
    straight off the spread of this family's children."""
    return REGISTRY.gauge(
        "znicz_fed_step",
        "Federated per-member training/serving step counter",
        labels=("gang", "process")).labels(gang=gang, process=process)

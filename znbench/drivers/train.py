"""Training cells: a ``StandardWorkflow`` built from the
configuration's layer table, driven through the calls a user's driver
makes — ``wf.run_chunked(k)`` (k scanned steps per dispatch) or
``wf.run()`` (one dispatch per step) — one epoch at a time.

The ``Decision`` is re-armed from outside between epochs (its epoch
budget is a plain attribute), so the loop body is the program's own:
loader bookkeeping, the fused step, the evaluator's accumulators, the
``Decision`` with its epoch-end read and guard tick.  A segment is a
fixed number of epochs ended by ``block_until_ready`` on a leaf the
step writes; ``throughput`` is the median segment rate per chip.

``correct``, decided after the window: the layer outputs that the
window's LAST step left on the units — so the program the window ran,
the scanned chunk where the cell scans — must agree with the plain
reference on the first rows of that step's minibatch (the system's own
dropout masks given to the reference); every epoch's loss finite, the
loss moved, no program built in the window.
"""

from __future__ import annotations

import time

import numpy as np

from znbench.harness import discovery
from znbench.harness.program import (engine_options,
                                     head_rows, host,
                                     layer_table, make_device)
from znbench.harness.result import Outcome, median


def make_data(config: dict, traffic: dict, n: int, seed: int) -> tuple:
    """``n`` seeded samples and labels, and the loader's keywords."""
    spec = config["input"]
    if spec["kind"] == "image_uint8":
        from znicz_tpu import datasets
        x, y = datasets.synthetic_imagenet(
            n, size=spec["shape"][0], n_classes=spec["classes"],
            seed=seed)
        return x, y, {"normalization_scale": spec["scale"],
                      "normalization_bias": spec["bias"]}
    if spec["kind"] == "tokens":
        rng = np.random.default_rng(seed)
        x = rng.integers(0, spec["vocab"],
                         size=(n, int(traffic["seq_len"])))
        y = rng.integers(0, spec["vocab"], size=n).astype(np.int32)
        # token ids ride the loader's float minibatch path
        return x.astype(np.float32), y, {}
    raise discovery.BenchmarkError(f"no input kind {spec['kind']!r}")


def sample_shape(config: dict, traffic: dict) -> tuple:
    spec = config["input"]
    return tuple(spec["shape"]) if spec["kind"] == "image_uint8" \
        else (int(traffic["seq_len"]),)


def build(ctx, layers: list):
    from znicz_tpu.loader.fullbatch import ArrayLoader
    from znicz_tpu.models.standard_workflow import StandardWorkflow
    from znicz_tpu.utils import prng
    from znicz_tpu.utils.config import root

    config, traffic = ctx.cell.config, ctx.cell.traffic
    batch = int(traffic["batch_per_chip"]) * ctx.cell.chips
    n = int(traffic["steps_per_epoch"]) * batch
    root.common.precision_type = config["precision"]["precision_type"]
    prng.seed_all(ctx.seed)
    ctx.mark("imports done")
    x, y, loader_kw = make_data(config, traffic, n, ctx.seed)
    ctx.mark(f"data made ({x.nbytes / 1e9:.2f} GB on the host)")
    wf = StandardWorkflow(
        name=config["workflow"]["name"],
        loader_factory=lambda w: ArrayLoader(
            w, train_data=x, train_labels=y, minibatch_size=batch,
            **loader_kw),
        layers=layers, decision_config={"max_epochs": 1})
    wf._max_fires = 10 ** 9
    with engine_options(config["precision"].get("engine", {})):
        wf.initialize(device=make_device(ctx))
    ctx.mark("workflow initialized")
    return wf, batch


class Trainer:
    """One epoch at a time through the user's driver."""

    def __init__(self, ctx, wf) -> None:
        self.ctx, self.wf = ctx, wf
        traffic = ctx.cell.traffic
        self.chunk = int(traffic["steps_per_dispatch"])
        self.steps_per_epoch = int(traffic["steps_per_epoch"])
        self.epochs = 0
        self.losses: list[float] = []

    def epoch(self) -> None:
        from znicz_tpu.loader.base import TRAIN
        decision = self.wf.decision
        self.epochs += 1
        decision.max_epochs = self.epochs      # re-arm: one more epoch
        decision.complete.value = False
        with self.ctx.span("znbench.epoch"):
            if self.chunk > 1:
                self.wf.run_chunked(self.chunk)
            else:
                self.wf.run()
        loss = decision.epoch_loss[TRAIN]
        self.losses.append(float("nan") if loss is None else loss)

    def fence(self) -> None:
        with self.ctx.span("znbench.fence"):
            self.wf.forwards[-1].weights.devmem.block_until_ready()


def params_of_last_step(wf) -> dict:
    """The parameters the last step ran its forward pass with.  They
    can be read only between dispatches, and a step updates them after
    its forward pass; under the program's update rule
    (``GradientDescentBase``: acc = moment·acc − lr·g, W += acc) the
    value before the update is W − acc, both read after the step."""
    params = {}
    for i, (unit, gd) in enumerate(zip(wf.forwards, wf.gds)):
        for attr in unit.EXPORT_PARAMS:
            vec = getattr(unit, attr)
            if not vec:
                continue
            acc = getattr(gd, f"accumulated_gradient_{attr}", None)
            if not acc:
                raise discovery.BenchmarkError(
                    f"layer {i} {attr}: no momentum accumulator to "
                    f"step back through")
            params[f"layer{i}_{attr}"] = host(vec) - host(acc)
    return params


def check_forward(ctx, wf, layers: list) -> tuple[float, list]:
    """The layer outputs the window's last step left on the units,
    compared layer by layer with the plain reference on the first rows
    of that step's minibatch: no further dispatch, no other program
    than the one the window ran.  Returns the worst relative error and
    a line per layer."""
    config = ctx.cell.config
    reference = discovery.load_module("reference", config["reference"])
    n = int(config["reference_rows"])
    x = head_rows(wf.loader.minibatch_data, n)
    masks = {i: head_rows(unit.mask, n)
             for i, unit in enumerate(wf.forwards)
             if layers[i]["type"] == "dropout" and unit.mask}
    expected = reference.forward(params_of_last_step(wf), layers, x,
                                 masks)
    worst, lines = 0.0, []
    for i, (unit, want) in enumerate(zip(wf.forwards, expected)):
        got = head_rows(unit.output, n).reshape(want.shape)
        err = float(np.abs(got - want).max()
                    / (np.abs(want).max() + 1e-12))
        worst = max(worst, err)
        lines.append(f"{i}:{layers[i]['type']}={err:.1e}")
    return worst, lines


def run(ctx) -> Outcome:
    """The traffic mix's own ``engine`` options (how often the driver
    reads the guard's state back, …) hold for the whole run, set the
    way a user sets them."""
    with engine_options(ctx.cell.traffic.get("engine", {})):
        return measure(ctx)


def measure(ctx) -> Outcome:
    config, traffic = ctx.cell.config, ctx.cell.traffic
    layers = layer_table(config)
    wf, batch = build(ctx, layers)
    trainer = Trainer(ctx, wf)
    per_segment = int(traffic["epochs_per_segment"])
    for _ in range(int(traffic.get("warmup_epochs", 2))):
        trainer.epoch()
    trainer.fence()
    ctx.mark("warmed up")
    warm = len(trainer.losses)
    seconds = ctx.seconds
    min_segments = int(traffic.get("min_segments", 10))
    if ctx.trace:                 # a short window of its own
        seconds = min(seconds, float(traffic.get("trace_seconds", 6)))
        min_segments = 2
    steps_per_segment = per_segment * trainer.steps_per_epoch
    per_item = int(traffic.get("seq_len", 1)) \
        if traffic["item"] == "tokens" else 1
    items_per_segment = steps_per_segment * batch * per_item

    ctx.open_window()
    durations = []
    while ctx.elapsed() < seconds or len(durations) < min_segments:
        t0 = time.perf_counter()
        with ctx.span("znbench.segment"):
            for _ in range(per_segment):
                trainer.epoch()
            trainer.fence()
        durations.append(time.perf_counter() - t0)
        if ctx.elapsed() > 4 * seconds + 60:
            break                 # never hang a check on a slow cell
    ctx.close_window()
    dispatches = sum(
        1 for s in ctx.program_spans()
        if s["name"].startswith("chunk:")
        or (s["cat"] == "unit"
            and s["args"].get("kind") == "RegionUnit"))

    losses = trainer.losses[warm:]
    steps = len(durations) * steps_per_segment
    bad_segments = sum(
        1 for i in range(len(durations))
        if not np.isfinite(losses[i * per_segment:
                                  (i + 1) * per_segment]).all())
    skipped = int(ctx.counters.get("znicz_step_anomalies_total", 0))
    failed = min(steps, bad_segments * steps_per_segment + skipped)
    built = int(ctx.counters["jax_programs"]
                + ctx.counters["znicz_xla_compiles_total"])
    notes = [f"engine options of the traffic mix: "
             f"{traffic.get('engine', {})}",
             f"segments={len(durations)} steps={steps} "
             f"loss {trainer.losses[0]:.4f}→{trainer.losses[-1]:.4f} "
             f"segment_s median={median(durations):.4f} "
             f"min={min(durations):.4f} max={max(durations):.4f}"]
    problems = []
    if len(durations) < min_segments:
        problems.append(f"only {len(durations)} segments")
    if len(set(trainer.losses)) < 2:
        problems.append("the loss did not move")
    if built:
        problems.append(f"{built} programs built in the window")
    worst, lines = check_forward(ctx, wf, layers)
    tolerance = float(config["reference_tolerance"])
    notes.append(f"reference: worst layer error {worst:.2e} "
                 f"(tolerance {tolerance:g}) {' '.join(lines)}")
    if not worst <= tolerance:
        problems.append(f"forward differs from the reference by "
                        f"{worst:.3g}")
    notes += [f"NOT CORRECT: {p}" for p in problems]
    rates = [items_per_segment / d / ctx.cell.chips for d in durations]
    return Outcome(
        correct=not problems and failed == 0,
        attempted=steps, failed=failed,
        end_to_end={"throughput": median(rates)},
        observations={
            "steps": steps, "segments": len(durations),
            "items": len(durations) * items_per_segment,
            "dispatches": dispatches, "batch": batch,
            "batch_per_chip": int(traffic["batch_per_chip"]),
            "layers": layers,
            "sample_shape": sample_shape(config, traffic),
            "model_dim": config.get("widths", {}).get("d_model"),
        },
        notes=notes)

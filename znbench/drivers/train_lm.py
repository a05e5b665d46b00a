"""Language-model training cells with a loss at every position: a
``StandardWorkflow`` built from the configuration's layer table and
driven through ``wf.run()`` one epoch at a time, as ``drivers/train.py``
drives its cells — the ``Trainer``, the stepping back to the last
step's parameters and the options of the traffic mix are that file's,
loaded by name.  What differs, and why this is a file of its own:

- the data: ``T + 1`` token ids are drawn per sequence, so that EVERY
  position has a next-token label — (B, T) labels, not one per
  sequence;
- the plain reference runs on the HOST's CPU device, layer by layer:
  a billion parameters with their momentum fill the chip, and a second
  copy of them beside it would not load;
- an expert layer's choice of experts is handed to the reference (as
  dropout masks are in ``train.py``): where the eighth and ninth
  largest of 64 probabilities nearly tie, bf16 matmul inputs upstream
  may order them the other way, and the comparison must not explode on
  that.  The router is held to the reference separately and SHARPLY,
  on the system's own input to it: its logits (computed in f32 from
  f32) within ``router_logits`` of the reference's spread, and every
  chosen expert in the reference's top k or trailing its k-th by at
  most ``router_gap`` of that spread.

``correct``, decided after the window: every layer's output of the
window's LAST step agrees with the reference on the first sequence of
that step's minibatch (the embedding exactly, the rest within
``layers``); the router holds as above; every epoch's loss finite, the
loss moved, no program built in the window, no step skipped.  Beside
each limit the log gives what the nearest lower precision would read —
a bf16 embedding table, a bf16 router — which has to come out as not
correct.
"""

from __future__ import annotations

import time

import numpy as np

from znbench.harness import discovery
from znbench.harness.program import (engine_options, head_rows,
                                     layer_table, make_device)
from znbench.harness.result import Outcome, median

train = discovery.load_module("drivers", "train")


def make_data(config: dict, traffic: dict, n: int, seed: int) -> tuple:
    """``n`` seeded sequences of T ids and their T next-token labels."""
    t = int(traffic["seq_len"])
    ids = np.random.default_rng(seed).integers(
        0, config["input"]["vocab"], size=(n, t + 1))
    # token ids ride the loader's float minibatch path
    return ids[:, :-1].astype(np.float32), ids[:, 1:].astype(np.int32)


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
    x, y = make_data(config, traffic, n, ctx.seed)
    wf = StandardWorkflow(
        name=config["workflow"]["name"],
        loader_factory=lambda w: ArrayLoader(
            w, train_data=x, train_labels=y, minibatch_size=batch),
        layers=layers, decision_config={"max_epochs": 1})
    wf._max_fires = 10 ** 9
    with engine_options(config["precision"].get("engine", {})):
        wf.initialize(device=make_device(ctx))
    stats = ctx.devices[0].memory_stats() or {}
    ctx.mark(f"workflow initialized: parameters drawn and uploaded, "
             f"the device holds "
             f"{stats.get('bytes_in_use', 0) / 1e9:.2f} of "
             f"{stats.get('bytes_limit', 0) / 1e9:.2f} GB")
    return wf, batch


def host_device():
    """The host's CPU device for the reference: the chip is full."""
    import jax
    return jax.devices("cpu")[0]


def relative(got, want) -> float:
    want = np.asarray(want, np.float32)
    return float(np.abs(np.asarray(got, np.float32).reshape(want.shape)
                        - want).max() / (np.abs(want).max() + 1e-12))


def bf16(a) -> np.ndarray:
    import jax.numpy as jnp
    return np.asarray(jnp.asarray(a, jnp.bfloat16).astype(jnp.float32))


def check_router(reference, params: dict, layers: list, wf, i: int,
                 n: int) -> dict:
    """Layer ``i``'s router against the reference ON THE SYSTEM'S OWN
    INPUT to it (the previous unit's output, first ``n`` sequences):
    the worst logit error and the worst trailing of a chosen expert
    behind the reference's k-th, both as a share of the spread of the
    reference's logits; and what a bf16 router would read."""
    unit, spec = wf.forwards[i], layers[i]["->"]
    x = head_rows(unit.input, n)
    gain = params.get(f"layer{i}_gain_norm")
    m = x.reshape(-1, x.shape[-1])
    if spec.get("pre_norm"):
        m = np.asarray(reference.rms_norm(
            m, gain, float(spec.get("norm_eps", 1e-5))))
    want = np.asarray(reference.route(m, params, i)[0])
    spread = float(want.max() - want.min()) + 1e-12
    got = head_rows(unit.router_logits, n).reshape(want.shape)
    chosen = head_rows(unit.last_choice, n).reshape(
        want.shape[0], -1).astype(np.int64)
    kth = np.sort(want, axis=-1)[:, -chosen.shape[1]][:, None]
    gap = np.maximum(kth - np.take_along_axis(want, chosen, axis=-1), 0)
    coarse = bf16(m) @ bf16(params[f"layer{i}_weights"])
    return {"logits": float(np.abs(got - want).max()) / spread,
            "gap": float(gap.max()) / spread,
            "bf16_router": float(np.abs(coarse - want).max()) / spread,
            "chosen": chosen}


def check(ctx, wf, layers: list) -> tuple[list, list]:
    """Problems and log lines of the comparison with the reference."""
    import jax
    config = ctx.cell.config
    reference = discovery.load_module("reference", config["reference"])
    limits = config["reference_tolerance"]
    n = int(config["reference_rows"])
    problems, notes = [], []
    params = train.params_of_last_step(wf)
    ctx.mark(f"parameters of the last step on the host "
             f"({sum(p.nbytes for p in params.values()) / 1e9:.2f} GB)")
    x = head_rows(wf.loader.minibatch_data, n)
    # the reference is eager jax.numpy: a few dozen small host
    # programs, which the window is over for and which are not worth
    # a file each in the compile cache (run.py keeps EVERY program)
    jax.config.update("jax_enable_compilation_cache", False)
    with jax.default_device(host_device()):
        routing, worst = {}, {"logits": 0.0, "gap": 0.0}
        coarse_router = []
        for i, layer in enumerate(layers):
            if layer["type"] != "moe":
                continue
            seen = check_router(reference, params, layers, wf, i, n)
            routing[i] = seen["chosen"]
            coarse_router.append(seen["bf16_router"])
            for key in worst:
                worst[key] = max(worst[key], seen[key])
        ctx.mark("router checked on its own input")
        expected = reference.forward(params, layers, x, routing)
    ctx.mark("reference computed on the host's CPU device")
    lines, worst_layer = [], 0.0
    for i, (unit, want) in enumerate(zip(wf.forwards, expected)):
        err = relative(head_rows(unit.output, n), want)
        kind = layers[i]["type"]
        lines.append(f"{i}:{kind}={err:.1e}")
        if kind == "embedding":
            coarse = relative(bf16(want), want)
            notes.append(f"reference: the embedding's rows differ by "
                         f"{err:.1e} (limit {limits['embedding']:g}; a "
                         f"bf16 table would read {coarse:.1e}: not "
                         f"correct)")
            if not err <= limits["embedding"]:
                problems.append(f"embedding differs by {err:.3g}")
        else:
            worst_layer = max(worst_layer, err)
    notes.append(f"reference: worst layer error {worst_layer:.2e} "
                 f"(limit {limits['layers']:g}) {' '.join(lines)}")
    if not worst_layer <= limits["layers"]:
        problems.append(f"forward differs from the reference by "
                        f"{worst_layer:.3g}")
    if routing:
        notes.append(
            f"reference: router logits differ by {worst['logits']:.1e} "
            f"of their spread (limit {limits['router_logits']:g}; a "
            f"bf16 router would read {min(coarse_router):.1e}: not "
            f"correct); a chosen expert trails the reference's k-th by "
            f"at most {worst['gap']:.1e} (limit "
            f"{limits['router_gap']:g})")
        if not worst["logits"] <= limits["router_logits"]:
            problems.append(f"router logits differ by "
                            f"{worst['logits']:.3g} of their spread")
        if not worst["gap"] <= limits["router_gap"]:
            problems.append(f"an expert was chosen that trails the "
                            f"reference's k-th by {worst['gap']:.3g}")
    return problems, notes


def run(ctx) -> Outcome:
    with engine_options(ctx.cell.traffic.get("engine", {})):
        return measure(ctx)


def measure(ctx) -> Outcome:
    config, traffic = ctx.cell.config, ctx.cell.traffic
    layers = layer_table(config)
    wf, batch = build(ctx, layers)
    trainer = train.Trainer(ctx, wf)
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
    per_segment = int(traffic["epochs_per_segment"])
    steps_per_segment = per_segment * trainer.steps_per_epoch
    seq_len = int(traffic["seq_len"])
    items_per_segment = steps_per_segment * batch * seq_len

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
        if s["cat"] == "unit" and s["args"].get("kind") == "RegionUnit")

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
    found, said = check(ctx, wf, layers)
    problems += found
    notes += said + [f"NOT CORRECT: {p}" for p in problems]
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
            "layers": layers, "sample_shape": (seq_len,),
            "model_dim": config["widths"]["d_model"],
            "moe_units": [unit.name
                          for unit, layer in zip(wf.forwards, layers)
                          if layer["type"] == "moe"],
        },
        notes=notes)

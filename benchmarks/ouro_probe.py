#!/usr/bin/env python3
"""Which depth of ``ouro_2_6b`` loads on one v5e chip?  (PERF.md §4 and
§6, PR 35.)

    chiprun --timeout 900 -- python3 benchmarks/ouro_probe.py --layers 8 --vocab 8192
    JAX_PLATFORMS=cpu python3 benchmarks/ouro_probe.py --layers 6 --vocab 6144 --compile-only

Builds the cell's workflow at its published widths with the layer table
re-cut to ``--layers`` blocks and ``--vocab`` rows (everything else as
``znbench/configs/ouro_2_6b.json`` and ``znbench/traffic/
train_lm_loop_ctx.json`` have it: T 4,096, batch 1, 4 passes), as
``znbench/drivers/train_lm.py`` builds it, and

- on the chip: initializes it (``bytes_in_use``), runs ``--epochs``
  epochs through ``wf.run()`` and prints the wall time of a step and
  ``peak_bytes_in_use`` — or the program's refusal, the step program
  that does not load;
- with ``--compile-only`` (no chip): compiles the step program for a
  DESCRIBED v5e with the flash kernels through Mosaic and prints the
  compiler's memory analysis, or its refusal.  A compile is not a run:
  nothing it prints is a time.

One JSON line; exit code 0 where the depth loads.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

CELL = "ouro_train_loop4_t4096"


def recut(layers: list, n_layers: int, vocab: int) -> list:
    """The cell's table with ``n_layers`` blocks and ``vocab`` rows."""
    block, tail = layers[1:3], layers[-2:]
    table = copy.deepcopy([layers[0]] + block * n_layers + tail)
    table[0]["->"]["vocab_size"] = vocab
    table[-1]["->"]["output_sample_shape"] = vocab
    return table


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--layers", type=int, required=True)
    parser.add_argument("--vocab", type=int, required=True)
    parser.add_argument("--epochs", type=int, default=3)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--compile-only", action="store_true")
    args = parser.parse_args()
    if args.compile_only:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import numpy as np
    from znbench.harness import discovery
    from znbench.harness.program import engine_options, layer_table
    from znicz_tpu.backends import TPUDevice, XLADevice
    from znicz_tpu.loader.fullbatch import ArrayLoader
    from znicz_tpu.models.standard_workflow import StandardWorkflow
    from znicz_tpu.utils import prng
    from znicz_tpu.utils.config import root

    cell = discovery.find_cell(CELL)
    config, traffic = cell.config, cell.traffic
    table = recut(layer_table(config), args.layers, args.vocab)
    line = {"layers": args.layers, "vocab": args.vocab,
            "seq_len": traffic["seq_len"], "passes": 4,
            "platform": jax.devices()[0].platform}
    if not args.compile_only and line["platform"] != "tpu":
        print("ouro_probe: no TPU; --compile-only rehearses the compile",
              file=sys.stderr)
        return 2
    root.common.precision_type = config["precision"]["precision_type"]
    prng.seed_all(args.seed)
    t, steps = int(traffic["seq_len"]), int(traffic["steps_per_epoch"])
    ids = np.random.default_rng(args.seed).integers(
        0, args.vocab, size=(steps, t + 1))
    options = dict(config["precision"].get("engine", {}),
                   **traffic.get("engine", {}))
    if args.compile_only:       # the kernels' set-up without a chip
        options.update(pallas_interpret=True, flash_attention=True)
    with engine_options(options):
        wf = StandardWorkflow(
            name=config["workflow"]["name"],
            loader_factory=lambda w: ArrayLoader(
                w, train_data=ids[:, :-1].astype(np.float32),
                train_labels=ids[:, 1:].astype(np.int32),
                minibatch_size=1),
            layers=table, decision_config={"max_epochs": 1})
        wf._max_fires = 10 ** 9
        wf.initialize(device=(XLADevice if args.compile_only
                              else TPUDevice)())
        line["parameters_m"] = round(sum(
            int(np.prod(getattr(u, a).shape)) for u in wf.forwards
            for a in u.EXPORT_PARAMS if getattr(u, a)) / 1e6, 1)
        try:
            if args.compile_only:
                line.update(compile_for_described_chip(wf))
            else:
                line.update(run_on_chip(wf, args.epochs, steps))
            line["loads"] = True
        except Exception as exc:  # noqa: BLE001 — the refusal is the result
            said = str(exc)
            at = said.find("RESOURCE_EXHAUSTED")
            line.update(loads=False, refusal=(
                said[at:at + 400] if at >= 0
                else f"{type(exc).__name__}: {said[:400]}"))
    print(json.dumps(line), flush=True)
    return 0 if line["loads"] else 1


def run_on_chip(wf, epochs: int, steps: int) -> dict:
    import jax
    from znicz_tpu.loader.base import TRAIN
    device = jax.devices()[0]

    def gb(key: str) -> float:
        return round((device.memory_stats() or {}).get(key, 0) / 1e9, 3)

    out = {"bytes_in_use_after_initialize_gb": gb("bytes_in_use"),
           "bytes_limit_gb": gb("bytes_limit")}
    seconds = []
    for epoch in range(1, epochs + 1):
        wf.decision.max_epochs = epoch
        wf.decision.complete.value = False
        t0 = time.perf_counter()
        wf.run()
        wf.forwards[-1].weights.devmem.block_until_ready()
        seconds.append(time.perf_counter() - t0)
    out.update(
        first_epoch_s=round(seconds[0], 2),
        step_s=[round(s / steps, 4) for s in seconds[1:]],
        loss=wf.decision.epoch_loss[TRAIN],
        exits=wf.forwards[-1].last_exit_stats,
        applications_per_step=wf.pass_spans[0].applications_per_step,
        peak_bytes_in_use_gb=gb("peak_bytes_in_use"))
    return out


def compile_for_described_chip(wf, text_to: str | None = None) -> dict:
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from znicz_tpu.accelerated_units import JitRegion
    for unit in wf.forwards:     # through Mosaic, not the interpreter
        plan = getattr(unit, "_flash", None)
        if plan is not None and plan.runs:
            unit._flash = plan._replace(interpret=False)
    region = wf._region_unit.region
    wf.loader.run()
    region._vectors = region._collect_vectors()
    for vec in region._vectors:
        vec.unmap()
    body = region.build_callable(
        tuple(bool(u.gate_skip) for u in region.units))
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    structs = [jax.ShapeDtypeStruct(v._devmem.shape, v._devmem.dtype,
                                    sharding=chip)
               for v in region._vectors]
    out = {"leaves_gb": round(sum(
        int(np.prod(s.shape)) * s.dtype.itemsize for s in structs) / 1e9,
        3)}
    t0 = time.perf_counter()
    # as the region jits it (the write-only leaves kept)
    lowered = JitRegion._jit(body, True, len(structs)).lower(*structs)
    t1 = time.perf_counter()      # tracing and lowering: what a warm
    compiled = lowered.compile()  # compile cache does not hide
    stats = compiled.memory_analysis()
    text = compiled.as_text()
    if text_to:                   # the compiled program, to be read
        with open(text_to, "w") as f:
            f.write(text)
    out.update(
        lower_s=round(t1 - t0, 1),
        compile_s=round(time.perf_counter() - t0, 1),
        temp_gb=round(stats.temp_size_in_bytes / 1e9, 3),
        arguments_gb=round(stats.argument_size_in_bytes / 1e9, 3),
        outputs_gb=round(stats.output_size_in_bytes / 1e9, 3),
        aliased_gb=round(stats.alias_size_in_bytes / 1e9, 3),
        kernels=text.count("tpu_custom_call"),
        rematerialised=text.count(".remat"))
    return out


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""The two-width (MLA) flash BACKWARD alone at the three latent cells'
shapes, one pass against two (PERF.md §6, PR 53): is a causal two-width
call faster as ONE kernel (``znicz_flash_bwd_mla``: a visited score tile
computed once, dq, dk and dv made from it, a pair's whole dq in VMEM)
than as ``znicz_flash_bwd_mla_dq`` + ``znicz_flash_bwd_mla_dkv`` (every
tile twice)?

The rule is read from the shapes (``pallas_mla.backward_passes``); the
probe gives ``_backward`` either count, so both arms run at every shape.

``xing``    (1, 2048), 32 heads: a K grid 4 tiles deep
``ling``    (1, 4096), 32 heads: 8 tiles
``kanana``  (1, 16384), 32 heads: 32 tiles

    chiprun -- python3 benchmarks/mla_bwd_probe.py              # all
    python3 benchmarks/mla_bwd_probe.py --compile-only          # here:
        the chip's compiler on a described v5e, nothing runs

Each line is JSON and names the platform it ran on; times are
``block_until_ready`` medians of ``REPEAT`` backward calls in one
program, per call; ``mxu_share`` is the work the form executes (1,024 or
1,408 lane-units of 2·512² FLOP a visited tile and head) ÷ the chip's
197 TFLOP/s ÷ that time.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax                                     # noqa: E402
import jax.numpy as jnp                        # noqa: E402

from znicz_tpu.ops import pallas_mla           # noqa: E402

REPEAT = 4
HEADS = 32
PEAK = 197e12
#: shape → T
SHAPES = {"xing": 2048, "ling": 4096, "kanana": 16384}
#: 128-lane contractions a visited tile and head: s (2), dp, and the
#: five gradient products; under two passes s and dp twice
UNITS = {1: 8, 2: 11}


def emit(**line) -> None:
    line["platform"] = jax.devices()[0].platform
    print(json.dumps(line), flush=True)


def widths():
    wide = HEADS * 128
    return wide, HEADS * 64, wide, 64, wide


def executed_flops(t: int, passes: int) -> float:
    tiles = t // min(pallas_mla.BLOCK, t)
    edge = t // tiles
    return tiles * (tiles + 1) / 2 * HEADS * UNITS[passes] \
        * 2 * edge * edge * 128


def stage(name: str, compile_only: bool, sharding=None) -> None:
    t = SHAPES[name]
    ruled = pallas_mla.backward_passes(t)

    @jax.jit
    def residuals(rows):
        return pallas_mla._forward(*rows, False)

    def run(passes):
        return jax.jit(lambda rows, o, lse, dos: [
            pallas_mla._backward(*rows, o, lse, do, False, passes)
            for do in dos])

    if compile_only:
        def struct(shape, dtype=jnp.bfloat16):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
        rows = tuple(struct((1, t, w)) for w in widths())
        o, lse = jax.eval_shape(residuals, rows)
        for passes in (1, 2):
            run(passes).lower(
                rows, struct(o.shape), struct(lse.shape, lse.dtype),
                [struct(o.shape)] * REPEAT).compile()
            emit(stage=name, t=t, passes=passes, ruled=ruled,
                 kernels="compile")
        return
    keys = jax.random.split(jax.random.key(53), 5 + REPEAT)
    rows = tuple(0.3 * jax.random.normal(key, (1, t, w), jnp.bfloat16)
                 for key, w in zip(keys, widths()))
    dos = [jax.random.normal(key, rows[0].shape, jnp.bfloat16)
           for key in keys[5:]]
    o, lse = residuals(rows)
    grads = {}
    for passes in (1, 2):
        program = run(passes)
        grads[passes] = [g.astype(jnp.float32) for g in
                         jax.block_until_ready(
                             program(rows, o, lse, dos))[0]]
        times = []
        for _ in range(10):
            t0 = time.perf_counter()
            jax.block_until_ready(program(rows, o, lse, dos))
            times.append((time.perf_counter() - t0) * 1e3 / REPEAT)
        median = statistics.median(times)
        emit(stage=name, t=t, passes=passes, ruled=ruled,
             backward_ms=median, fastest_ms=min(times),
             mxu_share=executed_flops(t, passes) / PEAK / median * 1e3)
    emit(stage=name, one_pass_against_two={
        grad: float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-6))
        for grad, a, b in zip(
            ("dq_nope", "dq_rope", "dk_nope", "dk_rope", "dv"),
            grads[1], grads[2])})


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("stages", nargs="*", default=list(SHAPES))
    parser.add_argument("--compile-only", action="store_true")
    args = parser.parse_args()
    sharding = None
    if args.compile_only:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        jax.config.update("jax_enable_compilation_cache", False)
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        sharding = SingleDeviceSharding(topo.devices[0])
    for name in args.stages:
        stage(name, args.compile_only, sharding)
    return 0


if __name__ == "__main__":
    sys.exit(main())

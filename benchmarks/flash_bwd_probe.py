#!/usr/bin/env python3
"""The flash BACKWARD alone at the three LM cells' shapes, one pass
against two (PERF.md §6, PR 30): is a causal call whose K side is one
grid tile faster as ONE kernel (``znicz_flash_bwd``: five matmuls and
one pass of exponentials per visible sub-tile) than as ``znicz_flash_dq``
+ ``znicz_flash_dkv`` (seven and two)?

The rule is read from the shapes (``pallas_attention.backward_passes``),
so the arms are K-side tiles: a tile as long as the keys gives the one
pass, a shorter one the two kernels — for which the probe holds the
backward to the forward's tile (``WHOLE_BLOCK_K`` at ``CAUSAL_BLOCK_K``:
the program before PR 30 widened it).

``lm``      one fused (32, 2048, 1536) projection, pairs of dh-64 heads
``olmoe``   three (1, 4096, 2048) tensors, 16 heads of 128
``laguna``  q (1, 4096, 6144) on k, v (1, 4096, 1024): 48 heads on 8

    chiprun -- python3 benchmarks/flash_bwd_probe.py            # all
    python3 benchmarks/flash_bwd_probe.py --compile-only        # here:
        the chip's compiler on a described v5e, nothing runs

Each line is JSON and names the platform it ran on; times are
``block_until_ready`` medians of ``REPEAT`` backward calls in one
program, per call.
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

from znicz_tpu.ops import pallas_attention as pa   # noqa: E402

REPEAT = 8
#: shape → (operand shapes, query heads, K/V heads, K-side tiles to try)
SHAPES = {
    "lm": (((32, 2048, 1536),), 8, 8, (2048, 1024)),
    "olmoe": (((1, 4096, 2048),) * 3, 16, 16, (4096, 2048)),
    "laguna": (((1, 4096, 6144), (1, 4096, 1024), (1, 4096, 1024)),
               48, 8, (4096, 2048)),
}


def emit(**line) -> None:
    line["platform"] = jax.devices()[0].platform
    print(json.dumps(line), flush=True)


def backward(shapes, heads: int, kv_heads: int, bk: int):
    """``(residuals, run)``: ``residuals(arrays, do)`` the forward's
    (lse, delta); ``run(arrays, lse, delta, dos)`` the backward once
    per cotangent in ``dos``."""
    t = shapes[0][1]
    fused = len(shapes) == 1
    dh = shapes[0][2] // (heads + 2 * kv_heads if fused else heads)
    _, pack = pa.head_layout(heads, dh, heads // kv_heads)
    cols = (heads // pack, pack * dh, kv_heads // pack)
    bq = pa.grid_blocks(True, t, t)[0]
    zero = pa._off_arr(None)
    static = (True, bq, bk, False, pack, None, cols)

    @jax.jit
    def residuals(arrays, do):
        out, lse = pa._fwd_call(arrays, zero, zero, *static)
        return lse, pa._delta(do, out, jnp.zeros_like(lse), pack, True)

    @jax.jit
    def run(arrays, lse, delta, dos):
        return [pa._bwd_call(arrays, lse, do, delta, zero, zero, *static)
                for do in dos]
    return residuals, run


def stage(name: str, compile_only: bool, sharding=None) -> None:
    shapes, heads, kv_heads, tiles = SHAPES[name]
    t = shapes[0][1]
    width = shapes[0][2] if len(shapes) == 3 \
        else shapes[0][2] * heads // (heads + 2 * kv_heads)
    do_shape = (shapes[0][0], t, width)
    grads = {}
    for bk in tiles:
        pa.WHOLE_BLOCK_K = max(bk, pa.CAUSAL_BLOCK_K)   # read at trace
        passes = pa.backward_passes(True, t, bk)
        residuals, run = backward(shapes, heads, kv_heads, bk)
        if compile_only:
            def struct(shape, dtype=jnp.bfloat16):
                return jax.ShapeDtypeStruct(shape, dtype,
                                            sharding=sharding)
            arrays = tuple(struct(s) for s in shapes)
            lse, delta = jax.eval_shape(residuals, arrays,
                                        struct(do_shape))
            run.lower(arrays, struct(lse.shape, lse.dtype),
                      struct(delta.shape, delta.dtype),
                      [struct(do_shape)] * REPEAT).compile()
            emit(stage=name, block_k=bk, passes=passes, kernels="compile")
            continue
        keys = jax.random.split(jax.random.key(7), len(shapes) + REPEAT)
        arrays = tuple(jax.random.normal(key, s, jnp.bfloat16)
                       for key, s in zip(keys, shapes))
        dos = [jax.random.normal(key, do_shape, jnp.bfloat16)
               for key in keys[len(shapes):]]
        lse, delta = residuals(arrays, dos[0])
        grads[bk] = [g.astype(jnp.float32) for g in
                     jax.block_until_ready(run(arrays, lse, delta, dos))[0]]
        times = []
        for _ in range(10):
            t0 = time.perf_counter()
            jax.block_until_ready(run(arrays, lse, delta, dos))
            times.append((time.perf_counter() - t0) * 1e3 / REPEAT)
        emit(stage=name, block_k=bk, passes=passes,
             backward_ms=statistics.median(times), fastest_ms=min(times))
    if len(grads) == 2:
        one, two = (grads[bk] for bk in tiles)
        emit(stage=name, one_pass_against_two={
            f"grad{i}": float(jnp.abs(a - b).max()
                              / (jnp.abs(b).max() + 1e-6))
            for i, (a, b) in enumerate(zip(one, two))})


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

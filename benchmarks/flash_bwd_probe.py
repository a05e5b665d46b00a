#!/usr/bin/env python3
"""The flash BACKWARD alone at the LM cells' shapes, one pass against
two (PERF.md §6, PR 30 and PR 55): is a causal call faster as ONE kernel
(``znicz_flash_bwd`` / ``_bwd_win``: five matmuls and one pass of
exponentials per visible sub-tile) than as ``znicz_flash_dq`` +
``znicz_flash_dkv`` (seven and two)?

The rule is read from the shapes (``pallas_attention.backward_passes``);
the probe gives ``_bwd_call`` each answer in turn.  An arm is (the
backward's K tile, passes): where the K side is ONE tile a Q tile's dq
is whole inside a grid step (PR 30; for the two-pass arm the probe holds
the backward to the forward's shorter tile, ``WHOLE_BLOCK_K`` at
``CAUSAL_BLOCK_K``: the program before PR 30 widened it); past one tile
the unfinished dq tiles wait in VMEM for their later K tiles (PR 55).

``lm``                one fused (32, 2048, 1536) projection, pairs of
                      dh-64 heads
``olmoe``             three (1, 4096, 2048) tensors, 16 heads of 128
``laguna``            q (1, 4096, 6144) on k, v (1, 4096, 1024): 48
                      heads on 8
``laguna_win``        72 heads on 8 under a window of 512: a band two
                      tiles of 512 wide (Laguna's sliding layers)
``smallthinker``      one fused (1, 16384, 4608) projection, 28 heads
                      on 4: eight K tiles of 2048, 7 × 16 dq tiles of
                      1024 waiting (56 MiB)
``smallthinker_win``  28 heads on 4 at T 16,384 under a window of
                      4,096: a band nine tiles wide, 7 × 9 slots

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
#: shape → (operand shapes, query heads, K/V heads, window, arms: (the
#: backward's K tile, passes))
SHAPES = {
    "lm": (((32, 2048, 1536),), 8, 8, None, ((2048, 1), (1024, 2))),
    "olmoe": (((1, 4096, 2048),) * 3, 16, 16, None,
              ((4096, 1), (2048, 2))),
    "laguna": (((1, 4096, 6144), (1, 4096, 1024), (1, 4096, 1024)),
               48, 8, None, ((4096, 1), (2048, 2))),
    "laguna_win": (((1, 4096, 9216), (1, 4096, 1024), (1, 4096, 1024)),
                   72, 8, 512, ((512, 1), (512, 2))),
    "smallthinker": (((1, 16384, 4608),), 28, 4, None,
                     ((2048, 1), (2048, 2))),
    "smallthinker_win": (
        ((1, 16384, 3584), (1, 16384, 512), (1, 16384, 512)), 28, 4, 4096,
        ((512, 1), (512, 2))),
}


def emit(**line) -> None:
    line["platform"] = jax.devices()[0].platform
    print(json.dumps(line), flush=True)


def backward(shapes, heads: int, kv_heads: int, window, bk: int,
             passes: int):
    """``(residuals, run)``: ``residuals(arrays, do)`` the forward's
    (lse, delta); ``run(arrays, lse, delta, dos)`` the backward once
    per cotangent in ``dos``."""
    t = shapes[0][1]
    fused = len(shapes) == 1
    dh = shapes[0][2] // (heads + 2 * kv_heads if fused else heads)
    _, pack = pa.head_layout(heads, dh, heads // kv_heads)
    cols = (heads // pack, pack * dh, kv_heads // pack)
    bq = pa.grid_blocks(True, t, t)[0] if window is None \
        else pa.band_blocks(t)[0]
    zero = pa._off_arr(None)
    static = (True, bq, bk, False, pack, None, cols, window)

    @jax.jit
    def residuals(arrays, do):
        out, lse = pa._fwd_call(arrays, zero, zero, *static)
        return lse, pa._delta(do, out, jnp.zeros_like(lse), pack, True)

    @jax.jit
    def run(arrays, lse, delta, dos):
        return [pa._bwd_call(arrays, lse, do, delta, zero, zero, *static,
                             passes) for do in dos]
    return residuals, run


def stage(name: str, compile_only: bool, sharding=None) -> None:
    shapes, heads, kv_heads, window, arms = SHAPES[name]
    t = shapes[0][1]
    width = shapes[0][2] if len(shapes) == 3 \
        else shapes[0][2] * heads // (heads + 2 * kv_heads)
    do_shape = (shapes[0][0], t, width)
    group = heads // kv_heads
    # what the rule reads beside T, the K tile and the window
    seen = dict(group=group, width=width // heads
                * pa.head_layout(heads, width // heads, group)[1])
    grads = []
    for bk, passes in arms:
        pa.WHOLE_BLOCK_K = max(bk, pa.CAUSAL_BLOCK_K)   # read at trace
        said = dict(stage=name, block_k=bk, passes=passes,
                    rule=pa.backward_passes(True, t, bk, window, **seen),
                    resident_dq_mib=pa.resident_dq_bytes(
                        True, t, bk, window, **seen) / 2 ** 20
                    if passes == 1 else 0)
        residuals, run = backward(shapes, heads, kv_heads, window, bk,
                                  passes)
        if compile_only:
            def struct(shape, dtype=jnp.bfloat16):
                return jax.ShapeDtypeStruct(shape, dtype,
                                            sharding=sharding)
            arrays = tuple(struct(s) for s in shapes)
            lse, delta = jax.eval_shape(residuals, arrays,
                                        struct(do_shape))
            t0 = time.perf_counter()
            run.lower(arrays, struct(lse.shape, lse.dtype),
                      struct(delta.shape, delta.dtype),
                      [struct(do_shape)] * REPEAT).compile()
            emit(**said, kernels="compile",
                 compile_s=time.perf_counter() - t0)
            continue
        keys = jax.random.split(jax.random.key(7), len(shapes) + REPEAT)
        arrays = tuple(jax.random.normal(key, s, jnp.bfloat16)
                       for key, s in zip(keys, shapes))
        dos = [jax.random.normal(key, do_shape, jnp.bfloat16)
               for key in keys[len(shapes):]]
        lse, delta = residuals(arrays, dos[0])
        grads.append([g.astype(jnp.float32) for g in
                      jax.block_until_ready(
                          run(arrays, lse, delta, dos))[0]])
        times = []
        for _ in range(10):
            t0 = time.perf_counter()
            jax.block_until_ready(run(arrays, lse, delta, dos))
            times.append((time.perf_counter() - t0) * 1e3 / REPEAT)
        emit(**said, backward_ms=statistics.median(times),
             fastest_ms=min(times))
    if len(grads) == 2:
        one, two = grads
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

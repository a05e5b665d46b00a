#!/usr/bin/env python3
"""The flash FORWARD alone at the LM cells' shapes (PERF.md §6, PR 36):
what ONE visit of the walk does between the score product and the
value product — whether a row block that meets all its keys at once
still carries state, where a row's statistics live, where 1/√dh
enters — as the chooser picks it from the shapes
(``pallas_attention.forward_form``) against the forward of another
checkout (``--parent DIR``: the tree before the change).

``lm``          one fused (32, 2048, 1536) projection, pairs of dh-64 heads
``lm_t1024``    the same at T 1024 (no cell; 4% slower after PR 24)
``olmoe``       three (1, 4096, 2048) tensors, 16 heads of 128 (Ouro's
                call too, sixteen times a step)
``laguna``      q (1, 4096, 6144) on k, v (1, 4096, 1024): 48 heads on 8
``laguna_win``  the same under a window of 512
``hybrid``      three (1, 4096, 3840) tensors, 30 heads of 128

    chiprun -- python3 benchmarks/flash_fwd_probe.py --parent _chip/parent
    python3 benchmarks/flash_fwd_probe.py --compile-only   # here: the
        chip's compiler on a described v5e, nothing runs

Each line is JSON and names the platform it ran on; times are
``block_until_ready`` medians of ``REPEAT`` forward calls in one
program, per call; ``build_s`` is the host's time to trace, lower and
compile that program.  (PR 36's sweep ran this script from the working
tree that still held the two transposed candidates, one more arm each;
they lost and are gone: PERF.md §6.)
"""

from __future__ import annotations

import argparse
import importlib.util
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
#: shape → (operand shapes, query heads, K/V heads, window)
SHAPES = {
    "lm": (((32, 2048, 1536),), 8, 8, None),
    "lm_t1024": (((32, 1024, 1536),), 8, 8, None),
    "olmoe": (((1, 4096, 2048),) * 3, 16, 16, None),
    "laguna": (((1, 4096, 6144), (1, 4096, 1024), (1, 4096, 1024)),
               48, 8, None),
    "laguna_win": (((1, 4096, 6144), (1, 4096, 1024), (1, 4096, 1024)),
                   48, 8, 512),
    "hybrid": (((1, 4096, 3840),) * 3, 30, 30, None),
}


def emit(**line) -> None:
    line["platform"] = jax.devices()[0].platform
    print(json.dumps(line), flush=True)


def load(directory: str):
    """``pallas_attention`` of another checkout, beside this one's."""
    path = os.path.join(directory, "znicz_tpu", "ops",
                        "pallas_attention.py")
    spec = importlib.util.spec_from_file_location("parent_attention", path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


def forward(module, shapes, heads: int, kv_heads: int, window):
    """``(form, run)``: ``run(operands)`` the forward of ``module``
    once per set of arrays in ``operands``; ``form`` what its chooser
    says of the call (None: a checkout from before there was one)."""
    t = shapes[0][1]
    fused = len(shapes) == 1
    dh = shapes[0][2] // (heads + 2 * kv_heads if fused else heads)
    _, pack = module.head_layout(heads, dh, heads // kv_heads)
    cols = (heads // pack, pack * dh, kv_heads // pack)
    bq, bk = module.grid_blocks(True, t, t) if window is None \
        else module.band_blocks(t)
    zero = module._off_arr(None)
    static = (True, bq, bk, False, pack, None, cols, window)
    form = None
    if hasattr(module, "forward_form"):
        form = "/".join(module.forward_form(t, bq, bk, dh, window))

    @jax.jit
    def run(operands):
        return [module._fwd_call(arrays, zero, zero, *static)
                for arrays in operands]
    return form, run


def stage(name: str, arms, compile_only: bool, sharding=None) -> None:
    shapes, heads, kv_heads, window = SHAPES[name]
    if compile_only:
        operands = [tuple(jax.ShapeDtypeStruct(s, jnp.bfloat16,
                                               sharding=sharding)
                          for s in shapes)] * REPEAT
    else:
        keys = jax.random.split(jax.random.key(7), REPEAT * len(shapes))
        operands = [tuple(jax.random.normal(keys[i * len(shapes) + j], s,
                                            jnp.bfloat16)
                          for j, s in enumerate(shapes))
                    for i in range(REPEAT)]
    first = {}
    for arm, module in arms:
        form, run = forward(module, shapes, heads, kv_heads, window)
        t0 = time.perf_counter()
        compiled = run.lower(operands).compile()
        build_s = time.perf_counter() - t0
        if compile_only:
            emit(stage=name, arm=arm, form=form, kernels="compile",
                 build_s=round(build_s, 2))
            continue
        out, lse = jax.block_until_ready(compiled(operands))[0]
        first[arm] = (out.astype(jnp.float32), lse)
        times = []
        for _ in range(10):
            t0 = time.perf_counter()
            jax.block_until_ready(compiled(operands))
            times.append((time.perf_counter() - t0) * 1e3 / REPEAT)
        emit(stage=name, arm=arm, form=form, build_s=round(build_s, 2),
             forward_ms=statistics.median(times), fastest_ms=min(times))
    if "parent" in first:
        out0, lse0 = first["parent"]
        for arm, (out, lse) in first.items():
            if arm != "parent":
                emit(stage=name, arm=arm, against_parent={
                    "out": float(jnp.abs(out - out0).max()),
                    "lse": float(jnp.abs(lse - lse0).max()
                                 / (jnp.abs(lse0).max() + 1e-6))})


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("stages", nargs="*", default=list(SHAPES))
    parser.add_argument("--compile-only", action="store_true")
    parser.add_argument("--parent", help="another checkout's root")
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
    arms = [("parent", load(args.parent))] if args.parent else []
    arms.append(("chooser", pa))
    for name in args.stages:
        stage(name, arms, args.compile_only, sharding)
    return 0


if __name__ == "__main__":
    sys.exit(main())

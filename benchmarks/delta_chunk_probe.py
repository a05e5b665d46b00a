#!/usr/bin/env python3
"""What is local to a chunk of the gated delta rule, alone, at the
Olmo-Hybrid cell's shapes (30 heads × 64 chunks of 64, d_k 96, d_v 192,
bf16 products): ``pallas_delta.chunk_local`` in ``jax.numpy`` under
autodiff against the ``znicz_gdr_chunk_fwd`` / ``_bwd`` kernels, for
several chunks a grid step and chunks a basic block (PERF.md §6,
PR 32).  The kernels' results are compared on the device with
``chunk_local``'s at f32.

    chiprun -- python3 benchmarks/delta_chunk_probe.py
    python3 benchmarks/delta_chunk_probe.py --compile-only      # here:
        the chip's compiler on a described v5e, nothing runs
    python3 benchmarks/delta_chunk_probe.py --compile-only --kernel kda_chunk_bwd
        # ONE chunk kernel as a program of its own — either family
        # (gdr: this cell's shapes; kda: Ling's 32 heads of 128 × 128,
        # a decay per key channel), either way — so that a dump of the
        # compiler's schedule (benchmarks/static_schedule.py), which
        # holds the FIRST kernel a process compiles, holds that one

Each line is JSON and names the platform it ran on; times are
``block_until_ready`` medians of ``REPEAT`` calls in one program, per
call (forward alone; forward + backward).
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

from znicz_tpu.ops import pallas_delta as pd   # noqa: E402

REPEAT = 4
G, N, C, DK, DV = 30, 64, 64, 96, 192
#: (chunks a grid step, chunks a basic block)
ARMS = ((8, 4), (8, 2), (16, 4), (8, 8))


def emit(**line) -> None:
    line["platform"] = jax.devices()[0].platform
    print(json.dumps(line), flush=True)


def programs(local, dot_dtype):
    """``local``'s forward, and its forward + backward under a fixed
    weighting, ``REPEAT`` times in one program each (every repeat on
    its own β, so nothing is shared between them)."""
    def outputs(q, k, v, log_alpha, beta):
        return local(q, k, v, log_alpha, beta, dot_dtype)

    @jax.jit
    def forward(q, k, v, log_alpha, betas):
        return [outputs(q, k, v, log_alpha, beta) for beta in betas]

    @jax.jit
    def both(q, k, v, log_alpha, betas, weights):
        def loss(q, k, v, log_alpha, beta):
            return sum(jnp.sum(o * w) for o, w in zip(
                outputs(q, k, v, log_alpha, beta), weights))
        return [jax.grad(loss, (0, 1, 2, 3, 4))(q, k, v, log_alpha, beta)
                for beta in betas]
    return forward, both


def inputs():
    keys = jax.random.split(jax.random.key(11), 6 + REPEAT)
    q = jax.random.normal(keys[0], (G, N, C, DK)) * DK ** -0.5
    k = jax.random.normal(keys[1], (G, N, C, DK)) + 0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], (G, N, C, DV))
    log_alpha = -jnp.exp(jax.random.uniform(
        keys[3], (G, N, C), minval=-7.0, maxval=0.5))
    betas = [jax.random.uniform(key, (G, N, C), maxval=2.0)
             for key in keys[6:]]
    shapes = [(G, N, C, DK), (G, N, C, DK), (G, N, C, DV), (G, N),
              (G, N, C, DK), (G, N, C, C)]
    weights = [jax.random.normal(key, s) for key, s in zip(
        jax.random.split(keys[4], 6), shapes)]
    return (q, k, v, log_alpha, betas), weights


def timed(run, *args) -> dict:
    jax.block_until_ready(run(*args))
    times = []
    for _ in range(8):
        t0 = time.perf_counter()
        jax.block_until_ready(run(*args))
        times.append((time.perf_counter() - t0) * 1e3 / REPEAT)
    return {"median_ms": statistics.median(times), "fastest_ms": min(times)}


def worst(got, want) -> float:
    return max(float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-30))
               for a, b in zip(got, want))


def one_kernel(name: str, struct):
    """The chunk kernel ``name`` (``gdr_chunk_bwd``, …) lowered alone
    at its cell's shapes with bf16 products: the backward from the
    shapes of what the forward hands it."""
    family, _, way = name.split("_")
    channels = family == "kda"
    g, dk, dv = (32, 128, 128) if channels else (G, DK, DV)
    rule = pd._rule_for(channels)
    static = (False, jnp.dtype(jnp.bfloat16), pd.CHUNKS_PER_STEP)
    f32 = jnp.float32
    rows = [jax.ShapeDtypeStruct(shape, f32) for shape in (
        (g, N, C, dk), (g, N, C, dk), (g, N, C, dv),
        (g, N, C, dk) if channels else (g, N, C), (g, N, C))]
    if way == "fwd":
        return pd._chunk_forward_call.lower(rule, *map(struct, rows),
                                            *static)
    outputs, inverse = jax.eval_shape(
        lambda *a: pd._chunk_forward_call(rule, *a, *static), *rows)
    # the backward is handed V at the width the rule keeps it
    rows[2] = jax.ShapeDtypeStruct(rows[2].shape, rule.width(static[1]))
    return pd._chunk_backward_call.lower(rule, *jax.tree.map(
        struct, (*rows, inverse, outputs)), *static)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--compile-only", action="store_true")
    parser.add_argument("--kernel", choices=[
        f"{family}_chunk_{way}" for family in ("gdr", "kda")
        for way in ("fwd", "bwd")], help="with --compile-only: this "
        "one kernel alone, for a dump of its schedule")
    args = parser.parse_args()
    bf16 = jnp.dtype(jnp.bfloat16)

    def kernels(block):
        return lambda *a: pd.chunk_local_kernels(*a, block=block)

    if args.compile_only:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        jax.config.update("jax_enable_compilation_cache", False)
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        one = SingleDeviceSharding(topo.devices[0])

        def struct(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)
        if args.kernel:
            one_kernel(args.kernel, struct).compile()
            emit(kernel=args.kernel, kernels="compile")
            return 0
        with jax.default_device(jax.devices("cpu")[0]):
            (q, k, v, log_alpha, betas), weights = jax.eval_shape(inputs)
        shaped = jax.tree.map(struct, (q, k, v, log_alpha, betas))
        for block, together in ARMS:
            pd._TOGETHER = together              # read at trace
            jax.clear_caches()
            for dot_dtype in (bf16, None):
                forward, both = programs(kernels(block), dot_dtype)
                forward.lower(*shaped).compile()
                both.lower(*shaped, jax.tree.map(struct,
                                                 weights)).compile()
            emit(block=block, together=together, kernels="compile")
        return 0

    (q, k, v, log_alpha, betas), weights = inputs()
    rows = (q, k, v, log_alpha, betas)
    plain_f, plain_b = programs(pd.chunk_local, bf16)
    t0 = time.perf_counter()
    plain_b.lower(*rows, weights)
    emit(path="jax.numpy", trace_and_lower_s=time.perf_counter() - t0,
         forward=timed(plain_f, *rows),
         forward_backward=timed(plain_b, *rows, weights))
    exact_f, exact_b = programs(pd.chunk_local, None)
    with jax.default_matmul_precision("highest"):
        want_f = exact_f(*rows)[0]
        want_b = exact_b(*rows, weights)[0]
    for block, together in ARMS:
        pd._TOGETHER = together                  # read at trace
        jax.clear_caches()
        forward, both = programs(kernels(block), bf16)
        t0 = time.perf_counter()
        both.lower(*rows, weights)
        line = dict(path="kernels", block=block, together=together,
                    trace_and_lower_s=time.perf_counter() - t0,
                    forward=timed(forward, *rows),
                    forward_backward=timed(both, *rows, weights))
        forward, both = programs(kernels(block), None)
        line["f32_against_jax_numpy"] = {
            "forward": worst(forward(*rows)[0], want_f),
            "backward": worst(both(*rows, weights)[0], want_b)}
        emit(**line)
    return 0


if __name__ == "__main__":
    sys.exit(main())

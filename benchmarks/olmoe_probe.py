#!/usr/bin/env python3
"""Two questions the OLMoE cell asks of the chip before it is built
(PERF.md §6, PR 25), answered in one process:

``flash``  the flash-attention kernels at B 2 × H 16 × T 4096 × dh 128,
           causal, forward and backward, against the XLA core
           (``parallel.ring_attention.local_attention``): the shape the
           cell runs and that no earlier run had.
``gmm``    the grouped matmul of the expert layer at the cell's shape
           (65,536 rows in 64 groups, 2048 × 1024 and 1024 × 2048),
           forward, row gradient and weight gradient, two arms:
           ``jax.lax.ragged_dot`` and JAX's Pallas grouped matmul
           (``jax.experimental.pallas.ops.tpu.megablox``) at a few
           tilings.  Times are ``block_until_ready`` medians.

    chiprun -- python3 benchmarks/olmoe_probe.py            # both
    python3 benchmarks/olmoe_probe.py --compile-only        # here: the
        chip's compiler on a described v5e, nothing runs

Every line is JSON and names the platform it ran on.
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
import numpy as np                             # noqa: E402

ROWS, GROUPS, D, F = 65536, 64, 2048, 1024
#: (m, k, n) tiles; past 512 x 1024 x 1024 the chip's compiler refuses
#: the kernels for the 16 MB of scoped VMEM (CPU, compile only, PR 25)
TILINGS = ((128, 128, 128), (512, 512, 512), (256, 1024, 1024),
           (512, 1024, 512), (512, 512, 1024), (512, 1024, 1024),
           (512, 2048, 512), (1024, 512, 1024), (1024, 1024, 512))


def emit(**line) -> None:
    line["platform"] = jax.devices()[0].platform
    print(json.dumps(line), flush=True)


def timed(fn, *args, reps: int = 10) -> float:
    """Median milliseconds of ``fn(*args)`` after one warm call."""
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(times)


def group_sizes(seed: int = 0) -> np.ndarray:
    """Rows per expert as a uniform router would leave them: a
    multinomial around ROWS / GROUPS, not multiples of any tile."""
    rng = np.random.default_rng(seed)
    return rng.multinomial(ROWS, np.full(GROUPS, 1.0 / GROUPS)).astype(
        np.int32)


# -- the two arms: (fwd, dlhs, drhs), each (lhs, rhs, grad, sizes) -----
def ragged_arm():
    def fwd(lhs, rhs, sizes):
        return jax.lax.ragged_dot(lhs, rhs, sizes,
                                  preferred_element_type=jnp.float32)

    def dlhs(grad, rhs, sizes):
        return jax.lax.ragged_dot(grad, rhs.swapaxes(1, 2), sizes,
                                  preferred_element_type=jnp.float32)

    def drhs(lhs, grad, sizes):
        dims = jax.lax.RaggedDotDimensionNumbers(
            dot_dimension_numbers=(((0,), (0,)), ((), ())),
            lhs_ragged_dimensions=[0], rhs_group_dimensions=[])
        return jax.lax.ragged_dot_general(
            lhs, grad, sizes, dims, preferred_element_type=jnp.float32)

    return fwd, dlhs, drhs


def megablox_arm(tiling):
    # the package's ``gmm`` attribute is the custom-vjp function, which
    # hides the module of that name: its backward would return the
    # weight gradient in the weights' bf16
    import importlib
    backend = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")

    def fwd(lhs, rhs, sizes):
        return backend.gmm(lhs, rhs, sizes, jnp.float32, tiling)

    def dlhs(grad, rhs, sizes):
        return backend.gmm(grad, rhs, sizes, jnp.float32, tiling,
                           transpose_rhs=True)

    def drhs(lhs, grad, sizes):
        return backend.tgmm(lhs.swapaxes(0, 1), grad, sizes, jnp.float32,
                            tiling)

    return fwd, dlhs, drhs


def gmm_cases():
    yield "ragged_dot", None, ragged_arm()
    for tiling in TILINGS:
        yield "megablox", tiling, megablox_arm(tiling)


def gmm_shapes(k: int, n: int, sharding=None):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return (spec((ROWS, k), jnp.bfloat16), spec((GROUPS, k, n),
                                                jnp.bfloat16),
            spec((ROWS, n), jnp.bfloat16), spec((GROUPS,), jnp.int32))


def stage_gmm(compile_only: bool, sharding=None) -> None:
    sizes_np = group_sizes()
    for k, n in ((D, F), (F, D)):
        flops = 2.0 * ROWS * k * n
        if not compile_only:
            key = jax.random.key(0)
            lhs = jax.random.normal(key, (ROWS, k), jnp.bfloat16)
            rhs = (jax.random.normal(key, (GROUPS, k, n), jnp.float32)
                   / np.sqrt(k)).astype(jnp.bfloat16)
            grad = jax.random.normal(key, (ROWS, n), jnp.bfloat16)
            sizes = jnp.asarray(sizes_np)
            want = None
        for arm, tiling, (fwd, dlhs, drhs) in gmm_cases():
            line = {"stage": "gmm", "arm": arm, "tiling": tiling,
                    "k": k, "n": n}
            if not compile_only:
                try:
                    out = jax.jit(fwd)(lhs, rhs, sizes)
                    if want is None:
                        want = out      # the first arm is the yardstick
                    line["max_diff_vs_ragged"] = float(
                        jnp.abs(out - want).max())
                except Exception as exc:
                    line["max_diff_vs_ragged"] = type(exc).__name__
                runs = (("fwd", fwd, (lhs, rhs, sizes)),
                        ("dlhs", dlhs, (grad, rhs, sizes)),
                        ("drhs", drhs, (lhs, grad, sizes)))
            else:
                s_lhs, s_rhs, s_grad, s_sizes = gmm_shapes(k, n, sharding)
                runs = (("fwd", fwd, (s_lhs, s_rhs, s_sizes)),
                        ("dlhs", dlhs, (s_grad, s_rhs, s_sizes)),
                        ("drhs", drhs, (s_lhs, s_grad, s_sizes)))
            for name, fn, args in runs:
                try:
                    if compile_only:
                        jax.jit(fn).lower(*args).compile()
                        line[name] = "compiles"
                    else:
                        ms = timed(jax.jit(fn), *args)
                        line[f"{name}_ms"] = ms
                        line[f"{name}_tflops"] = flops / ms / 1e9
                except Exception as exc:  # the compiler refuses it
                    line[name] = f"{type(exc).__name__}: " \
                                 f"{str(exc)[:160]}"
            emit(**line)


def stage_flash(compile_only: bool, sharding=None) -> None:
    from znicz_tpu.ops import pallas_attention
    from znicz_tpu.parallel.ring_attention import local_attention
    b, h, t, dh = 2, 16, 4096, 128

    def flash(q, k, v):
        return pallas_attention.flash_attention(
            q, k, v, causal=True, dot_dtype=jnp.bfloat16)

    def core(q, k, v):
        return local_attention(q, k, v, causal=True,
                               dot_dtype=jnp.bfloat16)

    def with_grads(fn):
        def run(q, k, v, cot):
            out, pull = jax.vjp(fn, q, k, v)
            return (out,) + pull(cot)
        return jax.jit(run)

    if compile_only:
        shape = jax.ShapeDtypeStruct((b, t, h, dh), jnp.float32,
                                     sharding=sharding)
        with_grads(flash).lower(shape, shape, shape, shape).compile()
        emit(stage="flash", shape=[b, h, t, dh], kernels="compile")
        return
    keys = jax.random.split(jax.random.key(1), 4)
    q, k, v, cot = (jax.random.normal(key, (b, t, h, dh), jnp.float32)
                    for key in keys)
    got = with_grads(flash)(q, k, v, cot)
    want = with_grads(core)(q, k, v, cot)
    errs = {name: float(jnp.abs(g - w).max() / (jnp.abs(w).max() + 1e-6))
            for name, g, w in zip(("out", "dq", "dk", "dv"), got, want)}
    unit = b * h * float(t) * t * dh
    fwd_ms = timed(jax.jit(flash), q, k, v)
    both_ms = timed(with_grads(flash), q, k, v, cot)
    core_ms = timed(with_grads(core), q, k, v, cot)
    emit(stage="flash", shape=[b, h, t, dh], causal=True, errs=errs,
         ok=bool(max(errs.values()) <= 3e-2),
         fwd_ms=fwd_ms, fwd_bwd_ms=both_ms, xla_core_fwd_bwd_ms=core_ms,
         # FlashAttention-2's accounting, causal half: 4 and 14 units
         fwd_tflops=0.5 * 4 * unit / fwd_ms / 1e9,
         fwd_bwd_tflops=0.5 * 14 * unit / both_ms / 1e9)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("stages", nargs="*", default=["flash", "gmm"])
    parser.add_argument("--compile-only", action="store_true")
    args = parser.parse_args()
    sharding = None
    if args.compile_only:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        sharding = SingleDeviceSharding(topo.devices[0])
    elif jax.devices()[0].platform != "tpu":
        print("olmoe_probe: no TPU; --compile-only rehearses the "
              "compiles", file=sys.stderr)
        return 2
    for stage in args.stages:
        {"flash": stage_flash, "gmm": stage_gmm}[stage](
            args.compile_only, sharding)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Two questions the OLMoE cell asks of the chip before it is built
(PERF.md §6, PR 25), answered in one process:

``flash``  the flash-attention kernels at B 2 × H 16 × T 4096 × dh 128,
           causal, forward and backward, against the XLA core
           (``parallel.ring_attention.local_attention``): the shape the
           cell runs and that no earlier run had.
``gmm``    the grouped matmuls of the expert layers at two cells'
           shapes — ``olmoe_train_t4096`` (32,768 rows in 64 groups,
           2048 × 1024 and 1024 × 2048) and ``laguna_train_1of32``'s
           held share (a 5,120-row buffer, ≈ 1,280 rows real, 8 groups,
           3072 × 1024 and back) — forward, row gradient and weight
           gradient, three arms: ``jax.lax.ragged_dot``, JAX's Pallas
           grouped matmul (``jax.experimental.pallas.ops.tpu.megablox``,
           what the program ran from PR 25 to PR 33) and the repo's own
           kernels (``ops/pallas_gmm.py``, PR 34) at the tile rule's
           choice and around it (``--no-sweep``: the rule's alone).
           Times are ``block_until_ready`` medians over streams of ten
           calls.

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

#: the two cells' grouped matmuls: the rows of the buffer, the rows that
#: are real (a held share's buffer is four times what uniform routing
#: sends: ``ops.moe.HELD_SLACK``), groups, model width, expert width
SHAPES = {
    "olmoe": dict(rows=32768, real=32768, groups=64, d=2048, f=1024),
    "laguna_held": dict(rows=5120, real=1280, groups=8, d=3072, f=1024),
}
#: (m, k, n) tiles of the library kernel: the one the program ran until
#: PR 34 first; past 512 x 1024 x 1024 the chip's compiler refuses it
#: for the 16 MB of scoped VMEM (CPU, compile only, PR 25)
TILINGS = ((256, 1024, 1024), (512, 512, 512), (512, 1024, 1024))
#: row tiles of the repo's kernels to try beside the rule's own
ROW_TILES = (128, 256, 512, 1024)


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


def timed_stream(fn, *args, calls: int = 10, reps: int = 5) -> float:
    """Median milliseconds a call over ``reps`` streams of ``calls``
    calls, each stream ended by ``block_until_ready``: the device's
    time where the host's part of one call (≈ 0.1 ms) would show in a
    call of 1 ms."""
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) * 1e3 / calls)
    return statistics.median(times)


def group_sizes(real: int, groups: int, seed: int = 0) -> np.ndarray:
    """Rows per expert as a uniform router would leave them: a
    multinomial around real / groups, not multiples of any tile."""
    rng = np.random.default_rng(seed)
    return rng.multinomial(real, np.full(groups, 1.0 / groups)).astype(
        np.int32)


# -- the arms: {"fwd": (lhs, rhs, sizes), "dlhs": (grad, rhs, sizes),
#    "drhs": (lhs, grad, sizes)} -> callables; an arm may bring some ---
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

    return {"fwd": fwd, "dlhs": dlhs, "drhs": drhs}


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

    return {"fwd": fwd, "dlhs": dlhs, "drhs": drhs}


def znicz_arm(gmm_tiles=None, gmm_t_tiles=None, tgmm_tiles=None,
              sub=None):
    """The repo's kernels (``ops/pallas_gmm.py``, PR 34); ``None`` =
    the tile rule's own choice; ``sub`` = the rows a straddling tile is
    computed by.  The row gradient leaves in bf16, as the program
    takes it."""
    from znicz_tpu.ops import pallas_gmm

    def fwd(lhs, rhs, sizes):
        return pallas_gmm.znicz_gmm(lhs, rhs, sizes, tiles=gmm_tiles,
                                    sub=sub)

    def dlhs(grad, rhs, sizes):
        return pallas_gmm.znicz_gmm(grad, rhs, sizes, transpose_rhs=True,
                                    out_dtype=jnp.bfloat16,
                                    tiles=gmm_t_tiles, sub=sub)

    def drhs(lhs, grad, sizes):
        # the slabs; the kernel sums their squares beside them (PR 44)
        # whether or not anyone reads the sum, so the call is timed
        # as the step runs it
        return pallas_gmm.znicz_tgmm(lhs, grad, sizes, tiles=tgmm_tiles,
                                     sub=sub)[0]

    return {"fwd": fwd, "dlhs": dlhs, "drhs": drhs}


def _only(arm: dict, name: str) -> dict:
    return {name: arm[name]}


def gmm_cases(rows: int, k: int, n: int, sweep: bool):
    """``(arm, tiling, {name: callable})``; the first is the
    yardstick."""
    from znicz_tpu.ops import pallas_gmm
    yield "ragged_dot", None, ragged_arm()
    for tiling in TILINGS if sweep else TILINGS[:1]:
        yield "megablox", tiling, megablox_arm(tiling)
    rule = {"gmm": pallas_gmm.gmm_tiles(rows, k, n),
            "gmm_t": pallas_gmm.gmm_tiles(rows, n, k),
            "tgmm": pallas_gmm.tgmm_tiles(rows, k, n),
            "sub": pallas_gmm.PART_ROWS}
    yield "znicz", rule, znicz_arm()
    if not sweep:
        return
    for tm in ROW_TILES:
        for sub in sorted({min(tm, 128), tm}):
            label = {"gmm": (tm, n), "gmm_t": (tm, k), "sub": sub}
            if (tm, sub) != (rule["gmm"][0], 128):
                arm = znicz_arm((tm, n), (tm, k), sub=sub)
                yield "znicz", label, {"fwd": arm["fwd"],
                                       "dlhs": arm["dlhs"]}
            if (tm, sub) != (rule["tgmm"][0], 128):
                yield "znicz", {"tgmm": (tm, k, n), "sub": sub}, _only(
                    znicz_arm(tgmm_tiles=(tm, k, n), sub=sub), "drhs")


def gmm_shapes(rows: int, groups: int, k: int, n: int, sharding=None):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return {"lhs": spec((rows, k), jnp.bfloat16),
            "rhs": spec((groups, k, n), jnp.bfloat16),
            "grad": spec((rows, n), jnp.bfloat16),
            "sizes": spec((groups,), jnp.int32)}


#: what each function takes
TAKES = {"fwd": ("lhs", "rhs", "sizes"), "dlhs": ("grad", "rhs", "sizes"),
         "drhs": ("lhs", "grad", "sizes")}


def stage_gmm(compile_only: bool, sharding=None, sweep: bool = True,
              shapes=tuple(SHAPES)) -> None:
    """Every arm at both cells' shapes, both orientations of the slab
    (model width × expert width: gate and up; expert width × model
    width: down): ms a call (:func:`timed_stream`), TFLOP/s over the
    REAL rows, and the largest difference from ``ragged_dot`` over the
    real rows (the rows past the groups are each arm's own)."""
    for shape in shapes:
        dims = SHAPES[shape]
        rows, real, groups = dims["rows"], dims["real"], dims["groups"]
        sizes_np = group_sizes(real, groups)
        for k, n in ((dims["d"], dims["f"]), (dims["f"], dims["d"])):
            flops = 2.0 * real * k * n
            if compile_only:
                data = gmm_shapes(rows, groups, k, n, sharding)
            else:
                key = jax.random.key(0)
                live = (np.arange(rows) < real)[:, None]
                data = {
                    "lhs": jnp.where(live, jax.random.normal(
                        key, (rows, k), jnp.bfloat16), 0),
                    "rhs": (jax.random.normal(key, (groups, k, n),
                                              jnp.float32)
                            / np.sqrt(k)).astype(jnp.bfloat16),
                    "grad": jnp.where(live, jax.random.normal(
                        key, (rows, n), jnp.bfloat16), 0),
                    "sizes": jnp.asarray(sizes_np)}
            want = {}
            for arm, tiling, fns in gmm_cases(rows, k, n, sweep):
                line = {"stage": "gmm", "shape": shape, "arm": arm,
                        "tiling": tiling, "k": k, "n": n}
                for name, fn in fns.items():
                    args = [data[a] for a in TAKES[name]]
                    try:
                        if compile_only:
                            jax.jit(fn).lower(*args).compile()
                            line[name] = "compiles"
                            continue
                        jitted = jax.jit(fn)
                        out = jitted(*args).astype(jnp.float32)
                        if name != "drhs":
                            out = out[:real]
                        if name not in want:   # the first arm
                            want[name] = out
                        line[f"{name}_diff"] = float(
                            jnp.abs(out - want[name]).max()
                            / jnp.abs(want[name]).max())
                        ms = timed_stream(jitted, *args)
                        line[f"{name}_ms"] = round(ms, 4)
                        line[f"{name}_tflops"] = round(
                            flops / ms / 1e9, 1)
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
    parser.add_argument("--no-sweep", action="store_true",
                        help="gmm: one tiling an arm, not the table")
    parser.add_argument("--shapes", nargs="*", default=list(SHAPES),
                        choices=list(SHAPES), help="gmm: the cells")
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
        if stage == "gmm":
            stage_gmm(args.compile_only, sharding, not args.no_sweep,
                      tuple(args.shapes))
        else:
            stage_flash(args.compile_only, sharding)
    return 0


if __name__ == "__main__":
    sys.exit(main())

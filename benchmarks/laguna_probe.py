#!/usr/bin/env python3
"""What the Laguna cell asks of the chip before it is built (PERF.md
§6, PR 29), answered in one process:

``band``   the windowed flash kernels at B 1 × T × dh 128, 72 query
           heads on 8 K/V heads, window 512, forward and backward, at
           grid tiles of 256 and 512, against the plain core with the
           band mask on a slice of the heads; executed and band shares
           beside the times.
``gqa``    the causal kernels at 48 query heads on 8 K/V heads (the
           full-attention layers): grouped index maps and the dk/dv
           sum over a group inside the kernel, against the plain core.
``held``   the grouped matmul over a buffer whose groups end before
           its rows do (8 experts' pairs of a step, capacity 2 × the
           expected), forward and both gradients, with the tail zeroed.

    chiprun -- python3 benchmarks/laguna_probe.py [--t 8192]
    python3 benchmarks/laguna_probe.py --compile-only      # here: the
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

DH, KV, WINDOW, D, F = 128, 8, 512, 3072, 1024


def emit(**line) -> None:
    line["platform"] = jax.devices()[0].platform
    print(json.dumps(line), flush=True)


def timed(fn, *args, reps: int = 10) -> float:
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(times)


def with_grads(fn):
    def run(q, k, v, cot):
        out, pull = jax.vjp(fn, q, k, v)
        return (out,) + pull(cot)
    return jax.jit(run)


def stage_attention(stage: str, t: int, heads: int, window, edges,
                    compile_only: bool, sharding) -> None:
    from znicz_tpu.ops import pallas_attention as pa
    from znicz_tpu.parallel.ring_attention import local_attention

    def flash(edge):
        def run(q, k, v):
            return pa.flash_attention(
                q, k, v, causal=True, dot_dtype=jnp.bfloat16,
                window=window, block_q=edge, block_k=edge)
        return run

    def core(q, k, v):
        return local_attention(q, k, v, causal=True,
                               dot_dtype=jnp.bfloat16, window=window)

    shapes = [(1, t, heads, DH), (1, t, KV, DH), (1, t, KV, DH),
              (1, t, heads, DH)]
    for edge in edges:
        if compile_only:
            specs = [jax.ShapeDtypeStruct(s, jnp.float32,
                                          sharding=sharding)
                     for s in shapes]
            with_grads(flash(edge)).lower(*specs).compile()
            emit(stage=stage, t=t, heads=heads, window=window,
                 edge=edge, kernels="compile")
            continue
        keys = jax.random.split(jax.random.key(1), 4)
        q, k, v, cot = (jax.random.normal(key, s, jnp.float32)
                        for key, s in zip(keys, shapes))
        got = with_grads(flash(edge))(q, k, v, cot)
        # the plain core on ONE group (its (T, T) scores fit): query
        # heads 0 … group − 1 on K/V head 0; dk, dv of that head sum
        # over exactly those
        group = heads // KV
        part = with_grads(core)(q[:, :, :group], k[:, :, :1],
                                v[:, :, :1], cot[:, :, :group])
        mine = with_grads(flash(edge))(q[:, :, :group], k[:, :, :1],
                                       v[:, :, :1], cot[:, :, :group])
        errs = {name: float(jnp.abs(g - w).max()
                            / (jnp.abs(w).max() + 1e-6))
                for name, g, w in zip(("out", "dq", "dk", "dv"), mine,
                                      part)}
        # the whole call against the one-group call: the grouping
        whole = {"out": got[0][:, :, :group], "dq": got[1][:, :, :group],
                 "dk": got[2][:, :, :1], "dv": got[3][:, :, :1]}
        errs.update({f"{name}_grouping": float(
            jnp.abs(whole[name] - m).max() / (jnp.abs(m).max() + 1e-6))
            for name, m in zip(("out", "dq", "dk", "dv"), mine)})
        fwd_ms = timed(jax.jit(flash(edge)), q, k, v)
        both_ms = timed(with_grads(flash(edge)), q, k, v, cot)
        bq, bk = (pa.band_blocks(t, edge, edge) if window
                  else pa.grid_blocks(True, t, t, edge, edge))
        sub = (bq, bk) if window else pa.sub_tile_for(True, bq, bk)
        counts = pa.causal_tile_counts(t, t, bq, bk, *sub, window=window)
        pairs = pa.band_share(t, window) * t * t
        emit(stage=stage, t=t, heads=heads, window=window,
             blocks=[bq, bk], errs=errs,
             ok=bool(max(errs.values()) <= 3e-2),
             fwd_ms=fwd_ms, fwd_bwd_ms=both_ms,
             executed_share=counts["executed_share"],
             band_share=pa.band_share(t, window),
             fwd_tflops=4 * DH * heads * pairs / fwd_ms / 1e9,
             fwd_bwd_tflops=14 * DH * heads * pairs / both_ms / 1e9)


def stage_held(t: int, compile_only: bool, sharding) -> None:
    from znicz_tpu.ops.moe import grouped_matmul
    held, experts, top_k = 8, 256, 10
    expected = t * top_k * held // experts
    capacity = 2 * expected
    rng = np.random.default_rng(0)
    sizes = rng.multinomial(expected, np.full(held, 1 / held)).astype(
        np.int32)

    def run(rows, w_up, w_down, cot, sizes):
        def f(rows, w_up, w_down):
            hidden = grouped_matmul(rows, w_up, sizes, True, False)
            return grouped_matmul(hidden.astype(jnp.bfloat16), w_down,
                                  sizes, True, False)
        out, pull = jax.vjp(f, rows, w_up, w_down)
        return (out,) + pull(cot)

    shapes = [((capacity, D), jnp.bfloat16), ((held, D, F), jnp.float32),
              ((held, F, D), jnp.float32), ((capacity, D), jnp.float32),
              ((held,), jnp.int32)]
    if compile_only:
        specs = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
                 for s, d in shapes]
        jax.jit(run).lower(*specs).compile()
        emit(stage="held", capacity=capacity, kernels="compile")
        return
    keys = jax.random.split(jax.random.key(2), 4)
    args = [jax.random.normal(key, s, jnp.float32).astype(d) * 0.05
            for key, (s, d) in zip(keys, shapes[:4])]
    live = np.arange(capacity) < expected
    args[0] = jnp.where(live[:, None], args[0], 0).astype(jnp.bfloat16)
    args[3] = jnp.where(live[:, None], args[3], 0)
    got = jax.jit(run)(*args, jnp.asarray(sizes))

    want = _plain_rows(args, sizes, capacity)
    err = float(jnp.abs(got[0] - want).max() / (jnp.abs(want).max()
                                                + 1e-9))
    tail = float(jnp.abs(got[0][expected:]).max())
    tail_grad = float(jnp.abs(got[1][expected:]).max())
    finite = bool(all(jnp.isfinite(g).all() for g in got))
    ms = timed(jax.jit(run), *args, jnp.asarray(sizes))
    emit(stage="held", capacity=capacity, rows_here=expected, err=err,
         tail_max=tail, tail_grad_max=tail_grad, finite=finite,
         ok=bool(err <= 2e-2 and tail == 0 and tail_grad == 0
                 and finite), fwd_bwd_ms=ms)


def _plain_rows(args, sizes, capacity: int):
    """The two grouped matmuls one expert at a time, the tail zero."""
    rows, w_up, w_down = (a.astype(jnp.bfloat16).astype(jnp.float32)
                          for a in args[:3])
    out, lo = [], 0
    for e, n in enumerate(int(n) for n in sizes):
        up = jnp.dot(rows[lo:lo + n], w_up[e], precision="highest")
        up = up.astype(jnp.bfloat16).astype(jnp.float32)
        out.append(jnp.dot(up, w_down[e], precision="highest"))
        lo += n
    out.append(jnp.zeros((capacity - lo, out[0].shape[1]), jnp.float32))
    return jnp.concatenate(out, axis=0)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("stages", nargs="*",
                        default=["band", "gqa", "held"])
    parser.add_argument("--t", type=int, default=8192)
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
        print("laguna_probe: no TPU; --compile-only rehearses the "
              "compiles", file=sys.stderr)
        return 2
    for stage in args.stages:
        if stage == "band":
            stage_attention("band", args.t, 72, WINDOW, (256, 512),
                            args.compile_only, sharding)
        elif stage == "gqa":
            stage_attention("gqa", args.t, 48, None, (None,),
                            args.compile_only, sharding)
        else:
            stage_held(args.t, args.compile_only, sharding)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Pallas TPU kernels for irregular hot ops.

SURVEY.md §2.3 maps the reference's hand-written OpenCL/CUDA kernel
corpus onto XLA ops, with Pallas reserved for the fused/irregular
cases.  This module holds those kernels; the first resident is the
**cross-channel LRN** (AlexNet's normalization layer, reference:
``znicz/ocl|cuda`` normalization kernels):

- the forward fuses square → sliding channel-window sum → pow →
  multiply into one VMEM pass over the activations (the plain-XLA
  path now rides the MXU via a constant band-matrix matmul — see
  ``normalization._window_sum`` — which is why Pallas stays opt-in);
- the backward fuses the analytic gradient the same way (one pass,
  two window sums) instead of re-running the forward under ``jax.vjp``.

Both run on a 1-D grid over row tiles with the channel axis resident
in lanes; ``interpret=True`` runs them on CPU for the test oracle
comparison (tests force the cpu platform).

Gating: units call :func:`use_pallas` — True only on real TPU devices
and when ``root.common.engine.use_pallas`` is not disabled, so every
other platform keeps the plain-XLA path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# THE window-sum definition (shared with the numpy oracle and the jnp
# forward — one source of truth for the window/adjoint convention)
from znicz_tpu.ops.normalization import _window_sum as _window_sum_xp

#: rows per grid step (sublane-aligned; channels ride the lane axis)
_TILE_ROWS = 512


def is_tpu_device(device) -> bool:
    """True when ``device`` fronts a real TPU (Pallas kernels can
    compile)."""
    jax_device = getattr(device, "jax_device", None)
    return jax_device is not None and jax_device.platform == "tpu"


def kernel_refusal(device, option: str, interpret: bool) -> str | None:
    """Resolve a default-on-TPU kernel gate (``engine.<option>``:
    "auto" or a bool) against ``device``: ``None`` when the kernel may
    engage, else the reason it may not — the units log it, so a run
    that fell back to the XLA cores says why.  "auto" engages on a
    real TPU only; an explicit True also engages under
    ``engine.pallas_interpret`` (the CPU-mesh testing lever)."""
    from znicz_tpu.utils.config import root
    flag = root.common.engine.get(option, "auto")
    if flag != "auto" and not flag:
        return f"engine.{option} is off"
    if is_tpu_device(device) or (flag != "auto" and interpret):
        return None
    platform = getattr(getattr(device, "jax_device", None), "platform",
                       getattr(device, "backend", None))
    return f"device platform {platform} is not a TPU"


def use_pallas(device, op: str | None = None) -> bool:
    """Pallas path gate: TPU platform + config switch.

    **Default OFF** (``root.common.engine.use_pallas`` opts in —
    ``True`` enables every Pallas variant; a list/tuple/set of op
    names (``["dropout"]``) enables per-op, which is how the in-graph
    A/Bs isolate one kernel).  The standalone microbenchmark
    (PALLAS_BENCH.md) has the Pallas LRN ahead of the jnp composition,
    but IN-GRAPH the picture inverts: `pallas_call` pins its operand
    to a 2-D row-major layout, so XLA brackets every call with layout
    copies + reshapes of the (n,55,55,96) activations — profiled at
    ~40% of the AlexNet step (profiles/r03_b256), and the chip A/B
    measured plain XLA 24% faster end-to-end (7795 vs 6263 img/s,
    batch 256).  The fused-XLA LRN fuses into its conv/pool neighbors
    with no layout constraint.

    **Compile-time flag**: units resolve this ONCE at ``initialize``
    and bake the result into their traced program — flipping
    ``root.common.engine.use_pallas`` after a region compiled has no
    effect for that workflow's lifetime (re-initialize to re-decide).
    """
    from znicz_tpu.utils.config import root
    if not is_tpu_device(device):
        return False
    val = root.common.engine.get("use_pallas", False)
    if isinstance(val, (list, tuple, set, frozenset)):
        return op is not None and op in val
    return bool(val)


# ----------------------------------------------------------------------
# LRN: d_i = k + α·Σ_{j∈win(i)} x_j² ;  y_i = x_i · d_i^{−β}
# ----------------------------------------------------------------------
def _window_sum(arr, n: int, half_low: int):
    """Sliding sum over the last (lane) axis — the shared xp-generic
    definition traced with jnp inside the kernel."""
    return _window_sum_xp(jnp, arr, n, half_low=half_low,
                          via_matmul=False)


def _lrn_fwd_kernel(x_ref, o_ref, *, alpha, beta, k, n):
    x = x_ref[:]
    d = k + alpha * _window_sum(x * x, n, n // 2)
    o_ref[:] = x * d ** (-beta)


def _lrn_bwd_kernel(x_ref, err_ref, o_ref, *, alpha, beta, k, n):
    # dy_i/dx_j = δ_ij·d_i^{−β} − 2αβ·x_i·x_j·d_i^{−β−1}·[j∈win(i)];
    # err_input_j = err_j·d_j^{−β} − 2αβ·x_j·Σ_{i: j∈win(i)} t_i with
    # t_i = err_i·x_i·d_i^{−β−1} — the second sum is the window
    # operator's ADJOINT (half_low mirrored; differs for even n)
    x = x_ref[:]
    err = err_ref[:]
    d = k + alpha * _window_sum(x * x, n, n // 2)
    t = err * x * d ** (-beta - 1.0)
    o_ref[:] = (err * d ** (-beta)
                - 2.0 * alpha * beta * x
                * _window_sum(t, n, n - 1 - n // 2))


def _row_tiled_call(kernel, out_like, *inputs, name, interpret=False):
    """Run an elementwise-rows kernel over (M, C) arrays on a 1-D row
    grid; ``name`` is what a profile calls the kernel."""
    m, c = out_like.shape
    tile = min(_TILE_ROWS, m)
    spec = pl.BlockSpec((tile, c), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(m, tile),),
        in_specs=[spec] * len(inputs),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((m, c), out_like.dtype),
        interpret=interpret,
        name=name,
    )(*inputs)


# ----------------------------------------------------------------------
# Dropout: PRNG mask + apply in one VMEM pass (candidate; measured
# against the jax.random path by benchmarks/pallas_microbench.py)
# ----------------------------------------------------------------------
def _dropout_kernel(seed_ref, x_ref, o_ref, *, drop_ratio):
    pltpu.prng_seed(seed_ref[0] + pl.program_id(0))
    bits = pltpu.prng_random_bits(x_ref.shape)
    threshold = jnp.uint32(int(drop_ratio * (2 ** 32 - 1)))
    keep = bits.astype(jnp.uint32) > threshold
    scale = 1.0 / (1.0 - drop_ratio)
    o_ref[:] = jnp.where(keep, x_ref[:] * scale, 0.0)


def dropout_apply(x, seed, drop_ratio: float, interpret: bool = False):
    """Fused mask-generate + apply: TPU-core PRNG bits in VMEM instead
    of a materialized threefry mask array from ``jax.random``.

    ``seed``: int32 scalar array.  Inverted-dropout scaling matches
    ``ops/dropout.py`` (keep → ×1/(1−ratio))."""
    shape = x.shape
    x2d = x.reshape(-1, shape[-1])
    m, c = x2d.shape
    tile = min(_TILE_ROWS, m)
    spec = pl.BlockSpec((tile, c), lambda i: (i, 0))
    kernel = functools.partial(_dropout_kernel, drop_ratio=drop_ratio)
    out = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(m, tile),),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((m, c), x.dtype),
        interpret=interpret,
        name="znicz_dropout",
    )(jnp.asarray(seed, jnp.int32).reshape(1), x2d)
    return out.reshape(shape)


# ----------------------------------------------------------------------
# LayerNorm: per-row statistics + scale/shift in one VMEM pass; the
# backward fuses dx with the cross-row γ/β grad accumulation (scratch
# accumulators over a sequential row-tile grid) — the XLA composition
# materializes xhat and the f32 upcasts between passes (profiled at
# ~8% of the T=2048 seq step, PERF.md round 5)
# ----------------------------------------------------------------------
def _ln_fwd_kernel(x_ref, g_ref, b_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    mu = jnp.mean(x, axis=1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    y = xc * jax.lax.rsqrt(var + eps) * g_ref[...] + b_ref[...]
    o_ref[...] = y.astype(o_ref.dtype)


def _ln_bwd_kernel(*refs, eps, m, tile, has_beta):
    if has_beta:
        (x_ref, e_ref, g_ref, dx_ref, gg_ref, gb_ref,
         gg_scr, gb_scr) = refs
    else:  # β-less layer norm: no grad_beta output/accumulator
        x_ref, e_ref, g_ref, dx_ref, gg_ref, gg_scr = refs
        gb_ref = gb_scr = None
    i = pl.program_id(0)
    n = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        gg_scr[...] = jnp.zeros_like(gg_scr)
        if has_beta:
            gb_scr[...] = jnp.zeros_like(gb_scr)

    x = x_ref[...].astype(jnp.float32)
    err = e_ref[...].astype(jnp.float32)
    # tail tile: rows beyond m are UNDEFINED padding — zero BOTH
    # operands so the cross-row grad sums stay clean (masked err
    # alone wouldn't neutralize a non-finite x̂ from garbage x:
    # 0·NaN = NaN would poison the accumulators); per-row dx for
    # padded rows is garbage-in-garbage-out and its stores land out
    # of bounds, which Pallas drops
    rows = i * tile + jax.lax.broadcasted_iota(
        jnp.int32, err.shape, 0)
    valid = rows < m
    err = jnp.where(valid, err, 0.0)
    x = jnp.where(valid, x, 0.0)
    mu = jnp.mean(x, axis=1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = xc * rstd
    dxhat = err * g_ref[...]
    dx = (dxhat - jnp.mean(dxhat, axis=1, keepdims=True)
          - xhat * jnp.mean(dxhat * xhat, axis=1, keepdims=True)) \
        * rstd
    dx_ref[...] = dx.astype(dx_ref.dtype)
    gg_scr[...] += jnp.sum(err * xhat, axis=0, keepdims=True)
    if has_beta:
        gb_scr[...] += jnp.sum(err, axis=0, keepdims=True)

    @pl.when(i == n - 1)
    def _finish():
        gg_ref[...] = gg_scr[...]
        if has_beta:
            gb_ref[...] = gb_scr[...]


def _row_shard_axes(spec) -> tuple[str, ...]:
    """The mesh axes a kernel shard spec splits rows over — the psum
    axes for cross-row reductions (γ/β gradient sums)."""
    return tuple(
        name for entry in spec if entry is not None
        for name in ((entry,) if isinstance(entry, str)
                     else tuple(entry)))


def layer_norm_forward(x, gamma, beta, eps: float,
                       interpret: bool = False, mesh=None, spec=None):
    """Fused layer norm over (..., D): f32 statistics in VMEM, output
    stored at the input dtype.  ``beta`` may be None (no-shift).

    ``mesh``/``spec`` (a PartitionSpec over ``x``'s dims, from
    :func:`znicz_tpu.parallel.mesh.kernel_shard_spec`) run the kernel
    per-shard under ``shard_map`` — the mesh-native path; an opaque
    ``pallas_call`` under GSPMD would gather the operand onto every
    device.  The feature (last) axis must stay whole; row dims (batch
    over ``data``, a ring-sharded time axis over ``model``) may
    shard freely since every statistic is per-row.
    """
    if mesh is not None and spec is not None \
            and any(a is not None for a in spec):
        if spec[len(x.shape) - 1] is not None:
            raise ValueError(
                f"layer_norm shard spec {spec} shards the feature "
                f"axis — statistics reduce over it; rows must stay "
                f"whole")
        from jax.sharding import PartitionSpec as P
        rep = P()
        # check_vma off: an opaque pallas_call has no replication
        # rule for the checker
        if beta is None:
            fn = jax.shard_map(
                lambda xs, g: layer_norm_forward(
                    xs, g, None, eps, interpret=interpret),
                mesh=mesh, in_specs=(spec, rep), out_specs=spec,
                check_vma=False)
            return fn(x, gamma)
        fn = jax.shard_map(
            lambda xs, g, bb: layer_norm_forward(
                xs, g, bb, eps, interpret=interpret),
            mesh=mesh, in_specs=(spec, rep, rep), out_specs=spec,
            check_vma=False)
        return fn(x, gamma, beta)
    shape = x.shape
    x2d = x.reshape(-1, shape[-1])
    m, d = x2d.shape
    if beta is None:
        beta = jnp.zeros((), jnp.float32)
    tile = min(_TILE_ROWS, m)
    spec = pl.BlockSpec((tile, d), lambda i: (i, 0))
    pspec = pl.BlockSpec((1, d), lambda i: (0, 0))
    out = pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps),
        grid=(pl.cdiv(m, tile),),
        in_specs=[spec, pspec, pspec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((m, d), x.dtype),
        interpret=interpret,
        name="znicz_layer_norm_fwd",
    )(x2d, gamma.reshape(1, d).astype(jnp.float32),
      jnp.broadcast_to(beta, (1, d)).astype(jnp.float32))
    return out.reshape(shape)


def layer_norm_backward(x, err, gamma, eps: float,
                        with_beta: bool = True,
                        interpret: bool = False, mesh=None, spec=None):
    """Fused layer-norm backward: per-row dx plus the cross-row γ (and
    β when ``with_beta``) gradient sums, one pass.  Returns
    (dx, grad_gamma, grad_beta-or-None) with the grads in f32 shape
    (D,).

    ``mesh``/``spec``: the mesh-native path (same contract as
    :func:`layer_norm_forward`); dx stays sharded like ``err`` while
    the γ/β partial sums — per-shard rows only — are ``psum``'d over
    every row-sharding mesh axis, landing replicated exactly like the
    GSPMD reduction the XLA fallback path gets for free.
    """
    if mesh is not None and spec is not None \
            and any(a is not None for a in spec):
        if spec[len(x.shape) - 1] is not None:
            raise ValueError(
                f"layer_norm shard spec {spec} shards the feature "
                f"axis — statistics reduce over it; rows must stay "
                f"whole")
        from jax.sharding import PartitionSpec as P
        reduce_axes = _row_shard_axes(spec)

        def body(xs, es, g):
            dx, gg, gb = layer_norm_backward(
                xs, es, g, eps, with_beta=with_beta,
                interpret=interpret)
            gg = jax.lax.psum(gg, reduce_axes)
            if gb is not None:
                gb = jax.lax.psum(gb, reduce_axes)
            return (dx, gg, gb) if with_beta else (dx, gg)

        rep = P()
        fn = jax.shard_map(
            body, mesh=mesh, in_specs=(spec, spec, rep),
            out_specs=(spec, rep, rep) if with_beta else (spec, rep),
            check_vma=False)
        if with_beta:
            return fn(x, err, gamma)
        dx, gg = fn(x, err, gamma)
        return dx, gg, None
    shape = x.shape
    d = shape[-1]

    x2d = x.reshape(-1, d)
    e2d = err.reshape(-1, d)
    m = x2d.shape[0]
    tile = min(_TILE_ROWS, m)
    spec = pl.BlockSpec((tile, d), lambda i: (i, 0))
    pspec = pl.BlockSpec((1, d), lambda i: (0, 0))
    out_specs = [spec, pspec] + ([pspec] if with_beta else [])
    out_shape = [jax.ShapeDtypeStruct((m, d), err.dtype),
                 jax.ShapeDtypeStruct((1, d), jnp.float32)] \
        + ([jax.ShapeDtypeStruct((1, d), jnp.float32)]
           if with_beta else [])
    scratch = [pltpu.VMEM((1, d), jnp.float32)
               for _ in range(2 if with_beta else 1)]
    out = pl.pallas_call(
        functools.partial(_ln_bwd_kernel, eps=eps, m=m, tile=tile,
                          has_beta=with_beta),
        grid=(pl.cdiv(m, tile),),
        in_specs=[spec, spec, pspec],
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shape),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="znicz_layer_norm_bwd",
    )(x2d, e2d, gamma.reshape(1, d).astype(jnp.float32))
    gb = out[2][0] if with_beta else None
    return out[0].reshape(shape), out[1][0], gb


# ----------------------------------------------------------------------
# Softmax (+ argmax): one row pass — max, exp, sum, divide, argmax
# fused in VMEM (candidate; the XLA composition is 3-4 HBM passes)
# ----------------------------------------------------------------------
def _softmax_argmax_kernel(v_ref, y_ref, idx_ref):
    v = v_ref[:]
    m = jnp.max(v, axis=1, keepdims=True)
    e = jnp.exp(v - m)
    y_ref[:] = e / jnp.sum(e, axis=1, keepdims=True)
    idx_ref[:] = jnp.argmax(v, axis=1, keepdims=True).astype(jnp.int32)


def softmax_argmax(v, interpret: bool = False):
    """Row softmax + winner index in one pass over (batch, n_classes).

    Returns ``(probs, max_idx)`` matching ``All2AllSoftmax``'s
    stabilized softmax + ``max_idx`` contract."""
    m, c = v.shape
    tile = min(_TILE_ROWS, m)
    spec = pl.BlockSpec((tile, c), lambda i: (i, 0))
    idx_spec = pl.BlockSpec((tile, 1), lambda i: (i, 0))
    probs, idx = pl.pallas_call(
        _softmax_argmax_kernel,
        grid=(pl.cdiv(m, tile),),
        in_specs=[spec],
        out_specs=(spec, idx_spec),
        out_shape=(jax.ShapeDtypeStruct((m, c), v.dtype),
                   jax.ShapeDtypeStruct((m, 1), jnp.int32)),
        interpret=interpret,
        name="znicz_softmax_argmax",
    )(v)
    return probs, idx[:, 0]


def lrn_forward(x, alpha: float, beta: float, k: float, n: int,
                interpret: bool = False):
    """Fused LRN forward over an ND array whose LAST axis is channels."""
    shape = x.shape
    x2d = x.reshape(-1, shape[-1])
    kernel = functools.partial(_lrn_fwd_kernel, alpha=alpha, beta=beta,
                               k=k, n=n)
    return _row_tiled_call(kernel, x2d, x2d, name="znicz_lrn_fwd",
                           interpret=interpret).reshape(shape)


def lrn_backward(x, err_output, alpha: float, beta: float, k: float,
                 n: int, interpret: bool = False):
    """Fused LRN analytic gradient (one pass, two window sums)."""
    shape = x.shape
    x2d = x.reshape(-1, shape[-1])
    err2d = err_output.reshape(-1, shape[-1])
    kernel = functools.partial(_lrn_bwd_kernel, alpha=alpha, beta=beta,
                               k=k, n=n)
    return _row_tiled_call(kernel, x2d, x2d, err2d,
                           name="znicz_lrn_bwd",
                           interpret=interpret).reshape(shape)

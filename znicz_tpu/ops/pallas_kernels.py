"""Pallas TPU kernels for row-wise hot ops.

SURVEY.md §2.3 maps the reference's hand-written OpenCL/CUDA kernel
corpus onto XLA ops, with Pallas reserved for the fused/irregular
cases.  This module holds the **layer-norm** pair (the flash-attention
kernels live in ``ops/pallas_attention.py``) and the two gates every
kernel-carrying unit asks: :func:`is_tpu_device` and
:func:`kernel_refusal`.

The kernels run on a 1-D grid over row tiles with the feature axis
resident in lanes; ``interpret=True`` runs them on CPU for the test
oracle comparison (tests force the cpu platform).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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

"""The gated short convolution's chain between its two projections
(``ops/short_conv.py``; LFM2's ``conv`` layers, PR 43) as one kernel
each way.  Per channel c of p = m W_in = [B | C | x̃], (B, T, 3·D) f32,

.. code-block:: text

    u_t = B_t · x̃_t
    c_t = Σ_{j<J} taps[c, j] · u_{t−J+1+j}      zeros before the sequence
    y_t = C_t · c_t                             no activation

``znicz_short_conv_fwd`` reads the three D-column blocks of the
projection where the matmul wrote them and writes y (B, T, D) at the
width W_out's matmul takes it in (bf16 in mixed precision, else f32).
``znicz_short_conv_bwd`` reads p, the taps and y's cotangent, makes u
and c again in VMEM and writes the projection's cotangent where p lies
(ONE array, all three column blocks) and the taps' (summed over the
row tiles and the batch in a block that stays in VMEM): what the
``custom_vjp`` keeps is p and the taps.

A file of its own, not a part of ``pallas_delta``'s ``qkv_prep``: that
pair is cut to the delta rule (heads, L2 norms, head-major outputs
padded to whole chunks, three calls a way over column ranges); this
chain has neither heads nor a layout to change, and its backward must
write three column blocks of ONE array from what it reads of all three
— so a grid step here takes ``ROWS`` rows of the projection at its FULL
width (contiguous rows) and walks the channels 128 lanes at a time
inside.  What the two share lies in ``pallas_taps``: the sublane
rotation that makes u_{t−s} (``delayed``), the taps' sum (``taps_sum``)
and the 8-row reads.  u_{t−s} at a grid step's first rows comes through
a second ``BlockSpec`` over the same array (the rows before: the halo;
zeros at a sequence's start), the backward's dc_{t+s} likewise from the
rows AFTER (zero past the sequence's end).  A halo block is 16 rows —
a whole tile at either width y's cotangent may have.  Sequences are a
grid axis: nothing crosses from one into the next.  Everything f32
inside; the formulas are ``short_conv.chain``'s, the ``jax.numpy`` form
that runs wherever :func:`legal` says no.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from znicz_tpu.ops.pallas_taps import (LANES, SUBLANES, column_sums, delayed,
                                       eight_rows, ones_where, taps_sum,
                                       unless)

#: rows of a sequence a grid step takes (a block is rows × 3·D f32:
#: 6.3 MB at D 2,048, twice over for the pipeline, each way), walked
#: inside the step in sub-tiles of ``_SUB`` rows × 128 lanes
ROWS = 256
_SUB = 64
#: rows of a halo block: a whole tile of f32 (8) and of bf16 (16)
_HALO = 16


def legal(t: int, d: int, taps: int) -> str | None:
    """``None`` where the kernels take a (·, ``t``, 3·``d``) projection
    under ``taps`` taps, else why not."""
    if d % LANES:
        return f"{d} channels are not whole {LANES}-lane tiles"
    if t % _HALO:
        return f"{t} positions are not whole {_HALO}-row tiles"
    if not 2 <= taps <= SUBLANES:
        return f"{taps} taps do not fit the {SUBLANES}-row halo"
    return None


def _walk(length: int):
    """Rows a grid step, the row tiles, the sub-tile, and what the
    backward's body is told beside it: the length and whether any block
    reaches past it (the forward's rows past the end are never written
    back)."""
    rows = min(ROWS, length)        # whole halo tiles: :func:`legal`
    tiles = pl.cdiv(length, rows)
    sub = _SUB if rows % _SUB == 0 else _HALO
    return rows, tiles, sub, dict(length=length,
                                  masked=length != tiles * rows)


def _specs(rows: int, length: int, d: int, taps: int):
    per = rows // _HALO

    def after(i):               # held inside the array; masked past it
        return jnp.minimum((i + 1) * per, pl.cdiv(length, _HALO) - 1)

    def block(height, width, at):
        return pl.BlockSpec((None, height, width),
                            lambda b, i: (b, at(i), 0))

    return dict(
        p=block(rows, 3 * d, lambda i: i),
        p_before=block(_HALO, 3 * d,
                       lambda i: jnp.maximum(i * per - 1, 0)),
        p_after=block(_HALO, 3 * d, after),
        y=block(rows, d, lambda i: i),
        y_after=block(_HALO, d, after),
        taps=pl.BlockSpec((taps, d), lambda b, i: (0, 0)))


def _params(semantics, rows: int, d: int, wide_blocks: int):
    """``wide_blocks`` (rows, 3·D) f32 blocks in flight, twice over for
    the pipeline, + the narrow ones and the halos."""
    return pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=2 * (wide_blocks * 3 + 1) * rows * d * 4
        + 8 * _HALO * 3 * d * 4 + (16 << 20))


def _last(ref):
    """The last 8 rows of a halo block, f32."""
    return ref[_HALO - SUBLANES:, :].astype(jnp.float32)


def _after(ref, at, rows: int):
    """The 8 rows of a block from ``at``, a sub-tile's end, f32: read
    as the 16-row tile that starts there (whole at either width; the
    block's last sub-tile takes the halo instead)."""
    start = pl.multiple_of(jnp.minimum(at, rows - _HALO), _HALO)
    return ref[pl.ds(start, _HALO), :].astype(jnp.float32)[:SUBLANES]


def _fwd_kernel(p_ref, before_ref, taps_ref, y_ref, *, sub):
    tile, rows = pl.program_id(1), p_ref.shape[0]
    d = y_ref.shape[1]
    width = taps_ref.shape[0]
    first = ones_where(tile > 0)          # zeros before the sequence
    for n in range(d // LANES):
        gate_in, gate_out, x = (
            slice(s * d + n * LANES, s * d + (n + 1) * LANES)
            for s in range(3))
        lanes = slice(n * LANES, (n + 1) * LANES)
        taps = [taps_ref[j:j + 1, lanes] for j in range(width)]
        halo = _last(before_ref.at[:, gate_in]) \
            * _last(before_ref.at[:, x]) * first

        def some(k, carry):     # traced here, inside this iteration
            start = pl.multiple_of(k * sub, sub)
            before = jnp.maximum(start - SUBLANES, 0)
            ext = jnp.concatenate(
                [jax.lax.select(
                    k == 0, halo, eight_rows(p_ref.at[:, gate_in], before)
                    * eight_rows(p_ref.at[:, x], before)),
                 p_ref[pl.ds(start, sub), gate_in]
                 * p_ref[pl.ds(start, sub), x]], axis=0)
            c = taps_sum(delayed(ext, width, sub), taps)
            y_ref[pl.ds(start, sub), lanes] = (
                p_ref[pl.ds(start, sub), gate_out] * c
            ).astype(y_ref.dtype)
            return carry

        jax.lax.fori_loop(0, rows // sub, some, None)


def _bwd_kernel(p_ref, before_ref, after_ref, dy_ref, dy_after_ref,
                taps_ref, dp_ref, dtaps_ref, *, sub, length, masked):
    tile, rows = pl.program_id(1), p_ref.shape[0]
    d = dy_ref.shape[1]
    width, steps = taps_ref.shape[0], rows // sub
    f32 = jnp.float32
    first = ones_where(tile > 0)
    # nothing follows the last tile: a zero cotangent there makes dc 0
    more = ones_where(tile < pl.num_programs(1) - 1)
    at = jax.lax.broadcasted_iota(jnp.int32, (sub + 2 * SUBLANES, 1), 0)

    @pl.when((pl.program_id(0) == 0) & (tile == 0))
    def _start():
        dtaps_ref[...] = jnp.zeros_like(dtaps_ref)

    for n in range(d // LANES):
        gate_in, gate_out, x = (
            slice(s * d + n * LANES, s * d + (n + 1) * LANES)
            for s in range(3))
        lanes = slice(n * LANES, (n + 1) * LANES)
        taps = [taps_ref[j:j + 1, lanes] for j in range(width)]
        halo = (_last(before_ref.at[:, gate_in]) * first,
                _last(before_ref.at[:, x]) * first)
        tail = tuple(after_ref[:SUBLANES, cols].astype(f32)
                     for cols in (gate_in, gate_out, x))
        dy_tail = dy_after_ref[:SUBLANES, lanes].astype(f32) * more

        def some(k, sums):      # traced here, inside this iteration
            start = pl.multiple_of(k * sub, sub)
            last = k == steps - 1
            before = jnp.maximum(start - SUBLANES, 0)

            def stretch(cols, head, end):
                """Rows from 8 before the sub-tile (``head`` given) or
                from its first to 8 after it, of one column block."""
                parts = [] if head is None else [jax.lax.select(
                    k == 0, head, eight_rows(p_ref.at[:, cols], before))]
                return jnp.concatenate(parts + [
                    p_ref[pl.ds(start, sub), cols],
                    jax.lax.select(
                        last, end,
                        _after(p_ref.at[:, cols], start + sub, rows))],
                    axis=0)

            b_ext = stretch(gate_in, halo[0], tail[0])
            x_ext = stretch(x, halo[1], tail[2])
            c_gate = stretch(gate_out, None, tail[1])
            dy = jnp.concatenate(
                [dy_ref[pl.ds(start, sub), lanes].astype(f32),
                 jax.lax.select(
                     last, dy_tail,
                     _after(dy_ref.at[:, lanes], start + sub, rows))],
                axis=0)
            ext = b_ext * x_ext
            if masked:      # a block past the array's end holds anything
                seen = tile * rows + start - SUBLANES + at < length
                ext = unless(seen, ext)
                dy = unless(seen[SUBLANES:], dy)
                c_gate = unless(seen[SUBLANES:], c_gate)
            shifted = delayed(ext, width, sub + SUBLANES)
            c = taps_sum(shifted, taps)
            dc = dy * c_gate
            du = dc[:sub] * taps[width - 1]
            for s in range(1, width):   # dc_{t+s}: up to J − 1 rows after
                du = du + pltpu.roll(dc, sub + SUBLANES - s, 0)[:sub] \
                    * taps[width - 1 - s]
            at_own = slice(SUBLANES, SUBLANES + sub)
            dp_ref[pl.ds(start, sub), gate_in] = du * x_ext[at_own]
            dp_ref[pl.ds(start, sub), gate_out] = dy[:sub] * c[:sub]
            dp_ref[pl.ds(start, sub), x] = du * b_ext[at_own]
            return [
                total + column_sums(dc[:sub] * shifted[width - 1 - j][:sub])
                for j, total in enumerate(sums)]

        sums = jax.lax.fori_loop(
            0, steps, some,
            [jnp.zeros((1, LANES), f32) for _ in range(width)])
        for j, total in enumerate(sums):
            dtaps_ref[j:j + 1, lanes] += total


# jitted, as the other kernels' entries are: a model's mixers trace and
# lower each of these once per program
@functools.partial(jax.jit, static_argnums=(2, 3))
def _forward(p, taps, out_dtype, interpret):
    b, length, wide = p.shape
    d = wide // 3
    rows, tiles, sub, _ = _walk(length)
    taps_t = taps.astype(jnp.float32).T               # (J, D)
    spec = _specs(rows, length, d, taps_t.shape[0])
    return pl.pallas_call(
        functools.partial(_fwd_kernel, sub=sub),
        grid=(b, tiles),
        in_specs=[spec["p"], spec["p_before"], spec["taps"]],
        out_specs=spec["y"],
        out_shape=jax.ShapeDtypeStruct((b, length, d), out_dtype),
        compiler_params=_params(("parallel", "parallel"), rows, d, 1),
        interpret=interpret, name="znicz_short_conv_fwd",
    )(p, p, taps_t)


@functools.partial(jax.jit, static_argnums=(3,))
def _backward(p, taps, dy, interpret):
    b, length, wide = p.shape
    d = wide // 3
    rows, tiles, sub, walk = _walk(length)
    taps_t = taps.astype(jnp.float32).T
    spec = _specs(rows, length, d, taps_t.shape[0])
    dp, dtaps = pl.pallas_call(
        functools.partial(_bwd_kernel, sub=sub, **walk),
        grid=(b, tiles),
        in_specs=[spec["p"], spec["p_before"], spec["p_after"],
                  spec["y"], spec["y_after"], spec["taps"]],
        out_specs=(spec["p"], spec["taps"]),
        out_shape=(jax.ShapeDtypeStruct(p.shape, p.dtype),
                   jax.ShapeDtypeStruct(taps_t.shape, jnp.float32)),
        compiler_params=_params(("arbitrary", "arbitrary"), rows, d, 2),
        interpret=interpret, name="znicz_short_conv_bwd",
    )(p, p, p, dy, dy, taps_t)
    return dp, dtaps.T.astype(taps.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _chain(p, taps, out_dtype, interpret):
    return _forward(p, taps, out_dtype, interpret)


def _chain_fwd(p, taps, *static):
    # what the backward keeps is the projection and the taps
    return _forward(p, taps, *static), (p, taps)


def _chain_bwd(out_dtype, interpret, residual, dy):
    del out_dtype
    return _backward(*residual, dy, interpret)


_chain.defvjp(_chain_fwd, _chain_bwd)


def short_conv(projected, taps, out_dtype=jnp.float32,
               interpret: bool = False):
    """``znicz_short_conv_fwd`` and, under differentiation,
    ``znicz_short_conv_bwd``: from ``projected`` (B, T, 3·D) f32 where
    the matmul wrote it and the ``taps`` (D, J) to y (B, T, D) at
    ``out_dtype`` (module docstring).  Needs :func:`legal` shapes."""
    b, t, wide = projected.shape
    refused = legal(t, wide // 3, taps.shape[1])
    if refused or wide % 3:
        raise ValueError(f"short_conv: {refused or 'not three blocks'}")
    return _chain(projected, taps, jnp.dtype(out_dtype), bool(interpret))

"""Causal flash attention over keys of TWO widths (multi-head latent
attention as it trains, DeepSeek-V2, arXiv:2405.04434 §2.1;
Ling-3.0-flash's full layers, PR 37): a head's score is the sum of two
products,

.. code-block:: text

    s_h = q_nope,h · k_nope,h  +  q_rope,h · k_r        o_h = softmax(s_h) v_h

the per-head part (``qk_nope`` wide, from the latent) and the rotary
part, whose key ``k_r`` is ONE for all heads.  Nothing of shape
(B, T, H, qk_nope + qk_rope) exists on either pass: the kernels read
q_nope, k_nope and v as 128-lane column blocks of (B, T, H·128) arrays
— the projections' own layout, as ``ops/pallas_attention.py`` reads its
operands since PR 28 —, q_rope as (B, T, H·64), and k_r as (B, T, 64);
values are not padded to the keys' width.

A grid step computes a PAIR of heads, so that the pair's two 64-wide
rotary queries are one 128-lane block.  The shared key comes in twice,
``[k_r | 0]`` and ``[0 | k_r]`` (T × 256 numbers, made outside): the
pair's rotary block times the first is the even head's rotary score,
times the second the odd head's — a contraction over 128 lanes, the
MXU's own depth, and no lane is sliced.  The same two operands turn
``ds`` into the pair's ``dq_rope`` block in one sum.

The kernels, all ``name=``\\ d for the trace: ``znicz_flash_fwd_mla``
(online softmax over K tiles, the tiles above the diagonal neither
computed nor fetched) and a backward that is ONE call where its
resident sums fit (:func:`backward_passes`, read from T and the keys'
widths; PR 53): ``znicz_flash_bwd_mla`` computes a visited tile's s, p,
dp and ds once (:func:`_p_and_ds`) and adds all five gradient products
from them — dv, dk_nope and dk_r into per-tile f32 accumulators (a K
tile stays while the Q tiles from the diagonal down pass; dk_r is the
sum over ALL heads: over a pair's Q tiles in VMEM, over the pairs by
one small sum outside), dq_nope and dq_rope into the pair's WHOLE dq,
which stays in VMEM in f32 from the pair's first K tile to its last —
(T, 256) + (T, 128): 24 MiB at T 16,384, asked for explicitly
(``vmem_limit_bytes``).  K tiles 0 … q feed dq tile q and the K tile is
the outer axis, so tile q is complete at row q's first visit, the
diagonal, and is cast and written there, once: no partial sum and no
f32 (B, T, H·128) array meets HBM.  Past ``RESIDENT_DQ_VMEM`` (T
32,768: 48 MiB a pair) the backward is the two calls it was before:
``znicz_flash_bwd_mla_dq`` (a Q tile stays) and
``znicz_flash_bwd_mla_dkv`` — the same kernel body without its dq —,
each computing every score tile again.  The sums keep their order in
either form (K tiles ascending into dq, Q tiles ascending into dk and
dv), so the two give the same bits.  The caller scales q by
(qk_nope + qk_rope)^(−1/2) before the cast; softmax statistics,
``delta`` and every accumulator are f32; the products take the operands'
dtype (bf16 in mixed precision) with f32 accumulation.

What is still simple on purpose: one (512, 512) tile a visit, the
diagonal's mask applied to every visited tile, a square grid whose
steps above the diagonal are skipped one by one.  ``kanana2_train_1of8``
runs the kernels where they are most of a step — five such layers at
T 16,384, a K grid 32 tiles deep.  Two passes took 74.8 ms a call there
and one takes 52.6 (−29.8%), at 87.6% of the MXU's peak on the work it
executes; 5.84 → 4.13 at Ling's T 4,096, 2.00 → 1.52 at Xing's 2,048
(my chip run, PR 53, ``benchmarks/mla_bwd_probe.py``).  What the visit
of PR 36 (only the diagonal's tiles need the mask: 6% of the visited at
32 tiles) and a triangular grid would save is PERF.md §7's and ROADMAP
S2 (7)'s to weigh.

What the forward keeps for the backward beside q, k, v and o is the
row statistic ``lse`` at ``_STAT`` lanes a head, (B, pairs, T, 16) f32:
at a whole 128-lane block a head it was 256 MiB a layer at T 16,384,
held from every layer's forward to its backward.  The per-head
cotangents leave their kernel in their operands' dtype.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: positions per Q and K tile
BLOCK = 512
_LANES = 128
#: lanes a head's per-row statistic takes in HBM (lse: a pair's block
#: is (rows, 2 · _STAT), the value repeated over a head's lanes — the
#: least a tile allows, as ``pallas_attention`` keeps its own; a whole
#: 128-lane block a head was 268 MB a layer at T 16,384, held from the
#: forward to the backward)
_STAT = 8
_NEG = -1e30
#: grid (batch, head pair, resident tile, passing tile)
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))
#: the one-pass backward keeps a PAIR's whole dq in VMEM, f32, from
#: its first K tile to its last — (T, 256) + (T, 128): 24 MiB at
#: T 16,384, 6 at 4,096, 3 at 2,048 — where that is at most this much
#: (of a v5e's 128 MiB; T 32,768 would take 48) …
RESIDENT_DQ_VMEM = 32 * 2 ** 20
#: … and asks for it plus what a call gets unasked, which holds a grid
#: step's tiles, their second buffers, the per-tile accumulators and a
#: visit's score tiles as it held the two-pass calls'
_STEP_VMEM = 16 * 2 ** 20


def _resident_dq_bytes(t: int, qk_nope: int = _LANES,
                       qk_rope: int = _LANES // 2) -> int:
    """A pair of heads' dq_nope and dq_rope over all of T, f32."""
    return t * 2 * (qk_nope + qk_rope) * 4


def backward_passes(t: int, qk_nope: int = _LANES,
                    qk_rope: int = _LANES // 2) -> int:
    """How many times the backward computes a visited score tile, from
    the call's shapes alone: 1 — ``znicz_flash_bwd_mla``, s, p, dp, ds
    once and all five gradient products from them — where a pair's
    whole dq fits ``RESIDENT_DQ_VMEM``; else 2, ``znicz_flash_bwd_mla_dq``
    + ``znicz_flash_bwd_mla_dkv``.  Static per program: in the plan
    (:func:`plan`), its line and the unit's gauge."""
    return 1 if _resident_dq_bytes(t, qk_nope, qk_rope) \
        <= RESIDENT_DQ_VMEM else 2


def kernel_legal(t: int, n_heads: int, qk_nope: int, qk_rope: int,
                 v_dim: int) -> bool:
    """Whether the kernels tile the call: a head's per-head key and its
    value are whole 128-lane blocks, a pair of rotary queries is one,
    and T is whole tiles."""
    block = min(BLOCK, t)
    return (qk_nope == _LANES and v_dim == _LANES
            and 2 * qk_rope == _LANES and n_heads % 2 == 0
            and t % block == 0 and block % 8 == 0)


def _dot(a, b, trans_a=False, trans_b=False):
    dims = (((0,) if trans_a else (1,), (1,) if trans_b else (0,)),
            ((), ()))
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _visible(iq, ik, bq: int, bk: int):
    rows = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return rows >= cols


def _scores(qn_ref, qr_ref, kn_ref, kr_ref, j: int):
    """Head ``j`` of the pair: its per-head product plus the pair's
    rotary block against the shared key in that head's lanes."""
    lanes = slice(j * _LANES, (j + 1) * _LANES)
    return _dot(qn_ref[:, lanes], kn_ref[:, lanes], trans_b=True) \
        + _dot(qr_ref[...], kr_ref[:, lanes], trans_b=True)


def _fwd_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref, lse_ref,
                m_ref, l_ref, acc_ref):
    iq, ik = pl.program_id(2), pl.program_id(3)
    bq, bk = qn_ref.shape[0], kn_ref.shape[0]

    @pl.when(ik == 0)
    def _start():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(ik <= iq)
    def _visit():
        seen = _visible(iq, ik, bq, bk)
        for j in range(2):
            lanes = slice(j * _LANES, (j + 1) * _LANES)
            s = jnp.where(seen, _scores(qn_ref, qr_ref, kn_ref, kr_ref, j),
                          _NEG)
            m_prev = m_ref[:, lanes]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new[:, :1])
            alpha = jnp.exp(m_prev - m_new)
            l_ref[:, lanes] = alpha * l_ref[:, lanes] \
                + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[:, lanes] = alpha * acc_ref[:, lanes] + _dot(
                p.astype(v_ref.dtype), v_ref[:, lanes])
            m_ref[:, lanes] = m_new

    @pl.when(ik == iq)
    def _leave():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)
        lse = m_ref[...] + jnp.log(l_ref[...])
        lse_ref[...] = jnp.concatenate(
            [lse[:, j * _LANES:j * _LANES + _STAT] for j in range(2)],
            axis=1)


def _p_and_ds(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref, do_ref,
              lse_ref, seen, j: int):
    """A head's probabilities and score cotangents on one tile."""
    lanes = slice(j * _LANES, (j + 1) * _LANES)
    s = _scores(qn_ref, qr_ref, kn_ref, kr_ref, j)
    p = jnp.where(seen, jnp.exp(s - lse_ref[:, j * _STAT:j * _STAT + 1]),
                  0.0)
    do = do_ref[:, lanes]
    delta = jnp.sum(do.astype(jnp.float32)
                    * o_ref[:, lanes].astype(jnp.float32),
                    axis=1, keepdims=True)
    dp = _dot(do, v_ref[:, lanes], trans_b=True)
    return p, p * (dp - delta)


def _dq_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref, do_ref,
               lse_ref, dqn_ref, dqr_ref, dqn_acc, dqr_acc):
    iq, ik = pl.program_id(2), pl.program_id(3)
    bq, bk = qn_ref.shape[0], kn_ref.shape[0]

    @pl.when(ik == 0)
    def _start():
        dqn_acc[...] = jnp.zeros_like(dqn_acc)
        dqr_acc[...] = jnp.zeros_like(dqr_acc)

    @pl.when(ik <= iq)
    def _visit():
        seen = _visible(iq, ik, bq, bk)
        for j in range(2):
            lanes = slice(j * _LANES, (j + 1) * _LANES)
            _, ds = _p_and_ds(qn_ref, qr_ref, kn_ref, kr_ref, v_ref,
                              o_ref, do_ref, lse_ref, seen, j)
            ds = ds.astype(kn_ref.dtype)
            dqn_acc[:, lanes] += _dot(ds, kn_ref[:, lanes])
            # [ds·k_r | 0] for the even head, [0 | ds·k_r] for the odd
            dqr_acc[...] += _dot(ds, kr_ref[:, lanes])

    @pl.when(ik == iq)
    def _leave():
        dqn_ref[...] = dqn_acc[...].astype(dqn_ref.dtype)
        dqr_ref[...] = dqr_acc[...].astype(dqr_ref.dtype)


def _dkv_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref, do_ref,
                lse_ref, dkn_ref, dv_ref, dkr_ref, dkn_acc, dv_acc,
                dkr_acc, dq=None):
    """A K tile stays, the Q tiles from the diagonal down pass: dk_nope,
    dv and the pair's dk_r.  With ``dq`` (:func:`_bwd_kernel`: dq_nope's
    and dq_rope's output blocks and the pair's WHOLE dq in VMEM, a Q
    tile a leading index) the same ``ds`` also feeds dq — the backward
    in one pass."""
    ik, iq = pl.program_id(2), pl.program_id(3)
    bq, bk = qn_ref.shape[0], kn_ref.shape[0]
    half = qr_ref.shape[1] // 2

    @pl.when(iq == 0)
    def _start():
        dkn_acc[...] = jnp.zeros_like(dkn_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)
        dkr_acc[...] = jnp.zeros_like(dkr_acc)

    if dq is not None:
        dqn_ref, dqr_ref, dqn_all, dqr_all = dq

        @pl.when(ik == 0)   # K tile 0 meets every Q tile: its sums start
        def _start_dq():
            dqn_all[iq] = jnp.zeros(dqn_all.shape[1:], dqn_all.dtype)
            dqr_all[iq] = jnp.zeros(dqr_all.shape[1:], dqr_all.dtype)

    @pl.when(iq >= ik)
    def _visit():
        seen = _visible(iq, ik, bq, bk)
        lane = jax.lax.broadcasted_iota(jnp.int32, qr_ref.shape, 1)
        for j in range(2):
            lanes = slice(j * _LANES, (j + 1) * _LANES)
            p, ds = _p_and_ds(qn_ref, qr_ref, kn_ref, kr_ref, v_ref,
                              o_ref, do_ref, lse_ref, seen, j)
            ds = ds.astype(qn_ref.dtype)
            dv_acc[:, lanes] += _dot(p.astype(do_ref.dtype),
                                     do_ref[:, lanes], trans_a=True)
            dkn_acc[:, lanes] += _dot(ds, qn_ref[:, lanes], trans_a=True)
            # the head's own rotary lanes only: the pair's two halves
            # are the even and the odd heads' sums, folded outside
            own = (lane >= j * half) & (lane < (j + 1) * half)
            dkr_acc[...] += _dot(
                ds, jnp.where(own, qr_ref[...], 0).astype(qr_ref.dtype),
                trans_a=True)
            if dq is not None:      # K tiles ascending, as _dq_kernel's
                dqn_all[iq, :, lanes] += _dot(ds, kn_ref[:, lanes])
                dqr_all[iq] += _dot(ds, kr_ref[:, lanes])

    if dq is not None:
        # K tiles 0 … q feed dq tile q; the K tile is the outer axis,
        # so the last of them is this row's first visit, the diagonal
        @pl.when(iq == ik)
        def _leave_dq():
            dqn_ref[...] = dqn_all[iq].astype(dqn_ref.dtype)
            dqr_ref[...] = dqr_all[iq].astype(dqr_ref.dtype)

    @pl.when(iq == pl.num_programs(3) - 1)
    def _leave():
        dkn_ref[...] = dkn_acc[...].astype(dkn_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)
        dkr_ref[...] = dkr_acc[...]


def _bwd_kernel(*refs):
    """``znicz_flash_bwd_mla``: :func:`_dkv_kernel` with dq — the eight
    operands, then the outputs and the scratch, each dk_nope, dv, dk_r,
    dq_nope, dq_rope."""
    dkn_ref, dv_ref, dkr_ref, dqn_ref, dqr_ref = refs[8:13]
    dkn_acc, dv_acc, dkr_acc, dqn_all, dqr_all = refs[13:]
    _dkv_kernel(*refs[:8], dkn_ref, dv_ref, dkr_ref, dkn_acc, dv_acc,
                dkr_acc, dq=(dqn_ref, dqr_ref, dqn_all, dqr_all))


def _specs(bq: int, bk: int, q_at, k_at):
    """Blocks of a pair of heads, column block ``p`` of each per-head
    array: the two block makers, a pair's width, the statistics' block
    (``lse`` lies (B, pairs, T, 2 · _STAT): a pair's rows are one block
    whatever its narrow last axis), and the specs of the operands in
    the kernels' order — q_nope, q_rope, k_nope, k_r twice, v (the
    forward's five), then o, do, lse (the backward's eight)."""
    pair = 2 * _LANES

    def q_side(width):
        return pl.BlockSpec((None, bq, width),
                            lambda b, p, i, j: (b, q_at(i, j), p))

    def k_side(width, shared=False):
        return pl.BlockSpec(
            (None, bk, width),
            lambda b, p, i, j: (b, k_at(i, j), 0 if shared else p))

    stat = pl.BlockSpec((None, None, bq, 2 * _STAT),
                        lambda b, p, i, j: (b, p, q_at(i, j), 0))
    operands = [q_side(pair), q_side(_LANES), k_side(pair),
                k_side(pair, shared=True), k_side(pair), q_side(pair),
                q_side(pair), stat]
    return q_side, k_side, pair, stat, operands


def _twice(kr):
    """(B, T, r) → (B, T, 4r) = [k_r | 0 | 0 | k_r]."""
    zero = jnp.zeros_like(kr)
    return jnp.concatenate([kr, zero, zero, kr], axis=-1)


@functools.partial(jax.jit, static_argnums=(5,))
def _forward(qn, qr, kn, kr, v, interpret):
    b, t, wide = qn.shape
    pairs = wide // (2 * _LANES)
    bq = bk = min(BLOCK, t)
    steps = t // bq
    q_side, _, pair, stat, ins = _specs(
        bq, bk, lambda i, j: i, lambda i, j: jnp.minimum(i, j))
    return pl.pallas_call(
        _fwd_kernel, grid=(b, pairs, steps, steps), in_specs=ins[:5],
        out_specs=(q_side(pair), stat),
        out_shape=(jax.ShapeDtypeStruct((b, t, wide), qn.dtype),
                   jax.ShapeDtypeStruct((b, pairs, t, 2 * _STAT),
                                        jnp.float32)),
        scratch_shapes=[pltpu.VMEM((bq, pair), jnp.float32)] * 3,
        compiler_params=_PARAMS,
        interpret=interpret, name="znicz_flash_fwd_mla",
    )(qn, qr, kn, _twice(kr), v)


@functools.partial(jax.jit, static_argnums=(8, 9))
def _backward(qn, qr, kn, kr, v, o, lse, do, interpret, passes):
    """dq_nope, dq_rope, dk_nope, dk_r, dv: the per-head ones leave
    their kernel in their operand's dtype (f32 accumulators cast at the
    one write: three (B, T, H·128) arrays are never f32 in HBM), the
    shared key's per pair in f32 for the sum outside.  ``passes``
    (:func:`backward_passes`): one call that keeps a pair's whole dq in
    VMEM, or the dq call and the dk/dv call."""
    b, t, wide = qn.shape
    pairs = wide // (2 * _LANES)
    bq = bk = min(BLOCK, t)
    steps = t // bq
    f32 = jnp.float32
    operands = (qn, qr, kn, _twice(kr), v, o, do, lse)
    grid = (b, pairs, steps, steps)
    # a K tile stays (grid axis 2), the Q tiles from the diagonal down
    # pass (axis 3): dk, dv — and in one pass dq, whose tile's block
    # follows the K tile (the Q block is the diagonal's from a row's
    # start) and is written once
    q_side, k_side, pair, _, ins = _specs(
        bq, bk, lambda i, j: jnp.maximum(i, j), lambda i, j: i)
    dq_specs = (q_side(pair), q_side(_LANES))
    dq_shapes = (jax.ShapeDtypeStruct((b, t, wide), qn.dtype),
                 jax.ShapeDtypeStruct((b, t, pairs * _LANES), qr.dtype))
    dkv_specs = (k_side(pair), k_side(pair),
                 pl.BlockSpec((None, None, bk, _LANES),
                              lambda b_, p, i, j: (b_, p, i, 0)))
    dkv_shapes = (jax.ShapeDtypeStruct((b, t, wide), kn.dtype),
                  jax.ShapeDtypeStruct((b, t, wide), v.dtype),
                  jax.ShapeDtypeStruct((b, pairs, t, _LANES), f32))
    dkv_scratch = [pltpu.VMEM((bk, pair), f32), pltpu.VMEM((bk, pair), f32),
                   pltpu.VMEM((bk, _LANES), f32)]
    if passes == 1:
        dkn, dv, dkr, dqn, dqr = pl.pallas_call(
            _bwd_kernel, grid=grid, in_specs=ins,
            out_specs=dkv_specs + dq_specs,
            out_shape=dkv_shapes + dq_shapes,
            scratch_shapes=dkv_scratch + [
                pltpu.VMEM((steps, bq, pair), f32),
                pltpu.VMEM((steps, bq, _LANES), f32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary",
                                     "arbitrary"),
                vmem_limit_bytes=_resident_dq_bytes(t) + _STEP_VMEM),
            interpret=interpret, name="znicz_flash_bwd_mla",
        )(*operands)
    else:
        # dq in a call of its own: a Q tile stays, the K tiles up to
        # the diagonal pass
        q_side, _, _, _, q_stays = _specs(
            bq, bk, lambda i, j: i, lambda i, j: jnp.minimum(i, j))
        dqn, dqr = pl.pallas_call(
            _dq_kernel, grid=grid, in_specs=q_stays,
            out_specs=(q_side(pair), q_side(_LANES)), out_shape=dq_shapes,
            scratch_shapes=[pltpu.VMEM((bq, pair), f32),
                            pltpu.VMEM((bq, _LANES), f32)],
            compiler_params=_PARAMS, interpret=interpret,
            name="znicz_flash_bwd_mla_dq",
        )(*operands)
        dkn, dv, dkr = pl.pallas_call(
            _dkv_kernel, grid=grid, in_specs=ins, out_specs=dkv_specs,
            out_shape=dkv_shapes, scratch_shapes=dkv_scratch,
            compiler_params=_PARAMS, interpret=interpret,
            name="znicz_flash_bwd_mla_dkv",
        )(*operands)
    # the shared key's cotangent: over the pairs, then the even and
    # the odd heads' halves
    dkr = dkr.sum(axis=1)
    half = dkr.shape[-1] // 2
    return dqn, dqr, dkn, dkr[..., :half] + dkr[..., half:], dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _attend(qn, qr, kn, kr, v, interpret, passes):
    return _forward(qn, qr, kn, kr, v, interpret)[0]


def _attend_fwd(qn, qr, kn, kr, v, interpret, passes):
    o, lse = _forward(qn, qr, kn, kr, v, interpret)
    return o, (qn, qr, kn, kr, v, o, lse)


def _attend_bwd(interpret, passes, residual, do):
    qn, qr, kn, kr, v, o, lse = residual
    grads = _backward(qn, qr, kn, kr, v, o, lse, do.astype(o.dtype),
                      interpret, passes)
    return tuple(g.astype(a.dtype)
                 for g, a in zip(grads, (qn, qr, kn, kr, v)))


_attend.defvjp(_attend_fwd, _attend_bwd)


def latent_flash_attention(q_nope, q_rope, k_nope, k_rope, v,
                           interpret: bool = False,
                           passes: int | None = None):
    """Causal attention of H heads over two-width keys, rows in the
    projections' layout: q_nope, k_nope, v (B, T, H·128), q_rope
    (B, T, H·64) — q already scaled —, k_rope (B, T, 64) shared by all
    heads → o (B, T, H·128) in the operands' dtype.  ``passes``: the
    backward's (None: :func:`backward_passes` of T)."""
    if passes is None:
        passes = backward_passes(q_nope.shape[1])
    return _attend(q_nope, q_rope, k_nope, k_rope, v, interpret, passes)


class LatentPlan(NamedTuple):
    """Whether and how the two-width kernels run one latent-K/V layer's
    call (:func:`plan`), as ``pallas_attention.FlashPlan`` does: why not
    (None: they do), interpreted or not, the tile edge along T, the
    backward's passes (:func:`backward_passes`)."""
    refused: str | None
    interpret: bool
    tile: int
    backward_passes: int

    @property
    def runs(self) -> bool:
        return self.refused is None

    def attend(self, *arrays):
        return latent_flash_attention(*arrays, interpret=self.interpret,
                                      passes=self.backward_passes)

    def line(self) -> str:
        if not self.runs:
            return f"plain core, K assembled in full ({self.refused})"
        return ("znicz_flash_fwd_mla / %s kernels, tiles of %d, "
                "backward passes %d%s" % (
                    "znicz_flash_bwd_mla" if self.backward_passes == 1
                    else "znicz_flash_bwd_mla_dq / _dkv",
                    self.tile, self.backward_passes,
                    ", INTERPRETED" if self.interpret else ""))


def plan(device, t: int, n_heads: int, qk_nope: int, qk_rope: int,
         v_dim: int) -> LatentPlan:
    """The :class:`LatentPlan` of a causal call over ``t`` positions on
    ``device``: ``engine.flash_attention`` and ``pallas_interpret``
    resolved once, one device, :func:`kernel_legal` shapes, the
    backward's passes from T and the keys' widths."""
    from znicz_tpu.ops import pallas_kernels
    from znicz_tpu.utils.config import root
    interpret = bool(root.common.engine.get("pallas_interpret", False))
    refused = pallas_kernels.kernel_refusal(device, "flash_attention",
                                            interpret)
    mesh = getattr(device, "mesh", None)
    if refused is None and mesh is not None and mesh.size > 1:
        refused = (f"a mesh of {mesh.size} devices: the two-width "
                   f"kernels have no sharding rule")
    if refused is None and not kernel_legal(t, n_heads, qk_nope, qk_rope,
                                            v_dim):
        refused = (f"T={t}, {n_heads} heads of {qk_nope} + {qk_rope} / "
                   f"{v_dim} do not tile (128 + 64 / 128, an even head "
                   f"count, T whole tiles)")
    return LatentPlan(refused, interpret, min(BLOCK, t),
                      backward_passes(t, qk_nope, qk_rope))

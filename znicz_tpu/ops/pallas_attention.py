"""Fused flash-attention Pallas TPU kernels (forward + backward).

Why this exists: the round-5 profile of the T=2048 sequence step
(PERF.md) shows the six attention-core GEMMs plus the softmax
reduction pinned at the HBM bandwidth roof (~660–800 GB/s, 11–50
TF/s) streaming (B, H, T, T) score/probability tensors — ~78% of the
step.  The XLA paths (plain einsum and the ``lax.scan`` blocked fold
in :mod:`znicz_tpu.parallel.ring_attention`) cannot avoid
materializing those tensors (plain) or the per-step carry round-trips
(scan).  A fused kernel keeps every (block_q, block_k) score tile in
VMEM: the only HBM traffic is q/k/v/o (+ per-row logsumexp), so the
core runs at MXU rate instead of bandwidth rate.

Design (the standard flash decomposition, implemented TPU-first):

- **forward**: grid (B, H, nq, nk), K-blocks innermost ("arbitrary"
  semantics — sequential per core); online-softmax state (running row
  max m, normalizer l, weighted accumulator) lives in VMEM scratch
  across the K iterations; the output block and the per-row
  logsumexp are written once at the last K block.
- **backward**: recompute-from-lse form — no (T, T) residual is ever
  stored.  Saves (q, k, v, o, lse) from the forward, precomputes
  ``delta = rowsum(do·o)`` (one cheap XLA pass), then two kernels:
  ``dq`` (grid over K blocks innermost, accumulating dq tiles) and
  ``dk/dv`` (grid over Q blocks innermost, accumulating dk/dv tiles);
  each recomputes the score tile p = exp(s − lse) in VMEM.
- **dtypes**: tile GEMMs run at the input dtype (bf16 in the
  framework's mixed-precision mode) with f32 accumulation via
  ``preferred_element_type``; softmax statistics, lse, delta and all
  accumulators are f32 — the same bf16-inputs/f32-accumulation
  convention as the rest of the repo.
- **causal**: global-position mask inside the tile (exact across
  block boundaries — same rule as ``ring_attention._visibility``);
  fully-masked tiles are skipped via ``pl.when``, so causal runs at
  ~2× effective rate.
- **global offsets** (round 6, the ring-fold composition): every
  kernel takes ``q_offset``/``k_offset`` scalars (SMEM) placing this
  call's q rows / k cols on the GLOBAL sequence axis, so one kernel
  invocation can be a single ring hop — the `pl.when` tile-skip then
  skips whole hops that sit entirely above the causal diagonal.
  Offsets are traced values (the ring derives them from
  ``axis_index``), which is why they ride SMEM instead of being
  Python constants.  With offsets, a hop can contain FULLY-MASKED
  rows (rows above the hop's first key) — the kernels guard those
  with explicit mask selects (forward p-tile and the backward
  recompute both) so the statistics degrade to (m=-inf, l=0) instead
  of exploding; such a hop contributes lse ≈ -1e30 and weight 0 to
  the cross-hop combination.
- **head packing** (round 6, ``pack=2``): pairs of dh=64 heads ride
  one kernel program as a (…, 128)-lane layout — q/k/v/o tiles carry
  both sub-heads side by side in the lane dim (full 128-lane VMEM
  loads/stores and element ops instead of half-width dh=64 tiles, the
  measured half-MXU bottleneck: MFU 0.25 at head_dim 64 vs 0.405 at
  128 — PERF.md round 5), while every GEMM and every softmax
  statistic stays per-sub-head (static lane slices), so the math is
  exactly per-head attention.  The pack happens as a free reshape at
  the (B, T, H, Dh) boundary (heads are adjacent to Dh there), never
  a model change.

Layout contract: (B, T, H, D) at the boundary (the unit-graph
convention); kernels run head-major (B, H, T, D) — the wrapper
transposes, which costs two cheap bandwidth passes versus the many
(T, T) passes saved.

Adoption is measured, not assumed: SEQ_BENCH.json / PERF.md round 5
carry the chip A/B against the plain and scan-blocked XLA forms (the
PALLAS_BENCH.md decision rule).  ``interpret=True`` runs the same
kernels on CPU for the oracle equality tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
#: default tile sizes — chip-swept (PERF.md round 5): 1024×1024 beats
#: 512×512 by ~1.2× (fewer grid revisits of the VMEM stats; the f32
#: score tile is 4 MB); 2048-wide tiles overflow VMEM and fail to
#: compile, so callers wanting other shapes pass block_q/block_k
BLOCK_Q = 1024
BLOCK_K = 1024
#: lane width for the per-row statistics arrays (lse, delta): the
#: minimum tile-legal last dim — the value is replicated across lanes
#: (with head packing, each sub-head owns one _LANES-wide lane group)
_LANES = 8
#: lane width of the f32 stats scratch (one VMEM tile row); sub-heads
#: split it into 128/pack-wide column groups
_STAT_LANES = 128


def _causal_mask(iq, ik, bq: int, bk: int, q_off, k_off):
    """(bq, bk) visibility tile from GLOBAL positions (rows
    q_off + iq·bq…, cols k_off + ik·bk…).  Offsets are traced int32
    scalars (0 outside the ring path)."""
    rows = q_off + iq * bq \
        + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = k_off + ik * bk \
        + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return rows >= cols


def _dot(a, b, trans_a: bool = False, trans_b: bool = False):
    """MXU dot with f32 accumulation, contracting dims picked so no
    operand is materialized transposed."""
    dims = (((0,) if trans_a else (1,), (1,) if trans_b else (0,)),
            ((), ()))
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _off_arr(v):
    """Offsets ride SMEM as (1, 1) int32 — accepts Python ints and
    traced scalars alike; None means 0."""
    if v is None:
        return jnp.zeros((1, 1), jnp.int32)
    return jnp.asarray(v, jnp.int32).reshape(1, 1)


def kernel_legal(t_q: int, t_k: int, dh: int, bq: int, bk: int) -> bool:
    """The kernel's tiling-legality gate (shared by the unit gate and
    the ring fold): blocks must tile T evenly and the head dim must be
    lane-legal (dh % 8 — e.g. dh=1 via a to_sequence net would crash
    Mosaic at trace instead of falling back; ADVICE round 5)."""
    return (t_q % bq == 0 and t_k % bk == 0
            and t_q % 8 == 0 and t_k % 8 == 0 and dh % 8 == 0)


def resolve_head_pack(flag, n_heads: int, dh: int) -> int:
    """Head-pack factor for the kernel call path: 2 when the
    ``engine.flash_head_pack`` gate is on and pairs of heads fit the
    128-lane tile (dh·2 ≤ 128, lane-legal, head count even) — else 1.
    A model change is never implied; packing is a kernel-boundary
    reshape."""
    if not flag:
        return 1
    if n_heads % 2 == 0 and dh % 8 == 0 and dh * 2 <= 128:
        return 2
    return 1


def causal_block_for(t: int, default_bq: int, default_bk: int,
                     min_block: int = 256):
    """Auto-pick causal blocks from grid depth (round-6 sweep,
    verdict item 3): at T=2048 the default 1024² tiles give a 2×2
    grid with ONE skippable tile, so causal ran at non-causal step
    time (MFU 0.167 vs 0.253).  Shrink blocks until the K-grid is at
    least 4 deep (≥ ~half the tiles skippable), floored at
    ``min_block`` (smaller tiles trade MXU efficiency for skip
    depth — the DMA/revisit floor the round-5 block sweep measured).
    Returns (block_q, block_k)."""
    bq, bk = min(default_bq, t), min(default_bk, t)
    while bk > min_block and t // bk < 4 and t % (bk // 2) == 0:
        bk //= 2
    while bq > min_block and t // bq < 4 and t % (bq // 2) == 0:
        bq //= 2
    return bq, bk


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------
def _fwd_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, causal, bq, bk, pack):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    q_off, k_off = qoff_ref[0, 0], koff_ref[0, 0]

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    visible = True if not causal \
        else q_off + iq * bq + bq - 1 >= k_off + ik * bk

    @pl.when(visible)
    def _fold():
        mask = (_causal_mask(iq, ik, bq, bk, q_off, k_off)
                if causal else None)
        q_all, k_all, v_all = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
        d = q_all.shape[1]
        dh, sw = d // pack, _STAT_LANES // pack
        m_all, l_all, acc_all = m_scr[...], l_scr[...], acc_scr[...]
        m_out, l_out, acc_out = [], [], []
        for p in range(pack):           # static: per-sub-head math
            fs = slice(p * dh, (p + 1) * dh)
            s = _dot(q_all[:, fs], k_all[:, fs], trans_b=True) * scale
            if causal:
                s = jnp.where(mask, s, _NEG_INF)
            m_prev = m_all[:, p * sw:p * sw + 1]        # (bq, 1)
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=1, keepdims=True))
            pt = jnp.exp(s - m_new)
            if causal:
                # offset hops can hold FULLY-masked rows (m stays
                # -inf): exp(s - m) = exp(0) there without this guard
                pt = jnp.where(mask, pt, 0.0)
            corr = jnp.exp(m_prev - m_new)              # (bq, 1)
            l_out.append(jnp.broadcast_to(
                l_all[:, p * sw:p * sw + 1] * corr
                + jnp.sum(pt, axis=1, keepdims=True), (bq, sw)))
            acc_out.append(acc_all[:, fs] * corr
                           + _dot(pt.astype(v_all.dtype),
                                  v_all[:, fs]))
            m_out.append(jnp.broadcast_to(m_new, (bq, sw)))
        m_scr[...] = jnp.concatenate(m_out, axis=1)
        l_scr[...] = jnp.concatenate(l_out, axis=1)
        acc_scr[...] = jnp.concatenate(acc_out, axis=1)

    @pl.when(ik == nk - 1)
    def _finish():
        d = o_ref.shape[3]
        dh, sw = d // pack, _STAT_LANES // pack
        o_out, lse_out = [], []
        for p in range(pack):
            fs = slice(p * dh, (p + 1) * dh)
            l = jnp.maximum(l_scr[:, p * sw:p * sw + 1], 1e-30)
            o_out.append((acc_scr[:, fs] / l).astype(o_ref.dtype))
            # row stats ride _LANES lanes per sub-head (minimum
            # tile-legal lane width; the value repeats in every lane)
            lse_out.append(jnp.broadcast_to(
                m_scr[:, p * sw:p * sw + 1] + jnp.log(l),
                (bq, _LANES)))
        o_ref[0, 0] = jnp.concatenate(o_out, axis=1)
        lse_ref[0, 0] = jnp.concatenate(lse_out, axis=1)


def _fwd_call(q, k, v, q_off, k_off, causal, bq, bk, interpret, pack):
    b, h, t, d = q.shape
    tk = k.shape[2]
    nq, nk = t // bq, tk // bk
    kernel = functools.partial(_fwd_kernel,
                               scale=1.0 / np.sqrt(d // pack),
                               causal=causal, bq=bq, bk=bk, pack=pack)
    off_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    qspec = pl.BlockSpec((1, 1, bq, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0))
    kspec = pl.BlockSpec((1, 1, bk, d), lambda b_, h_, iq, ik: (b_, h_, ik, 0))
    lanes = pack * _LANES
    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[off_spec, off_spec, qspec, kspec, kspec],
        out_specs=(qspec,
                   pl.BlockSpec((1, 1, bq, lanes),
                                lambda b_, h_, iq, ik: (b_, h_, iq, 0))),
        out_shape=(jax.ShapeDtypeStruct((b, h, t, d), q.dtype),
                   jax.ShapeDtypeStruct((b, h, t, lanes), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((bq, _STAT_LANES), jnp.float32),
                        pltpu.VMEM((bq, _STAT_LANES), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="znicz_flash_fwd",
    )(q_off, k_off, q, k, v)


# ----------------------------------------------------------------------
# backward: dq kernel (K blocks innermost), dk/dv kernel (Q innermost)
# ----------------------------------------------------------------------
def _p_tile(q, k, lse_col, scale, mask):
    """Recompute one sub-head's probability tile p = exp(s − lse) in
    VMEM.  The mask select also guards fully-masked rows (offset
    hops): there lse ≈ -1e30 and the unmasked exp overflows."""
    s = _dot(q, k, trans_b=True) * scale
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    p = jnp.exp(s - lse_col)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    return p


def _dq_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, do_ref,
               lse_ref, delta_ref, dq_ref, dq_scr, *, scale, causal,
               bq, bk, pack):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    q_off, k_off = qoff_ref[0, 0], koff_ref[0, 0]

    @pl.when(ik == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    visible = True if not causal \
        else q_off + iq * bq + bq - 1 >= k_off + ik * bk

    @pl.when(visible)
    def _fold():
        mask = (_causal_mask(iq, ik, bq, bk, q_off, k_off)
                if causal else None)
        q_all, k_all = q_ref[0, 0], k_ref[0, 0]
        v_all, do_all = v_ref[0, 0], do_ref[0, 0]
        dh = q_all.shape[1] // pack
        parts = []
        for p in range(pack):
            fs = slice(p * dh, (p + 1) * dh)
            ls = slice(p * _LANES, p * _LANES + 1)
            pt = _p_tile(q_all[:, fs], k_all[:, fs],
                         lse_ref[0, 0][:, ls], scale, mask)
            dp = _dot(do_all[:, fs], v_all[:, fs], trans_b=True)
            ds = pt * (dp - delta_ref[0, 0][:, ls]) * scale
            parts.append(_dot(ds.astype(k_all.dtype), k_all[:, fs]))
        dq_scr[...] += jnp.concatenate(parts, axis=1)

    @pl.when(ik == nk - 1)
    def _finish():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, do_ref,
                lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                scale, causal, bq, bk, pack):
    ik, iq = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)
    q_off, k_off = qoff_ref[0, 0], koff_ref[0, 0]

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    visible = True if not causal \
        else q_off + iq * bq + bq - 1 >= k_off + ik * bk

    @pl.when(visible)
    def _fold():
        mask = (_causal_mask(iq, ik, bq, bk, q_off, k_off)
                if causal else None)
        q_all, k_all = q_ref[0, 0], k_ref[0, 0]
        v_all, do_all = v_ref[0, 0], do_ref[0, 0]
        dh = q_all.shape[1] // pack
        dk_parts, dv_parts = [], []
        for p in range(pack):
            fs = slice(p * dh, (p + 1) * dh)
            ls = slice(p * _LANES, p * _LANES + 1)
            pt = _p_tile(q_all[:, fs], k_all[:, fs],
                         lse_ref[0, 0][:, ls], scale, mask)
            do = do_all[:, fs]
            # dv += pᵀ · do ; contract the q dim without
            # materializing pᵀ
            dv_parts.append(_dot(pt.astype(do.dtype), do,
                                 trans_a=True))
            dp = _dot(do, v_all[:, fs], trans_b=True)
            ds = pt * (dp - delta_ref[0, 0][:, ls]) * scale
            dk_parts.append(_dot(ds.astype(q_all.dtype),
                                 q_all[:, fs], trans_a=True))
        dk_scr[...] += jnp.concatenate(dk_parts, axis=1)
        dv_scr[...] += jnp.concatenate(dv_parts, axis=1)

    @pl.when(iq == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_call(q, k, v, lse, do, delta4, q_off, k_off, causal, bq, bk,
              interpret, pack):
    """``delta4``: (B, H, T, pack) f32 — rowsum(do·o) per SUB-head,
    already adjusted for any lse cotangent (the hop composition's
    extra term)."""
    b, h, t, d = q.shape
    tk = k.shape[2]
    nq, nk = t // bq, tk // bk
    lanes = pack * _LANES
    # per-sub-head delta rides _LANES lanes each, like lse
    delta = jnp.repeat(delta4, _LANES, axis=-1)      # (B, H, T, lanes)
    scale = 1.0 / np.sqrt(d // pack)
    off_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    qspec = pl.BlockSpec((1, 1, bq, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0))
    kspec = pl.BlockSpec((1, 1, bk, d), lambda b_, h_, iq, ik: (b_, h_, ik, 0))
    rspec = pl.BlockSpec((1, 1, bq, lanes),
                         lambda b_, h_, iq, ik: (b_, h_, iq, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, pack=pack),
        grid=(b, h, nq, nk),
        in_specs=[off_spec, off_spec, qspec, kspec, kspec, qspec,
                  rspec, rspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((b, h, t, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="znicz_flash_dq",
    )(q_off, k_off, q, k, v, do, lse, delta)
    # dk/dv: Q blocks innermost; the q-side specs index by the LAST
    # grid dim now, the k-side by dim 2
    qspec2 = pl.BlockSpec((1, 1, bq, d), lambda b_, h_, ik, iq: (b_, h_, iq, 0))
    kspec2 = pl.BlockSpec((1, 1, bk, d), lambda b_, h_, ik, iq: (b_, h_, ik, 0))
    rspec2 = pl.BlockSpec((1, 1, bq, lanes),
                          lambda b_, h_, ik, iq: (b_, h_, iq, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, pack=pack),
        grid=(b, h, nk, nq),
        in_specs=[off_spec, off_spec, qspec2, kspec2, kspec2, qspec2,
                  rspec2, rspec2],
        out_specs=(kspec2, kspec2),
        out_shape=(jax.ShapeDtypeStruct((b, h, tk, d), k.dtype),
                   jax.ShapeDtypeStruct((b, h, tk, d), v.dtype)),
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="znicz_flash_dkv",
    )(q_off, k_off, q, k, v, do, lse, delta)
    return dq, dk, dv


# ----------------------------------------------------------------------
# custom_vjp hop (head-major) + the (B, T, H, D) public entry
# ----------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_hop(q, k, v, q_off, k_off, causal, bq, bk, interpret, pack):
    """One flash pass over head-major (packed) operands at global
    positions (q_off, k_off) → (out, lse).  This is BOTH the plain
    single-call kernel (offsets 0, lse discarded) and the per-hop
    ring fold (lse feeds the cross-hop online-softmax combination);
    the lse cotangent folds into delta in the backward, so one
    custom_vjp serves both."""
    return _fwd_call(q, k, v, q_off, k_off, causal, bq, bk, interpret,
                     pack)


def _hop_fwd(q, k, v, q_off, k_off, causal, bq, bk, interpret, pack):
    out, lse = _fwd_call(q, k, v, q_off, k_off, causal, bq, bk,
                         interpret, pack)
    return (out, lse), (q, k, v, out, lse, q_off, k_off)


def _hop_bwd(causal, bq, bk, interpret, pack, res, cts):
    q, k, v, out, lse, q_off, k_off = res
    do, dlse = cts
    do = do.astype(q.dtype)
    b, h, t, d = q.shape
    dh = d // pack
    # delta = rowsum(do·o) per sub-head; the lse cotangent (hop
    # composition) enters the score gradient as ds += p·dlse, i.e.
    # delta -= dlse (lanes are value copies → group-sum them)
    delta4 = jnp.sum(
        (do.astype(jnp.float32) * out.astype(jnp.float32))
        .reshape(b, h, t, pack, dh), axis=-1)
    delta4 = delta4 - dlse.astype(jnp.float32) \
        .reshape(b, h, t, pack, _LANES).sum(axis=-1)
    dq, dk, dv = _bwd_call(q, k, v, lse, do, delta4, q_off, k_off,
                           causal, bq, bk, interpret, pack)
    zero = np.zeros((1, 1), jax.dtypes.float0)
    return dq, dk, dv, zero, zero


_flash_hop.defvjp(_hop_fwd, _hop_bwd)


def ring_hop(qh, kh, vh, q_offset, k_offset, causal: bool,
             block_q: int, block_k: int, interpret: bool = False,
             pack: int = 1):
    """One ring hop on head-major, already-packed operands
    (B, Hp, T, pack·dh): returns (out in qh.dtype, lse (B, Hp, T,
    pack) f32).  Offsets may be traced scalars (``axis_index``
    arithmetic under shard_map)."""
    out, lse = _flash_hop(qh, kh, vh, _off_arr(q_offset),
                          _off_arr(k_offset), causal, block_q,
                          block_k, interpret, pack)
    return out, lse[..., ::_LANES]


def pack_heads(x, pack: int):
    """(B, T, H, dh) boundary layout → head-major packed
    (B, H//pack, T, pack·dh).  Heads are adjacent to dh at the
    boundary, so the pack itself is a free reshape; the transpose is
    the same bandwidth pass the unpacked path already pays."""
    b, t, h, dh = x.shape
    return x.reshape(b, t, h // pack, pack * dh).transpose(0, 2, 1, 3)


def unpack_heads(x, pack: int, n_heads: int):
    """Inverse of :func:`pack_heads`: (B, Hp, T, pack·dh) →
    (B, T, H, dh)."""
    b, hp, t, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, n_heads, d // pack)


def flash_attention(q, k, v, causal: bool = False,
                    block_q: int = BLOCK_Q, block_k: int = BLOCK_K,
                    dot_dtype=None, interpret: bool = False,
                    mesh=None, spec=None, q_offset=None, k_offset=None,
                    head_pack: int = 1):
    """Fused flash attention: (B, T, H, D) → (B, T, H, D) f32.

    ``dot_dtype`` casts q/k/v (the tile-GEMM operand dtype — bf16 in
    the framework's mixed-precision mode); accumulation and softmax
    statistics are always f32.  Blocks must divide T (same contract as
    ``local_attention_blocked``).  Differentiable via the fused
    recompute backward — no (T, T) tensor ever reaches HBM in either
    direction.

    ``q_offset``/``k_offset`` place this call on the GLOBAL sequence
    axis for causal masking (the ring-hop geometry; may be traced
    scalars).  ``head_pack=2`` folds head pairs into 128-lane tiles
    (see the module docstring) — exact per-head math, resolved by the
    unit gate via :func:`resolve_head_pack`.

    ``mesh``/``spec`` is the mesh-native path: ``spec`` is a boundary-
    layout (B, T, H, D) PartitionSpec (derive it with
    :func:`znicz_tpu.parallel.mesh.kernel_shard_spec`) and the kernel
    runs per-shard under ``shard_map`` — without it an opaque
    ``pallas_call`` has no GSPMD sharding rule, so a multi-device mesh
    would replicate-and-gather the operands onto every device.  Only
    batch-like dims may shard (batch over ``data``; heads compose with
    TP the same way); sharding T is the ring's job and is rejected
    here, as is sharding the head dim.  Gradients flow through the
    shard_map (the custom_vjp backward runs per-shard — attention is
    independent per batch element and head, so no cross-shard
    reduction exists).
    """
    b, t, h, d = q.shape
    tk = k.shape[1]
    pack = int(head_pack) if head_pack else 1
    if pack > 1 and h % pack:
        raise ValueError(f"head_pack {pack} does not divide "
                         f"{h} heads")
    bq, bk = min(block_q, t), min(block_k, tk)
    if t % bq or tk % bk:
        raise ValueError(f"T {t}/{tk} not divisible by blocks "
                         f"({bq}, {bk})")
    if dot_dtype is not None:
        q, k, v = (a.astype(dot_dtype) for a in (q, k, v))
    qh, kh, vh = (pack_heads(a, pack) for a in (q, k, v))
    if mesh is not None and spec is not None \
            and any(a is not None for a in spec):
        if spec[1] is not None or spec[3] is not None:
            raise ValueError(
                f"flash_attention shard spec {spec} shards T or the "
                f"head dim — only batch-like dims (batch, heads) may "
                f"shard; time sharding rides the ring path")
        if q_offset is not None or k_offset is not None:
            raise ValueError(
                "global offsets ride the ring path (per-shard hops), "
                "not the batch-sharded shard_map path")
        from jax.sharding import PartitionSpec as P
        hspec = P(spec[0], spec[2], None, None)  # boundary → head-major
        # check_vma off: an opaque pallas_call (and the custom_vjp
        # around it) has no replication rule for the checker
        fn = jax.shard_map(
            lambda a, b_, c: _flash_hop(
                a, b_, c, _off_arr(None), _off_arr(None), causal, bq,
                bk, interpret, pack)[0],
            mesh=mesh, in_specs=(hspec, hspec, hspec), out_specs=hspec,
            check_vma=False)
        out = fn(qh, kh, vh)
    else:
        out = _flash_hop(qh, kh, vh, _off_arr(q_offset),
                         _off_arr(k_offset), causal, bq, bk,
                         interpret, pack)[0]
    return unpack_heads(out, pack, h).astype(jnp.float32)

"""The gated delta rule in its chunked form (Yang, Kautz, Hatamizadeh
2024, arXiv:2412.06464) as four Pallas kernels: what is local to a
chunk — forward and backward — and the state handed from chunk to
chunk — forward and reverse; the same algebra in plain ``jax.numpy``
beside them.  And, since PR 40, what a layer does between its
q ‖ k ‖ v projection and the rule as two more (the last section below).

Per head, with keys q_t, k_t of d_k, values v_t of d_v, a decay
α_t ∈ (0, 1) and a write strength β_t, the recurrence is

.. code-block:: text

    S_t = α_t S_{t−1} + β_t k_t (v_t − α_t S_{t−1}ᵀ k_t)ᵀ     o_t = S_tᵀ q_t

over a state S of d_k × d_v, S_0 = 0.  In chunks of C positions, with
c_i = Σ_{j≤i} log α_j inside a chunk, Γ_ij = exp(c_i − c_j) for i ≥ j
(only such differences are ever exponentiated: all ≤ 1) and K, Q, V
the chunk's rows:

.. code-block:: text

    A  = (I + strict_lower(diag(β)(Γ ⊙ K Kᵀ)))⁻¹ diag(β)
    W  = A (exp(c) ⊙ K)        U = A V         K̂ = K ⊙ exp(c_C − c)
    Qc = exp(c) ⊙ Q            P = Q Kᵀ ⊙ Γ, lower triangle         (·)
    V′ = U − W S_n             S_{n+1} = exp(c_C) S_n + K̂ᵀ V′     (*)
    O  = Qc S_n + P V′

The matrix under the inverse is unit lower triangular, its strict part
L nilpotent.  Its inverse is built by halves from the 2 × 2 blocks of
the diagonal (:func:`unit_lower_inverse`): ten whole-chunk matmuls on
the MXU at C 64, no triangular solve — and not the series
Π_p (I + (−L)^(2^p)), whose high powers cancel too many digits.

(·) is local to a chunk.  On a TPU it is ``znicz_gdr_chunk_fwd``: a
grid step takes ``CHUNKS_PER_STEP`` chunks' q, k, v, log α and β, and
Γ, K Kᵀ, L, the levels of the inverse and A live and die in VMEM; it
writes W, K̂, U, exp(c_C), Qc, P and ONE (C, C) matrix a chunk for the
backward, (I + L)⁻¹ (31 MB a layer at T 4,096 × 30 heads; under
autodiff Γ, K Kᵀ, L, the inverse and P's factors were kept, and every
level of the inverse was a round trip of such an array through HBM).
``znicz_gdr_chunk_bwd`` is written by hand under one ``custom_vjp``:
from the cotangents of the six outputs and that matrix to those of q,
k, v, log α and β, with d(M⁻¹) = −M⁻ᵀ dM M⁻ᵀ; Γ and K Kᵀ are computed
again, nothing of shape (C, C) is written.  Precision is
:func:`chunk_local`'s: the logarithms' sums (each from its own terms,
a 0/1 product, never a difference of prefixes), Γ, K Kᵀ, the inverse
and their cotangents f32 with f32 products at the highest precision;
the products into W, U and P, and their transposes in the backward,
take ``dot_dtype`` inputs with f32 accumulation.  "The highest
precision" is six bf16 passes — both factors in three bf16 parts, six
of the nine cross products — for a product of two real f32 factors:
K Kᵀ (M under a decay per channel), the inverse's levels, d(M⁻¹) and
their cotangents keep it (:data:`_exact`).  Where one factor is a
0 / ±1 matrix built from indices — every sum of log α, and the way
back into d log α — that factor IS its own first part and three of the
six passes multiply zeros: the kernels split the f32 factor alone into
its three bf16 parts (they add back to it bit for bit) and contract
them against the mask laid thrice along the contraction
(:func:`_mask_product`, PR 41): the same terms, each exact, summed in
f32 — three passes, and the masks that share a factor stacked into one
product.  :func:`chunk_local` is the path off a TPU and on a mesh, and
the kernels' oracle.

(*) is sequential: ``znicz_delta_state_fwd`` walks a head's chunks
along the grid's last axis with S in VMEM and writes V′ and, for the
backward, every chunk's S_n; ``znicz_delta_state_bwd`` walks them in
reverse with the state's cotangent carried.  :func:`state_scan`
without the kernels is the same algebra as a ``lax.scan`` over the
chunks.  O's two products and the moves between (B, T, H, ·) and the
chunked view are ``jax.numpy`` under autodiff.

Head sizes need not fill a 128-lane tile: a block spans a whole
(C, d_k) or (d_k, d_v) face of its array, which Mosaic lays out in
whole (8, 128) tiles — at d_k 96 × d_v 192 the state occupies
128 × 256 lanes' worth, 1.78 × its elements (:func:`padded_share`; at
128 × 128 exactly its own, 1.0); HBM holds no padding.

**Both decay shapes** run through ONE scaffold (PR 42): the chunk
kernels' wrappers over a grid step's chunks, their jitted calls and
``custom_vjp``, the walk's two kernels and theirs exist once and take a
RULE (:class:`_Rule`) — a record of what differs and nothing else: a
chunk's algebra (masks, L, outputs, cotangents), how a chunk's decay
lies in a row of lanes, how the walk scales S by it and takes its
cotangent, the width the backward's residuals are kept at, and the
kernels' names.  The shape of log α is a static property of the call
and picks the record: (…, H), one decay a head, is everything above
(``znicz_gdr_chunk_*``, ``znicz_delta_state_*``); (…, H, d_k), one
decay per KEY CHANNEL (Kimi Delta Attention, arXiv:2510.26692; PR 37),
is

.. code-block:: text

    S_t = Diag(α_t) S_{t−1} + β_t k_t (v_t − (Diag(α_t) S_{t−1})ᵀ k_t)ᵀ

with c_i ∈ R^{d_k}.  Γ then stands INSIDE the contraction,
M_ij = Σ_d K_id K_jd e^(c_id − c_jd) and P likewise, which two
(C, d_k) factors give only by exponentiating c_i − r and r − c_j
apart, r a reference point: "only differences ≤ 0 are exponentiated"
cannot hold for a whole chunk.  It holds by SUB-BLOCKS of
``SUB_BLOCK`` = 16 positions: a row's sub-block takes the prefix at
its own start as r, so against every earlier sub-block both exponents
are ≤ 0, and inside the sub-block r − c_j ≤ 16 · |lower bound| — **the
exponent bound, stated once: ``MAX_EXPONENT`` = 80** (e^80 · d_k = 128
terms stays under the f32 maximum, log 3.4e38 = 88.7), which is what a
bounded decay (log α ≥ −5) is published FOR; ``GatedDeltaNet``
refuses, by name at ``initialize``, a bound whose 16-fold passes it.
The rest is the scalar algebra with rows for numbers: W = A (e^c ⊙ K),
K̂ = K ⊙ e^(c_C − c), Qc = e^c ⊙ Q, S_{n+1} = Diag(e^(c_C)) S_n +
K̂ᵀ V′ — the walk scales S's ROWS.  Kernels ``znicz_kda_chunk_fwd`` /
``_bwd`` (the hand-written backward gives d log α per channel) and
``znicz_kda_state_fwd`` / ``_bwd``; precision as above (the sums of
log α, Γ's factors, M, the inverse f32 at the highest precision — the
C/16 + 3 sums of log α ONE :func:`_mask_product` with the masks stacked
row block under row block, their cotangents one more with the masks'
transposes side by side, M and the inverse six passes; W, U,
P and the state's update ``dot_dtype`` inputs, f32 accumulation, f32
state in VMEM — the per-chunk S_n this walk WRITES for the backward are
at ``dot_dtype``, the width every product takes them in: 64 MB a layer
less than f32 at T 4,096 × 32 heads of 128 × 128); :func:`chunk_local` / :func:`state_scan` in ``jax.numpy``,
which write Γ out as a (C, C, d_k) array, stay the oracle and the path
off a TPU.  Neither rule's kernel names hold the other's.

**Between the projection and the rule** (:func:`qkv_prep`, PR 40): per
column c of u = m (W_q ‖ W_k ‖ W_v), (B, T, H·(2 d_k + d_v)) f32,

.. code-block:: text

    c_t = Σ_{j<J} taps[c, j] · u_{t−J+1+j}     zeros before the sequence
    a_t = c_t / (1 + e^(−c_t))
    q: a_t / √(Σ_head a_t² + ε) · d_k^(−1/2)   k: a_t / √(Σ_head a_t² + ε)
    v: a_t

``znicz_qkv_prep_fwd`` takes a head's columns where the matmul wrote
them — d_k and d_v whole 128-lane tiles (:func:`prep_legal`), so a
``BlockSpec`` addresses a head and its norm is a reduction across the
lanes of its own tiles — and writes (B, H, T + pad, ·), the rows the
chunk kernels read (:func:`gated_delta_rule` ``head_major``), the
padding zeros.  ``znicz_qkv_prep_bwd`` reads u, the taps and the three
cotangents, makes c, a and the norms again in VMEM and writes du where
u lies and dtaps (summed over the row tiles and the batch in a block
that stays in VMEM): what its ``custom_vjp`` keeps is u and the taps.
Each way is ONE body called for three column ranges — q (normed,
scaled), k (normed), v (128 lanes at a time) — because their results
are three arrays forward and ONE array backward (du, handed from call
to call as an aliased operand, each writing its own columns).  A grid
step takes ``PREP_ROWS`` rows of one column block and walks them in
sub-tiles of 64; u_{t−s} is a sublane rotation of the sub-tile with
the 8 rows before it, which at a grid step's first rows come through a
second ``BlockSpec`` over the same array (8 rows: the halo; zeros at a
sequence's start), and the backward's dc_{t+s} likewise from the 8 rows
AFTER (u's and the cotangent's, dc made again for them; zero past the
sequence's end).  Sequences are a grid axis: nothing crosses from one
into the next.  Everything f32, the formulas and ε
``delta_net.causal_conv`` / ``moe._silu`` / ``delta_net.l2_normalize``'s.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import typing

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from znicz_tpu.ops.pallas_taps import (LANES, SUBLANES, column_sums, delayed,
                                       eight_rows, ones_where, taps_sum,
                                       unless)

#: positions per chunk (the program's, not a model's)
CHUNK = 64
_HIGHEST = jax.lax.Precision.HIGHEST


def kernel_legal(chunk: int = CHUNK) -> bool:
    """A chunk's rows are whole sublane tiles (and a power of two, as
    the inverse wants them)."""
    return chunk % SUBLANES == 0 and chunk & (chunk - 1) == 0


def padded_share(dk: int, dv: int) -> float:
    """Elements of the 128-lane tiles a d_k × d_v product occupies over
    d_k · d_v: d_k is the lane axis of W and K̂ and the contraction of
    their products with the state, d_v the lane axis of S, U and V′;
    each goes to the next multiple of 128 (1.0: nothing padded, as at
    Ling-3.0-flash's 128 × 128; 1.78 at Olmo-Hybrid's 96 × 192)."""
    def whole(n: int) -> int:
        return -(-n // LANES) * LANES
    return whole(dk) * whole(dv) / float(dk * dv)


def _mm(a, b, dot_dtype):
    """Batched ``a @ b`` over the leading axes: inputs in ``dot_dtype``
    with f32 accumulation, or f32 at the highest precision."""
    if dot_dtype is not None:
        return jnp.matmul(a.astype(dot_dtype), b.astype(dot_dtype),
                          preferred_element_type=jnp.float32)
    return jnp.matmul(a, b, precision=_HIGHEST)


def _pair_masks(c: int) -> list:
    """Per level of the inverse, sizes 1, 2, 4, … < C: the 0/1 (C, C)
    mask of the blocks BELOW the diagonal that join two inverted blocks
    of that size into one of twice it."""
    at = np.arange(c)
    masks, size = [], 1
    while size < c:
        same_pair = at[:, None] // (2 * size) == at[None, :] // (2 * size)
        masks.append((same_pair & (at[:, None] % (2 * size) >= size)
                      & (at[None, :] % (2 * size) < size)).astype(
                          np.float32))
        size *= 2
    return masks


@jax.custom_vjp
def unit_lower_inverse(lower):
    """(I + L)⁻¹ for strictly lower triangular ``lower`` (…, C, C), C a
    power of two, in f32 matmuls at the highest precision and no
    triangular solve — by halves:

    .. code-block:: text

        [[A, 0], [B, D]]⁻¹ = [[A⁻¹, 0], [−D⁻¹ B A⁻¹, D⁻¹]]

    from the 2 × 2 blocks on the diagonal (which invert exactly: I − L,
    L² = 0 there) up.  Every level is ONE pair of whole (C, C) batched
    matmuls: with X the block-diagonal inverse so far and B the level's
    blocks below the diagonal (L under a 0/1 mask), the next is
    X − X B X — the zeros ride along, and nothing is sliced, gathered
    or concatenated (sliced into its blocks the same algebra was 2,000
    operations and 29 ms a layer on the chip; PERF.md §6, PR 31).
    2 (log₂ C − 1) matmuls in all, ten at C 64.

    The nilpotent series in product form, Π_p (I + (−L)^(2^p)), is as
    many matmuls and loses digits as C grows — its high powers are
    large and cancel: at C 64 with keys as alike as a convolution
    leaves them it read 1.4e-4 of the mixer's output against the
    recurrence, and 1.6e-2 of the inverse with keys nearly parallel,
    where this form reads 6e-7 and 3e-6 (f32).

    Its derivative is its own rule, d(M⁻¹) = −M⁻¹ dM M⁻¹: two matmuls
    from the inverse the forward kept, not the transposes of ten."""
    c = lower.shape[-1]
    if c & (c - 1):
        raise ValueError(f"unit_lower_inverse: {c} rows are not a power "
                         f"of two")
    masks = _pair_masks(c)
    inverse = jnp.eye(c, dtype=lower.dtype)
    for mask in masks:
        joint = jnp.matmul(
            jnp.matmul(inverse, lower * mask, precision=_HIGHEST),
            inverse, precision=_HIGHEST)
        inverse = inverse - joint
    return inverse


def _inverse_fwd(lower):
    inverse = unit_lower_inverse(lower)
    return inverse, inverse


def _inverse_bwd(inverse, cotangent):
    back = jnp.swapaxes(inverse, -1, -2)
    c = inverse.shape[-1]
    strictly_lower = np.tril(np.ones((c, c), np.float32), -1)
    return (-jnp.matmul(jnp.matmul(back, cotangent, precision=_HIGHEST),
                        back, precision=_HIGHEST) * strictly_lower,)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def chunk_local(q, k, v, log_alpha, beta, dot_dtype=None):
    """What a chunk computes from its own rows: ``(W, K̂, U, decay,
    Qc, P)`` of the module docstring for q, k (G, N, C, d_k),
    v (G, N, C, d_v), log α and β (G, N, C) — ``decay`` = exp(c_C)
    (G, N), ``Qc`` = exp(c) ⊙ Q, ``P`` = Q Kᵀ ⊙ Γ on and below the
    diagonal.  The logarithms, their sums, Γ and the inverse are f32;
    the products into W, U and P take ``dot_dtype`` inputs.  A decay
    per key channel — log α (G, N, C, d_k) — takes
    :func:`_chunk_local_channels`, whose ``decay`` is (G, N, d_k)."""
    if log_alpha.ndim == q.ndim:
        return _chunk_local_channels(q, k, v, log_alpha, beta, dot_dtype)
    chunk = q.shape[-2]
    log_alpha = log_alpha.astype(jnp.float32)
    c = jnp.cumsum(log_alpha, axis=-1)
    rows = np.arange(chunk)[:, None]
    cols = np.arange(chunk)[None, :]
    # c_i − c_j summed from its own terms, Σ_{j<m≤i} log α_m (a 0/1
    # matmul), not as a difference of two prefixes: where the decay is
    # strong the prefixes are hundreds and their difference would
    # carry their rounding
    at = np.arange(chunk)
    between = (at[None, None, :] < at[:, None, None]) \
        & (at[:, None, None] <= at[None, :, None])  # [m, i, j]: j < m ≤ i
    between = between.astype(np.float32).reshape(chunk, -1)
    gamma = jnp.exp(jnp.where(
        rows >= cols,
        jnp.matmul(log_alpha, between, precision=_HIGHEST).reshape(
            log_alpha.shape + (chunk,)),
        -jnp.inf))
    kk = jnp.matmul(k, jnp.swapaxes(k, -1, -2), precision=_HIGHEST)
    lower = jnp.where(rows > cols, beta[..., :, None] * gamma * kk, 0.0)
    a = unit_lower_inverse(lower) * beta[..., None, :]
    grown = jnp.exp(c)[..., None]
    w = _mm(a, grown * k, dot_dtype)
    u = _mm(a, v, dot_dtype)
    # exp(c_C − c_i) from the suffix's own sum, for the same reason
    after = jnp.flip(jnp.cumsum(jnp.flip(log_alpha, -1), axis=-1), -1)
    after = jnp.concatenate(
        [after[..., 1:], jnp.zeros_like(after[..., :1])], axis=-1)
    k_hat = k * jnp.exp(after)[..., None]
    p = _mm(q, jnp.swapaxes(k, -1, -2), dot_dtype) * gamma
    return w, k_hat, u, jnp.exp(c[..., -1]), q * grown, p


# ----------------------------------------------------------------------
# what is local to a chunk: kernels
# ----------------------------------------------------------------------
#: chunks a grid step of the chunk-local kernels (≈ 7 MB of
#: double-buffered blocks in the backward at 96 × 192; 4, 8 and 16 read
#: the same on the chip to 2%: the kernels are bound by the MXU's
#: passes, not by a grid step's fixed cost — PERF.md §6, PR 32)
CHUNKS_PER_STEP = 8
#: of which this many stand in one basic block (:func:`_over_chunks`):
#: independent chains of the inverse for the scheduler to interleave
#: (2 is 3% slower on the chip, 8 2% faster and twice the lowering)
_TOGETHER = 4


def _dot(a, b, dot_dtype, trans_a=False, trans_b=False, exact=False):
    """2-D ``a @ b`` inside a kernel (either side transposed): inputs
    in ``dot_dtype`` with f32 accumulation, or f32 at the highest
    precision where ``exact``."""
    dims = (((0,) if trans_a else (1,), (1,) if trans_b else (0,)),
            ((), ()))
    if exact:
        return jax.lax.dot_general(a, b, dims, precision=_HIGHEST,
                                   preferred_element_type=jnp.float32)
    if dot_dtype is not None:
        a, b = a.astype(dot_dtype), b.astype(dot_dtype)
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


#: a kernel's 2-D product in f32 at the highest precision
_exact = functools.partial(_dot, dot_dtype=None, exact=True)


def _mixed(dot_dtype):
    """A kernel's 2-D product that takes ``dot_dtype`` inputs (f32 at
    the highest precision where there is none)."""
    return _exact if dot_dtype is None else functools.partial(
        _dot, dot_dtype=dot_dtype)


def _three_parts(x):
    """An f32 array as three bf16 arrays, ``hi + mid + lo == x`` bit for
    bit: 24 significant bits in 3 × 8, each part the rounding of what
    the parts before it left.  Exact for every finite x of magnitude
    from 2^-100 up (``lo`` is 2^-16 of x and has to stay a normal bf16):
    log α ∈ [−5, 0) and its cotangents are; below that ``lo``, then
    ``mid``, flush to zero and x keeps 16, then 8, bits of a number
    that small."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    return hi, mid, (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)


def _for_mask_product(mask, right: bool = False):
    """A 0 / ±1 matrix as :func:`_mask_product` takes it: bf16 (its own
    first part — the other two are zero), thrice along the contraction
    so that ONE product sums the three parts of the other factor inside
    the MXU.  Built once a kernel body, beside the positions."""
    return jnp.concatenate([mask.astype(jnp.bfloat16)] * 3,
                           axis=0 if right else 1)


def _mask_product(mask, x, right: bool = False):
    """``mask @ x`` (``x @ mask`` where ``right``) for a 0 / ±1 matrix
    from :func:`_for_mask_product` and a real f32 ``x``, to f32's last
    bits in three bf16 passes: the highest precision splits BOTH
    factors into three bf16 parts and runs six of the nine cross
    products, but a mask is its own first part, so the three passes
    that meet its second and third multiply zeros.  What is left is
    mask · (hi + mid + lo) — the same non-zero terms, each exact in the
    f32 accumulator (a part times 0 or ±1), so every sum is still made
    from its own terms in f32.  Only for factors that are 0 or ±1 BY
    CONSTRUCTION (:func:`_positions`, :func:`_kda_positions`): a
    product of two real f32 factors (M, K Kᵀ, the inverse, their
    cotangents) stays :data:`_exact`."""
    parts = jnp.concatenate(_three_parts(x), axis=1 if right else 0)
    a, b = (parts, mask) if right else (mask, parts)
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _positions(c: int, backward: bool = False):
    """A (C, C) matrix's entries on and below the diagonal, strictly
    below, strictly above, and on it, as 0/1 in f32 to multiply by; and
    as right factors of a :func:`_mask_product` ``sums`` [m, j]: j < m
    (Σ_{j<m≤i} log α_m from the row's own terms) and, for the
    ``backward``, its transpose ``back`` [j, m]."""
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    upto, below, above, eye = (
        ones_where(col <= row), ones_where(col < row),
        ones_where(col > row), ones_where(col == row))
    return (upto, below, above, eye,
            _for_mask_product(below, right=True),
            _for_mask_product(above, right=True) if backward else None)


def _rows(x):
    """Σ over a row's entries, (C, ·) → (C, 1)."""
    return jnp.sum(x, axis=1, keepdims=True)


def _inverse_levels(c: int, n: int):
    """0/1 masks of :func:`_inverses_in_vmem` for ``n`` (C, C) matrices
    side by side, from the indices: the identity; per level of sizes
    1, 2, 4, … < C the blocks below the diagonal that join two inverted
    blocks (:func:`_pair_masks`); and each matrix's own lanes."""
    row = jax.lax.broadcasted_iota(jnp.int32, (c, n * c), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (c, n * c), 1)
    col = lane & (c - 1)                  # within a lane's own matrix
    joins, size, shift = [], 1, 1
    while size < c:
        joins.append(ones_where(
            (row >> shift == col >> shift) & (row & size != 0)
            & (col & size == 0)))
        size, shift = 2 * size, shift + 1
    own = [ones_where(lane >> (shift - 1) == m) for m in range(n)]
    return ones_where(row == col), joins, own


def _inverses_in_vmem(lowers, levels):
    """:func:`unit_lower_inverse` on one (C, C) matrix, or on several
    side by side in the lanes of one (C, n·C) array — two chunks of 64
    fill a 128-lane tile, and a product of the chain then costs the MXU
    what one chunk's did (0.10 against 0.18 µs a chunk on the chip;
    PERF.md §6, PR 32).  The first level is I − B exactly (I B I), the
    others X − X B X with B and X each on the diagonal of an
    (n·C, n·C) right operand; ``levels`` from :func:`_inverse_levels`."""
    c, n = lowers[0].shape[0], len(lowers)
    eye, joins, own = levels
    lower = lowers[0] if n == 1 else jnp.concatenate(lowers, axis=1)

    def diagonal(x):    # every matrix opposite its own lanes' rows
        return x if n == 1 else jnp.concatenate(
            [x * lanes for lanes in own], axis=0)

    x = eye - lower * joins[0]
    for join in joins[1:]:
        x = x - _exact(_exact(x, diagonal(lower * join)), diagonal(x))
    return [x[:, m * c:(m + 1) * c] for m in range(n)]


def _decays(log_alpha, upto, above, sums):
    """From a chunk's log α as a row (1, C), every sum from its own
    terms: Γ (C, C), exp(c) and exp(c_C − c) as columns (C, 1), exp(c_C)
    (1, 1)."""
    own = upto * log_alpha                             # [i, m]: m ≤ i
    # Σ_{j<m≤i} log α_m
    gamma = upto * jnp.exp(_mask_product(sums, own, right=True))
    return (gamma, jnp.exp(_rows(own)), jnp.exp(_rows(above * log_alpha)),
            jnp.exp(_rows(log_alpha)))


def _side_by_side(block: int, c: int) -> int:
    """Chunks whose inverses share one chain of products
    (:func:`_inverses_in_vmem`): two where they fill a 128-lane tile
    and a grid step holds an even number."""
    return 2 if block % 2 == 0 and 2 * c <= LANES else 1


def chunk_products(channels: bool, chunk: int = CHUNK, sub: int = None,
                   dot_dtype=jnp.bfloat16) -> dict:
    """What the chunk-local kernels' bodies hold a chunk, forward and
    backward, counted from the algebra — ``{"exact_fwd", "exact_bwd"``:
    products of two real f32 factors at the highest precision, six
    bf16 passes each; ``"mask_fwd", "mask_bwd"``: products with a
    0 / ±1 matrix as three bf16 parts in one contraction
    (:func:`_mask_product`) ``}`` — for a decay per key ``channels``
    (sub-blocks of ``sub``) or per head.  The inverse's chain is
    2 (log₂ C − 1) whole products shared by the chunks side by side;
    without a ``dot_dtype`` the products into W, U and P, and their
    transposes, are exact ones too.  ``tests/test_delta_chunk_kernels``
    pins the bodies' jaxprs to it; ``GatedDeltaNet`` publishes it."""
    blocks = chunk // (sub or sub_block(chunk)) if channels else 0
    inverse = 2 * (chunk.bit_length() - 2) // _side_by_side(
        CHUNKS_PER_STEP, chunk)
    if channels:    # M by sub-blocks; d_lower, d_left_k and d_right
        exact = {"fwd": blocks + inverse, "bwd": 3 * blocks + 2}
        mixed = {"fwd": blocks + 2, "bwd": 2 * blocks + 4}
    else:           # K Kᵀ; d_lower, d_kk · k both ways
        exact = {"fwd": 1 + inverse, "bwd": 5}
        mixed = {"fwd": 3, "bwd": 7}
    return {f"exact_{way}": exact[way] + (mixed[way] if dot_dtype is None
                                          else 0) for way in exact} \
        | {"mask_fwd": 1, "mask_bwd": 2}


def _over_chunks(block: int, together: int, step: int, some) -> None:
    """``some(first)`` for every ``step`` of a grid step's ``block``
    chunks, ``together`` chunks in one basic block (Mosaic unrolls a
    loop whole or not at all): independent chains for the scheduler to
    interleave."""
    assert together % step == 0, (together, step)

    def group(at, carry):
        for first in range(0, together, step):
            some(at * together + first)
        return carry
    jax.lax.fori_loop(0, block // together, group, None)


# A grid step's chunks all do the same arithmetic on their own rows.
# It is written as jitted functions of VALUES, called once per chunk
# (or pair) from the kernels' bodies: the host traces each once and
# the body holds a call per chunk, where the same lines written into
# the body were traced once per chunk of a basic block — seconds of
# every process's start on the chip's host (PERF.md §6, PR 32).
@jax.jit
def _chunk_lower(positions, log_alpha, beta, k):
    """A chunk's L, exp(c_C), and what the outputs need of the decays
    (:func:`_decays`): Γ, exp(c), exp(c_C − c)."""
    upto, below, above, eye, sums, _ = positions
    gamma, grown, rest, decay = _decays(log_alpha, upto, above, sums)
    # β as a column: (1, C) → (C, 1) without a transpose
    lower = below * (_rows(eye * beta) * gamma
                     * _exact(k, k, trans_b=True))
    return lower, decay, gamma, grown, rest


_inverses = jax.jit(_inverses_in_vmem)


@functools.partial(jax.jit, static_argnames=("dot_dtype",))
def _chunk_outputs(positions, q, k, v, beta, x, gamma, grown, rest, *,
                   dot_dtype):
    """W, K̂, U, Qc, P from a chunk's rows, decays and (I + L)⁻¹ (Γ
    carries P's mask: ``positions`` are the per-channel rule's to
    use)."""
    mixed = _mixed(dot_dtype)
    a = x * beta
    return (mixed(a, grown * k), rest * k, mixed(a, v), grown * q,
            mixed(q, k, trans_b=True) * gamma)


def _chunk_fwd_kernel(q_ref, k_ref, v_ref, a_ref, b_ref, w_ref, kh_ref,
                      u_ref, d_ref, qc_ref, p_ref, x_ref, *, rule,
                      dot_dtype, together, side_by_side):
    """A ``rule``'s forward for a grid step's chunks: L from the rows
    the rule names (``lower_reads``: a row it does not use is not
    loaded), the inverses of ``side_by_side`` chunks in one chain, then
    the six outputs and (I + L)⁻¹."""
    block, c = q_ref.shape[0], q_ref.shape[1]
    positions = rule.positions(c)
    levels = _inverse_levels(c, side_by_side)
    rows = dict(q=q_ref, k=k_ref, v=v_ref, a=a_ref, b=b_ref)

    def some(first):
        at = [first + m for m in range(side_by_side)]
        held = [rule.lower(positions,
                           *(rows[r][i] for r in rule.lower_reads))
                for i in at]
        inverses = _inverses([lower for lower, *_ in held], levels)
        for i, (_, decay, *factors), x in zip(at, held, inverses):
            x_ref[i] = x
            w_ref[i], kh_ref[i], u_ref[i], qc_ref[i], p_ref[i] = \
                rule.outputs(positions, q_ref[i], k_ref[i], v_ref[i],
                             b_ref[i], x, *factors, dot_dtype=dot_dtype)
            # a number a head fills its row's lanes
            d_ref[i] = jnp.broadcast_to(decay, d_ref.shape[1:])

    _over_chunks(block, together, side_by_side, some)


@functools.partial(jax.jit, static_argnames=("dot_dtype",))
def _chunk_cotangents(positions, q, k, v, log_alpha, beta, x, d_w, d_kh,
                      d_u, d_decay, d_qc, d_p, *, dot_dtype):
    """Cotangents of a chunk's q, k, v, log α, β from those of its W, K̂,
    U, decay (a row), Qc and P, and its (I + L)⁻¹."""
    upto, below, above, eye, sums, back = positions
    exact, mixed = _exact, _mixed(dot_dtype)
    gamma, grown, rest, decay = _decays(log_alpha, upto, above, sums)
    beta_col = _rows(eye * beta)
    kk = exact(k, k, trans_b=True)
    a = x * beta
    # W = A (exp(c) ⊙ K), U = A V
    d_a = mixed(d_w, grown * k, trans_b=True) \
        + mixed(d_u, v, trans_b=True)
    d_kg = mixed(a, d_w, trans_a=True)
    d_v = mixed(a, d_u, trans_a=True)
    # d(M⁻¹) = −M⁻ᵀ dM M⁻ᵀ, on the strictly lower part
    d_lower = -below * exact(exact(x, d_a * beta, trans_a=True), x,
                             trans_b=True)
    # L = β_i Γ_ij (K Kᵀ)_ij;  P = Q Kᵀ ⊙ Γ
    d_gamma = d_lower * beta_col * kk + d_p * mixed(q, k, trans_b=True)
    d_kk = d_lower * beta_col * gamma
    d_qk = d_p * gamma
    d_q = mixed(d_qk, k) + grown * d_qc
    d_k = exact(d_kk, k) + exact(d_kk, k, trans_a=True) \
        + mixed(d_qk, q, trans_a=True) + grown * d_kg + rest * d_kh
    # β: a column's sum where it scales A's columns, a row's (as a
    # row: (C, 1) → (1, C) without a transpose) where L's rows
    d_beta = column_sums(d_a * x) \
        + column_sums(eye * _rows(d_lower * gamma * kk))
    # log α: Γ = exp(Σ_{j<m≤i}), exp(c), exp(c_C − c), exp(c_C)
    d_upto = _mask_product(back, d_gamma * gamma, right=True)
    d_c = grown * (_rows(d_qc * q) + _rows(d_kg * k))
    d_rest = rest * _rows(d_kh * k)
    d_alpha = column_sums(upto * (d_upto + d_c) + above * d_rest) \
        + d_decay * decay
    return d_q, d_k, d_v, d_alpha, d_beta


def _chunk_bwd_kernel(*refs, rule, dot_dtype, together):
    """A ``rule``'s cotangents for a grid step's chunks: the five rows,
    (I + L)⁻¹ — the one (C, C) matrix kept — and six cotangents in,
    five cotangents out."""
    ins, outs = refs[:12], refs[12:]
    block, c = ins[0].shape[0], ins[0].shape[1]
    positions = rule.positions(c, backward=True)

    def one(i):
        results = rule.cotangents(
            positions, *(ref[i] for ref in ins), dot_dtype=dot_dtype)
        for ref, result in zip(outs, results):
            ref[i] = result

    _over_chunks(block, together, 1, one)


def _chunk_call(kernel, name: str, arrays, outs, block: int, interpret):
    """``kernel`` over (chunks, rows, width) ``arrays``, ``block``
    chunks a grid step (the last may hold fewer), to f32 results of
    ``outs`` = (rows, width) each: independent steps, and room in VMEM
    for their double-buffered blocks."""
    total = arrays[0].shape[0]
    faces = [a.shape[1:] for a in arrays] + list(outs)
    held = sum(-(-rows // SUBLANES) * SUBLANES
               * -(-width // LANES) * LANES * 4 for rows, width in faces)

    def spec(face):
        return pl.BlockSpec((block,) + tuple(face), lambda n: (n, 0, 0))

    return pl.pallas_call(
        kernel, grid=(pl.cdiv(total, block),),
        in_specs=[spec(a.shape[1:]) for a in arrays],
        out_specs=tuple(spec(face) for face in outs),
        out_shape=tuple(jax.ShapeDtypeStruct((total,) + face, jnp.float32)
                        for face in outs),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=2 * block * held + (24 << 20)),
        interpret=interpret, name=name)(*arrays)


def _flat(a):
    """(G, N, C, ·) → (G·N, C, ·); a chunk's vector (G, N, ·) as a row
    of lanes, (G·N, 1, ·)."""
    total = a.shape[0] * a.shape[1]
    return a.reshape((total, 1, a.shape[2]) if a.ndim == 3
                     else (total,) + a.shape[2:])


# jitted: a model's linear layers call these with the same shapes and
# static arguments, so each kernel is traced and lowered once per
# program, not once per layer (ROADMAP S6)
@functools.partial(jax.jit, static_argnums=(0, 6, 7, 8))
def _chunk_forward_call(rule, q, k, v, log_alpha, beta, interpret,
                        dot_dtype, block):
    """W, K̂, U, decay, Qc, P of :func:`chunk_local` and (I + L)⁻¹ for
    the backward, everything else of a chunk's (C, C) in VMEM.  A
    chunk's decay comes out as a row of as many lanes as log α's last
    axis: C for a number a head, d_k for one per channel."""
    g, n, c, dk = q.shape
    dv, block = v.shape[-1], min(block, g * n)
    side_by_side = _side_by_side(block, c)
    w, k_hat, u, decay, qc, p, x = _chunk_call(
        functools.partial(
            _chunk_fwd_kernel, rule=rule, dot_dtype=dot_dtype,
            together=max(math.gcd(block, _TOGETHER), side_by_side),
            side_by_side=side_by_side),
        rule.chunk_kernel + "_fwd",
        [_flat(a) for a in (q, k, v, log_alpha, beta)],
        [(c, dk), (c, dk), (c, dv), (1, log_alpha.shape[-1]), (c, dk),
         (c, c), (c, c)], block, interpret)

    def heads(a):
        return a.reshape((g, n) + a.shape[1:])

    return (heads(w), heads(k_hat), heads(u), rule.from_row(decay, g, n),
            heads(qc), heads(p)), heads(x)


@functools.partial(jax.jit, static_argnums=(0, 8, 9, 10))
def _chunk_backward_call(rule, q, k, v, log_alpha, beta, x, cotangent,
                         interpret, dot_dtype, block):
    g, n, c, dk = q.shape
    dv, block = v.shape[-1], min(block, g * n)
    d_w, d_kh, d_u, d_decay, d_qc, d_p = cotangent
    rows = [_flat(a) for a in (q, k, v, log_alpha, beta, x, d_w, d_kh,
                               d_u, rule.as_row(d_decay, c), d_qc, d_p)]
    d_q, d_k, d_v, d_a, d_b = _chunk_call(
        functools.partial(_chunk_bwd_kernel, rule=rule,
                          dot_dtype=dot_dtype,
                          together=math.gcd(block, _TOGETHER)),
        rule.chunk_kernel + "_bwd", rows,
        [(c, dk), (c, dk), (c, dv), rows[3].shape[1:], (1, c)], block,
        interpret)
    return (d_q.reshape(q.shape), d_k.reshape(k.shape),
            d_v.reshape(v.shape), d_a.reshape(log_alpha.shape),
            d_b.reshape(g, n, c))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 6, 7, 8))
def _chunk_local_kernels(rule, q, k, v, log_alpha, beta, interpret,
                         dot_dtype, block):
    return _chunk_forward_call(rule, q, k, v, log_alpha, beta, interpret,
                               dot_dtype, block)[0]


def _chunk_fwd(rule, q, k, v, log_alpha, beta, interpret, dot_dtype,
               block):
    out, x = _chunk_forward_call(rule, q, k, v, log_alpha, beta,
                                 interpret, dot_dtype, block)
    # V enters the backward as a ``dot_dtype`` matmul input only (dA =
    # … + dU Vᵀ): a narrow rule keeps it at that width
    kept = v.astype(rule.width(dot_dtype))
    return out, (q, k, kept, log_alpha, beta, x)


def _chunk_bwd(rule, interpret, dot_dtype, block, residual, cotangent):
    return _chunk_backward_call(rule, *residual, cotangent, interpret,
                                dot_dtype, block)


_chunk_local_kernels.defvjp(_chunk_fwd, _chunk_bwd)


def chunk_local_kernels(q, k, v, log_alpha, beta, dot_dtype=None,
                        interpret: bool = False,
                        block: int = CHUNKS_PER_STEP):
    """:func:`chunk_local` as the rule's chunk kernels
    (``znicz_gdr_chunk_fwd``, or ``znicz_kda_chunk_fwd`` for a decay
    per key channel, and under differentiation their ``_bwd``):
    ``block`` chunks a grid step (the last step may hold fewer), one
    (C, C) matrix a chunk kept between them."""
    f32 = jnp.float32
    return _chunk_local_kernels(
        _rule_for(log_alpha.ndim == q.ndim),
        q.astype(f32), k.astype(f32), v.astype(f32),
        log_alpha.astype(f32), beta.astype(f32), interpret,
        None if dot_dtype is None else jnp.dtype(dot_dtype), block)


# ----------------------------------------------------------------------
# the walk over the chunks: plain
# ----------------------------------------------------------------------
def _state_scan_plain(w, k_hat, u, decay, dot_dtype):
    def step(s, chunk):
        w_n, k_n, u_n, d_n = chunk
        v_new = u_n - _mm(w_n, s, dot_dtype)
        # one number a head, or one per key channel: S's rows
        kept = d_n[:, None, None] if d_n.ndim == 1 else d_n[:, :, None]
        s_next = kept * s + _mm(
            jnp.swapaxes(k_n, -1, -2), v_new, dot_dtype)
        return s_next, (v_new, s)

    g, _, _, dk = w.shape
    start = jnp.zeros((g, dk, u.shape[-1]), jnp.float32)
    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (w, k_hat, u, decay))
    _, (v_new, states) = jax.lax.scan(step, start, xs)
    return jnp.moveaxis(v_new, 0, 1), jnp.moveaxis(states, 0, 1)


# ----------------------------------------------------------------------
# the walk over the chunks: kernels
# ----------------------------------------------------------------------
def _state_fwd_kernel(w_ref, k_ref, u_ref, d_ref, v_ref, s_ref, state, *,
                      rule, dot_dtype):
    @pl.when(pl.program_id(1) == 0)
    def _start():
        state[...] = jnp.zeros_like(state)

    s = state[...]
    s_ref[...] = s.astype(s_ref.dtype)
    v_new = u_ref[...] - _dot(w_ref[...], s, dot_dtype)
    v_ref[...] = v_new
    state[...] = rule.scale(d_ref[...])[0] * s + _dot(
        k_ref[...], v_new, dot_dtype, trans_a=True)


def _state_bwd_kernel(w_ref, k_ref, d_ref, s_ref, v_ref, dv_ref, ds_ref,
                      dw_ref, dk_ref, du_ref, dd_ref, carry, *, rule,
                      dot_dtype):
    """One chunk of the reverse walk; ``carry`` is the cotangent of
    S_{n+1}, this chunk's output state."""
    @pl.when(pl.program_id(1) == 0)
    def _start():
        carry[...] = jnp.zeros_like(carry)

    g, s = carry[...], s_ref[...].astype(jnp.float32)
    kept, eye = rule.scale(d_ref[...])
    dv = dv_ref[...] + _dot(k_ref[...], g, dot_dtype)
    du_ref[...] = dv
    dk_ref[...] = _dot(v_ref[...], g, dot_dtype, trans_b=True)
    dw_ref[...] = -_dot(dv, s, dot_dtype, trans_b=True)
    dd_ref[...] = rule.scale_cotangent(g * s, eye)
    carry[...] = ds_ref[...] + kept * g - _dot(
        w_ref[...], dv, dot_dtype, trans_a=True)


def _face(rows: int, cols: int, at):
    """One chunk's (rows, cols) face of a (G, N, rows, cols) array."""
    return pl.BlockSpec((None, None, rows, cols),
                        lambda g, n: (g, at(n), 0, 0))


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))


def _forward_call(rule, w, k_hat, u, decay, interpret, dot_dtype):
    g, n, c, dk = w.shape
    dv = u.shape[-1]
    decay = rule.walk_row(decay, dv)        # (G, N, 1, lanes)

    def first(i):
        return i

    return pl.pallas_call(
        functools.partial(_state_fwd_kernel, rule=rule,
                          dot_dtype=dot_dtype),
        grid=(g, n),
        in_specs=[_face(c, dk, first), _face(c, dk, first),
                  _face(c, dv, first), _face(1, decay.shape[-1], first)],
        out_specs=(_face(c, dv, first), _face(dk, dv, first)),
        out_shape=(jax.ShapeDtypeStruct((g, n, c, dv), jnp.float32),
                   jax.ShapeDtypeStruct((g, n, dk, dv),
                                        rule.width(dot_dtype))),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=_PARAMS, interpret=interpret,
        name=rule.state_kernel + "_fwd",
    )(w, k_hat, u, decay)


def _backward_call(rule, w, k_hat, decay, states, v_new, d_v, d_s,
                   interpret, dot_dtype):
    g, n, c, dk = w.shape
    dv = v_new.shape[-1]
    decay = rule.walk_row(decay, dv)
    lanes = decay.shape[-1]

    def back(i):
        return n - 1 - i

    f32 = jnp.float32
    dw, dk_hat, du, dd = pl.pallas_call(
        functools.partial(_state_bwd_kernel, rule=rule,
                          dot_dtype=dot_dtype),
        grid=(g, n),
        in_specs=[_face(c, dk, back), _face(c, dk, back),
                  _face(1, lanes, back), _face(dk, dv, back),
                  _face(c, dv, back), _face(c, dv, back),
                  _face(dk, dv, back)],
        out_specs=(_face(c, dk, back), _face(c, dk, back),
                   _face(c, dv, back), _face(1, lanes, back)),
        out_shape=(jax.ShapeDtypeStruct((g, n, c, dk), f32),
                   jax.ShapeDtypeStruct((g, n, c, dk), f32),
                   jax.ShapeDtypeStruct((g, n, c, dv), f32),
                   jax.ShapeDtypeStruct((g, n, 1, lanes), f32)),
        scratch_shapes=[pltpu.VMEM((dk, dv), f32)],
        compiler_params=_PARAMS, interpret=interpret,
        name=rule.state_kernel + "_bwd",
    )(w, k_hat, decay, states, v_new, d_v, d_s)
    return dw, dk_hat, du, rule.from_walk_row(dd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 5, 6))
def _state_scan_kernels(rule, w, k_hat, u, decay, interpret, dot_dtype):
    return _forward_call(rule, w, k_hat, u, decay, interpret, dot_dtype)


def _scan_fwd(rule, w, k_hat, u, decay, interpret, dot_dtype):
    v_new, states = _forward_call(rule, w, k_hat, u, decay, interpret,
                                  dot_dtype)
    # W, K̂ and V′ enter the reverse walk as ``dot_dtype`` matmul inputs
    # only, so a narrow rule's bf16 copies give the same numbers at half
    # the bytes (the f32 arrays die with the forward); its per-chunk
    # states are WRITTEN at that width (the carried state stays f32 in
    # VMEM): O's product and dW take them as ``dot_dtype`` inputs, and
    # only the decay's cotangent Σ g ⊙ S reads the rounded copy
    # elementwise
    width = rule.width(dot_dtype)
    return (v_new, states), (w.astype(width), k_hat.astype(width), decay,
                             states, v_new.astype(width))


def _scan_bwd(rule, interpret, dot_dtype, residual, cotangent):
    w, k_hat, decay, states, v_new = residual
    d_v, d_s = cotangent
    return _backward_call(rule, w, k_hat, decay, states, v_new, d_v, d_s,
                          interpret, dot_dtype)


_state_scan_kernels.defvjp(_scan_fwd, _scan_bwd)


def state_scan(w, k_hat, u, decay, kernel: bool = False,
               interpret: bool = False, dot_dtype=None):
    """(*) of the module docstring over a head's chunks: W, K̂
    (G, N, C, d_k), U (G, N, C, d_v), decay (G, N) → V′ (G, N, C, d_v)
    and every chunk's starting state S_n (G, N, d_k, d_v), f32.  A
    ``decay`` per key channel, (G, N, d_k), scales S's ROWS
    (``znicz_kda_state_fwd`` / ``_bwd``, which write S_n at
    ``dot_dtype``)."""
    if kernel:
        return _state_scan_kernels(
            _rule_for(decay.ndim == w.ndim - 1),
            *(a.astype(jnp.float32) for a in (w, k_hat, u, decay)),
            interpret, dot_dtype)
    return _state_scan_plain(w, k_hat, u, decay, dot_dtype)


# ----------------------------------------------------------------------
# a decay per key channel (Kimi Delta Attention, arXiv:2510.26692)
# ----------------------------------------------------------------------
#: positions per sub-block of a chunk under a per-channel decay: Γ is
#: then INSIDE the contraction, Σ_d K_id K_jd e^(c_id − c_jd), which two
#: (C, d_k) factors give only by exponentiating c_i − r and r − c_j
#: apart, r a reference point.  A row's sub-block takes the prefix at
#: its own start as r: against every EARLIER sub-block both exponents
#: are ≤ 0; inside the sub-block r − c_j ≤ SUB_BLOCK · |lower bound|.
SUB_BLOCK = 16
#: the largest such exponent admitted: a product of the two factors
#: summed over d_k ≤ 128 channels stays under the f32 maximum
#: (log 3.4e38 = 88.7; 80 + log 128 = 84.9) — the ONE place the bound
#: is stated; ``GatedDeltaNet.initialize`` refuses a ``lower_bound``
#: whose SUB_BLOCK-fold passes it
MAX_EXPONENT = 80.0


def sub_block(chunk: int) -> int:
    return min(SUB_BLOCK, chunk)


def _chunk_local_channels(q, k, v, log_alpha, beta, dot_dtype=None):
    """:func:`chunk_local` for log α (G, N, C, d_k): Γ_ijd =
    exp(c_id − c_jd) written out as a (C, C, d_k) array — plain, and
    as large as that reads; the kernels' oracle and the path off a TPU
    (``decay`` comes back (G, N, d_k))."""
    chunk = q.shape[-2]
    log_alpha = log_alpha.astype(jnp.float32)
    at = np.arange(chunk)
    rows, cols = at[:, None], at[None, :]
    # [i, j, m]: j < m ≤ i — every sum from its own terms
    between = ((at[None, None, :] <= at[:, None, None])
               & (at[None, :, None] < at[None, None, :])).astype(np.float32)
    gamma = jnp.exp(jnp.where(
        (rows >= cols)[..., None],
        jnp.einsum("ijm,...md->...ijd", between, log_alpha,
                   precision=_HIGHEST), -jnp.inf))
    kk = jnp.einsum("...id,...jd,...ijd->...ij", k, k, gamma,
                    precision=_HIGHEST)
    lower = jnp.where(rows > cols, beta[..., :, None] * kk, 0.0)
    a = unit_lower_inverse(lower) * beta[..., None, :]
    c = jnp.einsum("im,...md->...id", (rows >= cols).astype(np.float32),
                   log_alpha, precision=_HIGHEST)
    after = jnp.einsum("im,...md->...id", (rows < cols).astype(np.float32),
                       log_alpha, precision=_HIGHEST)
    grown = jnp.exp(c)
    w = _mm(a, grown * k, dot_dtype)
    u = _mm(a, v, dot_dtype)
    k_hat = k * jnp.exp(after)
    # P exact here: the kernels round its two factors to ``dot_dtype``
    p = jnp.einsum("...id,...jd,...ijd->...ij", q, k, gamma,
                   precision=_HIGHEST)
    return w, k_hat, u, jnp.exp(c[..., -1, :]), q * grown, p


def _kda_positions(c: int, sub: int, backward: bool = False):
    """0/1 (C, C) matrices of a chunk of ``sub``-blocks, from the
    indices: on and below the diagonal, strictly below, on it, in f32
    to multiply by.  And the masks whose products with log α are its
    sums (:func:`_kda_factors`), stacked for ONE
    :func:`_mask_product`: ``within`` [i, m]: m ≤ i in i's own sub-block
    (c̃ = within · log α, a row's prefix from its sub-block's start); per
    sub-block A the signed ``reach`` [j, m]: +1 where j < m < A's start
    (r_A − c_j for an earlier row), −1 where A's start ≤ m ≤ j inside A
    (−c̃_j), rows past A zero; on and below the diagonal (c); strictly
    above (c_C − c) — ``sums`` (C/sub + 3 of them row block under row
    block, in that order) and, for the ``backward``, ``back``: their
    transposes side by side, which sums the cotangents of all of them
    into d log α in one contraction."""
    shift = sub.bit_length() - 1

    def masks(row, col):
        within = ones_where((col <= row)
                             & (row >> shift == col >> shift))
        reach = []
        for a in range(c // sub):
            start = a * sub
            reach.append(
                ones_where((col > row) & (col < start))
                - ones_where((col >= start) & (col <= row)
                              & (row < start + sub)))
        return [within, *reach, ones_where(col <= row),
                ones_where(col > row)]

    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    sums = _for_mask_product(jnp.concatenate(masks(row, col), axis=0))
    back = _for_mask_product(jnp.concatenate(
        masks(col, row), axis=1)) if backward else None
    return (ones_where(col <= row), ones_where(col < row),
            ones_where(col == row), sums, back)


def _kda_factors(sums, q, k, log_alpha):
    """The two-factor form of Γ inside the contraction: the left
    factors K ⊙ e^c̃ and Q ⊙ e^c̃ (every exponent ≤ 0), and per
    sub-block A the right factor's scale e^(r_A − c_j) (≤ 1 for the
    rows before A, ≤ e^MAX_EXPONENT inside it); e^c and e^(c_C − c)
    with them — every sum of a chunk's log α (C, d_k) that is
    exponentiated, each from its own terms, out of ONE product with
    the stacked masks of :func:`_kda_positions`."""
    near, *scales, grown, rest = (
        jnp.exp(block) for block in jnp.split(
            _mask_product(sums, log_alpha),
            sums.shape[0] // log_alpha.shape[0], axis=0))
    return near, k * near, q * near, scales, grown, rest


def _kda_products(left, rights, sub, dot):
    """(C, C): rows of sub-block A = left[A] · rights[A]ᵀ."""
    return jnp.concatenate(
        [dot(left[a * sub:(a + 1) * sub], right, trans_b=True)
         for a, right in enumerate(rights)], axis=0)


@jax.jit
def _kda_chunk_lower(positions, log_alpha, beta, q, k):
    """A chunk's L = strict_lower(diag(β) M), e^(c_C), and what the
    outputs need of the decays: e^c, e^(c_C − c), Q's left factor and
    K's right factors (P is the same two-factor product as M)."""
    _, below, eye, sums, _ = positions
    _, left_k, left_q, scales, grown, rest = _kda_factors(
        sums, q, k, log_alpha)
    rights = [k * e for e in scales]
    m = _kda_products(left_k, rights, sub_block(k.shape[0]), _exact)
    lower = below * (_rows(eye * beta) * m)
    decay = jnp.exp(column_sums(log_alpha))
    return lower, decay, grown, rest, left_q, rights


@functools.partial(jax.jit, static_argnames=("dot_dtype",))
def _kda_outputs(positions, q, k, v, beta, x, grown, rest, left_q, rights,
                 *, dot_dtype):
    """W, K̂, U, Qc, P from a chunk's rows, decays, Γ's factors and
    (I + L)⁻¹."""
    mixed = _mixed(dot_dtype)
    p = positions[0] * _kda_products(left_q, rights,
                                     sub_block(k.shape[0]), mixed)
    a = x * beta
    return mixed(a, grown * k), rest * k, mixed(a, v), grown * q, p


@functools.partial(jax.jit, static_argnames=("dot_dtype",))
def _kda_cotangents(positions, q, k, v, log_alpha, beta, x, d_w, d_kh,
                    d_u, d_decay, d_qc, d_p, *, dot_dtype):
    """Cotangents of a chunk's q, k, v, log α (C, d_k), β from those of
    its W, K̂, U, decay (1, d_k), Qc and P, and its (I + L)⁻¹."""
    upto, below, eye, sums, back = positions
    exact, mixed, sub = _exact, _mixed(dot_dtype), sub_block(k.shape[0])
    near, left_k, left_q, scales, grown, rest = _kda_factors(
        sums, q, k, log_alpha)
    rights = [k * e for e in scales]
    m = _kda_products(left_k, rights, sub, exact)
    decay = jnp.exp(column_sums(log_alpha))
    beta_col = _rows(eye * beta)
    a = x * beta
    # W = A (exp(c) ⊙ K), U = A V
    d_a = mixed(d_w, grown * k, trans_b=True) \
        + mixed(d_u, v, trans_b=True)
    d_kg = mixed(a, d_w, trans_a=True)
    d_v = mixed(a, d_u, trans_a=True)
    # d(M⁻¹) = −M⁻ᵀ dM M⁻ᵀ, on the strictly lower part; L = β_i M_ij
    d_lower = -below * exact(exact(x, d_a * beta, trans_a=True), x,
                             trans_b=True)
    d_m = d_lower * beta_col
    d_pl = upto * d_p
    d_beta = column_sums(d_a * x) + column_sums(eye * _rows(d_lower * m))
    # M and P by sub-blocks: rows A = left[A] · (K ⊙ scale_A)ᵀ
    d_k = grown * d_kg + rest * d_kh
    d_left_k, d_left_q, d_scales = [], [], []
    for at, (right, scale) in enumerate(zip(rights, scales)):
        rows = slice(at * sub, (at + 1) * sub)
        d_left_k.append(exact(d_m[rows], right))
        d_left_q.append(mixed(d_pl[rows], right))
        d_right = exact(d_m[rows], left_k[rows], trans_a=True) \
            + mixed(d_pl[rows], left_q[rows], trans_a=True)
        d_k = d_k + d_right * scale
        d_scales.append(d_right * right)
    d_left_k = jnp.concatenate(d_left_k, axis=0)
    d_left_q = jnp.concatenate(d_left_q, axis=0)
    d_k = d_k + d_left_k * near
    d_q = d_left_q * near + grown * d_qc
    # log α: the cotangents of its sums' exponents, in the order of
    # :func:`_kda_positions`, through the masks' transposes — the MXU
    # sums all of them over ONE contraction
    d_alpha = _mask_product(back, jnp.concatenate(
        [d_left_k * left_k + d_left_q * left_q, *d_scales,
         grown * (d_qc * q + d_kg * k), rest * d_kh * k], axis=0)) \
        + d_decay * decay
    return d_q, d_k, d_v, d_alpha, d_beta


def _column(row):
    """(1, d) → (d, 1) without a transpose, and the identity that made
    it."""
    d = row.shape[1]
    eye = ones_where(jax.lax.broadcasted_iota(jnp.int32, (d, d), 0)
                      == jax.lax.broadcasted_iota(jnp.int32, (d, d), 1))
    return _rows(eye * row), eye


# ----------------------------------------------------------------------
# the two rules
# ----------------------------------------------------------------------
@dataclasses.dataclass(frozen=True, eq=False)
class _Rule:
    """One decay shape as the ONE scaffold above takes it (the chunk
    kernels' wrappers and calls, the walk's kernels, their
    ``custom_vjp``s): what differs between a decay a head and a decay
    per key channel, and nothing else.  The shape of log α picks the
    record (:func:`_rule_for`); nothing else branches on it."""
    #: the Mosaic kernels' names less ``_fwd`` / ``_bwd``: what is
    #: local to a chunk, and the walk
    chunk_kernel: str
    state_kernel: str
    #: a chunk's algebra.  ``positions(c, backward=False)``: its 0/1
    #: masks; ``lower(positions, *rows)`` from the rows ``lower_reads``
    #: names (of q k v a b: log α is a, β b) → L, the decay's row, then
    #: what ``outputs(positions, q, k, v, β, (I + L)⁻¹, *those,
    #: dot_dtype=)`` needs of the decays → W, K̂, U, Qc, P;
    #: ``cotangents(positions, *rows, (I + L)⁻¹, *six cotangents,
    #: dot_dtype=)`` → those of q, k, v, log α, β
    positions: typing.Callable
    lower_reads: str
    lower: typing.Callable
    outputs: typing.Callable
    cotangents: typing.Callable
    #: a chunk's decay between the chunk kernels' rows (G·N, 1, lanes)
    #: and the (G, N[, d_k]) the walk is handed: out of the forward's,
    #: and its cotangent into the backward's
    from_row: typing.Callable
    as_row: typing.Callable
    #: the walk: the decay as one row a chunk (G, N, 1, lanes); that row
    #: as what multiplies S (and what made it so); the row's cotangent
    #: from g ⊙ S; and that out of the kernel's rows
    walk_row: typing.Callable
    scale: typing.Callable
    scale_cotangent: typing.Callable
    from_walk_row: typing.Callable
    #: whether the backward's residuals that only ever enter a product
    #: as ``dot_dtype`` inputs (the chunk's V; the walk's W, K̂, V′ and
    #: the S_n it writes) are KEPT at ``dot_dtype``.  The two shapes
    #: differ here for no reason the algebra gives (PR 37 measured the
    #: narrow form in its own cell only): flipping the scalar rule's is
    #: ROADMAP S6's lead, a change to measure
    narrow: bool

    def width(self, dot_dtype):
        return dot_dtype if self.narrow and dot_dtype is not None \
            else jnp.float32


#: one decay a head: a number, laid along a row's lanes
_HEAD = _Rule(
    chunk_kernel="znicz_gdr_chunk", state_kernel="znicz_delta_state",
    positions=_positions, lower_reads="abk", lower=_chunk_lower,
    outputs=_chunk_outputs, cotangents=_chunk_cotangents,
    from_row=lambda rows, g, n: rows[:, 0, 0].reshape(g, n),
    as_row=lambda d, c: jnp.broadcast_to(d[..., None], d.shape + (c,)),
    walk_row=lambda decay, dv: jnp.broadcast_to(
        decay[..., None, None], decay.shape + (1, dv)).astype(jnp.float32),
    scale=lambda row: (row, None),
    scale_cotangent=lambda gs, _: column_sums(gs),
    from_walk_row=lambda dd: dd.sum(axis=(-1, -2)),
    narrow=False)
#: one decay per key channel: a row of d_k, which scales S's ROWS
_CHANNEL = _Rule(
    chunk_kernel="znicz_kda_chunk", state_kernel="znicz_kda_state",
    positions=lambda c, backward=False: _kda_positions(
        c, sub_block(c), backward),
    lower_reads="abqk", lower=_kda_chunk_lower, outputs=_kda_outputs,
    cotangents=_kda_cotangents,
    from_row=lambda rows, g, n: rows.reshape(g, n, rows.shape[-1]),
    as_row=lambda d, c: d,
    walk_row=lambda decay, dv: decay[:, :, None, :],
    scale=_column,
    scale_cotangent=lambda gs, eye: column_sums(eye * _rows(gs)),
    from_walk_row=lambda dd: dd[:, :, 0, :],
    narrow=True)


def _rule_for(per_channel: bool) -> _Rule:
    return _CHANNEL if per_channel else _HEAD


# ----------------------------------------------------------------------
# between the projection and the rule: the taps, the SiLU, the L2 norms
# ----------------------------------------------------------------------
#: rows of a sequence a grid step of the prep kernels takes (a block is
#: rows × one head's columns: 1 MB at 128 lanes), walked inside the step
#: in sub-tiles of ``_PREP_SUB`` rows, whose values stay near the
#: registers
PREP_ROWS = 2048
_PREP_SUB = 64


def prep_legal(dk: int, dv: int) -> bool:
    """A head's q, k and v are whole 128-lane column blocks of the
    q ‖ k ‖ v projection: a ``BlockSpec`` addresses them where the
    matmul wrote them, and a head's L2 norm is a reduction across the
    lanes of its own tiles."""
    return dk % LANES == 0 and dv % LANES == 0


def _prep_sections(heads: int, dk: int, dv: int):
    """The projection's three column ranges as the calls walk them:
    ``(width, first, blocks, per_head, scale)`` — column blocks of
    ``width`` lanes from block ``first`` on, ``per_head`` of them a
    head, L2-normed and scaled where ``scale`` is a number.  q and k
    take a head whole (its norm runs across it); v has no norm and goes
    128 lanes at a time, so that its first column is a whole block
    whatever d_k and d_v are."""
    return ((dk, 0, heads, 1, dk ** -0.5), (dk, heads, heads, 1, 1.0),
            (LANES, 2 * heads * dk // LANES, heads * dv // LANES,
             dv // LANES, None))


def _prep_rows(ext, taps, scale, eps):
    """A sub-tile's rows of q or k (``scale`` a number: x / √(‖x‖² + ε)
    · scale, ``delta_net.l2_normalize``'s formula as a reciprocal root
    a ROW and a product: a row statistic costs a whole vreg whatever it
    holds, so a division by it was as dear as the SiLU's) or of v, from
    u."""
    c = taps_sum(delayed(ext, len(taps), ext.shape[0] - SUBLANES), taps)
    a = c / (1.0 + jnp.exp(-c))                  # ``moe._silu``'s form
    if scale is None:
        return a
    a = a * jax.lax.rsqrt(_rows(a * a) + eps)
    return a if scale == 1.0 else a * scale


def _prep_cotangents(ext, dy, taps, scale, eps):
    """From u (8 rows before a sub-tile, the sub-tile, 8 rows after it)
    and the cotangent of the sub-tile's and the next 8 rows' q, k or v:
    du of the sub-tile's rows, and per tap Σ_t dc_t · u_{t−J+1+j} over
    them as a row.  c, a and the norm are made again."""
    width, rows = len(taps), dy.shape[0]          # the sub-tile's + 8
    shifted = delayed(ext, width, rows)
    c = taps_sum(shifted, taps)
    sigma = 1.0 / (1.0 + jnp.exp(-c))
    if scale is not None:
        a = c * sigma              # silu(c): one division, not two
        inverse = jax.lax.rsqrt(_rows(a * a) + eps)
        dy = inverse * (dy - a * (inverse * inverse * _rows(dy * a)))
        if scale != 1.0:
            dy = dy * scale
    # silu′(c) = σ (1 + c (1 − σ))
    dc = dy * (sigma * (1.0 + c * (1.0 - sigma)))
    own = rows - SUBLANES
    du = dc[:own] * taps[width - 1]
    for s in range(1, width):         # dc_{t+s}: up to J − 1 rows after
        du = du + pltpu.roll(dc, rows - s, 0)[:own] * taps[width - 1 - s]
    return du, [column_sums(dc[:own] * shifted[width - 1 - j][:own])
                for j in range(width)]


def _before(ref, halo, k, start):
    """The 8 rows before sub-tile ``k`` of a block, which starts at
    ``start``: the ``halo`` before the block's first."""
    return jax.lax.select(
        k == 0, halo, eight_rows(ref, jnp.maximum(start - SUBLANES, 0)))


def _prep_fwd_kernel(u_ref, before_ref, taps_ref, out_ref, *, sub, scale,
                     eps, length, masked):
    tile, rows = pl.program_id(2), u_ref.shape[0]
    taps = [taps_ref[j:j + 1, :] for j in range(taps_ref.shape[0])]
    f32 = jnp.float32
    # zeros before the sequence
    halo = before_ref[...].astype(f32) * ones_where(tile > 0)
    at = jax.lax.broadcasted_iota(jnp.int32, (sub + SUBLANES, 1), 0)

    def some(k, carry):
        start = pl.multiple_of(k * sub, sub)
        ext = jnp.concatenate(
            [_before(u_ref, halo, k, start),
             u_ref[pl.ds(start, sub), :].astype(f32)], axis=0)
        if masked:
            seen = tile * rows + start - SUBLANES + at < length
            ext = unless(seen, ext)
        out = _prep_rows(ext, taps, scale, eps)
        if masked:      # the padding's rows are zeros, as jnp.pad's were
            out = unless(seen[SUBLANES:], out)
        out_ref[pl.ds(start, sub), :] = out
        return carry

    jax.lax.fori_loop(0, rows // sub, some, None)


def _prep_bwd_kernel(u_ref, before_ref, after_ref, dy_ref, dy_after_ref,
                     taps_ref, *rest, sub, scale, eps, length, masked):
    du_ref, dtaps_ref = rest[-2:]       # after du itself, where aliased
    tile, rows = pl.program_id(2), u_ref.shape[0]
    width, steps = taps_ref.shape[0], rows // sub
    taps = [taps_ref[j:j + 1, :] for j in range(width)]
    f32 = jnp.float32
    halo = before_ref[...].astype(f32) * ones_where(tile > 0)
    # nothing follows the last tile: a zero cotangent there makes dc 0
    tail = after_ref[...].astype(f32)
    dy_tail = dy_after_ref[...] * ones_where(
        tile < pl.num_programs(2) - 1)
    at = jax.lax.broadcasted_iota(jnp.int32, (sub + 2 * SUBLANES, 1), 0)

    @pl.when((pl.program_id(1) == 0) & (tile == 0))
    def _start():
        dtaps_ref[...] = jnp.zeros_like(dtaps_ref)

    def some(k, sums):
        start = pl.multiple_of(k * sub, sub)
        last = k == steps - 1
        after = jnp.minimum(start + sub, rows - SUBLANES)
        ext = jnp.concatenate(
            [_before(u_ref, halo, k, start),
             u_ref[pl.ds(start, sub), :].astype(f32),
             jax.lax.select(last, tail, eight_rows(u_ref, after))],
            axis=0)
        dy = jnp.concatenate(
            [dy_ref[pl.ds(start, sub), :],
             jax.lax.select(last, dy_tail, eight_rows(dy_ref, after))],
            axis=0)
        if masked:
            seen = tile * rows + start - SUBLANES + at < length
            ext, dy = unless(seen, ext), unless(seen[SUBLANES:], dy)
        du, parts = _prep_cotangents(ext, dy, taps, scale, eps)
        du_ref[pl.ds(start, sub), :] = du.astype(du_ref.dtype)
        return [s + part for s, part in zip(sums, parts)]

    sums = jax.lax.fori_loop(
        0, steps, some,
        [jnp.zeros((1, u_ref.shape[1]), f32) for _ in range(width)])
    for j, s in enumerate(sums):
        dtaps_ref[j:j + 1, :] += s


def _prep_walk(length: int, pad: int, rows):
    """Rows a grid step, the row tiles and the padded length for a
    sequence of ``length`` positions + ``pad``, and what the kernels'
    bodies are told of them: the sub-tile, the length, and whether any
    block reaches past it."""
    padded = length + pad
    rows = min(rows or PREP_ROWS, padded)
    if padded % SUBLANES or rows % SUBLANES:
        raise ValueError(f"qkv_prep: {padded} positions in tiles of "
                         f"{rows} are not whole sublanes")
    tiles = pl.cdiv(padded, rows)
    return rows, tiles, padded, dict(
        sub=math.gcd(rows, _PREP_SUB), length=length,
        masked=length != tiles * rows)


def _prep_specs(section, rows: int, length: int, padded: int, taps: int):
    """The ``BlockSpec``s of one section's walk over the grid (column
    block, sequence, row tile): the projection's (rows, width) block
    where the matmul wrote it, the 8 rows before and after it, the
    head-major (B, H, T, ·) block of q, k or v with ITS next 8 rows,
    the taps' (J, width) block, and a section's own taps' block."""
    width, first, _, per_head, _ = section
    eighth = rows // SUBLANES

    def head(n):
        return (n, 0) if per_head == 1 else (
            jax.lax.div(n, per_head), jax.lax.rem(n, per_head))

    def after(i, positions):    # held inside the array; masked past it
        return jnp.minimum((i + 1) * eighth,
                           pl.cdiv(positions, SUBLANES) - 1)

    return dict(
        u=pl.BlockSpec((None, rows, width),
                       lambda n, b, i: (b, i, first + n)),
        before=pl.BlockSpec(
            (None, SUBLANES, width),
            lambda n, b, i: (b, jnp.maximum(i * eighth - 1, 0),
                             first + n)),
        after=pl.BlockSpec(
            (None, SUBLANES, width),
            lambda n, b, i: (b, after(i, length), first + n)),
        heads=pl.BlockSpec(
            (None, None, rows, width),
            lambda n, b, i: (b, head(n)[0], i, head(n)[1])),
        heads_after=pl.BlockSpec(
            (None, None, SUBLANES, width),
            lambda n, b, i: (b, head(n)[0], after(i, padded),
                             head(n)[1])),
        taps=pl.BlockSpec((taps, width), lambda n, b, i: (0, first + n)),
        own_taps=pl.BlockSpec((taps, width), lambda n, b, i: (0, n)))


def _prep_params(semantics, rows: int, width: int, blocks: int):
    return pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=2 * blocks * rows * width * 4 + (16 << 20))


# jitted, as the chunk kernels' entries are: a model's linear layers
# trace and lower each of these once per program
@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7, 8))
def _qkv_prep_forward(u, taps, heads, dk, dv, eps, pad, rows, interpret):
    b, length, _ = u.shape
    rows, tiles, padded, walk = _prep_walk(length, pad, rows)
    taps = taps.astype(jnp.float32).T                 # (J, columns)
    out = []
    for section in _prep_sections(heads, dk, dv):
        width, _, blocks, per_head, scale = section
        spec = _prep_specs(section, rows, length, padded, taps.shape[0])
        out.append(pl.pallas_call(
            functools.partial(_prep_fwd_kernel, scale=scale, eps=eps,
                              **walk),
            grid=(blocks, b, tiles),
            in_specs=[spec["u"], spec["before"], spec["taps"]],
            out_specs=spec["heads"],
            out_shape=jax.ShapeDtypeStruct(
                (b, heads, padded, width * per_head), jnp.float32),
            compiler_params=_prep_params(("parallel",) * 3, rows, width,
                                         2),
            interpret=interpret, name="znicz_qkv_prep_fwd",
        )(u, u, taps))
    return tuple(out)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8, 9))
def _qkv_prep_backward(u, taps, cotangents, heads, dk, dv, eps, pad, rows,
                       interpret):
    b, length, _ = u.shape
    rows, tiles, padded, walk = _prep_walk(length, pad, rows)
    taps_t = taps.astype(jnp.float32).T
    du, d_taps = None, []
    for section, dy in zip(_prep_sections(heads, dk, dv), cotangents):
        width, _, blocks, _, scale = section
        spec = _prep_specs(section, rows, length, padded, taps_t.shape[0])
        operands = [u, u, u, dy, dy, taps_t]
        in_specs = [spec[s] for s in ("u", "before", "after", "heads",
                                      "heads_after", "taps")]
        aliases = {}
        if du is not None:      # ONE du: a section's columns at a time
            operands.append(du)
            in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
            aliases = {len(operands) - 1: 0}
        du, part = pl.pallas_call(
            functools.partial(_prep_bwd_kernel, scale=scale, eps=eps,
                              **walk),
            grid=(blocks, b, tiles), in_specs=in_specs,
            out_specs=(spec["u"], spec["own_taps"]),
            out_shape=(jax.ShapeDtypeStruct(u.shape, u.dtype),
                       jax.ShapeDtypeStruct(
                           (taps_t.shape[0], blocks * width),
                           jnp.float32)),
            input_output_aliases=aliases,
            compiler_params=_prep_params(
                ("parallel", "arbitrary", "arbitrary"), rows, width, 3),
            interpret=interpret, name="znicz_qkv_prep_bwd",
        )(*operands)
        d_taps.append(part)
    return du, jnp.concatenate(d_taps, axis=1).T.astype(taps.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7, 8))
def _qkv_prep(u, taps, heads, dk, dv, eps, pad, rows, interpret):
    return _qkv_prep_forward(u, taps, heads, dk, dv, eps, pad, rows,
                             interpret)


def _qkv_prep_fwd(u, taps, *static):
    # what the backward keeps is the projection and the taps
    return _qkv_prep_forward(u, taps, *static), (u, taps)


def _qkv_prep_bwd(*args):
    *static, (u, taps), cotangents = args
    return _qkv_prep_backward(u, taps, tuple(cotangents), *static)


_qkv_prep.defvjp(_qkv_prep_fwd, _qkv_prep_bwd)


def qkv_prep(u, taps, heads: int, dk: int, dv: int, eps: float,
             pad: int = 0, rows: int | None = None,
             interpret: bool = False):
    """What lies between the q ‖ k ‖ v projection and the rule, as
    ``znicz_qkv_prep_fwd`` and, under differentiation,
    ``znicz_qkv_prep_bwd``: from ``u`` (B, T, H·(2 d_k + d_v)) where the
    matmul wrote it and the ``taps`` (columns, J) — the causal
    convolution, the SiLU, q's and k's L2 norms over a head and q's
    d_k^(−1/2) — to q, k (B, H, T + pad, d_k) and v (B, H, T + pad, d_v),
    f32 and head-major, the ``pad`` positions zeros.  The backward keeps
    u and the taps and makes the rest again in VMEM.  Needs
    :func:`prep_legal` head sizes."""
    if not prep_legal(dk, dv):
        raise ValueError(f"qkv_prep: heads of {dk} x {dv} are not whole "
                         f"{LANES}-lane tiles")
    return _qkv_prep(u, taps, int(heads), int(dk), int(dv), float(eps),
                     int(pad), rows, bool(interpret))


# ----------------------------------------------------------------------
# the rule
# ----------------------------------------------------------------------
def gated_delta_rule(q, k, v, log_alpha, beta, chunk: int = CHUNK,
                     kernel: bool = False, interpret: bool = False,
                     dot_dtype=None, head_major: bool = False):
    """o (B, T, H, d_v) of the recurrence in its chunked form for
    q, k (B, T, H, d_k), v (B, T, H, d_v) — (B, H, T, ·) where
    ``head_major``, as :func:`qkv_prep` writes them: no move —, log α ≤ 0
    and β (B, T, H); T a multiple of ``chunk``.  log α (B, T, H, d_k) is
    a decay per key channel: its shape picks the body (module
    docstring)."""
    b, t, h = beta.shape
    if t % chunk:
        raise ValueError(f"gated_delta_rule: {t} positions are not "
                         f"whole chunks of {chunk}")
    n = t // chunk

    def chunks(a, moved=False):   # (B, T, H, ·) → (B·H, N, C, ·)
        a = a.astype(jnp.float32)
        if not moved:
            a = jnp.moveaxis(a, 2, 1)
        return a.reshape((b * h, n, chunk) + a.shape[3:])

    rows = (chunks(q, head_major), chunks(k, head_major),
            chunks(v, head_major), chunks(log_alpha), chunks(beta))
    w, k_hat, u, decay, q_grown, p = chunk_local_kernels(
        *rows, dot_dtype, interpret) if kernel \
        else chunk_local(*rows, dot_dtype)
    v_new, states = state_scan(w, k_hat, u, decay, kernel, interpret,
                               dot_dtype)
    o = _mm(q_grown, states, dot_dtype) + _mm(p, v_new, dot_dtype)
    return jnp.moveaxis(o.reshape(b, h, t, o.shape[-1]), 1, 2)

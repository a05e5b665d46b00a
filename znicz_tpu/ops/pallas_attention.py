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
  semantics — sequential per core).  What ONE visit of the walk does
  between the score product and the value product follows from the
  call's static shapes (:func:`forward_form`, never an option; PR 36):

  - *state*.  Where the grid's last axis has ONE step (every causal
    call at T ≤ 2048 — the LM cell; a ring hop whose keys are one tile)
    a row block meets all its keys in one visit and carries nothing:
    the body computes m, l and o of its rows and writes ``o`` and
    ``lse`` itself — no initialisation, no scratch, no correction
    factor, no finishing pass.  Else the online-softmax state (running
    row max m, normalizer l, f32 accumulator) waits in VMEM scratch
    for the next K tile and ``o`` / ``lse`` leave at the last — except
    for a Q tile whose keys ALL lie in the first K tile (rows below
    2048 at T 4096, read from scalars at run time): its row blocks
    visit state-free too.
  - *statistics*.  A row's m and l are lane-REPLICATED (rows, 128)
    tiles through all of the arithmetic and in the scratch (a tile per
    sub-head): nothing is narrowed to one lane, broadcast back or
    concatenated; the max and the sum over a visit's keys are plain
    elementwise folds over the runs' 128-column slabs and over the
    runs, and the lanes are folded ONCE per visit and statistic.
  - *scale*.  1/√dh goes into q once per visit where that rounds
    nothing (a power of two: dh 64, 256); else into the multiply the
    exponential already makes (p = 2^((s − m)·log2e/√dh), m the max
    of the unscaled scores), so no pass over the scores is a multiply
    only.  A masked score needs ONE select: fully-masked rows (offset
    hops) exponentiate against 0 instead of their m of -1e30, so the
    masked scores underflow to the zeros they are.
- **backward**: recompute-from-lse form — no (T, T) residual is ever
  stored.  Saves (q, k, v, o, lse) from the forward, precomputes
  ``delta = rowsum(do·o)`` (one cheap XLA pass), then recomputes the
  score sub-tiles p = exp(s − lse), dp = do·vᵀ and ds = p·(dp − delta)
  in VMEM — ONCE where the call's shapes allow, else twice
  (:func:`backward_passes`, never an option):

  - *one pass*, ``znicz_flash_bwd`` (``znicz_flash_bwd_win`` under a
    window) — every causal call whose unfinished dq fits
    ``RESIDENT_DQ_VMEM``.  The grid is the dk/dv grid, (B, H_kv, K
    tiles, group · Q tiles): beside ``dv += pᵀ·do`` and ``dk += dsᵀ·q``
    the walk adds ``dq[rows] += ds·k`` from the ``ds`` it has: five
    matmuls and one pass of exponentials per visible sub-tile, the
    count FlashAttention-2 gives and ``znbench/flops.py`` holds the
    kernels to.  Where the K side is ONE grid tile of the backward —
    T ≤ 2048 under ``CAUSAL_BLOCK_K`` (the LM cell; a ring hop whose
    keys are one tile), and up to ``WHOLE_BLOCK_K`` 4096 because the
    backward takes such a key range whole (:func:`backward_block_k`;
    OLMoE, Laguna's full layers) — a step's Q tile meets every key it
    can see, and its dq is an f32 (bq, width) scratch that is zeroed as
    the step starts and leaves, cast, as it ends.  On a v5e (PERF.md
    §6, PR 30): the LM cell's backward 42.9 → 30.9 ms a step; a layer
    at T 4096 × 128 lanes 2.01 → 1.40 ms (16 heads), 5.48 → 3.71 (48
    query heads on 8).  Past one K tile (PR 55) the K tile is an
    ordered axis and a Q tile's dq WAITS in VMEM, f32, from the first K
    tile that feeds it to the last one, the diagonal's, where it is
    cast and sent to HBM once, by DMA (:func:`dq_slots`): without a
    window every Q tile of the K/V head's group waits — SmallThinker's
    NoPE full layer at T 16,384, eight K tiles of 2048 under seven
    query heads of sixteen Q tiles: 56 MiB of a v5e's 128, asked for
    with ``vmem_limit_bytes`` —; under a window only the Q tiles one K
    tile meets are unfinished at once, a ring of slots indexed by the
    Q tile modulo their number (SmallThinker's band of 4,096: 7 heads ×
    9 slots of 512 × 128 = 15.75 MiB; Laguna's of 512: 9 × 2 = 4.5).
    Which K tile is a Q tile's first and which its last is read from
    the offsets' scalars, so a ring hop over several K tiles takes the
    same path.  K tiles add into dq ascending and Q tiles into dk and
    dv ascending, as in the two-pass kernels: under one body per tile
    (the bands) the cotangents are theirs bit for bit; where a K tile
    is walked in column blocks, dq's partial sums inside the tile add
    in another order (an ulp).
  - *two passes*, ``znicz_flash_dq`` (grid over K blocks innermost,
    accumulating dq tiles) and ``znicz_flash_dkv`` (grid over Q blocks
    innermost, accumulating dk/dv tiles): seven matmuls and the
    exponentials twice.  What the rule still sends there: non-causal
    calls (every Q tile would wait for the LAST K tile) and calls past
    the budget — T 32,768 un-windowed at a group of seven would keep
    112 MiB.  No cell runs them since PR 55.

  Both forms share one dk/dv body (:func:`_dkv_kernel`), `_p_tile`,
  the walk, the masks and the fully-masked-row guards.
- **dtypes**: tile GEMMs run at the input dtype (bf16 in the
  framework's mixed-precision mode) with f32 accumulation via
  ``preferred_element_type``; softmax statistics, lse, delta and all
  accumulators are f32 — the same bf16-inputs/f32-accumulation
  convention as the rest of the repo.
- **causal**: a two-level tile schedule (PR 24).  The GRID tile
  (bq, bk) is the unit of DMA, of the ``BlockSpec``s, of the VMEM
  accumulators and of the whole-tile ``pl.when`` skip; inside it each
  kernel walks COMPUTE sub-tiles (sq, sk) (`_walk`), classified from
  scalars only: above the diagonal — in no visit, so they cost neither
  MXU nor VPU work; interior (first row ≥ last column) — computed with
  no mask code at all; and the short run the diagonal can cross —
  masked by global position (exact across block boundaries, the rule
  of ``ring_attention._visibility``).  A row block (fwd, dq) or column
  block (dk/dv) is ONE visit over all its visible sub-tiles, so
  whatever the kernel keeps per row or column (the forward's softmax
  state where it carries one, the backward's accumulators) is read
  and written once per visit — on the chip that, not the skipped work,
  is most of what the forward gained (PR 24).  Shapes are static: the
  visit's extent is picked by
  ``pl.when`` among the few a tile allows.  Share of the T × T square
  executed at the chooser's tiles (`grid_blocks`, `sub_tile_for`,
  `causal_tile_counts`): T 512 1.0, T 1024 0.75, T 2048 0.625, T 4096
  0.5625, T 16384 0.516 — one body per 1024² tile executed 0.75 at
  T 2048, not the half "causal at ~2× rate" promised.  Non-causal
  calls keep one unmasked body per tile.
- **global offsets** (round 6, the ring-fold composition): every
  kernel takes ``q_offset``/``k_offset`` scalars (SMEM) placing this
  call's q rows / k cols on the GLOBAL sequence axis, so one kernel
  invocation can be a single ring hop — the `pl.when` tile-skip then
  skips whole hops that sit entirely above the causal diagonal, and
  the sub-tile walk takes its bounds from the same scalars.
  Offsets are traced values (the ring derives them from
  ``axis_index``), which is why they ride SMEM instead of being
  Python constants.  With offsets, a hop can contain FULLY-MASKED
  rows (rows above the hop's first key) — the kernels guard those
  with explicit mask selects (forward p-tile and the backward
  recompute both) so the statistics degrade to (m=-inf, l=0) instead
  of exploding; such a hop contributes lse ≈ -1e30 and weight 0 to
  the cross-hop combination.
- **head packing** (``pack=2``, from the shapes: :func:`head_pack_for`):
  pairs of dh=64 heads ride one kernel program as a (…, 128)-lane
  tile — q/k/v/o tiles carry both sub-heads side by side in the lane
  dim (full 128-lane VMEM loads/stores and element ops instead of
  half-width dh=64 tiles), while every GEMM and every softmax
  statistic stays per-sub-head (static lane slices), so the math is
  exactly per-head attention.  The pair is adjacent in the
  projection's columns, so packing moves nothing.  Measured on the LM
  cell with the layout copies still in place (PR 28, PERF.md §6): the
  pair body takes 61.26 ms of kernels a step where one head per
  program takes 62.70 (forward +0.39, dq −0.62, dk/dv −1.21); it was
  ``engine.flash_head_pack``, opt-in, and is now simply what dh 64
  with an even head count gets.  Four, eight or sixteen narrower heads
  to a program outgrow the scoped VMEM and are not packed.

Layout contract: the kernels read and write the projections' OWN
layout.  At the boundary q, k, v are (B, T, D) rows — column ranges of
one (B, T, 3·D) projection result, or three tensors — and a head (a
pair at dh 64) is a 128-lane COLUMN BLOCK of them: the ``BlockSpec``
index map picks block ``col0 + head`` (:func:`_tile`, :func:`_operands`),
so no transpose, slice or concatenate stands between a projection and
a kernel, forward or backward.  ``o`` is written at its column block
of a (B, T, D) result (the out-projection's (B·T, D) by a free
reshape), ``do`` is read in place, and dq, dk, dv land in column
blocks of the cotangent: for one fused array ONE (B, T, 3·D) result —
the one-pass call writes all of it (a blocked output is one block per
grid step, so its finished tiles leave by DMA from VMEM: a Q tile's dq
as its step ends — past one K tile as its LAST K tile's step ends,
which is how three separate cotangents get their dq too —, under the
next steps' walk; dk and dv at a K tile's last step);
under two passes the dq call begins the result and the dk/dv call
takes it aliased and puts its two tiles beside.  ``lse`` and ``delta``
stay head-major (B, Hp, T, lanes): they are the kernels' own.  Head
widths with no
lane-legal column block (dh 32, 80, 96, 192, an odd head count at
dh 64: :func:`head_layout`) keep the head-major address (B, Hp, T,
pack·dh) — ``pack_heads`` / ``unpack_heads`` transpose around the
call — as does the ring, whose K and V travel between chips in it.
The bodies are shared: every ``BlockSpec`` squeezes its leading dims,
so a kernel sees (rows, width) tiles wherever they lay.  Before PR 28
the wrapper transposed every operand ("two cheap bandwidth passes"):
eleven activation-sized moves per layer and step in the compiled LM
program, 20.8 ms of ``copy`` a step on the chip (PERF.md §6).

Adoption is measured, not assumed: SEQ_BENCH.json / PERF.md round 5
carry the chip A/B against the plain and scan-blocked XLA forms.
``interpret=True`` runs the same kernels on CPU for the oracle
equality tests.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LOG2_E = math.log2(math.e)
#: default GRID tile sizes — the unit of DMA, of the BlockSpecs and of
#: the VMEM accumulators.  Chip-swept with one body per tile (PERF.md
#: round 5): 1024×1024 beats 512×512 by 1.2–1.75× per kernel (PERF.md
#: §6, PR 24: every grid step pays its prologue, its DMA waits and the
#: statistics' read-modify-write), and a 2048² score tile overflows
#: the 16 MB scoped VMEM.  Non-causal calls still run one body per
#: tile, so they keep these.
BLOCK_Q = 1024
BLOCK_K = 1024
#: causal calls walk compute sub-tiles inside the grid tile (`_walk`),
#: so their score tile no longer grows with it: the K-side tile is as
#: long as this where T allows — at T ≤ 2048 one grid step then holds
#: a row block's whole key range and its softmax state is updated once
#: (PERF.md §6, PR 24: fwd 3.99 → 3.23, dq 4.50 → 3.00, dk/dv 5.64 →
#: 4.22 ms per call at T 2048, dh 64, on a v5e)
CAUSAL_BLOCK_K = 2048
#: compute sub-tile edge inside a causal grid tile (chip-swept, PR 24:
#: 512² beats 256² in fwd and dk/dv, ties it in dq) …
_SUB_TILE = 512
#: … shrunk where one visit's f32 score run would outgrow what Mosaic
#: fits beside its copies in the scoped VMEM: a row block visits up to
#: sq × bk scores, a column block (dk/dv) up to bq × sk (compile-checked
#: for a described v5e: 512 × 2048 and 1024 × 512 fit, 1024 × 2048 and
#: 2048 × 512 do not)
_ROW_VISIT_ELEMS = 512 * 2048
_COL_VISIT_ELEMS = 1024 * 512
#: the BACKWARD takes a causal call's key range whole up to here: under
#: one K tile no dq tile has to wait (`dq_slots`).  At T 4096 × 128 lanes
#: that pass takes 1.40 ms where dq + dk/dv under 2048-long tiles take
#: 2.01 (16 MHA heads), 3.71 against 5.48 (48 query heads on 8) on a
#: v5e (PERF.md §6, PR 30); the forward keeps ``CAUSAL_BLOCK_K`` …
WHOLE_BLOCK_K = 4096
#: … and the call then asks for this much VMEM: K, V tiles and their
#: f32 accumulators of 4096 × 128 overflow the 16 MB a call gets
#: unasked by 0.4–1.4 MB (compiled for a described v5e, PR 30)
_WHOLE_K_VMEM = 40 * 2 ** 20
#: past one K tile the one-pass backward keeps every UNFINISHED dq tile
#: of a K/V head's group in VMEM, f32, from the first K tile that feeds
#: it to the diagonal's (`dq_slots`, `resident_dq_bytes`) where that is
#: at most this much of a v5e's 128 MiB: SmallThinker's full layer at
#: T 16,384 — seven query heads × 16 tiles of 1024 × 128 — takes 56 MiB,
#: its band of 4,096 7 × 9 tiles of 512 = 15.75, Laguna's band of 512
#: 9 × 2 = 4.5; T 32,768 un-windowed at a group of seven would take 112
#: and keeps the two calls (`backward_passes`; PR 55) …
RESIDENT_DQ_VMEM = 64 * 2 ** 20
#: … and asks for it on top of what a call gets unasked, which holds a
#: grid step's tiles, their second buffers, dk's and dv's accumulators
#: and a visit's score run as it held the dk/dv call's
_STEP_VMEM = 16 * 2 ** 20
#: lane width of the per-row statistics that live in HBM (lse, delta):
#: the minimum tile-legal last dim — the value is replicated across
#: lanes (with head packing, each sub-head owns one _LANES-wide lane
#: group)
_LANES = 8
#: the lane width of a vreg: the forward keeps a row's m and l
#: replicated over this many lanes, in its arithmetic and (a tile per
#: sub-head) in its scratch; also what a pair of dh-64 heads fills
_STAT_LANES = 128


def _causal_mask(row0, col0, sq: int, sk: int, window=None):
    """(sq, sk) visibility of one sub-tile from GLOBAL positions: its
    first row sits at ``row0``, its first column at ``col0`` (traced
    int32 scalars: offset + grid tile + sub-tile).  With a ``window`` a
    row also stops seeing columns ≤ row − window (the band's lower
    edge)."""
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
    if window is None:
        return rows >= cols
    return (rows >= cols) & (cols > rows - window)


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


def head_pack_for(n_heads: int, dh: int) -> int:
    """How many heads ride one kernel program, from the shapes alone:
    2 where a pair fills the 128 lanes exactly (dh 64) and the head
    count is even, else 1.  Never a model change: the pair lies side by
    side in the projection's columns, and the kernels' matmuls and
    statistics stay per head.  Narrower heads would fill the lanes
    four, eight or sixteen at a time, but with every sub-head's score
    run live the kernels outgrow the scoped VMEM at the chooser's tiles
    (compiled for a described v5e at dh 32, 16 and 8: PR 28), so they
    keep one head per program, as before."""
    if 2 * dh == _STAT_LANES and n_heads % 2 == 0:
        return 2
    return 1


def head_layout(n_heads: int, dh: int, group: int = 1) -> tuple:
    """``(layout, pack)`` of a call over ``n_heads`` heads of ``dh``:
    ``"boundary"`` where a program's tile is a lane-legal column block
    of the (B, T, H·dh) projection (``pack·dh`` a multiple of 128:
    dh 128, 256, …, or pairs of dh 64), so the kernels address it where
    it lies; else ``"head_major"`` (dh 32, 80, 96, 192, an odd head
    count at dh 64: no such block exists, the wrapper transposes — a
    latent-K/V layer's keys of 128 + 64 never come here: its score is
    the sum of two products, a 128-wide per-head block at the boundary
    layout and a 64-wide rotary block shared by all heads,
    ``ops/pallas_mla.py``).  Static per program: in the plan
    (:func:`plan`) and its line."""
    # grouped queries: a pair of query heads does not read a pair of
    # K/V heads, so every head is a program of its own
    pack = head_pack_for(n_heads, dh) if group == 1 else 1
    if (pack * dh) % _STAT_LANES == 0:
        return "boundary", pack
    return "head_major", pack


# ----------------------------------------------------------------------
# the causal tile schedule: compute sub-tiles inside a grid tile
# ----------------------------------------------------------------------
def grid_blocks(causal: bool, t_q: int, t_k: int, block_q=None,
                block_k=None) -> tuple:
    """Grid tile (bq, bk) of a call: the caller's blocks where it names
    them, else the defaults — for a causal call the K side as long as
    ``CAUSAL_BLOCK_K`` where that still tiles T."""
    bq = min(block_q or BLOCK_Q, t_q)
    if block_k:
        return bq, min(block_k, t_k)
    if causal and t_k % CAUSAL_BLOCK_K == 0:
        return bq, CAUSAL_BLOCK_K
    return bq, min(BLOCK_K, t_k)


def sub_tile_for(causal: bool, bq: int, bk: int) -> tuple:
    """Compute sub-tile (sq, sk) the three kernels walk inside one
    (bq, bk) grid tile — derived from what the call can see, never an
    option.  Non-causal calls keep the single body (sub-tile = tile:
    nothing to skip, nothing to leave unmasked).  Causal calls take the
    largest lane-legal edge ≤ ``_SUB_TILE`` that divides the tile and
    keeps one visit's score run inside the scoped VMEM; tiles too small
    to cut (the interpret-mode tests) stay whole."""
    if not causal:
        return bq, bk
    return (_sub_edge(bq, min(_SUB_TILE, _ROW_VISIT_ELEMS // bk)),
            _sub_edge(bk, min(_SUB_TILE, _COL_VISIT_ELEMS // bq)))


def _sub_edge(block: int, want: int) -> int:
    """The largest of ``want``, ``want``/2, … (≥ 128, the lane width)
    that divides ``block``; ``block`` itself where none does."""
    size = 128
    while size * 2 <= want:
        size *= 2
    while size >= 128:
        if block % size == 0:
            return size
        size //= 2
    return block


def causal_tile_counts(t_q: int, t_k: int, bq: int, bk: int, sq: int,
                       sk: int, q_off: int = 0, k_off: int = 0,
                       causal: bool = True, window=None) -> dict:
    """How a causal call's (t_q, t_k) rectangle splits into compute
    sub-tiles: ``interior`` (wholly on or under the diagonal),
    ``crossing`` (the diagonal passes through), ``skipped`` (wholly
    above: never computed, whether inside a visited grid tile or in a
    grid tile the ``pl.when`` drops), and ``executed_share`` =
    (interior + crossing) ÷ all.  The classes are geometry: the kernels
    mask the run of ≤ 2 sub-tiles per block the diagonal CAN cross, so
    with aligned offsets one interior neighbour shares the masked run.
    ``causal=False`` counts the whole rectangle as interior.  With a
    ``window`` (row r sees columns in (r − window, r]) a sub-tile
    wholly below the band is ``skipped`` too, one wholly inside it
    ``interior``, and ``band_edge`` counts those the band's lower edge
    passes through and the diagonal does not (computed under the mask,
    so executed).  Static per program: in the plan (:func:`plan`) and
    its line."""
    if t_q % bq or t_k % bk or bq % sq or bk % sk:
        raise ValueError(f"({t_q}, {t_k}) / ({bq}, {bk}) / ({sq}, {sk})"
                         f" do not tile")
    counts = {"interior": 0, "crossing": 0, "skipped": 0}
    if window is not None:
        counts["band_edge"] = 0
    for r0 in range(q_off, q_off + t_q, sq):
        for c0 in range(k_off, k_off + t_k, sk):
            if causal and r0 + sq - 1 < c0:
                counts["skipped"] += 1
            elif window is not None and c0 + sk - 1 <= r0 - window:
                counts["skipped"] += 1          # below the band
            elif causal and r0 < c0 + sk - 1:
                counts["crossing"] += 1
            elif window is not None and c0 <= r0 + sq - 1 - window:
                counts["band_edge"] += 1
            else:
                counts["interior"] += 1
    total = (t_q // sq) * (t_k // sk)
    counts["executed_share"] = 1.0 - counts["skipped"] / total
    return counts


def band_share(t: int, window) -> float:
    """The share of the T × T square a causal row's band covers:
    Σ_r min(r + 1, window) ÷ T² (the causal half where ``window`` is
    None or ≥ T)."""
    w = t if window is None else min(int(window), t)
    return (w * (w + 1) / 2 + (t - w) * w) / (t * t)


def backward_block_k(causal: bool, t_k: int, bk: int, window=None) -> int:
    """K-side grid tile of the BACKWARD of a call whose forward walks
    K tiles of ``bk``: the same — except that a causal, un-windowed
    call already at the chooser's longest tile (``CAUSAL_BLOCK_K``)
    takes a key range of up to ``WHOLE_BLOCK_K`` whole, because a Q
    tile's dq is then finished inside one grid step of the one-pass
    backward and nothing waits in VMEM (:func:`dq_slots`).  The
    statistics the forward saved do not depend on its tiles."""
    if causal and window is None \
            and bk == CAUSAL_BLOCK_K < t_k <= WHOLE_BLOCK_K:
        return t_k
    return bk


def dq_slots(t_q: int, t_k: int, bq: int, bk: int, window=None) -> int:
    """dq tiles of ONE query head that are unfinished at once while the
    one-pass backward of a causal call walks K tiles of ``bk`` (the
    BACKWARD's: :func:`backward_block_k`): 0 where the K side is one
    tile — a Q tile's dq is whole as its grid step ends and nothing
    waits; every Q tile where the call has no window (tile q is fed
    from K tile 0 to its diagonal); under a window the Q tiles ONE K
    tile meets (``band_steps``), a ring of slots indexed by the Q tile
    modulo their number — a tile's slot is zeroed at the first K tile
    its band touches and leaves at the diagonal, before the tile that
    takes the slot next starts."""
    if window is not None:
        return band_steps(t_q, bq, bk, window)[1]
    return 0 if bk == t_k else t_q // bq


def resident_dq_bytes(causal: bool, t_k: int, bk: int, window=None,
                      t_q=None, bq=None, group: int = 1,
                      width: int = _STAT_LANES) -> int:
    """The f32 VMEM the one-pass backward of a causal call keeps across
    its K tiles: :func:`dq_slots` tiles of (bq, ``width``) for each of
    the ``group`` query heads that share a K/V head (``bk``: the
    forward's K tile; ``t_q`` / ``bq`` left out: self-attention at the
    chooser's Q tile).  0 where nothing outlives a grid step."""
    t_q = t_k if t_q is None else t_q
    if bq is None:
        bq = band_blocks(t_q)[0] if window is not None \
            else grid_blocks(causal, t_q, t_k)[0]
    slots = dq_slots(t_q, t_k, bq,
                     backward_block_k(causal, t_k, bk, window), window)
    return group * slots * bq * width * 4


def backward_passes(causal: bool, t_k: int, bk: int, window=None,
                    t_q=None, bq=None, group: int = 1,
                    width: int = _STAT_LANES) -> int:
    """How many times the backward recomputes a score sub-tile, from
    the call's shapes alone (``bk``: the forward's K tile; ``t_q``,
    ``bq``, ``group``, ``width``: the query side's length and tile, the
    query heads a K/V head and a program's lane width — what
    :func:`resident_dq_bytes` reads): 1 where the call is causal and
    the dq tiles that have to wait for a later K tile fit
    ``RESIDENT_DQ_VMEM`` — none where the backward's K side is ONE grid
    tile (:func:`backward_block_k`: a dk/dv grid step then holds every
    key its Q tile can see), every Q tile of a deeper un-windowed grid,
    a band's width of them under a window.  The backward is then one
    ``pallas_call``, ``znicz_flash_bwd`` / ``znicz_flash_bwd_win`` (five
    matmuls and one pass of exponentials per visible sub-tile); else 2,
    ``znicz_flash_dq`` + ``znicz_flash_dkv`` (seven and two): a
    non-causal call, whose Q tiles all finish at the LAST K tile, and a
    call past the budget (T 32,768 un-windowed at a group of seven).
    Static per program: in the plan (:func:`plan`), its line and the
    unit's gauge."""
    if not causal:
        return 2
    return 1 if resident_dq_bytes(causal, t_k, bk, window, t_q, bq, group,
                                  width) <= RESIDENT_DQ_VMEM else 2


# ----------------------------------------------------------------------
# the window: a grid that visits only the tiles a band touches
# ----------------------------------------------------------------------
#: grid tile edge of a windowed call (one body per tile, no sub-tile
#: walk: a band of 512 is two or three tiles wide — Laguna's — and one
#: of 4,096 nine — SmallThinker's, at T 8,192 and 16,384 —, of which
#: seven lie wholly inside the band and run with no mask code:
#: ``_band_visit``)
BAND_BLOCK = 512


def band_blocks(t: int, block_q=None, block_k=None) -> tuple:
    """Grid tile (bq, bk) of a windowed call: the caller's, else the
    largest power of two ≤ ``BAND_BLOCK`` that divides T."""
    edge = BAND_BLOCK
    while edge > 8 and t % edge:
        edge //= 2
    return min(block_q or edge, t), min(block_k or edge, t)


def _band_k_first(row0, window: int, bk: int):
    """The first K tile that rows from ``row0`` on can see."""
    return jnp.maximum(row0 - window + 1, 0) // bk


def _band_q_first(col0, bq: int):
    """The first Q tile that can see columns from ``col0`` on."""
    return col0 // bq


def band_steps(t: int, bq: int, bk: int, window: int) -> tuple:
    """``(K tiles a row block's band touches at most, Q tiles a column
    block's)``: the length of the grid's last axis in the forward and
    dq kernels, and in dk/dv."""
    k_steps = max(
        min(r0 + bq - 1, t - 1) // bk - max(r0 - window + 1, 0) // bk + 1
        for r0 in range(0, t, bq))
    q_steps = max(
        min(c0 + bk + window - 2, t - 1) // bq - c0 // bq + 1
        for c0 in range(0, t, bk))
    return k_steps, q_steps


def _band_visit(body, row0, col0, rows: int, cols: int, window: int,
                cols_outer: bool = False, live=True):
    """One grid tile of a windowed call = one visit: skipped where the
    band misses it (or the step is past the sequence's end: ``live``),
    with no mask code where it lies wholly inside."""
    visible = (row0 + rows - 1 >= col0) \
        & (col0 + cols - 1 > row0 - window) & live
    interior = (row0 >= col0 + cols - 1) \
        & (col0 > row0 + rows - 1 - window)
    extent = rows if cols_outer else cols
    pl.when(visible & interior)(
        functools.partial(body, 0, [(0, extent, False)]))
    pl.when(visible & jnp.logical_not(interior))(
        functools.partial(body, 0, [(0, extent, True)]))


def _row_walk_bounds(d, sq: int, sk: int, bk: int):
    """For the sub-tile row block whose first row lies ``d`` positions
    after the grid tile's first column: column sub-tiles [0, n_int) are
    interior, [n_int, n_vis) cross the diagonal, the rest lie above it.
    Numerators are clipped to ≥ 0 first, so ``//`` never sees a
    negative traced value."""
    n_int = jnp.clip(d + 1, 0, bk) // sk
    n_vis = jnp.clip(d + sq - 1 + sk, 0, bk) // sk
    return n_int, n_vis


def _col_walk_bounds(e, sq: int, sk: int, bq: int):
    """For the sub-tile column block whose first column lies ``e``
    positions after the grid tile's first row: row sub-tiles
    [0, i_vis) lie above the diagonal, [i_vis, i_int) cross it,
    [i_int, bq // sq) are interior."""
    i_vis = jnp.clip(e, 0, bq) // sq
    i_int = jnp.clip(e + sk - 1 + sq - 1, 0, bq) // sq
    return i_vis, i_int


def _ds(start, size: int):
    """Slice of a ref's sublane dim; traced starts carry the alignment
    hint Mosaic needs."""
    if isinstance(start, int):
        return pl.ds(start, size)
    return pl.ds(pl.multiple_of(start, size), size)


def _walk(body, causal: bool, row0, col0, bq: int, bk: int, sq: int,
          sk: int, cols_outer: bool = False):
    """Visit the compute sub-tiles of one grid tile whose first row /
    column sit at global (row0, col0).

    The tile is cut into row blocks of ``sq`` (or, ``cols_outer``,
    column blocks of ``sk``: dk/dv accumulate per column block).  Each
    block is ONE visit, ``body(start, parts)``: ``start`` is the
    block's offset inside the tile (a traced scalar) and ``parts`` a
    static list of ``(offset, size, masked)`` runs of sub-tiles along
    the other axis — so whatever the body keeps per row (the online-
    softmax state) is updated once per visit, not once per sub-tile.
    Sub-tiles above the diagonal are in no part; interior ones form an
    unmasked part (the body emits no mask code for it); the few the
    diagonal can cross (``band``) form the masked part.  Which static
    shape runs is picked from scalars alone, so traced ring-hop
    offsets take the same code."""
    n_r, n_c = bq // sq, bk // sk
    if not causal:
        inner = [(0, bq if cols_outer else bk, False)]
        _loop(n_c if cols_outer else n_r,
              lambda n: body(n * (sk if cols_outer else sq), inner))
        return
    # how many sub-tiles of one block the diagonal can cross
    band = -(-(sq + sk - 1) // (sq if cols_outer else sk))

    def col_block(j):
        # the visible rows of a column block are a SUFFIX of the tile:
        # its first ``band`` row blocks can cross, the rest are interior
        c = j * sk
        i_vis, i_int = _col_walk_bounds(col0 + c - row0, sq, sk, bq)
        for h in range(1, n_r + 1):             # static heights
            cut = min(h, band)
            parts = _parts(n_r - h, cut, h - cut, sq, masked_first=True)
            pl.when((i_vis == n_r - h) & (i_int > 0))(
                functools.partial(body, c, parts))
        pl.when(i_int == 0)(functools.partial(body, c, [(0, bq, False)]))

    def row_block(i):
        # the visible columns of a row block are a PREFIX of the tile:
        # its last ``band`` sub-tiles can cross, the rest are interior
        r = i * sq
        n_int, n_vis = _row_walk_bounds(row0 + r - col0, sq, sk, bk)
        for w in range(1, n_c + 1):             # static widths
            cut = min(w, band)
            parts = _parts(0, w - cut, cut, sk, masked_first=False)
            pl.when((n_vis == w) & (n_int < n_c))(
                functools.partial(body, r, parts))
        pl.when(n_int == n_c)(functools.partial(body, r, [(0, bk, False)]))

    if cols_outer:
        _loop(n_c, col_block)
    else:
        _loop(n_r, row_block)


def _parts(first: int, n_a: int, n_b: int, edge: int,
           masked_first: bool) -> list:
    """Two adjacent runs of ``n_a`` then ``n_b`` sub-tiles of ``edge``
    starting at sub-tile ``first``, as ``(offset, size, masked)``; the
    masked one is the first or the second, an empty run is left out."""
    runs = [(first * edge, n_a * edge, masked_first),
            ((first + n_a) * edge, n_b * edge, not masked_first)]
    return [run for run in runs if run[1]]


def _loop(n: int, visit):
    """``visit(i)`` for i in [0, n): inline where there is one."""
    if n == 1:
        visit(0)
    else:
        jax.lax.fori_loop(0, n, lambda i, _: visit(i), None)


def _k_col0(koff_ref, row0, ik, bk: int, window):
    """First column of the K tile at step ``ik`` of the grid's last
    axis: the tiles in turn, or (windowed) those the band of the row
    block at ``row0`` touches, the first of them at step 0."""
    if window is None:
        return koff_ref[0, 0] + ik * bk
    return (_band_k_first(row0, window, bk) + ik) * bk


def _fold_rows(body, causal: bool, row0, col0, bq: int, bk: int, sq: int,
               sk: int, window, cols_outer: bool = False, live=True):
    """Run ``body`` over what a grid tile holds of the visible region:
    the causal sub-tile walk, or one visit of a windowed call's tile."""
    if window is not None:
        _band_visit(body, row0, col0, bq, bk, window, cols_outer, live)
        return
    visible = True if not causal else row0 + bq - 1 >= col0

    @pl.when(visible)
    def _fold():
        _walk(body, causal, row0, col0, bq, bk, sq, sk, cols_outer)


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------
class ForwardForm(NamedTuple):
    """What ONE visit of the forward's walk does between the score
    product and the value product (:func:`forward_form`)."""
    #: ``"none"`` — a row block meets all its keys in one visit and
    #: writes ``o`` and ``lse`` itself; ``"carried"`` — m, l and the
    #: accumulator wait in VMEM scratch for the next K tile
    state: str
    #: where a row's statistics live through the arithmetic
    stats: str
    #: where 1/√dh enters: ``"q"`` — into q, once per visit (a power of
    #: two: nothing is rounded); ``"exp"`` — into the exponential's own
    #: multiply, the scores staying unscaled
    scale: str


def forward_form(t_k: int, bq: int, bk: int, dh: int,
                 window=None) -> ForwardForm:
    """The forward body a call over ``t_k`` keys in (bq, bk) grid tiles
    gets, from its static shapes alone: no state where the grid's last
    axis has ONE step (the K tiles of a causal call, the tiles a band
    touches of a windowed one), 1/√dh in q where it is a power of two.
    Static per program: in the plan (:func:`plan`) and its line."""
    k_steps = t_k // bk if window is None \
        else band_steps(t_k, bq, bk, window)[0]
    mantissa, _ = math.frexp(dh ** -0.5)
    return ForwardForm("none" if k_steps == 1 else "carried", "lanes",
                       "q" if mantissa == 0.5 else "exp")


#: an elementwise fold → the reduction that finishes it across lanes
_ACROSS = {jnp.maximum: jnp.max, jnp.add: jnp.sum}


def _lane_fold(part, x, op):
    """``part`` ∘ the 128-lane slabs of ``x``, elementwise: a row
    statistic of a visit stays lane-wise — plain VPU operations — over
    a run's slabs and over its runs, and the lanes are folded once per
    visit.  A run narrower than the lanes (the tests') folds at once."""
    if x.shape[1] % _STAT_LANES:
        slabs = [_ACROSS[op](x, axis=1, keepdims=True)]
    else:
        slabs = [x[:, j:j + _STAT_LANES]
                 for j in range(0, x.shape[1], _STAT_LANES)]
    for slab in slabs:
        part = slab if part is None else op(part, slab)
    return part


def _replicated(part, op):
    """The lanes of a visit's partial statistic folded — the ONE
    cross-lane reduction — and the value put back in every lane."""
    return jnp.broadcast_to(_ACROSS[op](part, axis=1, keepdims=True),
                            (part.shape[0], _STAT_LANES))


def _spread(stat, width: int):
    """A lane-replicated (rows, 128) statistic against ``width``
    columns: its own lanes, side by side as often as needed."""
    if width <= _STAT_LANES:
        return stat[:, :width]
    if width % _STAT_LANES:
        return stat[:, :1]
    return pltpu.repeat(stat, width // _STAT_LANES, 1)


def _visit(q, k_ref, v_ref, fs, parts, masks, prev, mul):
    """One sub-head's softmax of rows ``q`` (sq, dh) over every column
    run of a visit: ``(m, l, acc)``, m and l lane-replicated (sq, 128)
    through all of the arithmetic, so nothing is broadcast from or
    narrowed to one lane.  ``prev`` is the state the rows bring (None:
    they have no history); ``mul`` the factor of the exponential's
    argument, p = 2^((s − m)·mul) — the EUP's exponential is a power of
    two, so log2(e) and, where q could not take it, 1/√dh are ONE
    multiply."""
    scores, top = [], None
    for (c, n, _), mask in zip(parts, masks):
        s = _dot(q, k_ref[c:c + n, fs], trans_b=True)
        if mask is not None:
            s = jnp.where(mask, s, _NEG_INF)
        scores.append(s)
        top = _lane_fold(top, s, jnp.maximum)
    m_new = _replicated(top, jnp.maximum)
    if prev is not None:
        m_new = jnp.maximum(prev[0], m_new)
    m_exp = m_new
    if any(mask is not None for mask in masks):
        # offset hops can hold FULLY-masked rows (m stays -1e30, and
        # exp(s − m) = exp(0) there): against 0 instead their masked
        # scores underflow to the zeros they are.  A row with one
        # visible score has a finite m and needs nothing; neither does
        # a visit of interior runs only
        m_exp = jnp.where(m_new > _NEG_INF, m_new, 0.0)
    total, acc = None, None
    for (c, n, _), s in zip(parts, scores):
        pt = jnp.exp2((s - _spread(m_exp, n)) * mul)
        total = _lane_fold(total, pt, jnp.add)
        v = v_ref[c:c + n, fs]
        part = _dot(pt.astype(v.dtype), v)
        acc = part if acc is None else acc + part
    l_new = _replicated(total, jnp.add)
    if prev is not None:
        corr = jnp.exp2((prev[0] - m_new) * mul)
        l_new = prev[1] * corr + l_new
        acc = prev[2] * _spread(corr, acc.shape[1]) + acc
    return m_new, l_new, acc


def _fwd_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                *scratch, scale, causal, bq, bk, sq, sk, pack, form,
                window=None):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    row0 = qoff_ref[0, 0] + iq * bq
    col0 = _k_col0(koff_ref, row0, ik, bk, window)
    carried = form.state == "carried"
    dh = q_ref.shape[1] // pack
    in_q = form.scale == "q"
    mul = _LOG2_E if in_q else scale * _LOG2_E

    def leave(rows, stats):
        """``o`` and ``lse`` of ``rows`` from every sub-head's final
        (m, l, acc)."""
        o_out, lse_out = [], []
        for m, l, acc in stats:
            l = jnp.maximum(l, 1e-30)
            if not in_q:    # m is a maximum of unscaled scores
                m = jnp.where(m > _NEG_INF, m * scale, _NEG_INF)
            o_out.append((acc / _spread(l, dh)).astype(o_ref.dtype))
            # row stats ride _LANES lanes per sub-head (minimum
            # tile-legal lane width; the value repeats in every lane)
            lse_out.append((m + jnp.log(l))[:, :_LANES])
        o_ref[rows, :] = jnp.concatenate(o_out, axis=1)
        lse_ref[rows, :] = jnp.concatenate(lse_out, axis=1)

    # a Q tile of a carried call whose keys ALL lie in the K tile of step
    # 0, its first row seeing that tile's first key: each of its row
    # blocks has exactly one visit there too, and carries nothing
    once = False
    if carried and causal and window is None:
        once = (row0 + bq - 1 < koff_ref[0, 0] + bk) \
            & (row0 >= koff_ref[0, 0])
    if carried:
        m_scr, l_scr, acc_scr = scratch

        @pl.when((ik == 0) & jnp.logical_not(once))
        def _init():
            m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

    def state(p, rs):
        """Sub-head ``p``'s (m, l, acc) of rows ``rs`` as the scratch
        holds them."""
        return (m_scr[p, rs, :], l_scr[p, rs, :],
                acc_scr[rs, p * dh:(p + 1) * dh])

    def body(r, parts, carried=carried):
        """ONE softmax update of rows [r, r+sq) by every column run in
        ``parts``."""
        rs = _ds(r, sq)
        q_all = q_ref[rs, :]
        if in_q:
            q_all = (q_all.astype(jnp.float32) * scale).astype(q_all.dtype)
        masks = [_causal_mask(row0 + r, col0 + c, sq, n, window)
                 if masked else None for c, n, masked in parts]
        stats = []
        for p in range(pack):           # static: per-sub-head math
            fs = slice(p * dh, (p + 1) * dh)
            stats.append(_visit(q_all[:, fs], k_ref, v_ref, fs, parts,
                                masks, state(p, rs) if carried else None,
                                mul))
        if not carried:
            leave(rs, stats)
            return
        for p, (m, l, _) in enumerate(stats):
            m_scr[p, rs, :], l_scr[p, rs, :] = m, l
        acc_scr[rs, :] = jnp.concatenate([acc for *_, acc in stats],
                                         axis=1)

    if causal and not carried:
        # an offset hop's rows above every key get no visit at all, and
        # no state waits here to speak for them: they read o 0 and
        # lse -1e30 (the units' calls, at offsets 0, never come here)
        @pl.when(row0 < col0)
        def _unseen():
            o_ref[...] = jnp.zeros_like(o_ref)
            lse_ref[...] = jnp.full_like(lse_ref, _NEG_INF)

    def fold(visit):
        _fold_rows(visit, causal, row0, col0, bq, bk, sq, sk, window)

    if once is False:
        fold(body)
    else:
        pl.when(once)(functools.partial(
            fold, functools.partial(body, carried=False)))
        pl.when(jnp.logical_not(once))(functools.partial(fold, body))

    if carried:
        @pl.when((ik == nk - 1) & jnp.logical_not(once))
        def _finish():
            leave(slice(None),
                  [state(p, slice(None)) for p in range(pack)])


# ----------------------------------------------------------------------
# backward: dq kernel (K blocks innermost), dk/dv kernel (Q innermost)
# ----------------------------------------------------------------------
def _p_tile(q, k, lse_col, scale, mask):
    """Recompute one sub-head's probability sub-tile p = exp(s − lse)
    in VMEM.  Where the diagonal crosses (``mask`` given) the select
    also guards fully-masked rows (offset hops): there lse ≈ -1e30 and
    the unmasked exp overflows.  An interior sub-tile passes no mask:
    every entry is visible and lse is finite."""
    s = _dot(q, k, trans_b=True) * scale
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    p = jnp.exp(s - lse_col)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    return p


def _dq_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, do_ref,
               lse_ref, delta_ref, dq_ref, dq_scr, *, scale, causal,
               bq, bk, sq, sk, pack, window=None):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    row0 = qoff_ref[0, 0] + iq * bq
    col0 = _k_col0(koff_ref, row0, ik, bk, window)

    @pl.when(ik == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def body(r, parts):
        """dq of rows [r, r+sq) from every column run in ``parts``."""
        rs = _ds(r, sq)
        q_all, do_all = q_ref[rs, :], do_ref[rs, :]
        lse, delta = lse_ref[rs, :], delta_ref[rs, :]
        dh = q_all.shape[1] // pack
        out = []
        for p in range(pack):
            fs = slice(p * dh, (p + 1) * dh)
            ls = slice(p * _LANES, p * _LANES + 1)
            acc = None
            for c, n, masked in parts:
                mask = (_causal_mask(row0 + r, col0 + c, sq, n, window)
                        if masked else None)
                k, v = k_ref[c:c + n, fs], v_ref[c:c + n, fs]
                pt = _p_tile(q_all[:, fs], k, lse[:, ls], scale, mask)
                dp = _dot(do_all[:, fs], v, trans_b=True)
                ds = pt * (dp - delta[:, ls]) * scale
                part = _dot(ds.astype(k.dtype), k)
                acc = part if acc is None else acc + part
            out.append(acc)
        dq_scr[rs, :] += jnp.concatenate(out, axis=1)

    _fold_rows(body, causal, row0, col0, bq, bk, sq, sk, window)

    @pl.when(ik == nk - 1)
    def _finish():
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)


def _when(cond, fn) -> None:
    """``fn()`` where ``cond`` holds: a traced scalar, or True as
    Python knows it — then no branch is made."""
    if cond is True:
        fn()
    else:
        pl.when(cond)(fn)


def _dkv_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, do_ref,
                lse_ref, delta_ref, *rest, scale, causal, bq, bk, sq,
                sk, pack, shared, window=None, q_steps=None, q_tiles=None,
                group=1, one_pass=False, slots=0, dq_block=None):
    """``q_steps`` (None, or the Q tiles one query head brings to the
    grid's last axis): grouped queries and a window make that axis
    something else than the Q tiles in turn — it runs over every query
    head of this K/V head's group, each with the Q tiles its band
    touches (all of them without a window), and dk, dv accumulate over
    the lot.  ``shared`` (None, or the first column blocks (q, k, v) of
    dq, dk and dv): all are column blocks of ONE result — the cotangent
    of a fused projection.  A kernel's blocked output is one block per
    grid step, so the finished tiles go there by DMA from VMEM.

    ``one_pass`` (:func:`backward_passes`): the body also contracts the
    ``ds`` it has with k, into dq — this kernel is the whole backward
    (``znicz_flash_bwd``) and nothing is recomputed twice.  ``slots`` 0
    (the K side is ONE grid tile): a grid step's Q tile meets here every
    key it can see, so its dq is an f32 (bq, width) scratch that is
    zeroed as the step starts and leaves as it ends.  Else (:func:`
    dq_slots`) the scratch holds ``slots`` such tiles for each query
    head of the group and the step's tile has a slot of its own: zeroed
    at the first K tile that feeds it, added to in every K tile between
    — ascending, as :func:`_dq_kernel` adds them —, cast and sent to
    its rows of dq (``dq_block``: their first column block, None
    head-major) by DMA at the last, the diagonal's; both read from the
    offsets' scalars, so a hop's rows above every key leave as the
    zeros they are at K tile 0.  Without ``one_pass`` the dq call
    (:func:`_dq_kernel`) has begun a fused result and hands it in
    aliased."""
    refs = list(rest)
    dq_ref = dq_scr = dq_tile = pending = None
    if shared is None:
        if one_pass:
            dq_ref = refs.pop(0)
        dk_ref, dv_ref = refs.pop(0), refs.pop(0)
    else:
        if not one_pass:
            refs.pop(0)     # the aliased operand itself is never read
        out_ref = dq_ref = refs.pop(0)
        dq_block = shared[0]
    dk_scr, dv_scr = refs.pop(0), refs.pop(0)
    if one_pass:
        dq_scr = refs.pop(0)
        if shared is not None or slots:
            dq_tile = refs.pop(0)
    if slots:
        pending = refs.pop()    # whether a dq tile is on its way out
    if shared is not None:
        dk_tile, dv_tile, sems = refs
    elif slots:
        sems, = refs
    batch, head = pl.program_id(0), pl.program_id(1)
    ik, step = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)
    col0 = koff_ref[0, 0] + ik * bk
    iq = step if q_steps is None else step % q_steps
    live = True
    if window is not None:      # a band's last steps may pass the end
        iq = _band_q_first(col0, bq) + iq
        live = iq < q_tiles
    row0 = qoff_ref[0, 0] + iq * bq

    @pl.when(step == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)
        if slots:
            pending[0] = 0

    # the step's dq tile: the scratch itself, begun and finished here
    # — or its slot, between the first K tile of its rows and the last
    slot, begins, leaves = (), True, True
    if slots:
        slot = ((step // q_steps) * slots + iq % slots,)
        first_k = 0 if window is None \
            else _band_k_first(row0, window, bk)
        last_k = jnp.clip(row0 + bq - 1 - koff_ref[0, 0], 0,
                          pl.num_programs(2) * bk - 1) // bk
        begins, leaves = (ik == first_k) & live, (ik == last_k) & live
    if one_pass:
        def _begin_dq():
            dq_scr[slot] = jnp.zeros(dq_scr.shape[len(slot):],
                                     dq_scr.dtype)
        _when(begins, _begin_dq)

    def body(c, parts):
        """dk, dv of columns [c, c+sk) from every row run in
        ``parts`` — and, one pass, those columns' share of the runs'
        dq."""
        cs = _ds(c, sk)
        k_all, v_all = k_ref[cs, :], v_ref[cs, :]
        dh = k_all.shape[1] // pack
        dk_out, dv_out = [], []
        dq_out = [[] for _ in parts]
        for p in range(pack):
            fs = slice(p * dh, (p + 1) * dh)
            ls = slice(p * _LANES, p * _LANES + 1)
            dk = dv = None
            for (r, n, masked), dq_run in zip(parts, dq_out):
                mask = (_causal_mask(row0 + r, col0 + c, n, sk, window)
                        if masked else None)
                q, do = q_ref[r:r + n, fs], do_ref[r:r + n, fs]
                pt = _p_tile(q, k_all[:, fs],
                             lse_ref[r:r + n, ls], scale, mask)
                # dv += pᵀ · do ; contract the q dim without
                # materializing pᵀ
                dv_part = _dot(pt.astype(do.dtype), do, trans_a=True)
                dp = _dot(do, v_all[:, fs], trans_b=True)
                ds = (pt * (dp - delta_ref[r:r + n, ls])
                      * scale).astype(q.dtype)
                dk_part = _dot(ds, q, trans_a=True)
                dk = dk_part if dk is None else dk + dk_part
                dv = dv_part if dv is None else dv + dv_part
                if one_pass:    # the fifth matmul, on the ds at hand
                    dq_run.append(_dot(ds, k_all[:, fs]))
            dk_out.append(dk)
            dv_out.append(dv)
        dk_scr[cs, :] += jnp.concatenate(dk_out, axis=1)
        dv_scr[cs, :] += jnp.concatenate(dv_out, axis=1)
        if one_pass:
            for (r, n, _), dq_run in zip(parts, dq_out):
                dq_scr[(*slot, slice(r, r + n), slice(None))] \
                    += jnp.concatenate(dq_run, axis=1)

    _fold_rows(body, causal, row0, col0, bq, bk, sq, sk, window,
               cols_outer=True, live=live)

    d = dk_scr.shape[1]
    last = step == nq - 1

    def to_column_block(tile, out, rows, block, sem):
        """The DMA of a finished tile to ``rows`` of column block
        ``block`` of ``out``."""
        lanes = pl.ds(pl.multiple_of(block * d, d), d)
        return pltpu.make_async_copy(
            tile, out.at[batch, rows, lanes], sems.at[sem])

    if one_pass and dq_tile is None:
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)
    elif one_pass:
        # the step's own Q tile: the copy runs under the next steps'
        # walk and is waited for where its tile is written again
        q_head = head if q_steps is None else head * group + step // q_steps
        rows = pl.ds(pl.multiple_of(iq * bq, bq), bq)
        if dq_block is None:    # head-major: the query head's rows
            dq_copy = pltpu.make_async_copy(
                dq_tile, dq_ref.at[batch, q_head, rows, :], sems.at[2])
        else:
            dq_copy = to_column_block(dq_tile, dq_ref, rows,
                                      dq_block + q_head, 2)

        def _leave_dq():
            # every step sends a tile where none waits; else the flag says
            pl.when(pending[0] == 1 if slots else step > 0)(dq_copy.wait)
            dq_tile[...] = dq_scr[slot].astype(dq_tile.dtype)
            dq_copy.start()
            if slots:
                pending[0] = 1
        _when(leaves, _leave_dq)

    @pl.when(last)
    def _finish():
        if slots:   # the flag lives one K tile: its last copy ends here
            pl.when(pending[0] == 1)(dq_copy.wait)
        if shared is None:
            dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
            dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)
            return
        rows = pl.ds(pl.multiple_of(ik * bk, bk), bk)
        copies = []
        for acc, tile, first, sem in ((dk_scr, dk_tile, shared[1], 0),
                                      (dv_scr, dv_tile, shared[2], 1)):
            tile[...] = acc[...].astype(tile.dtype)
            copies.append(to_column_block(tile, out_ref, rows,
                                          first + head, sem))
            copies[-1].start()
        if one_pass and not slots:
            copies.append(dq_copy)
        for copy in copies:
            copy.wait()


def _tile(rows: int, width: int, col0, at):
    """``BlockSpec`` of one head's (rows, width) tile at grid position
    (batch, head, i, j); ``at(h, i, j)`` is its (head, row block).
    ``col0`` None: the operand is head-major (B, H, T, width).  Else it
    is in the boundary layout (B, T, C) and the tile is column block
    ``col0 + head`` — an address, so nothing is moved to where the
    kernel could have fetched it.  Leading dims are squeezed: the
    kernels see (rows, width) either way."""
    if col0 is None:
        def head_major(b, h, i, j):
            head, block = at(h, i, j)
            return b, head, block, 0
        return pl.BlockSpec((None, None, rows, width), head_major)

    def boundary(b, h, i, j):
        head, block = at(h, i, j)
        return b, block, col0 + head
    return pl.BlockSpec((None, rows, width), boundary)


def _first(h, i, j):
    """The tile of the grid's own head whose row block follows the
    grid's third axis."""
    return h, i


def _second(h, i, j):
    """… or its last."""
    return h, j


def _k_side(group: int, window, t_k: int, bq: int, bk: int):
    """Where the forward and dq kernels (grid: batch, QUERY head, Q
    tile, step) find a K/V tile: the K/V head of the query head's group
    and the step's tile — windowed, the step-th tile of the Q tile's
    band, held at the last tile where the band has fewer (the kernel
    skips the repeat, and a repeated block is not fetched again)."""
    if group == 1 and window is None:
        return _second

    def at(h, i, j):
        if window is not None:
            j = jnp.minimum(_band_k_first(i * bq, window, bk) + j,
                            t_k // bk - 1)
        return h // group, j
    return at


def _q_side(group: int, window, q_steps: int, t_q: int, bq: int, bk: int):
    """Where the dk/dv kernel (grid: batch, K/V head, K tile, step)
    finds a tile of q, do, lse, delta: the step runs over the query
    heads of the group, each with ``q_steps`` Q tiles — all of them,
    or (windowed) those that can see the K tile."""
    if group == 1 and window is None:
        return _second

    def at(h, i, j):
        block = j % q_steps
        if window is not None:
            block = jnp.minimum(_band_q_first(i * bk, bq) + block,
                                t_q // bq - 1)
        return h * group + j // q_steps, block
    return at


def _operands(arrays, cols):
    """(q, k, v), (batch, query-head programs, K/V-head programs, T_q,
    T_k, tile width) and the three first column blocks of a call's
    ``arrays``.  ``cols`` None: three head-major (B, Hp, T, width)
    arrays, no column blocks.  Else ``cols`` = (query-head programs,
    width, K/V-head programs) of boundary-layout (B, T, C) operands:
    three arrays, each from its block 0 on, or ONE projection result
    whose column ranges are q, k, v in turn."""
    q, k, v = arrays if len(arrays) == 3 else arrays * 3
    if cols is None:
        b, h, t, d = q.shape
        return (q, k, v), (b, h, k.shape[1], t, k.shape[2], d), \
            (None,) * 3
    h, d, h_kv = cols
    first = (0, 0, 0) if len(arrays) == 3 else (0, h, h + h_kv)
    return (q, k, v), (q.shape[0], h, h_kv, q.shape[1], k.shape[1], d), \
        first


# jitted: every layer of a model calls these with the same static
# arguments and shapes, so the kernels are traced and lowered ONCE per
# program, not once per layer (on the chip's host, lowering the LM
# cell's 18 flash calls cost 7 s of every start, cached program or not;
# PERF.md §6, PR 24)
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _fwd_call(arrays, q_off, k_off, causal, bq, bk, interpret, pack,
              sub=None, cols=None, window=None):
    """``arrays``: (q, k, v) head-major (B, Hp, T, pack·dh) where
    ``cols`` is None; else boundary-layout (B, T, C) operands, ``cols``
    = (head programs, tile width, K/V head programs) — three arrays,
    or ONE that holds all three (:func:`_operands`).  Fewer K/V heads
    than query heads are grouped queries: query head h reads K/V head
    h // group.  ``window``: row r sees columns in (r − window, r] and
    the grid's last axis visits only the K tiles a band touches.
    Returns (out, lse): out in the operands' layout ((B, T,
    heads·width) for the boundary layout), lse head-major (B, Hp, T,
    pack·_LANES)."""
    (q, k, v), (b, h, h_kv, t, tk, d), (cq, ck, cv) = \
        _operands(arrays, cols)
    nq, nk = t // bq, tk // bk
    sq, sk = sub or sub_tile_for(causal, bq, bk)
    static = dict(scale=1.0 / np.sqrt(d // pack), causal=causal, bq=bq,
                  bk=bk, sq=sq, sk=sk, pack=pack)
    name = "znicz_flash_fwd"
    if window is not None:
        nk = band_steps(t, bq, bk, window)[0]
        static.update(sq=bq, sk=bk, window=window)
        name += "_win"
    form = forward_form(tk, bq, bk, d // pack, window)
    scratch = []
    if form.state == "carried":
        # m and l lane-replicated, a tile per sub-head, and the f32
        # accumulator
        stat = pltpu.VMEM((pack, bq, _STAT_LANES), jnp.float32)
        scratch = [stat, stat, pltpu.VMEM((bq, d), jnp.float32)]
    k_at = _k_side(h // h_kv, window, tk, bq, bk)
    off_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    lanes = pack * _LANES
    out_shape = (b, h, t, d) if cols is None else (b, t, h * d)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, form=form, **static),
        grid=(b, h, nq, nk),        # q rows follow axis 2, k rows axis 3
        in_specs=[off_spec, off_spec, _tile(bq, d, cq, _first),
                  _tile(bk, d, ck, k_at), _tile(bk, d, cv, k_at)],
        out_specs=(_tile(bq, d, None if cols is None else 0, _first),
                   _tile(bq, lanes, None, _first)),
        out_shape=(jax.ShapeDtypeStruct(out_shape, q.dtype),
                   jax.ShapeDtypeStruct((b, h, t, lanes), jnp.float32)),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name=name,
    )(q_off, k_off, q, k, v)


@functools.partial(jax.jit,
                   static_argnums=(6, 7, 8, 9, 10, 11, 12, 13, 14))
def _bwd_call(arrays, lse, do, delta4, q_off, k_off, causal, bq, bk,
              interpret, pack, sub, cols, window, passes):
    """Cotangents of ``arrays`` (see :func:`_fwd_call`), in their
    layout: (dq, dk, dv), or for ONE fused array its one cotangent —
    written whole by the one-pass call (``passes``:
    :func:`backward_passes` of the shapes, asked by the caller so that
    the call is traced per answer); under
    two passes the dq call writes q's column blocks of a (B, T, C)
    result and the dk/dv call takes that result aliased and puts its
    tiles beside them — so no concatenate of activation size stands
    before the projection's backward.  ``do`` is in the layout of the
    forward's out;
    ``delta4``: (B, Hp, T, pack) f32 — rowsum(do·o) per SUB-head,
    already adjusted for any lse cotangent (the hop composition's
    extra term).  With grouped queries the dk/dv grid is over the K/V
    heads, and its last axis over the group's query heads and their Q
    tiles: a K/V head's dk, dv accumulate in VMEM over all of them and
    are written once."""
    (q, k, v), (b, h, h_kv, t, tk, d), (cq, ck, cv) = \
        _operands(arrays, cols)
    shared = len(arrays) == 1
    group = h // h_kv
    one_pass = passes == 1
    bk = backward_block_k(causal, tk, bk, window)
    nq, nk = t // bq, tk // bk
    sq, sk = sub or sub_tile_for(causal, bq, bk)
    lanes = pack * _LANES
    # per-sub-head delta rides _LANES lanes each, like lse
    delta = jnp.repeat(delta4, _LANES, axis=-1)      # (B, H, T, lanes)
    static = dict(scale=1.0 / np.sqrt(d // pack), causal=causal, bq=bq,
                  bk=bk, sq=sq, sk=sk, pack=pack)
    k_steps, q_steps, suffix = nk, nq, ""
    if window is not None:
        k_steps, q_steps = band_steps(t, bq, bk, window)
        static.update(sq=bq, sk=bk, window=window)
        suffix = "_win"
    off_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    semantics = ("parallel", "parallel", "parallel", "arbitrary")
    params = pltpu.CompilerParams(dimension_semantics=semantics)
    c_do = None if cols is None else 0

    def like(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    def specs(q_at, k_at):
        return [off_spec, off_spec, _tile(bq, d, cq, q_at),
                _tile(bk, d, ck, k_at), _tile(bk, d, cv, k_at),
                _tile(bq, d, c_do, q_at),
                _tile(bq, lanes, None, q_at),
                _tile(bq, lanes, None, q_at)]

    operands = [q_off, k_off, q, k, v, do, lse, delta]
    if not one_pass:
        dq = pl.pallas_call(
            functools.partial(_dq_kernel, **static),
            grid=(b, h, nq, k_steps),
            in_specs=specs(_first, _k_side(group, window, tk, bq, bk)),
            out_specs=_tile(bq, d, cq, _first),
            out_shape=like(q),
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
            compiler_params=params,
            interpret=interpret,
            name="znicz_flash_dq" + suffix,
        )(*operands)
    # dk/dv: Q blocks innermost; the q-side specs index by the LAST
    # grid dim now, the k-side by dim 2
    q_at = _q_side(group, window, q_steps, t, bq, bk)
    in_specs = specs(q_at, _first)
    slots = dq_slots(t, tk, bq, bk, window) if one_pass else 0
    if group > 1 or window is not None or slots:
        static["q_steps"] = q_steps
    if window is not None:
        static["q_tiles"] = nq
    name = "znicz_flash_dkv" + suffix
    outs = [(k, ck, bk, _first), (v, cv, bk, _first)]
    scratch = [pltpu.VMEM((bk, d), jnp.float32),
               pltpu.VMEM((bk, d), jnp.float32)]
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    if one_pass:        # the whole backward: dq leaves with dk and dv
        static.update(one_pass=True, group=group)
        name = "znicz_flash_bwd" + suffix
        outs.insert(0, (q, cq, bq, q_at))
        scratch.append(pltpu.VMEM(
            (group * slots, bq, d) if slots else (bq, d), jnp.float32))
        limit = _WHOLE_K_VMEM if bk > CAUSAL_BLOCK_K else None
        if slots:
            # dq tiles wait in their slots from K tile to K tile: that
            # axis runs in turn, and the call asks for the room
            static.update(slots=slots)
            semantics = ("parallel", "parallel", "arbitrary", "arbitrary")
            limit = group * slots * bq * d * 4 + (limit or _STEP_VMEM)
        if limit:
            params = pltpu.CompilerParams(
                dimension_semantics=semantics, vmem_limit_bytes=limit)
    call = functools.partial(
        pl.pallas_call, grid=(b, h_kv, nk, group * q_steps),
        compiler_params=params, interpret=interpret, name=name)
    # the tiles that leave by DMA from a VMEM copy in the cotangent's
    # dtype: all of ONE fused result's, and a dq tile that waited
    sent = outs if shared else outs[:1] if slots else []
    scratch += [pltpu.VMEM((rows, d), a.dtype) for a, _, rows, _ in sent]
    if sent:    # dk's, dv's and (the kernel's index 2) dq's
        scratch.append(pltpu.SemaphoreType.DMA((len(outs),)))
    if slots:   # whether a dq tile is on its way out
        scratch.append(pltpu.SMEM((1,), jnp.int32))
    if not shared:
        if slots:
            static.update(dq_block=cq)
        grads = call(
            functools.partial(_dkv_kernel, shared=None, **static),
            in_specs=in_specs,
            out_specs=tuple(anywhere if slots and i == 0
                            else _tile(rows, d, col, at)
                            for i, (_, col, rows, at) in enumerate(outs)),
            out_shape=tuple(like(a) for a, *_ in outs),
            scratch_shapes=scratch,
        )(*operands)
        return tuple(grads) if one_pass else (dq, *grads)
    # ONE result
    kernel = functools.partial(_dkv_kernel, shared=(cq, ck, cv), **static)
    if one_pass:
        return (call(kernel, in_specs=in_specs, out_specs=anywhere,
                     out_shape=like(q), scratch_shapes=scratch)(*operands),)
    return (call(kernel, in_specs=in_specs + [anywhere],
                 out_specs=anywhere, out_shape=like(dq),
                 input_output_aliases={len(operands): 0},
                 scratch_shapes=scratch)(*operands, dq),)


# ----------------------------------------------------------------------
# custom_vjp: the head-major hop (the ring's) and the boundary-layout
# pass (the attention unit's), over the same calls
# ----------------------------------------------------------------------
def _delta(do, out, dlse, pack: int, boundary: bool):
    """delta = rowsum(do·o) per sub-head, (B, Hp, T, pack) f32, from
    ``do`` / ``out`` head-major (B, Hp, T, pack·dh) or in the boundary
    layout (B, T, Hp·pack·dh).  There a head's sum runs over a PART of
    the minor dim, which as a reduce makes XLA relayout the whole f32
    product twice (compiled for a described v5e, PR 28); contracted
    with a 0/1 (D, heads) selector at full f32 precision it is one
    fusion over do and o where they lie, and what is transposed to the
    kernels' head-major statistics is a number per row and head.  An
    lse cotangent (the hop composition) enters the score gradient as
    ds += p·dlse, i.e. delta -= dlse (lanes are value copies →
    group-sum them)."""
    prod = do.astype(jnp.float32) * out.astype(jnp.float32)
    hp = dlse.shape[1]
    if boundary:
        b, t, d = prod.shape
        heads = hp * pack
        select = np.repeat(np.eye(heads, dtype=np.float32), d // heads,
                           axis=0)
        delta4 = jnp.dot(prod.reshape(b * t, d), select,
                         precision=jax.lax.Precision.HIGHEST) \
            .reshape(b, t, hp, pack).transpose(0, 2, 1, 3)
    else:
        b, _, t, _ = prod.shape
        delta4 = prod.reshape(b, hp, t, pack, -1).sum(axis=-1)
    return delta4 - dlse.astype(jnp.float32) \
        .reshape(b, hp, t, pack, _LANES).sum(axis=-1)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash_pass(arrays, q_off, k_off, causal, bq, bk, interpret, pack,
                sub=None, cols=None, window=None):
    """One flash pass at global positions (q_off, k_off) → (out, lse)
    over ``arrays`` as :func:`_fwd_call` takes them: head-major
    (``cols`` None) or addressed in the boundary layout.  This is BOTH
    the plain single-call kernel (offsets 0, lse discarded) and the
    per-hop ring fold (lse feeds the cross-hop online-softmax
    combination); the lse cotangent folds into delta in the backward,
    so one custom_vjp serves both."""
    return _fwd_call(arrays, q_off, k_off, causal, bq, bk, interpret,
                     pack, sub, cols, window)


def _pass_fwd(arrays, q_off, k_off, causal, bq, bk, interpret, pack,
              sub, cols, window):
    out, lse = _fwd_call(arrays, q_off, k_off, causal, bq, bk,
                         interpret, pack, sub, cols, window)
    return (out, lse), (arrays, out, lse, q_off, k_off)


def _pass_bwd(causal, bq, bk, interpret, pack, sub, cols, window, res,
              cts):
    arrays, out, lse, q_off, k_off = res
    do, dlse = cts
    do = do.astype(out.dtype)
    delta4 = _delta(do, out, dlse, pack, cols is not None)
    _, h, h_kv, t, tk, d = _operands(arrays, cols)[1]
    passes = backward_passes(causal, tk, bk, window, t, bq, h // h_kv, d)
    grads = _bwd_call(arrays, lse, do, delta4, q_off, k_off, causal,
                      bq, bk, interpret, pack, sub, cols, window, passes)
    zero = np.zeros((1, 1), jax.dtypes.float0)
    return tuple(grads), zero, zero


_flash_pass.defvjp(_pass_fwd, _pass_bwd)


def ring_hop(qh, kh, vh, q_offset, k_offset, causal: bool,
             block_q: int, block_k: int, interpret: bool = False,
             pack: int = 1, sub_tile=None):
    """One ring hop on head-major, already-packed operands
    (B, Hp, T, pack·dh): returns (out in qh.dtype, lse (B, Hp, T,
    pack) f32).  Offsets may be traced scalars (``axis_index``
    arithmetic under shard_map).  K and V travel between chips in this
    layout, so the ring keeps the head-major address (no cell and no
    chip run covers it: PERF.md §7)."""
    out, lse = _flash_pass((qh, kh, vh), _off_arr(q_offset),
                           _off_arr(k_offset), causal, block_q,
                           block_k, interpret, pack, sub_tile)
    return out, lse[..., ::_LANES]


def pack_heads(x, pack: int):
    """(B, T, H, dh) boundary layout → head-major packed
    (B, H//pack, T, pack·dh).  Heads are adjacent to dh at the
    boundary, so the pack itself is a free reshape; the transpose is a
    bandwidth pass (the ring's, and a lane-illegal head width's)."""
    b, t, h, dh = x.shape
    return x.reshape(b, t, h // pack, pack * dh).transpose(0, 2, 1, 3)


def unpack_heads(x, pack: int, n_heads: int):
    """Inverse of :func:`pack_heads`: (B, Hp, T, pack·dh) →
    (B, T, H, dh)."""
    b, hp, t, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, n_heads, d // pack)


def _rows_pass(arrays, n_heads: int, q_off, k_off, causal, bq, bk,
               interpret, sub, n_kv_heads=None, window=None):
    """(B, T, ·) operands → (B, T, H·dh) out, by the address their head
    width allows (:func:`head_layout`)."""
    fused = len(arrays) == 1
    b, t, c = arrays[0].shape
    h_kv = n_kv_heads or n_heads
    dh = c // (n_heads + 2 * h_kv) if fused else c // n_heads
    layout, pack = head_layout(n_heads, dh, n_heads // h_kv)
    if layout == "boundary":
        cols = (n_heads // pack, pack * dh, h_kv // pack)
        return _flash_pass(arrays, q_off, k_off, causal, bq, bk,
                           interpret, pack, sub, cols, window)[0]
    # a head width that is neither a multiple nor a divisor of the 128
    # lanes: no lane-legal column block, so the tiles are moved
    if fused:
        edges = np.cumsum([0, n_heads, h_kv, h_kv]) * dh
        arrays = tuple(arrays[0][..., lo:hi]
                       for lo, hi in zip(edges[:-1], edges[1:]))
    heads = tuple(pack_heads(a.reshape(b, a.shape[1], -1, dh), pack)
                  for a in arrays)
    out = _flash_pass(heads, q_off, k_off, causal, bq, bk, interpret,
                      pack, sub, None, window)[0]
    return unpack_heads(out, pack, n_heads).reshape(b, t, n_heads * dh)


def flash_attention_rows(arrays, n_heads: int, causal: bool = False,
                         block_q: int | None = None,
                         block_k: int | None = None,
                         dot_dtype=None, interpret: bool = False,
                         mesh=None, spec=None, q_offset=None,
                         k_offset=None, sub_tile=None,
                         n_kv_heads: int | None = None,
                         window: int | None = None):
    """Fused flash attention in the projections' own layout:
    ``arrays`` is ``(qkv,)`` — ONE (B, T, 3·D) projection result whose
    column ranges are q, k, v — or ``(q, k, v)``, each (B, T, D); the
    result is (B, T, D) in their dtype, ready for the out-projection as
    (B·T, D).

    ``n_kv_heads`` < ``n_heads`` is grouped queries: k and v are
    (B, T, n_kv_heads·dh) — the fused array (B, T, (H + 2·H_kv)·dh) —
    and query head h reads K/V head h // (H / H_kv); nothing is
    repeated in memory, the kernels' index maps share the tiles, and
    dk/dv sum over a group inside the kernel.  ``window`` (causal
    only): row r attends to columns in (r − window, r]; the kernels'
    grids visit only the tiles a band touches and the three
    ``pallas_call``s are named ``znicz_flash_*_win``.  A window that
    covers the sequence is the causal call.  Heads are column blocks (module docstring, "Layout
    contract"): nothing is transposed, sliced or concatenated on the
    way in or out, forward or backward; a fused array's cotangent is
    ONE (B, T, 3·D) array.

    ``dot_dtype`` casts the operands (the tile-GEMM operand dtype —
    bf16 in the framework's mixed-precision mode); accumulation and
    softmax statistics are always f32.  Blocks must divide T (same
    contract as ``local_attention_blocked``).  Differentiable via the
    fused recompute backward — no (T, T) tensor ever reaches HBM in
    either direction.

    ``q_offset``/``k_offset`` place this call on the GLOBAL sequence
    axis for causal masking (the ring-hop geometry; may be traced
    scalars).  ``block_q``/``block_k`` name the grid tile and
    ``sub_tile`` the compute sub-tile inside it; left out, both come
    from the shapes (:func:`grid_blocks`, :func:`sub_tile_for`) —
    ``sub_tile`` is there for the tests, which put all three classes of
    sub-tile into tiles small enough to interpret.

    ``mesh``/``spec`` is the mesh-native path: ``spec`` is a
    (B, T, H, D) PartitionSpec (derive it with
    :func:`znicz_tpu.parallel.mesh.kernel_shard_spec`) and the kernel
    runs per-shard under ``shard_map`` — without it an opaque
    ``pallas_call`` has no GSPMD sharding rule, so a multi-device mesh
    would replicate-and-gather the operands onto every device.  Only
    batch-like dims may shard (batch over ``data``; the heads of
    separate q, k, v compose with TP the same way, as their column
    dim); sharding T is the ring's job and is rejected here, as is
    sharding the head dim.  Gradients flow through the shard_map (the
    custom_vjp backward runs per-shard — attention is independent per
    batch element and head, so no cross-shard reduction exists).
    """
    t, tk = arrays[0].shape[1], arrays[-1].shape[1]
    if window is not None and window >= tk:
        window = None
    if window is not None:
        if not causal or t != tk or q_offset is not None \
                or k_offset is not None or window < 1:
            raise ValueError(
                f"a window ({window}) needs a causal self-attention "
                f"call without global offsets")
        bq, bk = band_blocks(t, block_q, block_k)
        sub_tile = None
    else:
        bq, bk = grid_blocks(causal, t, tk, block_q, block_k)
    if n_kv_heads is not None and n_heads % n_kv_heads:
        raise ValueError(f"{n_heads} query heads do not divide over "
                         f"{n_kv_heads} K/V heads")
    if t % bq or tk % bk:
        raise ValueError(f"T {t}/{tk} not divisible by blocks "
                         f"({bq}, {bk})")
    if dot_dtype is not None:
        arrays = tuple(a.astype(dot_dtype) for a in arrays)
    if mesh is not None and spec is not None \
            and any(a is not None for a in spec):
        if spec[1] is not None or spec[3] is not None:
            raise ValueError(
                f"flash_attention shard spec {spec} shards T or the "
                f"head dim — only batch-like dims (batch, heads) may "
                f"shard; time sharding rides the ring path")
        if spec[2] is not None and len(arrays) == 1:
            raise ValueError(
                f"flash_attention shard spec {spec} shards the heads "
                f"of a fused projection, whose columns are q, k and v "
                f"in turn; pass q, k, v apart")
        if q_offset is not None or k_offset is not None:
            raise ValueError(
                "global offsets ride the ring path (per-shard hops), "
                "not the batch-sharded shard_map path")
        from jax.sharding import PartitionSpec as P
        rspec = P(spec[0], None, spec[2])   # (B, T, H, D) → (B, T, H·D)
        shards = 1 if spec[2] is None else mesh.shape[spec[2]]
        # check_vma off: an opaque pallas_call (and the custom_vjp
        # around it) has no replication rule for the checker
        fn = jax.shard_map(
            lambda *shard: _rows_pass(
                shard, n_heads // shards, _off_arr(None),
                _off_arr(None), causal, bq, bk, interpret, sub_tile,
                None if n_kv_heads is None else n_kv_heads // shards,
                window),
            mesh=mesh, in_specs=(rspec,) * len(arrays),
            out_specs=rspec, check_vma=False)
        return fn(*arrays)
    return _rows_pass(arrays, n_heads, _off_arr(q_offset),
                      _off_arr(k_offset), causal, bq, bk, interpret,
                      sub_tile, n_kv_heads, window)


def flash_attention(q, k, v, **kwargs):
    """:func:`flash_attention_rows` over (B, T, H, D) tensors →
    (B, T, H, D) f32: at the boundary the heads are adjacent to D, so
    both reshapes are free."""
    b, t, h, d = q.shape
    if k.shape[2] != h:
        kwargs["n_kv_heads"] = k.shape[2]
    out = flash_attention_rows(
        tuple(a.reshape(a.shape[0], a.shape[1], -1)
              for a in (q, k, v)), h, **kwargs)
    return out.reshape(b, t, h, d).astype(jnp.float32)


# ----------------------------------------------------------------------
# the plan: how the kernels run ONE unit's call, decided once
# ----------------------------------------------------------------------
class FlashPlan(NamedTuple):
    """What the choosers above make of one attention unit's call
    (:func:`plan`): whether the kernels run it and in which form.  The
    unit holds this ONE value; it says itself (:meth:`line`) and runs
    itself (:meth:`attend`).  ``refused``: why the kernels do not run
    the call (None: they do, and only then is anything from ``block_q``
    on set); ``window`` as the kernels see it (None where it covers T);
    ``sub_tile``, ``layout`` and ``head_pack``, ``forward``,
    ``backward`` (passes), ``tiles`` and ``band_share`` (without a
    window the causal half) are :func:`sub_tile_for`,
    :func:`head_layout`, :func:`forward_form`, :func:`backward_passes`,
    :func:`causal_tile_counts` and :func:`band_share` of the call;
    ``resident_dq``: the bytes of unfinished dq a one-pass backward
    keeps in VMEM from K tile to K tile (:func:`resident_dq_bytes`; 0
    under two passes and where the K side is one tile);
    ``mesh`` / ``spec``: per shard under ``shard_map``."""
    refused: str | None
    interpret: bool
    n_heads: int
    n_kv_heads: int
    causal: bool
    window: int | None
    block_q: int | None = None
    block_k: int | None = None
    sub_tile: tuple | None = None
    layout: str | None = None
    head_pack: int | None = None
    forward: ForwardForm | None = None
    backward: int | None = None
    resident_dq: int | None = None
    tiles: dict | None = None
    band_share: float | None = None
    mesh: object = None
    spec: object = None

    @property
    def runs(self) -> bool:
        return self.refused is None

    def attend(self, arrays, dot_dtype=None):
        """:func:`flash_attention_rows` over ``arrays`` as planned."""
        # the group and the window only where a layer has them: a layer
        # without keeps the call (and the program) it had
        more = {}
        if self.n_kv_heads != self.n_heads:
            more["n_kv_heads"] = self.n_kv_heads
        if self.window is not None:
            more["window"] = self.window
        return flash_attention_rows(
            arrays, self.n_heads, causal=self.causal,
            block_q=self.block_q, block_k=self.block_k,
            dot_dtype=dot_dtype, interpret=self.interpret, mesh=self.mesh,
            spec=self.spec, **more)

    def line(self) -> str:
        """The plan in one line, for the unit's log."""
        if not self.runs:
            return f"XLA attention core — {self.refused}"
        tiles, group = self.tiles, self.n_heads // self.n_kv_heads
        text = (
            "flash kernel, blocks (%d, %d), sub-tiles (%d, %d): %d "
            "interior + %d crossing of %d = %.4f of T×T executed, "
            "layout=%s, head pack %d, fwd_state: %s, fwd_stats: %s, "
            "fwd_scale: %s, backward passes %d" % (
                self.block_q, self.block_k, *self.sub_tile,
                tiles["interior"],
                tiles["crossing"] + tiles.get("band_edge", 0),
                sum(n for cls, n in tiles.items()
                    if cls != "executed_share"),
                tiles["executed_share"], self.layout, self.head_pack,
                *self.forward, self.backward))
        if self.resident_dq:
            text += " (%.2f MiB of dq wait in VMEM)" % (
                self.resident_dq / 2 ** 20)
        if group != 1 or self.window is not None:
            text += (", %d query heads to a K/V head, window %s (band "
                     "%.4f of T×T)" % (group, self.window,
                                       self.band_share))
        if self.mesh is not None:
            text += ", per shard under shard_map"
        return text + (", INTERPRETED" if self.interpret else "")


def plan(device, batch: int, t: int, n_heads: int, n_kv_heads: int,
         dh: int, causal: bool, window: int | None = None,
         flash_block_k: int | None = None, model_sharded: bool = False,
         ring: bool = False) -> FlashPlan:
    """The :class:`FlashPlan` of a self-attention call over (``batch``,
    ``t``) positions of ``n_heads`` query and ``n_kv_heads`` K/V heads
    of ``dh`` on ``device`` (and its mesh): the choosers above on what
    the call can see (module docstring: no option steers a tile, a
    layout or a form), and ``engine.flash_attention`` /
    ``pallas_interpret`` / ``pallas_shard_map`` resolved ONCE here, like
    every engine flag.  Default ON for real TPU devices (the measured
    winner at every T: PERF.md round 5 / SEQ_BENCH.json); shapes the
    tiling cannot cover fall back to the XLA cores, and where the
    ``ring`` owns the core (it folds its hops with the same kernels)
    the call is not the kernels'.  On a mesh they run per shard
    (:func:`flash_attention_rows`, ``mesh`` / ``spec``);
    ``engine.pallas_shard_map = False`` restores the conservative
    single-device gate — kernel off on meshes, the safe fallback."""
    from znicz_tpu.ops import pallas_kernels
    from znicz_tpu.parallel.mesh import kernel_shard_spec, spec_divides
    from znicz_tpu.utils.config import root
    engine = root.common.engine
    # interpret-mode lever: lets the virtual CPU mesh run the REAL
    # kernels (shard_map oracle tests / dryruns); never default
    interpret = bool(engine.get("pallas_interpret", False))
    # a window that covers the sequence is the causal call
    window = window if window is not None and window < t else None
    bq, bk = grid_blocks(causal, t, t, None, flash_block_k) \
        if window is None else band_blocks(t)
    mesh, shard = getattr(device, "mesh", None), (None, None)
    refused = pallas_kernels.kernel_refusal(device, "flash_attention",
                                            interpret)
    if refused is None and ring:
        refused = "the ring owns the core"
    elif refused is None and not kernel_legal(t, t, dh, bq, bk):
        refused = (f"T={t}, head dim {dh} do not tile by blocks "
                   f"({bq}, {bk})")
    elif refused is None and mesh is not None and mesh.size > 1:
        spec, _ = kernel_shard_spec(mesh, 4)
        if not engine.get("pallas_shard_map", True):
            refused = "engine.pallas_shard_map is off"
        elif model_sharded:
            refused = "the input is model-sharded"
        elif not spec_divides(mesh, (batch, t, n_heads, dh), spec):
            refused = (f"batch {batch} does not divide over mesh "
                       f"{dict(mesh.shape)}")
        else:
            shard = (mesh, spec)
    said = FlashPlan(refused, interpret, n_heads, n_kv_heads, causal,
                     window)
    if refused is not None:
        return said
    group = n_heads // n_kv_heads
    layout, head_pack = head_layout(n_heads, dh, group)
    sq, sk = sub_tile_for(causal, bq, bk) if window is None else (bq, bk)
    # what the backward's rule reads: per shard the same — a mesh
    # splits the batch, and the heads of a group stay together
    seen = (causal, t, bk, window, t, bq, group, head_pack * dh)
    passes = backward_passes(*seen)
    return said._replace(
        block_q=bq, block_k=bk, sub_tile=(sq, sk), layout=layout,
        head_pack=head_pack, forward=forward_form(t, bq, bk, dh, window),
        backward=passes,
        resident_dq=resident_dq_bytes(*seen) if passes == 1 else 0,
        tiles=causal_tile_counts(t, t, bq, bk, sq, sk, causal=causal,
                                 window=window),
        band_share=band_share(t, window),
        mesh=shard[0], spec=shard[1])

"""The expert layer's grouped matmuls as two Pallas kernels whose grids
follow the GROUPS (PERF.md §6, PR 34).

(M, K) rows lie in E contiguous groups, ``group_sizes`` rows each (a
traced value); group e multiplies the slab ``rhs[e]``:

``znicz_gmm``   rows × slabs → (M, N): the forward, and with
                ``transpose_rhs`` the row gradient (the slab's other
                dim contracted, same kernel).
``znicz_tgmm``  rowsᵀ × rows → (E, K, N): the weight gradient, one f32
                slab a group — and, beside it, Σ slab² in a few
                partial sums (what the update's anomaly guard reads).

Both walk the same list of VISITS (:func:`group_visits`): group by
group, the row tiles a group touches, first to last.  What that buys:

* A slab of ``znicz_gmm`` is read from HBM once per group (and column
  tile), not once per row tile: the kernel asks for it itself, into
  one of two VMEM buffers, when the group BEFORE it starts — a whole
  group's matmuls hide its way in, where the pipeline's own prefetch
  had one visit's.  The contraction is whole in one block, so nothing
  accumulates across grid steps.
* A tile that lies inside one group goes to the MXU as loaded and is
  stored plainly.  A tile that STRADDLES a group boundary is computed
  ``PART_ROWS`` (128) at a time, the parts that hold none of the
  group's rows not at all, and only there is anything masked:
  ``znicz_gmm`` selects the group's rows into the resident output
  tile, ``znicz_tgmm`` zeroes the other groups' rows of ONE operand,
  which zeroes their contribution.  So the row tile can be long (512:
  a finished tile's or slab's way out hides behind one visit) at a
  128-row tile's overwork.
* ``znicz_tgmm`` reads both operands as rows, where they lie, and
  contracts their row dim; its f32 result block stays resident over a
  group's visits and is written once per group.  At a group's LAST
  visit the finished block is squared and summed down its rows into a
  second, small result that stays resident over all the visits: the
  whole result's Σ x² costs a multiply-add an element of a block that
  is in VMEM anyway, where a reduction outside the kernel reads the
  (E, K, N) slab from HBM once more (PERF.md §6, PR 44).
* Rows past the last group (one chip's share of the pairs under a
  static capacity) are a last group of their own with no slab:
  ``znicz_gmm`` writes them zero, ``znicz_tgmm`` never visits them.

ONE rule picks the tiles from what a call can see (:func:`row_tile`,
:func:`gmm_tiles`, :func:`tgmm_tiles`: the rows, K, N, the operands'
width); every call passes the ``vmem_limit_bytes`` its blocks need
(:func:`gmm_vmem_bytes`, :func:`tgmm_vmem_bytes`).  The kernels are
not jitted here: ``ops.moe.grouped_matmul`` is the one jitted entry.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
#: the largest slab block (bytes, one buffer) the column tile may make
_SLAB_BLOCK = 8 << 20
#: room beside the blocks for what Mosaic keeps of its own
_VMEM_SLACK = 8 << 20


# ----------------------------------------------------------------------
# the visits
# ----------------------------------------------------------------------
def _tile_span(group_sizes, tm: int):
    """``(starts, ends, first tile, tiles touched)`` per group; an
    empty group touches none."""
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    touched = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    return starts, ends, first, touched


def visited_rows(group_sizes, tm: int):
    """Rows the visits of one call cover at row tile ``tm``: Σ over the
    non-empty groups of (last tile − first tile + 1) · tm — the real
    rows are ``group_sizes.sum()``; the rest is computed and masked
    away on the tiles that straddle a boundary."""
    return _tile_span(group_sizes, tm)[3].sum() * tm


def group_visits(group_sizes, rows: int, tm: int, *, tail: bool,
                 visit_empty: bool):
    """``((offsets, group of a visit, row tile of a visit, the group
    visited after a group, a group's buffer, the row tile to READ at a
    visit), visits)``.

    ``tail`` appends the rows past the last group as a group of their
    own (index E); ``visit_empty`` gives an empty group one visit (the
    weight gradient has its slab to zero).  The lists by visit are as
    long as the visits can get, ``rows / tm + groups − 1``; only the
    first ``visits`` are walked.  The last two are by group: the next
    group that has a visit (−1 after the last) and the parity of a
    group's place among those visited — which of two buffers holds
    its slab.  The tile to read is the visit's own, but for the tail's
    visits, which read nothing: they name the last tile that was
    read, so that nothing is fetched for them."""
    sizes = group_sizes.astype(jnp.int32)
    if tail:
        sizes = jnp.concatenate([sizes, (rows - sizes.sum())[None]])
    starts, ends, first, touched = _tile_span(sizes, tm)
    if visit_empty:
        touched = jnp.maximum(touched, 1)
    tiles_m = rows // tm
    groups = sizes.shape[0]
    visit_end = jnp.cumsum(touched)
    visit = jnp.arange(tiles_m + groups - 1, dtype=jnp.int32)
    group = jnp.minimum(
        (visit[:, None] >= visit_end[None, :]).sum(axis=1), groups - 1
    ).astype(jnp.int32)
    tile = first[group] + visit - (visit_end - touched)[group]
    tile = jnp.clip(tile, 0, tiles_m - 1).astype(jnp.int32)
    offsets = jnp.concatenate([starts[:1], ends]).astype(jnp.int32)
    visits = visit_end[-1]
    following = jnp.where(
        visit_end < visits,
        group[jnp.minimum(visit_end, visit.shape[0] - 1)], -1
    ).astype(jnp.int32)
    buffer = ((jnp.cumsum(touched > 0) - 1) % 2).astype(jnp.int32)
    read = tile
    if tail:
        before = jnp.maximum(visits - touched[-1] - 1, 0)
        read = jnp.where(group == groups - 1, tile[before], tile)
    return (offsets, group, tile, following, buffer, read), visits


# ----------------------------------------------------------------------
# the tile rule
# ----------------------------------------------------------------------
#: rows of a straddling tile computed at a time: the MXU's own height,
#: so a group's overwork is what a 128-row tile's would be
PART_ROWS = 128


#: the longest row tile
ROW_TILE = 512


def row_tile(rows: int) -> int:
    """The row tile of every kernel here: the longest of 512, 256 and
    128 rows that divides ``rows`` (512 rows or fewer are one tile).
    On the chip the visits want to be LONG — a slab's way in, a
    finished tile's or slab's way out hide behind one visit's matmuls
    — and a long tile costs no overwork, because a tile that straddles
    a group boundary is computed ``PART_ROWS`` at a time (PERF.md §6,
    PR 34: 128, 256, 512 and 1,024 rows at both cells' shapes)."""
    if rows <= ROW_TILE:
        return rows
    for tm in (ROW_TILE, ROW_TILE // 2):
        if rows % tm == 0:
            return tm
    return ROW_TILE // 4


def part_rows(tm: int) -> int:
    """The rows of a straddling tile computed at a time."""
    return PART_ROWS if tm % PART_ROWS == 0 else tm


def _column_tile(n: int, k: int, itemsize: int) -> int:
    """All ``n`` columns if a (k, n) slab block fits ``_SLAB_BLOCK``,
    else the largest 128-multiple divisor of ``n`` that does."""
    if k * n * itemsize <= _SLAB_BLOCK or n % _LANES:
        return n
    tn = n
    while tn > _LANES and (k * tn * itemsize > _SLAB_BLOCK or n % tn):
        tn -= _LANES
    return tn


def gmm_tiles(rows: int, k: int, n: int, itemsize: int = 2) -> tuple:
    """``(tm, tn)`` of ``znicz_gmm`` for (rows, k) × (groups, k, n)."""
    return row_tile(rows), _column_tile(n, k, itemsize)


def tgmm_tiles(rows: int, k: int, n: int) -> tuple:
    """``(tm, tk, tn)`` of ``znicz_tgmm`` for (rows, k)ᵀ × (rows, n):
    the whole (k, n) f32 slab as the result block, its longer side
    halved while it is over ``_SLAB_BLOCK``."""
    tk, tn = k, n
    while tk * tn * 4 > _SLAB_BLOCK and max(tk, tn) % (2 * _LANES) == 0:
        tk, tn = (tk // 2, tn) if tk >= tn else (tk, tn // 2)
    return row_tile(rows), tk, tn


def gmm_vmem_bytes(tm: int, tn: int, k: int, itemsize: int,
                   out_itemsize: int) -> int:
    """What ``znicz_gmm`` asks of VMEM: the row and result blocks
    double-buffered, the slab's two buffers, the f32 product and its
    selected copy, and slack."""
    blocks = tm * k * itemsize + k * tn * itemsize + tm * tn * out_itemsize
    return 2 * blocks + 2 * tm * tn * 4 + _VMEM_SLACK


def tgmm_vmem_bytes(tm: int, tk: int, tn: int, itemsize: int) -> int:
    """What ``znicz_tgmm`` asks of VMEM: two row blocks and the f32
    result block double-buffered, a transposed and a masked row tile,
    the f32 product, and slack."""
    blocks = tm * (tk + tn) * itemsize + tk * tn * 4
    return 2 * blocks + tm * (tk + tn) * itemsize + tk * tn * 4 \
        + _VMEM_SLACK


# ----------------------------------------------------------------------
# rows × slabs
# ----------------------------------------------------------------------
def _rows_of(start, end, row0, shape):
    """Which rows of a ``shape`` block at ``row0`` are in [start, end)."""
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return jnp.logical_and(rows >= start, rows < end)


def _meet(start, end, row0, rows: int):
    """Whether [start, end) has a row among the ``rows`` at ``row0``
    (an empty group has none anywhere)."""
    return jnp.maximum(start, row0) < jnp.minimum(end, row0 + rows)


def _gmm_kernel(offsets, groups, tiles, following, buffers, read, lhs,
                slabs, out, slab, arrived, *, tm: int, tn: int, sub: int,
                n_groups: int, transpose_rhs: bool):
    visit = pl.program_id(1)
    group = groups[visit]
    start, end = offsets[group], offsets[group + 1]
    row0 = tiles[visit] * tm
    inside = jnp.logical_and(start <= row0, end >= row0 + tm)
    real = group < n_groups
    held = buffers[group]
    column0 = pl.multiple_of(pl.program_id(0) * tn, tn)
    dims = (((1,), (1,) if transpose_rhs else (0,)), ((), ()))

    def fetch(g, into):
        """The DMA of group ``g``'s slab (this column tile of it) from
        HBM into buffer ``into``."""
        columns = pl.ds(column0, tn)
        source = slabs.at[g, columns, :] if transpose_rhs \
            else slabs.at[g, :, columns]
        return pltpu.make_async_copy(source, slab.at[into],
                                     arrived.at[into])

    @pl.when(jnp.logical_and(real, jnp.logical_or(
        visit == 0, groups[jnp.maximum(visit - 1, 0)] != group)))
    def _first_of_its_group():
        # a slab is read once per group: asked for when the group
        # BEFORE it starts, so that a whole group's matmuls hide its
        # way in; the walk's first has nobody to ask for it
        pl.when(visit == 0)(fetch(group, held).start)
        fetch(group, held).wait()
        after = following[group]

        @pl.when(jnp.logical_and(after >= 0, after < n_groups))
        def _ask_for_the_next():
            fetch(after, 1 - held).start()

    def product(rows):
        return jax.lax.dot_general(lhs[rows, :], slab[held], dims,
                                   preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(real, inside))
    def _whole():
        out[...] = product(slice(None)).astype(out.dtype)

    def part(lo: int):
        # ``sub`` rows of a straddling tile: this group's are selected
        # into the resident output tile, the others' stay
        rows = slice(lo, lo + sub)
        mine = _rows_of(start, end, row0 + lo, (sub, out.shape[1]))
        out[rows, :] = jax.lax.select(
            mine, product(rows), out[rows, :].astype(jnp.float32)
        ).astype(out.dtype)

    for lo in range(0, tm, sub):
        # a part none of whose rows are the group's is not computed
        pl.when(jnp.logical_and(
            jnp.logical_and(real, jnp.logical_not(inside)),
            _meet(start, end, row0 + lo, sub)))(functools.partial(part, lo))

    @pl.when(jnp.logical_not(real))
    def _tail():
        out[...] = jax.lax.select(
            _rows_of(start, end, row0, out.shape),
            jnp.zeros(out.shape, jnp.float32),
            out[...].astype(jnp.float32)).astype(out.dtype)


def znicz_gmm(lhs, rhs, group_sizes, *, transpose_rhs: bool = False,
              out_dtype=jnp.float32, tiles: tuple | None = None,
              sub: int | None = None, interpret: bool = False):
    """(M, K) rows × (E, K, N) slabs (``transpose_rhs``: (E, N, K)) →
    (M, N) ``out_dtype`` from an f32 accumulator; rows past the last
    group come back zero.  Both operands in one dtype; ``tiles`` =
    ``(tm, tn)``, else :func:`gmm_tiles`; ``tm`` divides M; a
    straddling tile is computed ``sub`` rows at a time."""
    m, k = lhs.shape
    n_groups = rhs.shape[0]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm, tn = tiles or gmm_tiles(m, k, n, lhs.dtype.itemsize)
    if m % tm or n % tn:
        raise ValueError(f"znicz_gmm: tiles {(tm, tn)} do not divide "
                         f"{(m, n)}")
    sub = sub or part_rows(tm)
    metadata, visits = group_visits(group_sizes, m, tm, tail=True,
                                    visit_empty=False)
    out_itemsize = jnp.dtype(out_dtype).itemsize
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, tn=tn, sub=sub,
                          n_groups=n_groups, transpose_rhs=transpose_rhs),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(metadata),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, v, *meta: (meta[5][v], 0)),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, v, *meta: (meta[2][v], j)),
            grid=(n // tn, visits),
            scratch_shapes=[
                pltpu.VMEM((2, tn, k) if transpose_rhs else (2, k, tn),
                           rhs.dtype),
                pltpu.SemaphoreType.DMA((2,))]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=gmm_vmem_bytes(
                tm, tn, k, lhs.dtype.itemsize, out_itemsize)),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(m * k * (n // tn) + n_groups * k * n)
            * lhs.dtype.itemsize + m * n * out_itemsize),
        interpret=interpret,
        name="znicz_gmm_t" if transpose_rhs else "znicz_gmm",
    )(*metadata, lhs, rhs)


# ----------------------------------------------------------------------
# rowsᵀ × rows
# ----------------------------------------------------------------------
#: rows of a finished block squared at a time: a register's sublanes
SQUARE_ROWS = 8


def _tgmm_kernel(offsets, groups, tiles, lhs, grad, out, squares, *,
                 tm: int, sub: int, mask_grad: bool):
    visit = pl.program_id(2)
    last_visit = pl.num_programs(2) - 1
    group = groups[visit]
    start, end = offsets[group], offsets[group + 1]
    row0 = tiles[visit] * tm
    inside = jnp.logical_and(start <= row0, end >= row0 + tm)
    dims = (((0,), (0,)), ((), ()))

    @pl.when(jnp.logical_or(
        visit == 0, groups[jnp.maximum(visit - 1, 0)] != group))
    def _first_of_its_group():
        out[...] = jnp.zeros(out.shape, out.dtype)

    @pl.when(visit == 0)
    def _first_of_all():
        squares[...] = jnp.zeros(squares.shape, squares.dtype)

    @pl.when(inside)
    def _whole():
        out[...] += jax.lax.dot_general(
            lhs[...], grad[...], dims, preferred_element_type=jnp.float32)

    def part(lo: int):
        # ``sub`` rows of a straddling tile, the other groups' rows of
        # ONE operand zeroed: their products are zero whatever the
        # other operand holds there
        a, b = lhs[lo:lo + sub, :], grad[lo:lo + sub, :]
        if mask_grad:
            b = jax.lax.select(_rows_of(start, end, row0 + lo, b.shape),
                               b, jnp.zeros(b.shape, b.dtype))
        else:
            a = jax.lax.select(_rows_of(start, end, row0 + lo, a.shape),
                               a, jnp.zeros(a.shape, a.dtype))
        out[...] += jax.lax.dot_general(
            a, b, dims, preferred_element_type=jnp.float32)

    for lo in range(0, tm, sub):
        # a part none of whose rows are the group's is not computed
        pl.when(jnp.logical_and(
            jnp.logical_not(inside),
            _meet(start, end, row0 + lo, sub)))(functools.partial(part, lo))

    @pl.when(jnp.logical_or(
        visit == last_visit,
        groups[jnp.minimum(visit + 1, last_visit)] != group))
    def _last_of_its_group():
        # the block is whole (an empty group's: zero): its squares,
        # summed down the rows, join the other groups' — strip by
        # strip into a running sum of a few registers: the vector
        # unit's two operations an element and nothing else (the whole
        # block squared at once spills every register it fills, 2.5
        # times the bundles: PERF.md §6, PR 44)
        rows = out.shape[0]
        strip = SQUARE_ROWS if rows % SQUARE_ROWS == 0 else rows
        total = jnp.zeros((strip, out.shape[1]), jnp.float32)
        for lo in range(0, rows, strip):
            part = out[lo:lo + strip, :]
            total += part * part
        squares[...] += jnp.sum(total, axis=0, keepdims=True)


def znicz_tgmm(lhs, grad, group_sizes, *, tiles: tuple | None = None,
               sub: int | None = None, interpret: bool = False):
    """(M, K) rows and (M, N) rows in the same E groups → ``(slabs,
    squares)``: the (E, K, N) f32 ``lhs[rows of e]ᵀ @ grad[rows of
    e]``, an empty group's slab zero, rows past the last group
    belonging to none; and (K / tk, N / tn, 1, tn) f32 partial sums of
    the slabs' squares, ``squares.sum() == (slabs ** 2).sum()`` up to
    the order of the additions — non-finite where any element of the
    slabs is.  A caller that wants no sum ignores a few kilobytes.
    ``tiles`` = ``(tm, tk, tn)``, else :func:`tgmm_tiles`; ``tm``
    divides M."""
    m, k = lhs.shape
    n = grad.shape[1]
    n_groups = group_sizes.shape[0]
    tm, tk, tn = tiles or tgmm_tiles(m, k, n)
    if m % tm or k % tk or n % tn:
        raise ValueError(f"znicz_tgmm: tiles {(tm, tk, tn)} do not "
                         f"divide {(m, k, n)}")
    sub = sub or part_rows(tm)
    metadata, visits = group_visits(group_sizes, m, tm, tail=False,
                                    visit_empty=True)
    metadata = metadata[:3]

    def lhs_index(i, j, v, offsets, groups, tiles_):
        return tiles_[v], i

    def grad_index(i, j, v, offsets, groups, tiles_):
        return tiles_[v], j

    def out_index(i, j, v, offsets, groups, tiles_):
        return groups[v], i, j

    def squares_index(i, j, v, offsets, groups, tiles_):
        return i, j, 0, 0

    itemsize = lhs.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm, sub=sub,
                          mask_grad=tn <= tk),
        out_shape=(
            jax.ShapeDtypeStruct((n_groups, k, n), jnp.float32),
            jax.ShapeDtypeStruct((k // tk, n // tn, 1, tn), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec((tm, tk), lhs_index),
                      pl.BlockSpec((tm, tn), grad_index)],
            out_specs=(pl.BlockSpec((None, tk, tn), out_index),
                       pl.BlockSpec((None, None, 1, tn), squares_index)),
            grid=(k // tk, n // tn, visits)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=tgmm_vmem_bytes(tm, tk, tn, itemsize)),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(m * k * (n // tn) + m * n * (k // tk))
            * itemsize + n_groups * k * n * 4),
        interpret=interpret, name="znicz_tgmm",
    )(*metadata, lhs, grad)

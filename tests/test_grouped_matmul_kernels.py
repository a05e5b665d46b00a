"""The expert layer's grouped-matmul kernels (``ops/pallas_gmm.py``,
PR 34) in interpret mode on the CPU: against a per-group dense
reference on the bf16-rounded operands — forward, row gradient
(``transpose_rhs``), weight gradient — over the group layouts that
exercise each branch of a visit (inside one group, straddling a
boundary, an empty group, rows past the last group); the bf16 store;
``jax.grad`` through ``grouped_matmul``; the weight gradient's sum of
squares beside it (PR 44) and its way out of the pullback; the tile
rule at the two cells' shapes; the visited / real row counts."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from znicz_tpu.ops import pallas_gmm
from znicz_tpu.ops.moe import grouped_matmul

M, K, N, E, TM = 64, 32, 48, 5, 16

#: rows per group of (M = 64 rows, row tile 16)
LAYOUTS = {
    "ragged_with_an_empty_group": [10, 0, 22, 16, 16],
    "groups_smaller_than_a_tile": [3, 5, 2, 50, 4],
    "every_group_ends_on_a_tile_edge": [16, 32, 0, 16, 0],
    "every_row_in_one_group": [0, 0, 64, 0, 0],
    "rows_past_the_last_group": [9, 0, 14, 3, 6],
    "no_row_in_any_group": [0, 0, 0, 0, 0],
}


def operands(seed: int = 0, m: int = M, k: int = K, n: int = N):
    keys = jax.random.split(jax.random.key(seed), 3)
    lhs = jax.random.normal(keys[0], (m, k), jnp.bfloat16)
    rhs = jax.random.normal(keys[1], (E, k, n), jnp.bfloat16)
    grad = jax.random.normal(keys[2], (m, n), jnp.bfloat16)
    return lhs, rhs, grad


def f32(a) -> np.ndarray:
    return np.asarray(a.astype(jnp.float32), np.float64)


def rows_by_slabs(lhs, rhs, sizes) -> np.ndarray:
    """Group by group; rows past the last group zero."""
    lhs, rhs = f32(lhs), f32(rhs)
    out, lo = np.zeros((lhs.shape[0], rhs.shape[2])), 0
    for e, size in enumerate(sizes):
        out[lo:lo + size] = lhs[lo:lo + size] @ rhs[e]
        lo += size
    return out


def slabs_of_rows(lhs, grad, sizes) -> np.ndarray:
    lhs, grad = f32(lhs), f32(grad)
    out, lo = np.zeros((len(sizes), lhs.shape[1], grad.shape[1])), 0
    for e, size in enumerate(sizes):
        out[e] = lhs[lo:lo + size].T @ grad[lo:lo + size]
        lo += size
    return out


@pytest.mark.parametrize("transpose_rhs", [False, True],
                         ids=["forward", "row_gradient"])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_gmm_against_the_per_group_reference(layout, transpose_rhs):
    sizes = LAYOUTS[layout]
    lhs, rhs, _ = operands()
    slabs = rhs.swapaxes(1, 2) if transpose_rhs else rhs
    group_sizes = jnp.asarray(sizes, jnp.int32)
    got = pallas_gmm.znicz_gmm(lhs, slabs, group_sizes,
                               transpose_rhs=transpose_rhs, tiles=(TM, N),
                               interpret=True)
    assert got.dtype == jnp.float32 and got.shape == (M, N)
    np.testing.assert_allclose(got, rows_by_slabs(lhs, rhs, sizes),
                               rtol=1e-5, atol=1e-5)
    assert not np.asarray(got[sum(sizes):]).any()     # exactly zero
    # tiles of two and of four parts: a straddling tile is computed
    # part by part, to the same bits
    for parts in (2, 4):
        by_parts = pallas_gmm.znicz_gmm(
            lhs, slabs, group_sizes, transpose_rhs=transpose_rhs,
            tiles=(parts * TM, N), sub=TM, interpret=True)
        np.testing.assert_array_equal(np.asarray(by_parts),
                                      np.asarray(got))


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_tgmm_against_the_per_group_reference(layout):
    sizes = LAYOUTS[layout]
    lhs, _, grad = operands()
    got, _ = pallas_gmm.znicz_tgmm(lhs, grad, jnp.asarray(sizes, jnp.int32),
                                   tiles=(TM, K, N), interpret=True)
    assert got.dtype == jnp.float32 and got.shape == (E, K, N)
    np.testing.assert_allclose(got, slabs_of_rows(lhs, grad, sizes),
                               rtol=1e-5, atol=1e-5)
    for e, size in enumerate(sizes):
        if not size:               # an empty group's slab: exactly zero
            assert not np.asarray(got[e]).any(), e
    # tiles of two and of four parts: a straddling tile is computed
    # part by part
    for parts in (2, 4):
        by_parts, _ = pallas_gmm.znicz_tgmm(
            lhs, grad, jnp.asarray(sizes, jnp.int32),
            tiles=(parts * TM, K, N), sub=TM, interpret=True)
        np.testing.assert_allclose(by_parts, got, rtol=1e-5, atol=1e-5)


#: ``znicz_tgmm`` masks the operand with the narrower block
#: (``mask_grad`` = tn <= tk): (k, n, tiles) of either orientation, the
#: second with two column tiles, so two partial results
ORIENTATIONS = {"lhs_masked": (K, N, (TM, K, N)),
                "grad_masked": (64, 64, (TM, 64, 32))}


@pytest.mark.parametrize("orientation", list(ORIENTATIONS))
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_tgmm_sums_the_squares_of_the_slabs_it_writes(layout,
                                                      orientation):
    """The second result against ``jnp.sum(slabs ** 2)`` of the first:
    groups that straddle row tiles, an empty group (its block is zeroed
    at its one visit and adds 0), rows past the last group (in no
    block), no rows at all; row tiles of one, two and four parts."""
    k, n, (tm, tk, tn) = ORIENTATIONS[orientation]
    lhs, _, grad = operands(4, k=k, n=n)
    sizes = jnp.asarray(LAYOUTS[layout], jnp.int32)
    for parts in (1, 2, 4):
        slabs, squares = pallas_gmm.znicz_tgmm(
            lhs, grad, sizes, tiles=(parts * tm, tk, tn), sub=tm,
            interpret=True)
        assert squares.dtype == jnp.float32
        assert squares.shape == (k // tk, n // tn, 1, tn)
        want = float(jnp.sum(slabs ** 2))
        assert float(squares.sum()) == pytest.approx(want, rel=1e-5)
        assert (want > 0) == bool(sum(LAYOUTS[layout]))


@pytest.mark.parametrize("orientation", list(ORIENTATIONS))
@pytest.mark.parametrize("planted", [np.nan, np.inf, -np.inf],
                         ids=["nan", "inf", "minus_inf"])
def test_a_non_finite_gradient_row_reads_a_non_finite_sum(planted,
                                                          orientation):
    """One element of one ``grad`` row: the slab of the row's group
    is non-finite, and so is the sum — what the update's guard reads
    in the slab's place."""
    k, n, tiles = ORIENTATIONS[orientation]
    lhs, _, grad = operands(5, k=k, n=n)
    sizes = jnp.asarray(LAYOUTS["ragged_with_an_empty_group"], jnp.int32)
    clean = pallas_gmm.znicz_tgmm(lhs, grad, sizes, tiles=tiles,
                                  interpret=True)[1]
    assert np.isfinite(float(clean.sum()))
    row = 12                  # in group 2, on a tile group 0 ends in
    slabs, squares = pallas_gmm.znicz_tgmm(
        lhs, grad.at[row, 3].set(planted), sizes, tiles=tiles,
        interpret=True)
    assert not np.isfinite(np.asarray(slabs[2])).all()
    assert not np.isfinite(float(squares.sum()))


@pytest.mark.parametrize("kernel", [True, False],
                         ids=["kernels_interpreted", "ragged_dot"])
def test_the_tap_of_grouped_matmul_brings_the_sum_out_of_the_pullback(
        kernel):
    """``grouped_matmul``'s ``tap`` enters no result; on the kernel
    path the pullback returns Σ (weight gradient)² in its place, on the
    ``ragged_dot`` path 0 — and the gradients are the untapped
    call's."""
    sizes = jnp.asarray(LAYOUTS["rows_past_the_last_group"], jnp.int32)
    lhs, rhs, grad = (a.astype(jnp.float32) for a in operands(6))
    tap = jnp.zeros((), jnp.float32)
    out, pullback = jax.vjp(
        lambda lhs, rhs, tap: grouped_matmul(lhs, rhs, sizes, kernel,
                                             kernel, tap=tap),
        lhs, rhs, tap)
    plain, plain_pullback = jax.vjp(
        lambda lhs, rhs: grouped_matmul(lhs, rhs, sizes, kernel, kernel),
        lhs, rhs)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(plain))
    d_lhs, d_rhs, tapped = pullback(grad)
    for got, want in zip((d_lhs, d_rhs), plain_pullback(grad)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert tapped.shape == () and tapped.dtype == jnp.float32
    if kernel:
        assert float(tapped) == pytest.approx(
            float(jnp.sum(d_rhs ** 2)), rel=1e-5)
    else:
        assert float(tapped) == 0.0


@pytest.mark.parametrize("kernel", ["gmm", "gmm_t", "tgmm"])
def test_two_tiles_along_the_contraction_and_the_columns(kernel):
    """K and N at two tiles each: the column tile of ``znicz_gmm``,
    both result tiles of ``znicz_tgmm``."""
    sizes = LAYOUTS["ragged_with_an_empty_group"]
    lhs, rhs, grad = operands(1, k=64, n=64)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    if kernel == "tgmm":
        got, _ = pallas_gmm.znicz_tgmm(lhs, grad, group_sizes,
                                       tiles=(TM, 32, 32), interpret=True)
        want = slabs_of_rows(lhs, grad, sizes)
    else:
        trans = kernel == "gmm_t"
        got = pallas_gmm.znicz_gmm(
            lhs, rhs.swapaxes(1, 2) if trans else rhs, group_sizes,
            transpose_rhs=trans, tiles=(TM, 32), interpret=True)
        want = rows_by_slabs(lhs, rhs, sizes)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_row_gradient_stored_in_bf16_is_the_f32_result_cast():
    """One rounding of the same f32 accumulator: bit-equal."""
    sizes = jnp.asarray(LAYOUTS["rows_past_the_last_group"], jnp.int32)
    grad, rhs, _ = operands(2)

    def run(out_dtype):
        return pallas_gmm.znicz_gmm(
            grad, rhs.swapaxes(1, 2), sizes, transpose_rhs=True,
            out_dtype=out_dtype, tiles=(TM, N), interpret=True)

    stored = run(jnp.bfloat16)
    assert stored.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(stored.astype(jnp.float32)),
        np.asarray(run(jnp.float32).astype(jnp.bfloat16)
                   .astype(jnp.float32)))


@pytest.mark.parametrize("layout", ["ragged_with_an_empty_group",
                                    "rows_past_the_last_group"])
def test_grad_through_grouped_matmul_equals_the_ragged_dot_paths(layout):
    """Forward, row gradient and weight gradient of the jitted entry,
    kernels interpreted against ``jax.lax.ragged_dot``; f32 rows, so
    the row gradient's store rounds nothing."""
    sizes = jnp.asarray(LAYOUTS[layout], jnp.int32)
    lhs, rhs, grad = (a.astype(jnp.float32) for a in operands(3))
    live = (jnp.arange(M) < sizes.sum())[:, None]
    lhs, grad = jnp.where(live, lhs, 0.0), jnp.where(live, grad, 0.0)

    def loss(kernel):
        def f(lhs, rhs):
            out = grouped_matmul(lhs, rhs, sizes, kernel, kernel)
            return (out * grad).sum(), out
        return jax.value_and_grad(f, argnums=(0, 1), has_aux=True)

    (_, got), got_grads = loss(True)(lhs, rhs)
    (_, want), want_grads = loss(False)(lhs, rhs)
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(got, want, atol=1e-5 * scale)
    for g, w in zip(got_grads, want_grads):
        assert g.dtype == w.dtype == jnp.float32
        np.testing.assert_allclose(g, w, rtol=1e-5,
                                   atol=1e-5 * float(jnp.abs(w).max()))


#: (rows, groups, model width, expert width) of the two expert cells
CELLS = {"olmoe_train_t4096": (32768, 64, 2048, 1024),
         "laguna_train_1of32": (5120, 8, 3072, 1024)}
#: what a v5e core has (128 MiB), less what the rest of a program keeps
VMEM_CEILING = 96 << 20


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_tile_rule_fits_its_own_vmem_limit_at_the_cells_shapes(cell):
    rows, groups, d, f = CELLS[cell]
    for k, n in ((d, f), (f, d)):
        for kk, nn, out_bytes in ((k, n, 4), (n, k, 2)):   # fwd, dlhs
            tm, tn = pallas_gmm.gmm_tiles(rows, kk, nn)
            assert rows % tm == 0 and nn % tn == 0 and tm % 16 == 0
            assert tn == nn or tn % 128 == 0
            blocks = 2 * (tm * kk * 2 + kk * tn * 2 + tm * tn * out_bytes)
            limit = pallas_gmm.gmm_vmem_bytes(tm, tn, kk, 2, out_bytes)
            assert blocks + tm * tn * 4 <= limit <= VMEM_CEILING
        tm, tk, tn = pallas_gmm.tgmm_tiles(rows, k, n)
        assert rows % tm == 0 and k % tk == 0 and n % tn == 0
        assert tk % 128 == 0 and tn % 128 == 0
        blocks = 2 * (tm * (tk + tn) * 2 + tk * tn * 4)
        limit = pallas_gmm.tgmm_vmem_bytes(tm, tk, tn, 2)
        assert blocks + tk * tn * 4 <= limit <= VMEM_CEILING
    assert pallas_gmm.row_tile(rows) \
        == pallas_gmm.gmm_tiles(rows, d, f)[0] \
        == pallas_gmm.tgmm_tiles(rows, d, f)[0]
    assert pallas_gmm.part_rows(pallas_gmm.row_tile(rows)) == 128
    assert groups * 128 <= rows    # a part is under a group's share


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_visited_and_real_rows_against_a_brute_force_count(layout):
    """The counter's arithmetic and the kernels' own list of visits
    say the same as a walk over every (group, tile) pair."""
    sizes = LAYOUTS[layout]
    touched, lo = 0, 0
    for size in sizes:
        rows = set(range(lo, lo + size))
        touched += sum(bool(rows & set(range(t * TM, (t + 1) * TM)))
                       for t in range(M // TM))
        lo += size
    group_sizes = jnp.asarray(sizes, jnp.int32)
    assert int(pallas_gmm.visited_rows(group_sizes, TM)) == touched * TM
    (offsets, group, tile, following, buffer, read), visits = \
        pallas_gmm.group_visits(group_sizes, M, TM, tail=False,
                                visit_empty=False)
    assert list(np.asarray(read)) == list(np.asarray(tile))
    assert int(visits) == touched
    assert list(np.asarray(offsets)) == [0] + list(np.cumsum(sizes))
    walked = list(zip(np.asarray(group)[:touched],
                      np.asarray(tile)[:touched]))
    assert walked == sorted(walked) and len(set(walked)) == touched
    # the slabs' schedule: each visited group names the next one, and
    # neighbours among them hold their slabs in different buffers
    visited = [e for e, size in enumerate(sizes) if size]
    for e, after in zip(visited, visited[1:] + [-1]):
        assert int(following[e]) == after
        assert after < 0 or int(buffer[e]) != int(buffer[after])
    # with the tail as a group of its own, every tile is visited, and
    # the tail's visits read the tile that was read last before them
    (_, group, tile, _, _, read), visits = pallas_gmm.group_visits(
        group_sizes, M, TM, tail=True, visit_empty=False)
    group, tile, read = (np.asarray(a)[:int(visits)]
                         for a in (group, tile, read))
    real = group < len(sizes)
    assert list(read[real]) == list(tile[real])
    assert set(read[~real]) <= {tile[real][-1] if real.any() else 0}
    tail = set(range(lo, M))
    assert int(visits) == touched + sum(
        bool(tail & set(range(t * TM, (t + 1) * TM)))
        for t in range(M // TM))


@pytest.mark.parametrize("kernel", [True, False],
                         ids=["kernels_interpreted", "ragged_dot"])
def test_the_layer_counts_what_its_kernels_grids_did(kernel):
    """``MoE`` keeps the visited and the real rows beside its routing
    totals on the device and publishes them when the epoch ends — from
    ``group_sizes`` alone, no read per step; a layer on the XLA path
    counts nothing and sets no series."""
    from znicz_tpu.backends import XLADevice
    from znicz_tpu.dummy import DummyUnit, DummyWorkflow
    from znicz_tpu.memory import Vector
    from znicz_tpu.observe import metrics as obs_metrics
    from znicz_tpu.ops import moe
    from znicz_tpu.utils.config import root
    # (conftest's ``fresh_state`` gives every test a pristine config)
    root.common.engine.pallas_interpret = kernel
    root.common.engine.moe_grouped_matmul = kernel
    wf = DummyWorkflow()
    x = np.random.default_rng(0).normal(0, 1, (2, 8, 16)).astype(np.float32)
    src = DummyUnit(wf, output=Vector(x, name="x"))
    unit = moe.MoE(wf, n_experts=8, top_k=2, width=12,
                   name=f"moe_grid_{kernel}")
    unit.link_attrs(src, ("input", "output"))
    unit.initialize(device=XLADevice())
    assert unit._gmm_kernel == kernel
    steps = 3
    for _ in range(steps):
        unit.run()
    unit.moe_stats.map_read()
    stats = np.array(unit.moe_stats.mem)
    unit.on_epoch_ended()
    rows = 2 * 8 * 2                       # N · k: one tile, one part
    family = obs_metrics.REGISTRY.get("znicz_moe_gmm_rows")
    mine = {stat: gauge.value for (name, stat), gauge in
            (family.items() if family else []) if name == unit.name}
    if not kernel:
        assert not stats[-2:].any() and not mine
        return
    groups = int((stats[:8] > 0).sum())    # the same rows every step
    assert stats[-1] == steps * rows
    assert stats[-2] == steps * groups * unit._gmm_row_tile
    assert mine == {"visited": groups * rows, "real": rows}

"""Fused flash-attention Pallas kernels vs the local_attention oracle.

Runs the REAL kernels in interpret mode on CPU (same pattern as
test_pallas_kernels.py): forward and every gradient must match the
plain-XLA oracle to float32 tolerance, causal and not, across block
geometries including partial diagonal tiles.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from znicz_tpu.ops.pallas_attention import flash_attention
from znicz_tpu.parallel.ring_attention import local_attention


def _rand(shape, seed):
    return jnp.asarray(np.random.default_rng(seed)
                       .normal(0, 1, shape).astype(np.float32))


def _split(x, edges):
    """The column ranges of ``x`` between consecutive ``edges``."""
    return tuple(x[..., lo:hi] for lo, hi in zip(edges[:-1], edges[1:]))


@pytest.fixture
def dq_budget(monkeypatch):
    """``dq_budget(n)`` sets ``pallas_attention.RESIDENT_DQ_VMEM`` for
    one test — 0 sends every call past one K tile back to the two-pass
    kernels.  The rule is asked while a backward is traced and JAX
    keeps what it traced, so the traces are dropped with every change
    of the constant, the one back included."""
    from znicz_tpu.ops import pallas_attention as pa

    def set_budget(n_bytes: int) -> None:
        monkeypatch.setattr(pa, "RESIDENT_DQ_VMEM", n_bytes)
        jax.clear_caches()
    yield set_budget
    jax.clear_caches()


#: (grid tile, compute sub-tile): ``None`` lets the chooser decide
#: (sub-tile = tile at these sizes); the explicit shapes put interior,
#: crossing and skipped sub-tiles inside ONE 128-wide grid tile
SCHEDULES = [((128, 128), None), ((256, 128), None), ((128, 256), None),
             ((128, 128), (32, 32)), ((128, 128), (64, 32)),
             ((256, 128), (32, 64))]


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("blocks,sub", SCHEDULES)
def test_flash_matches_oracle_fwd_and_grads(causal, blocks, sub):
    b, t, h, d = 2, 256, 4, 64
    q, k, v = (_rand((b, t, h, d), s) for s in (0, 1, 2))
    dy = _rand((b, t, h, d), 3)
    bq, bk = blocks
    kw = dict(causal=causal, block_q=bq, block_k=bk, interpret=True,
              sub_tile=sub)

    ref = local_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, **kw)
    np.testing.assert_allclose(out, ref, atol=2e-5)

    g_ref = jax.grad(
        lambda *a: jnp.vdot(local_attention(*a, causal=causal), dy),
        argnums=(0, 1, 2))(q, k, v)
    g_new = jax.grad(
        lambda *a: jnp.vdot(flash_attention(*a, **kw), dy),
        argnums=(0, 1, 2))(q, k, v)
    for name, a, b_ in zip("qkv", g_ref, g_new):
        np.testing.assert_allclose(b_, a, atol=5e-5,
                                   err_msg=f"grad d{name}")


def test_flash_bf16_operands_match_bf16_oracle_band():
    """dot_dtype=bf16 (the production mode): kernel vs the bf16-core
    oracle agree to bf16 resolution."""
    b, t, h, d = 2, 256, 4, 64
    q, k, v = (_rand((b, t, h, d), s) for s in (5, 6, 7))
    ref = local_attention(q, k, v, dot_dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, dot_dtype=jnp.bfloat16,
                          block_q=128, block_k=128, interpret=True)
    # both paths round operands to bf16; outputs agree to bf16 eps
    np.testing.assert_allclose(out, ref, atol=2e-2)
    # and the bf16 kernel tracks the f32 oracle within bf16 rounding
    f32 = local_attention(q, k, v)
    assert float(jnp.abs(out - f32).max()) < 5e-2


def test_flash_rejects_indivisible_t():
    q = _rand((1, 192, 2, 64), 0)
    with pytest.raises(ValueError):
        flash_attention(q, q, q, block_q=128, block_k=128,
                        interpret=True)


def _offset_oracle(q, k, v, q_off, k_off):
    """Plain-XLA attention masked by GLOBAL positions (the ring-hop
    geometry the offset kernels implement)."""
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    tq, tk = q.shape[1], k.shape[1]
    mask = (q_off + jnp.arange(tq)[:, None]) \
        >= (k_off + jnp.arange(tk)[None, :])
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def test_offsets_place_the_causal_diagonal_globally():
    """q_offset/k_offset: q rows [64:128] of a global sequence vs k
    cols [0:64] must reproduce the corresponding block of full causal
    attention (fully visible), and a diagonal-crossing geometry must
    match the global-position oracle on every visible row."""
    b, t, h, d = 2, 128, 2, 16
    q, k, v = (_rand((b, t, h, d), s) for s in (0, 1, 2))
    qs, ks, vs = q[:, 64:], k[:, :64], v[:, :64]
    ref = _offset_oracle(qs, ks, vs, 64, 0)
    out = flash_attention(qs, ks, vs, causal=True, block_q=16,
                          block_k=16, interpret=True, q_offset=64,
                          k_offset=0)
    np.testing.assert_allclose(out, ref, atol=2e-5)


#: (grid block, compute sub-tile) for the offset geometries: 16² tiles
#: as before, and 32² tiles walked in 8² and 16 × 8 sub-tiles so the
#: offset diagonal splits a tile into all three classes
OFFSET_SCHEDULES = [(16, None), (32, (8, 8)), (32, (16, 8))]


@pytest.mark.parametrize("block,sub", OFFSET_SCHEDULES)
def test_offsets_diagonal_mid_tile_and_masked_rows_fwd_and_grads(block,
                                                                 sub):
    """The hard offset geometry: q rows 8…71 vs k cols 40…103 — the
    diagonal crosses mid-tile AND rows 8…39 are FULLY masked (no
    visible key in this hop at all).  Masked rows must come out
    exactly 0 (hop weight 0 in the ring combination, not NaN), and
    every gradient must match the oracle on the visible rows."""
    b, t, h, d = 2, 64, 2, 16
    q, k, v = (_rand((b, t, h, d), s) for s in (3, 4, 5))
    q_off, k_off = 8, 40
    kw = dict(causal=True, block_q=block, block_k=block, interpret=True,
              q_offset=q_off, k_offset=k_off, sub_tile=sub)
    vis = (q_off + np.arange(t)) >= k_off
    ref = _offset_oracle(q, k, v, q_off, k_off)
    out = flash_attention(q, k, v, **kw)
    np.testing.assert_allclose(np.asarray(out)[:, vis],
                               np.asarray(ref)[:, vis], atol=2e-5)
    assert np.all(np.asarray(out)[:, ~vis] == 0.0)
    # grads against the oracle, cotangent zeroed on masked rows (the
    # oracle's all-masked softmax is garbage there by construction)
    dy = _rand(ref.shape, 6)
    dy = jnp.asarray(np.where(vis[None, :, None, None],
                              np.asarray(dy), 0.0))
    g_ref = jax.grad(
        lambda *a: jnp.vdot(_offset_oracle(*a, q_off, k_off), dy),
        argnums=(0, 1, 2))(q, k, v)
    g_new = jax.grad(
        lambda *a: jnp.vdot(flash_attention(*a, **kw), dy),
        argnums=(0, 1, 2))(q, k, v)
    for name, a, b_ in zip("qkv", g_ref, g_new):
        np.testing.assert_allclose(np.asarray(b_), np.asarray(a),
                                   atol=5e-5, err_msg=f"grad d{name}")


@pytest.mark.parametrize("block,sub", OFFSET_SCHEDULES)
def test_offsets_whole_hop_below_and_above_the_diagonal(block, sub):
    """The two ring hops that hold no diagonal.  Wholly BELOW it
    (q rows 64…127 vs k cols 0…63) every sub-tile is interior: the
    unmasked body alone must reproduce plain non-causal attention,
    forward and gradients.  Wholly ABOVE it (q rows 0…63 vs k cols
    64…127) nothing is visited: output exactly 0, gradients exactly 0,
    nothing NaN."""
    b, t, h, d = 2, 64, 2, 16
    q, k, v = (_rand((b, t, h, d), s) for s in (13, 14, 15))
    dy = _rand((b, t, h, d), 16)
    kw = dict(causal=True, block_q=block, block_k=block, interpret=True,
              sub_tile=sub)
    below = dict(kw, q_offset=64, k_offset=0)
    np.testing.assert_allclose(flash_attention(q, k, v, **below),
                               local_attention(q, k, v), atol=2e-5)
    g_ref = jax.grad(lambda *a: jnp.vdot(local_attention(*a), dy),
                     argnums=(0, 1, 2))(q, k, v)
    g_new = jax.grad(
        lambda *a: jnp.vdot(flash_attention(*a, **below), dy),
        argnums=(0, 1, 2))(q, k, v)
    for name, a, b_ in zip("qkv", g_ref, g_new):
        np.testing.assert_allclose(np.asarray(b_), np.asarray(a),
                                   atol=5e-5, err_msg=f"grad d{name}")
    above = dict(kw, q_offset=0, k_offset=64)
    assert np.all(np.asarray(flash_attention(q, k, v, **above)) == 0.0)
    g_new = jax.grad(
        lambda *a: jnp.vdot(flash_attention(*a, **above), dy),
        argnums=(0, 1, 2))(q, k, v)
    for g in g_new:
        assert np.all(np.asarray(g) == 0.0)


def _np_attention(q, k, v, causal):
    """The numpy oracle over (B, T, H, dh) float64: out and the three
    gradients of ``vdot(out, dy)`` written out by hand."""
    def run(dy):
        d = q.shape[-1]
        s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
        if causal:
            t = q.shape[1]
            s = np.where(np.tril(np.ones((t, t), bool)), s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out = np.einsum("bhqk,bkhd->bqhd", p, v)
        dv = np.einsum("bhqk,bqhd->bkhd", p, dy)
        dp = np.einsum("bqhd,bkhd->bhqk", dy, v)
        ds = p * (dp - (dp * p).sum(-1, keepdims=True)) / np.sqrt(d)
        return (out, np.einsum("bhqk,bkhd->bqhd", ds, k),
                np.einsum("bhqk,bqhd->bkhd", ds, q), dv)
    return run


#: (heads, head width) → the address and pack the shapes give: the
#: pair body at dh 64, one head a block at dh 128, and three shapes
#: with no lane-legal column block
LAYOUTS = {(4, 64): ("boundary", 2), (2, 128): ("boundary", 1),
           (4, 32): ("head_major", 1), (2, 96): ("head_major", 1),
           (3, 64): ("head_major", 1)}


@pytest.mark.parametrize("fused", [True, False],
                         ids=["one_array", "three_arrays"])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("h,dh", list(LAYOUTS))
def test_head_pack_matches_unpacked_fwd_and_grads(h, dh, causal, fused):
    """The boundary-layout entry against the numpy oracle, forward and
    every gradient: heads as column blocks of the projection (the pair
    body at dh 64 — exact per-head math), q / k / v as column offsets
    of ONE (B, T, 3D) array (whose cotangent is one array too) and as
    three arrays, and widths that fall to the head-major address."""
    from znicz_tpu.ops.pallas_attention import (flash_attention_rows,
                                                head_layout)
    assert head_layout(h, dh) == LAYOUTS[h, dh]
    b, t, d = 2, 128, h * dh
    qkv = np.random.default_rng(7).normal(0, 1, (b, t, 3 * d))
    dy = np.random.default_rng(8).normal(0, 1, (b, t, d))
    want = _np_attention(*(qkv[..., i * d:(i + 1) * d]
                           .reshape(b, t, h, dh) for i in range(3)),
                         causal)(dy.reshape(b, t, h, dh))
    kw = dict(causal=causal, block_q=64, block_k=32, interpret=True,
              sub_tile=(32, 32) if causal else None)
    dy32 = jnp.asarray(dy, jnp.float32)

    def loss(*arrays):
        return jnp.vdot(flash_attention_rows(arrays, h, **kw), dy32)

    x = jnp.asarray(qkv, jnp.float32)
    if fused:
        out = flash_attention_rows((x,), h, **kw)
        g = jax.grad(loss)(x)
        grads = [g[..., i * d:(i + 1) * d] for i in range(3)]
    else:
        parts = [x[..., i * d:(i + 1) * d] for i in range(3)]
        out = flash_attention_rows(tuple(parts), h, **kw)
        grads = jax.grad(loss, argnums=(0, 1, 2))(*parts)
    np.testing.assert_allclose(out, want[0].reshape(b, t, d), atol=2e-5)
    for name, got, ref in zip("qkv", grads, want[1:]):
        np.testing.assert_allclose(got, ref.reshape(b, t, d), atol=5e-5,
                                   err_msg=f"grad d{name}")


@pytest.mark.parametrize("n_heads,dh,want", [
    (8, 64, ("boundary", 2)),       # the LM cell: a pair per block
    (16, 128, ("boundary", 1)),     # OLMoE: a head is a block
    (4, 256, ("boundary", 1)),      # a multiple of the lanes
    (8, 32, ("head_major", 1)),     # four to a block outgrow VMEM
    (7, 64, ("head_major", 1)),     # odd head count: no pair
    (8, 96, ("head_major", 1)),     # neither multiple nor divisor
    (4, 192, ("head_major", 1)),
    (8, 4, ("head_major", 1)),      # sublane-illegal dh
])
def test_resolve_head_pack_rules(n_heads, dh, want):
    """The pack factor is what the shape says — a pair where two heads
    fill the 128 lanes and the head count is even, 1 at multiples of
    128 — and with it the address: no option enters."""
    from znicz_tpu.ops.pallas_attention import (head_layout,
                                                head_pack_for)
    assert head_layout(n_heads, dh) == want
    assert head_pack_for(n_heads, dh) == want[1]


def _brute_counts(t_q, t_k, sq, sk, q_off, k_off):
    """Classify every sub-tile from the boolean (t_q, t_k) mask."""
    mask = (q_off + np.arange(t_q)[:, None]) \
        >= (k_off + np.arange(t_k)[None, :])
    tiles = mask.reshape(t_q // sq, sq, t_k // sk, sk)
    seen = tiles.sum(axis=(1, 3))
    return {"interior": int((seen == sq * sk).sum()),
            "crossing": int(((seen > 0) & (seen < sq * sk)).sum()),
            "skipped": int((seen == 0).sum())}


@pytest.mark.parametrize("t_q,t_k,bq,bk,sq,sk,q_off,k_off", [
    (2048, 2048, 1024, 1024, 256, 256, 0, 0),      # the LM cell's shape
    (2048, 2048, 1024, 1024, 512, 512, 0, 0),
    (2048, 2048, 1024, 1024, 1024, 1024, 0, 0),    # what ran before
    (512, 512, 512, 512, 128, 256, 0, 0),
    (256, 256, 128, 128, 64, 32, 0, 0),
    (1024, 1024, 512, 512, 256, 256, 2048, 1024),  # ring hop, below
    (1024, 1024, 512, 512, 256, 256, 1024, 1024),  # ring hop, diagonal
    (64, 64, 32, 32, 16, 8, 8, 40),                # mid-tile offsets
    (1024, 1024, 1024, 1024, 256, 256, 0, 1024),   # ring hop, above
])
def test_causal_tile_counts_match_the_boolean_mask(t_q, t_k, bq, bk, sq,
                                                   sk, q_off, k_off):
    from znicz_tpu.ops.pallas_attention import causal_tile_counts
    got = causal_tile_counts(t_q, t_k, bq, bk, sq, sk, q_off, k_off)
    want = _brute_counts(t_q, t_k, sq, sk, q_off, k_off)
    share = got.pop("executed_share")
    assert got == want
    total = (t_q // sq) * (t_k // sk)
    assert share == (want["interior"] + want["crossing"]) / total


def test_causal_tile_counts_of_the_lm_cell():
    """T 2048 at 1024² grid tiles: sub-tiles of 256 give 28 interior,
    8 crossing, 28 skipped of 64 (0.5625 of the square); of 512, 6 + 4
    of 16 (0.625); the tile itself, 1 + 2 of 4 (0.75: what ran before
    the walk)."""
    from znicz_tpu.ops.pallas_attention import causal_tile_counts
    assert causal_tile_counts(2048, 2048, 1024, 1024, 256, 256) == {
        "interior": 28, "crossing": 8, "skipped": 28,
        "executed_share": 0.5625}
    assert causal_tile_counts(2048, 2048, 1024, 1024, 512, 512)[
        "executed_share"] == 0.625
    assert causal_tile_counts(2048, 2048, 1024, 1024, 1024, 1024)[
        "executed_share"] == 0.75
    with pytest.raises(ValueError):
        causal_tile_counts(2048, 2048, 1024, 1024, 384, 256)


@pytest.mark.parametrize("sq,sk", [(8, 8), (16, 8), (8, 16), (32, 32)])
def test_walk_bounds_classify_like_the_definition(sq, sk):
    """The loop bounds the kernels compute from scalars (first row −
    first column of the grid tile) name exactly the sub-tiles the
    definition does: interior = first row ≥ last column, skipped =
    last row < first column, crossing = the rest — for every offset,
    negative ones (hops above the diagonal) included."""
    from znicz_tpu.ops.pallas_attention import (_col_walk_bounds,
                                                _row_walk_bounds)
    bq = bk = 32
    for d in range(-80, 81):            # row0 + r - col0
        n_int, n_vis = (int(x) for x in
                        _row_walk_bounds(d, sq, sk, bk))
        for j in range(bk // sk):
            c = j * sk                  # this sub-tile: rows d…, cols c…
            interior, skipped = d >= c + sk - 1, d + sq - 1 < c
            assert (j < n_int) == interior, (d, j)
            assert (j >= n_vis) == skipped, (d, j)
        i_vis, i_int = (int(x) for x in
                        _col_walk_bounds(d, sq, sk, bq))   # col0+c-row0
        for i in range(bq // sq):
            r = i * sq                  # rows r…, cols d…
            interior, skipped = r >= d + sk - 1, r + sq - 1 < d
            assert (i >= i_int) == interior, (d, i)
            assert (i < i_vis) == skipped, (d, i)


def test_tile_schedule_is_derived_from_the_shapes():
    """The chooser that replaced ``causal_block_for`` and
    ``engine.flash_causal_block``: grid tile and compute sub-tile come
    from ``causal``, T and the caller's blocks alone.  Same rows as the
    old auto-pick test (T 512, 2048, 4096, 16384); dh and the head pack
    do not enter (the kernels compile for a described v5e at dh 64, at
    dh 128 and at pack 2 under the same rule; PERF.md §6, PR 24)."""
    from znicz_tpu.ops.pallas_attention import (causal_tile_counts,
                                                grid_blocks,
                                                sub_tile_for)

    def schedule(causal, t, **blocks):
        bq, bk = grid_blocks(causal, t, t, **blocks)
        return (bq, bk), sub_tile_for(causal, bq, bk)

    def share(t):
        (bq, bk), (sq, sk) = schedule(True, t)
        return causal_tile_counts(t, t, bq, bk, sq, sk)[
            "executed_share"]

    # T=2048, the LM cell: the K tile spans the sequence, 512² inside
    assert schedule(True, 2048) == ((1024, 2048), (512, 512))
    assert share(2048) == 0.625                 # 0.75 before the walk
    # small T: the tile is the sequence; at 512 nothing is left to cut
    assert schedule(True, 512) == ((512, 512), (512, 512))
    assert share(512) == 1.0
    assert schedule(True, 1024) == ((1024, 1024), (512, 512))
    assert share(1024) == 0.75
    # deep grids: 2048-long K tiles, the share falls towards a half
    assert schedule(True, 4096) == ((1024, 2048), (512, 512))
    assert share(4096) == 0.5625
    assert schedule(True, 16384) == ((1024, 2048), (512, 512))
    assert share(16384) == 0.515625
    # a T that 2048 does not tile keeps the 1024 K tile
    assert schedule(True, 3072) == ((1024, 1024), (512, 512))
    # non-causal: one body per 1024² tile, nothing skipped
    assert schedule(False, 2048) == ((1024, 1024), (1024, 1024))
    # a caller's own blocks are kept; the sub-tile shrinks where one
    # visit's score run would outgrow the scoped VMEM
    assert schedule(True, 2048, block_k=512) == ((1024, 512), (512, 512))
    assert schedule(True, 2048, block_q=2048) \
        == ((2048, 2048), (512, 256))
    # tiles too small to cut (the interpret-mode tests) stay whole
    assert schedule(True, 256, block_q=128, block_k=128) \
        == ((128, 128), (128, 128))
    assert sub_tile_for(True, 16, 16) == (16, 16)


# ----------------------------------------------------------------------
# the one-pass backward: dq accumulates in the dk/dv walk
# ----------------------------------------------------------------------
@pytest.mark.parametrize("causal,t_k,bk,window,group,want", [
    (True, 2048, 2048, None, 1, (2048, 1, 0)),  # attn_lm_train_t2048
    (True, 4096, 2048, None, 1, (4096, 1, 0)),  # olmoe_train_t4096 …
    (True, 4096, 2048, None, 6, (4096, 1, 0)),  # … Laguna's full layers:
                                                # K taken whole
    # past WHOLE_BLOCK_K: every dq tile of the group waits in VMEM
    (True, 8192, 2048, None, 1, (2048, 1, 4)),
    (True, 8192, 2048, None, 7, (2048, 1, 28)),
    (True, 16384, 2048, None, 7, (2048, 1, 56)),    # SmallThinker's NoPE
    (True, 32768, 2048, None, 1, (2048, 1, 16)),
    (True, 32768, 2048, None, 7, (2048, 2, 112)),   # past the budget
    (True, 3072, 1024, None, 1, (1024, 1, 1.5)),    # 2048 does not tile T
    (True, 4096, 1024, None, 1, (1024, 1, 2)),  # a caller's (the ring's)
    (True, 1024, 1024, None, 1, (1024, 1, 0)),
    # a window: a band's width of slots a query head
    (True, 4096, 512, 512, 9, (512, 1, 4.5)),   # Laguna's sliding layers
    (True, 16384, 512, 4096, 7, (512, 1, 15.75)),   # SmallThinker's RoPE
    (True, 8192, 512, 4096, 7, (512, 1, 15.75)),
    (True, 4096, 2048, 512, 1, (2048, 1, 1.25)),
    (False, 2048, 1024, None, 1, (1024, 2, 0)),     # non-causal
    (False, 1024, 1024, None, 1, (1024, 2, 0)),
])
def test_backward_tile_and_passes_of_the_cells(causal, t_k, bk, window,
                                               group, want):
    """The backward's K tile, its passes and the dq it keeps in VMEM
    (MiB) at the chooser's tiles, next to ``grid_blocks`` /
    ``sub_tile_for``: from ``causal``, T, the forward's K tile, the
    window, the query heads a K/V head and the 128 lanes of a head
    alone, against ONE constant."""
    from znicz_tpu.ops import pallas_attention as pa
    assert pa.RESIDENT_DQ_VMEM == 64 * 2 ** 20
    if window is None:
        assert pa.grid_blocks(causal, t_k, t_k, block_k=bk)[1] == bk
    resident = pa.resident_dq_bytes(causal, t_k, bk, window, group=group) \
        if causal else 0
    assert (pa.backward_block_k(causal, t_k, bk, window),
            pa.backward_passes(causal, t_k, bk, window, group=group),
            resident / 2 ** 20) == want


def _kernel_names(fn, *args) -> list:
    """The ``name`` of every ``pallas_call`` ``fn`` traces, in order."""
    return re.findall(r"name=(znicz_flash_\w+)",
                      str(jax.make_jaxpr(fn)(*args)))


#: (K tiles, causal, window, query heads per K/V head, the budget in
#: bytes of dq that may wait in VMEM — None: the module's) → passes over
#: the score tiles in the backward, and the kernels the program holds
PASSES = [
    (1, True, None, 1, None, 1),    # the LM cell: T 2048, a 2048 K tile
    (1, True, None, 6, None, 1),    # grouped queries change nothing
    (1, True, None, 6, 0, 1),       # … and one K tile keeps nothing
    (2, True, None, 1, None, 1),    # a caller's shorter K tiles: dq waits
    (2, True, None, 6, None, 1),
    (4, True, None, 1, None, 1),
    (4, True, None, 1, 4 * 16 * 16 * 4, 1),     # 4 tiles of (16, 16) f32
    (4, True, None, 1, 4 * 16 * 16 * 4 - 1, 2),  # a byte short: two calls
    (1, False, None, 1, None, 2),   # non-causal: the two kernels
    (1, True, 24, 1, None, 1),      # a window: a Q tile meets two K tiles
    (4, True, 24, 6, None, 1),
    (4, True, 24, 6, 0, 2),
    (1, True, 64, 6, None, 1),  # a window that covers T is the causal call
    (8, True, None, 7, None, 1),    # SmallThinker's full layer: 7 query
                                    # heads a K/V head over a deep K grid
    (8, True, None, 7, 0, 2),
    (8, True, 40, 7, None, 1),      # … and its band, six tiles wide here
    (8, True, 40, 7, 0, 2),
]


@pytest.mark.parametrize("nk,causal,window,group,budget,passes", PASSES)
def test_backward_passes_are_read_from_the_shapes(nk, causal, window,
                                                  group, budget, passes,
                                                  dq_budget):
    """The rule (``backward_passes``) and the program it gives: ONE
    ``znicz_flash_bwd`` (``_bwd_win`` under a window) where the call is
    causal and the dq tiles that wait for a later K tile fit the
    budget, else ``znicz_flash_dq`` + ``znicz_flash_dkv`` — no argument
    but the call's own shapes enters."""
    from znicz_tpu.ops import pallas_attention as pa
    if budget is not None:
        dq_budget(budget)
    t, dh, bk = 64, 16, 64 // nk
    live = window if window is not None and window < t else None
    assert pa.backward_passes(causal, t, bk, live, t, 16, group,
                              dh) == passes
    q = _rand((1, t, group, dh), 0)
    k = _rand((1, t, 1, dh), 1)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=16,
                               block_k=bk, interpret=True,
                               window=window).sum()

    names = _kernel_names(jax.grad(loss, argnums=(0, 1, 2)), q, k, k)
    win = "_win" if live is not None else ""
    assert names == [f"znicz_flash_fwd{win}"] + (
        [f"znicz_flash_bwd{win}"] if passes == 1 else
        [f"znicz_flash_dq{win}", f"znicz_flash_dkv{win}"])


#: shape → (query heads, K/V heads, dh, one fused array?): the pair body
#: with ONE cotangent array (the LM cell's form), dh 128 apart (OLMoE's),
#: six query heads on a K/V head apart (Laguna's) and fused
ONE_PASS = {
    "pair_fused": (4, 4, 64, True),
    "dh128_apart": (2, 2, 128, False),
    "gqa6_apart": (12, 2, 128, False),
    "gqa6_fused": (6, 1, 128, True),
    "gqa2_head_major": (4, 2, 32, False),
}


@pytest.mark.parametrize("shape", list(ONE_PASS))
def test_one_pass_backward_matches_the_core_and_the_two_kernels(
        shape, dq_budget):
    """dq, dk, dv of the one-pass call (K tile = T: two Q tiles walk it
    in 32² sub-tiles, interior, crossing and skipped) against the
    plain-XLA core; against the one-pass call under TWO K tiles, whose
    first Q tile's dq leaves at K tile 0 and whose second waits for K
    tile 1; and against the two-kernel call the same operands get there
    once nothing may wait — only the order of dq's f32 partial sums
    differs between the three."""
    from znicz_tpu.ops.pallas_attention import flash_attention_rows
    h, h_kv, dh, fused = ONE_PASS[shape]
    b, t, group = 2, 128, h // h_kv
    widths = [h * dh, h_kv * dh, h_kv * dh]
    edges = np.cumsum([0] + widths)
    x = _rand((b, t, edges[-1]), 21)
    dy = _rand((b, t, h * dh), 22)

    def core(x):
        q, k, v = (a.reshape(b, t, -1, dh) for a in _split(x, edges))
        k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
        return local_attention(q, k, v, causal=True).reshape(b, t, -1)

    def kernels(block_k):
        def run(x):
            return flash_attention_rows(
                (x,) if fused else _split(x, edges), h, causal=True,
                block_q=64, block_k=block_k, sub_tile=(32, 32), interpret=True,
                n_kv_heads=h_kv)
        return run

    def grad(fn):
        return jax.grad(lambda x: jnp.vdot(fn(x), dy))

    one, two = kernels(t), kernels(t // 2)
    for fn in (one, two):
        assert _kernel_names(grad(fn), x) == ["znicz_flash_fwd",
                                              "znicz_flash_bwd"]
    np.testing.assert_allclose(one(x), core(x), atol=2e-5)
    want, got, deep = grad(core)(x), grad(one)(x), grad(two)(x)
    dq_budget(0)
    assert _kernel_names(grad(two), x) == [
        "znicz_flash_fwd", "znicz_flash_dq", "znicz_flash_dkv"]
    apart = grad(two)(x)
    for name, (lo, hi) in zip(("dq", "dk", "dv"),
                              zip(edges[:-1], edges[1:])):
        np.testing.assert_allclose(got[..., lo:hi], want[..., lo:hi],
                                   atol=5e-5, err_msg=name)
        np.testing.assert_allclose(deep[..., lo:hi], want[..., lo:hi],
                                   atol=5e-5, err_msg=name + " / deep")
        np.testing.assert_allclose(got[..., lo:hi], apart[..., lo:hi],
                                   atol=1e-5, err_msg=name + " / two")
        np.testing.assert_allclose(deep[..., lo:hi], apart[..., lo:hi],
                                   atol=1e-5, err_msg=name + " / deep, two")
    # dk and dv add their Q tiles in ONE order in both forms
    np.testing.assert_array_equal(deep[..., edges[1]:],
                                  apart[..., edges[1]:])


#: case → (T, query heads, K/V heads, dh, (block_q, block_k), window,
#: one fused array?): the shapes past one K tile in small — SmallThinker's
#: full layer (seven query heads a K/V head over a deep K grid), its band
#: (nine tiles wide: the ring of slots wraps), Laguna's (two tiles wide,
#: nine query heads), each where a head is a column block and where it is
#: not, as three arrays and as ONE fused (B, T, 3·D) result, pairs of
#: dh-64 heads, and Q tiles longer and shorter than the K tiles
PAST_ONE_K_TILE = {
    "deep_gqa7_head_major": (128, 7, 1, 16, (16, 16), None, False),
    "deep_gqa7_boundary": (128, 7, 1, 128, (16, 32), None, False),
    "deep_gqa7_fused": (128, 7, 1, 128, (16, 32), None, True),
    "deep_pairs_fused": (128, 4, 4, 64, (32, 16), None, True),
    "band9_gqa7_head_major": (192, 7, 1, 16, (16, 16), 128, False),
    "band9_gqa7_boundary": (192, 7, 1, 128, (16, 16), 128, False),
    "band2_gqa9_boundary": (128, 9, 1, 128, (16, 16), 16, False),
    "band3_gqa3_fused": (128, 3, 1, 128, (16, 16), 24, True),
    "band_long_q_tiles": (128, 2, 1, 128, (32, 16), 40, False),
    "band_long_k_tiles": (128, 2, 1, 128, (16, 32), 40, False),
}


@pytest.mark.parametrize("case", list(PAST_ONE_K_TILE))
def test_one_pass_past_one_k_tile_gives_the_two_kernels_bits(case,
                                                             dq_budget):
    """The one-pass backward whose dq tiles wait in VMEM from K tile to
    K tile (``dq_slots``), one body per grid tile: every cotangent
    matches the plain oracle, and EQUALS the two-pass kernels' bit for
    bit — K tiles add into dq ascending and Q tiles into dk and dv
    ascending in both forms (PR 55)."""
    from tests.test_laguna_reference import oracle as _gqa_oracle
    from znicz_tpu.ops import pallas_attention as pa
    t, h, h_kv, dh, (bq, bk), window, fused = PAST_ONE_K_TILE[case]
    win = "" if window is None else "_win"
    widths = [h * dh, h_kv * dh, h_kv * dh]
    edges = np.cumsum([0] + widths)
    x = _rand((1, t, edges[-1]), 51)
    dy = _rand((1, t, h * dh), 52)

    def kernels(x):
        return pa.flash_attention_rows(
            (x,) if fused else _split(x, edges), h, causal=True, block_q=bq,
            block_k=bk, interpret=True, n_kv_heads=h_kv, window=window)

    def plain(x):
        q, k, v = (a.reshape(1, t, -1, dh) for a in _split(x, edges))
        return _gqa_oracle(q, k, v, window).reshape(1, t, -1)

    def grad(fn):
        return jax.grad(lambda x: jnp.vdot(fn(x), dy))

    slots = pa.dq_slots(t, t, bq, bk, window)
    assert slots == (t // bq if window is None
                     else pa.band_steps(t, bq, bk, window)[1]) > 1
    assert _kernel_names(grad(kernels), x) == [
        f"znicz_flash_fwd{win}", f"znicz_flash_bwd{win}"]
    one = grad(kernels)(x)
    np.testing.assert_allclose(one, grad(plain)(x), atol=2e-4, rtol=2e-4)
    dq_budget(0)
    assert _kernel_names(grad(kernels), x) == [
        f"znicz_flash_fwd{win}", f"znicz_flash_dq{win}",
        f"znicz_flash_dkv{win}"]
    np.testing.assert_array_equal(one, grad(kernels)(x))


@pytest.mark.parametrize("heads", [(1, 1), (2, 1)], ids=["mha", "gqa2"])
def test_backward_takes_a_4096_key_range_whole_at_the_choosers_tiles(
        heads):
    """T 4096 × dh 128 with NO block named (OLMoE's and Laguna's full
    layers' call): the forward walks two 2048-long K tiles, the
    backward takes the keys whole — one ``znicz_flash_bwd`` on four Q
    tiles of 1024 under (256, 512) sub-tiles — and every gradient
    matches the core."""
    h, h_kv = heads
    t, dh = 4096, 128
    q = _rand((1, t, h, dh), 41)
    k, v = _rand((1, t, h_kv, dh), 42), _rand((1, t, h_kv, dh), 43)
    dy = _rand((1, t, h, dh), 44)

    def core(q, k, v):
        k, v = (jnp.repeat(a, h // h_kv, axis=2) for a in (k, v))
        return jnp.vdot(local_attention(q, k, v, causal=True), dy)

    def kernels(q, k, v):
        return jnp.vdot(flash_attention(q, k, v, causal=True,
                                        interpret=True), dy)

    assert _kernel_names(jax.grad(kernels, (0, 1, 2)), q, k, v) \
        == ["znicz_flash_fwd", "znicz_flash_bwd"]
    for name, got, want in zip(("dq", "dk", "dv"),
                               jax.grad(kernels, (0, 1, 2))(q, k, v),
                               jax.grad(core, (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(got, want, atol=5e-5, err_msg=name)


def _hop_oracle(q, k, v, q_off, k_off):
    """(out, lse) of one head-major hop, masked by global position."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    mask = (q_off + jnp.arange(q.shape[2])[:, None]) \
        >= (k_off + jnp.arange(k.shape[2])[None, :])
    s = jnp.where(mask, s, -1e30)
    return (jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v),
            jax.nn.logsumexp(s, axis=-1))


@pytest.mark.parametrize("pack", [1, 2])
@pytest.mark.parametrize("sub", [None, (8, 8), (16, 8)])
def test_one_pass_hop_with_offsets_masked_rows_and_an_lse_cotangent(
        sub, pack, dq_budget):
    """The ring's hop through the one pass: q rows 8…71 against k
    columns 40…103 in ONE K tile — the first Q tile (rows 8…39) sees no
    key at all, the diagonal crosses the second mid-tile — with a
    cotangent on the hop's lse as the cross-hop combination sends one.
    Every gradient matches the oracle, the one-pass hop over TWO K
    tiles (the unseen Q tile's dq leaves at K tile 0 as the zeros it
    is, the other waits for K tile 1: both read from the offsets'
    scalars) and the two-kernel hop; the masked rows' dq is exactly
    0."""
    from znicz_tpu.ops.pallas_attention import ring_hop
    b, hp, t, dh = 2, 2, 64, 16
    q_off, k_off = 8, 40
    q, k, v = (_rand((b, hp, t, pack * dh), s) for s in (31, 32, 33))
    vis = (q_off + np.arange(t)) >= k_off
    rows = jnp.asarray(vis, jnp.float32)[None, None, :, None]
    dy = _rand((b, hp, t, pack * dh), 34) * rows
    dl = _rand((b, hp, t, pack), 35) * rows

    def heads(a):       # (B, Hp, T, pack·dh) → one head per program
        return a.reshape(b, hp, t, pack, dh).transpose(0, 1, 3, 2, 4) \
            .reshape(b, hp * pack, t, dh)

    def core(q, k, v):
        out, lse = _hop_oracle(heads(q), heads(k), heads(v), q_off, k_off)
        lse = jnp.where(rows > 0, lse.reshape(b, hp, pack, t)
                        .transpose(0, 1, 3, 2), 0.0)
        return jnp.vdot(out, heads(dy)) + jnp.vdot(lse, dl)

    def hop(block_k):
        def loss(q, k, v):
            out, lse = ring_hop(q, k, v, q_off, k_off, True, 32, block_k,
                                interpret=True, pack=pack, sub_tile=sub)
            return jnp.vdot(out, dy) \
                + jnp.vdot(jnp.where(rows > 0, lse, 0.0), dl)
        return loss

    for block_k in (t, t // 2):
        assert _kernel_names(jax.grad(hop(block_k), (0, 1, 2)), q, k, v) \
            == ["znicz_flash_fwd", "znicz_flash_bwd"]
    want = jax.grad(core, (0, 1, 2))(q, k, v)
    got = jax.grad(hop(t), (0, 1, 2))(q, k, v)
    deep = jax.grad(hop(t // 2), (0, 1, 2))(q, k, v)
    dq_budget(0)
    assert "znicz_flash_dq" in _kernel_names(
        jax.grad(hop(t // 2), (0, 1, 2)), q, k, v)
    apart = jax.grad(hop(t // 2), (0, 1, 2))(q, k, v)
    for name, a, d, w, two in zip(("dq", "dk", "dv"), got, deep, want,
                                  apart):
        np.testing.assert_allclose(a, w, atol=5e-5, err_msg=name)
        np.testing.assert_allclose(d, w, atol=5e-5, err_msg=name + " / deep")
        np.testing.assert_allclose(a, two, atol=1e-5,
                                   err_msg=name + " / two")
        np.testing.assert_allclose(d, two, atol=1e-5,
                                   err_msg=name + " / deep, two")
    for dq in (got[0], deep[0]):
        assert np.all(np.asarray(dq)[:, :, ~vis] == 0.0)


# ---- the forward's visit (PR 36): state, statistics, scale ------------
def _plain_heads(q, k, v, causal, window=None, q_off=0, k_off=0):
    """(out, lse) of (B, T, H, dh) q on (B, T_k, H_kv, dh) k, v in f32,
    masked by global position; a fully-masked row reads out 0 and
    lse −1e30."""
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    rows = q_off + jnp.arange(q.shape[1])[:, None]
    cols = k_off + jnp.arange(k.shape[1])[None, :]
    mask = jnp.ones_like(rows >= cols)
    if causal:
        mask = rows >= cols
    if window is not None:
        mask = mask & (cols > rows - window)
    s = jnp.where(mask, s, -1e30)
    seen = mask.any(axis=1)[None, None, :, None]
    p = jnp.where(seen, jax.nn.softmax(s, axis=-1), 0.0)
    return (jnp.einsum("bhqk,bkhd->bqhd", p, v),
            jnp.where(seen[..., 0], jax.nn.logsumexp(s, axis=-1), -1e30))


#: name → (T, query heads, K/V heads, dh, causal, grid tile, sub-tile,
#: window, fused operand, the form the chooser has to pick): tiles and
#: sub-tiles 128 lanes wide, so the lane-wise folds run as on the chip
FORWARD_CASES = {
    "one_k_tile_pairs": (256, 2, 2, 64, True, (128, 256), (128, 128),
                         None, True, ("none", "lanes", "q")),
    "one_k_tile_dh128": (256, 1, 1, 128, True, (128, 256), (128, 128),
                         None, False, ("none", "lanes", "exp")),
    "two_k_tiles_pairs": (512, 2, 2, 64, True, (256, 256), (128, 128),
                          None, True, ("carried", "lanes", "q")),
    "two_k_tiles_dh128": (512, 1, 1, 128, True, (256, 256), (128, 128),
                          None, False, ("carried", "lanes", "exp")),
    "grouped_4_on_2": (512, 4, 2, 128, True, (256, 256), (128, 128),
                       None, False, ("carried", "lanes", "exp")),
    "window_128": (512, 2, 1, 128, True, (128, 128), None, 128, False,
                   ("carried", "lanes", "exp")),
    "head_major_dh96": (256, 2, 2, 96, True, (128, 256), (128, 128),
                        None, True, ("none", "lanes", "exp")),
    "not_causal_two_k_tiles": (256, 2, 2, 64, False, (128, 128), None,
                               None, True, ("carried", "lanes", "q")),
}


def _forward_case(name):
    t, h, h_kv, dh, causal, blocks, sub, window, fused, _ = \
        FORWARD_CASES[name]
    q = _rand((1, t, h, dh), 50)
    k, v = (_rand((1, t, h_kv, dh), s) for s in (51, 52))
    kw = dict(causal=causal, block_q=blocks[0], block_k=blocks[1],
              sub_tile=sub, window=window, interpret=True,
              n_kv_heads=h_kv)

    def rows(q, k, v):
        flat = [a.reshape(1, t, -1) for a in (q, k, v)]
        if fused:
            flat = [jnp.concatenate(flat, axis=-1)]
        from znicz_tpu.ops.pallas_attention import flash_attention_rows
        return flash_attention_rows(tuple(flat), h, **kw) \
            .reshape(1, t, h, dh)
    return (q, k, v), rows, (causal, window)


@pytest.mark.parametrize("name", list(FORWARD_CASES))
def test_forward_form_o_and_lse_match_the_plain_reference(name,
                                                          monkeypatch):
    """Every form of the forward's visit — no state where a row block
    meets its keys in one visit, state carried over K tiles (and left
    out again for the Q tiles whose keys are all in the first),
    statistics lane-replicated, 1/√dh in q or in the exponential —
    gives the reference's ``o`` and the ``lse`` the backward reads."""
    from znicz_tpu.ops import pallas_attention as pa
    (q, k, v), rows, (causal, window) = _forward_case(name)
    seen = {}
    call = pa._fwd_call

    def spy(*args):
        seen["out"], seen["lse"] = call(*args)
        bq, bk, pack, cols, win = args[4], args[5], args[7], args[9], \
            args[10]
        width = args[0][0].shape[-1] if cols is None else cols[1]
        seen["form"] = tuple(pa.forward_form(q.shape[1], bq, bk,
                                             width // pack, win))
        return seen["out"], seen["lse"]

    monkeypatch.setattr(pa, "_fwd_call", spy)
    out = rows(q, k, v)
    want, want_lse = _plain_heads(q, k, v, causal, window)
    assert seen["form"] == FORWARD_CASES[name][-1]
    np.testing.assert_allclose(out, want, atol=2e-5)
    lse = seen["lse"]                   # (B, Hp, T, pack · _LANES)
    b, hp, t, lanes = lse.shape
    pack = lanes // pa._LANES
    per_head = lse.reshape(b, hp, t, pack, pa._LANES) \
        .transpose(0, 1, 3, 2, 4).reshape(b, hp * pack, t, pa._LANES)
    np.testing.assert_allclose(
        per_head, jnp.broadcast_to(want_lse[..., None], per_head.shape),
        atol=2e-5, rtol=1e-6)


@pytest.mark.parametrize("name", ["one_k_tile_pairs", "one_k_tile_dh128",
                                  "two_k_tiles_pairs", "grouped_4_on_2",
                                  "window_128", "head_major_dh96"])
def test_backward_fed_by_the_forwards_lse_matches_autodiff(name):
    """The backward recomputes p = exp(s − lse) from what the forward
    saved: with every form's ``lse`` the three gradients are
    ``jax.grad`` of the reference's."""
    (q, k, v), rows, (causal, window) = _forward_case(name)
    dy = _rand(q.shape, 53)
    want = jax.grad(lambda *a: jnp.vdot(
        _plain_heads(*a, causal, window)[0], dy), (0, 1, 2))(q, k, v)
    got = jax.grad(lambda *a: jnp.vdot(rows(*a), dy), (0, 1, 2))(q, k, v)
    for label, a, w in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(a, w, atol=5e-5, err_msg=label)


@pytest.mark.parametrize("pack,t_k,block_k", [(1, 256, 256), (2, 256, 256),
                                              (1, 512, 256), (2, 512, 256)])
def test_forward_hop_with_fully_masked_rows_in_every_form(pack, t_k,
                                                          block_k):
    """A ring hop whose first rows see no key (q rows 64…319 against k
    columns 192…): state-free (one K tile) and carried (two) alike keep
    m at −1e30 there — ``lse`` ≈ −1e30, ``o`` exactly 0, weight 0 in the
    cross-hop combination — and give the reference's values for the
    rows that see a key."""
    from znicz_tpu.ops.pallas_attention import ring_hop
    b, hp, t, dh = 1, 2, 256, 128 // pack
    q_off, k_off = 64, 192
    q = _rand((b, hp, t, pack * dh), 60)
    k, v = (_rand((b, hp, t_k, pack * dh), s) for s in (61, 62))
    out, lse = ring_hop(q, k, v, q_off, k_off, True, 128, block_k,
                        interpret=True, pack=pack, sub_tile=(128, 128))

    def heads(a):       # (B, Hp, T, pack·dh) → (B, T, Hp·pack, dh)
        return a.reshape(b, hp, a.shape[2], pack, dh) \
            .transpose(0, 2, 1, 3, 4).reshape(b, a.shape[2], hp * pack, dh)

    want, want_lse = _plain_heads(heads(q), heads(k), heads(v), True,
                                  None, q_off, k_off)
    hidden = (q_off + np.arange(t)) < k_off
    got = np.asarray(heads(out))
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert np.all(got[:, hidden] == 0.0)
    got_lse = np.asarray(lse).transpose(0, 1, 3, 2) \
        .reshape(b, hp * pack, t)
    np.testing.assert_allclose(got_lse[..., ~hidden],
                               np.asarray(want_lse)[..., ~hidden],
                               atol=2e-5, rtol=1e-6)
    assert np.all(got_lse[..., hidden] <= -0.99e30)


@pytest.mark.parametrize("shape,t_k,blocks,dh,window,want", [
    ("attn_lm_train_t2048", 2048, None, 64, None, ("none", "lanes", "q")),
    ("olmoe / ouro / hybrid", 4096, None, 128, None,
     ("carried", "lanes", "exp")),
    ("laguna's window", 4096, None, 128, 512,
     ("carried", "lanes", "exp")),
    ("T 1024", 1024, None, 64, None, ("none", "lanes", "q")),
    ("the ring's 1024-long K tile", 1024, (1024, 1024), 64, None,
     ("none", "lanes", "q")),
    ("a hop over two K tiles", 2048, (1024, 1024), 128, None,
     ("carried", "lanes", "exp")),
    ("dh 256", 2048, None, 256, None, ("none", "lanes", "q")),
    ("dh 96, head-major", 2048, None, 96, None, ("none", "lanes", "exp")),
])
def test_forward_form_is_read_from_the_shapes(shape, t_k, blocks, dh,
                                              window, want):
    """No option picks the forward's body: one K step means no state,
    and 1/√dh goes into q only where it is a power of two."""
    from znicz_tpu.ops import pallas_attention as pa
    if blocks is None:
        blocks = pa.grid_blocks(True, t_k, t_k) if window is None \
            else pa.band_blocks(t_k)
    if window is not None:
        assert blocks == (512, 512)
        assert pa.band_steps(t_k, *blocks, window)[0] == 2
    assert tuple(pa.forward_form(t_k, *blocks, dh, window)) == want


def test_unit_engages_flash_only_on_tpu(monkeypatch):
    """The default-on resolution: CPU devices never engage the kernel
    (is_tpu_device gates it), so the oracle tests above are the
    kernel's correctness story and the unit tests stay on XLA."""
    from znicz_tpu.ops import pallas_kernels

    class FakeDev:
        platform = "cpu"
        device_kind = "cpu"

    class D:
        jax_device = FakeDev()

    assert not pallas_kernels.is_tpu_device(D())
    FakeDev.platform = "tpu"
    assert pallas_kernels.is_tpu_device(D())
    # the platform decides, not a device_kind that merely names a TPU
    FakeDev.platform = "cpu"
    FakeDev.device_kind = "TPU v5 lite"
    assert not pallas_kernels.is_tpu_device(D())



# ----------------------------------------------------------------------
# the shapes of smallthinker_train_1of8 in small (PR 50): seven query
# heads a K/V head; an un-windowed causal call past WHOLE_BLOCK_K, so a
# deep K grid at the chooser's own tiles; a band NINE tiles wide.  Since
# PR 55 both backwards are one pass whose dq tiles wait in VMEM
# ----------------------------------------------------------------------
@pytest.mark.parametrize("case", ["causal_past_the_whole_key_range",
                                  "band_nine_tiles_wide"])
@pytest.mark.parametrize("dh", [16, 128], ids=["head_major", "boundary"])
def test_seven_queries_a_kv_head_at_the_long_cells_tilings(case, dh,
                                                           monkeypatch,
                                                           dq_budget):
    from tests.test_laguna_reference import oracle as _gqa_oracle
    from znicz_tpu.ops import pallas_attention as pa
    t, h, h_kv = 192, 7, 1
    seen = dict(t_q=t, group=h, width=dh)
    if case == "band_nine_tiles_wide":
        window = 128
        monkeypatch.setattr(pa, "BAND_BLOCK", 16)
        assert pa.band_blocks(t) == (16, 16)
        assert pa.band_steps(t, 16, 16, window) == (9, 9)
        # a ring of nine slots a query head, which twelve Q tiles share
        assert pa.dq_slots(t, t, 16, 16, window) == 9
        assert pa.resident_dq_bytes(True, t, 16, window, **seen) \
            == 7 * 9 * 16 * dh * 4
        assert pa.backward_passes(True, t, 16, window, **seen) == 1
        kernels = ["znicz_flash_fwd_win", "znicz_flash_bwd_win"]
        apart = ["znicz_flash_fwd_win", "znicz_flash_dq_win",
                 "znicz_flash_dkv_win"]
    else:
        # the chooser's own tiles, a key range it does not take whole
        window = None
        monkeypatch.setattr(pa, "BLOCK_Q", 32)
        monkeypatch.setattr(pa, "CAUSAL_BLOCK_K", 32)
        monkeypatch.setattr(pa, "WHOLE_BLOCK_K", 64)
        assert pa.grid_blocks(True, t, t) == (32, 32)
        assert pa.backward_block_k(True, t, 32) == 32
        # every Q tile of the seven heads waits
        assert pa.dq_slots(t, t, 32, 32) == 6
        assert pa.resident_dq_bytes(True, t, 32, **seen) \
            == 7 * 6 * 32 * dh * 4
        assert pa.backward_passes(True, t, 32, **seen) == 1
        kernels = ["znicz_flash_fwd", "znicz_flash_bwd"]
        apart = ["znicz_flash_fwd", "znicz_flash_dq", "znicz_flash_dkv"]
    q = _rand((1, t, h, dh), 1)
    k = _rand((1, t, h_kv, dh), 2)
    v = _rand((1, t, h_kv, dh), 3)
    weight = _rand((1, t, h, dh), 4)

    def kernel(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=True,
                               window=window)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * weight)

    assert _kernel_names(jax.grad(loss(kernel), (0, 1, 2)), q, k, v) \
        == kernels
    want = _gqa_oracle(q, k, v, window)
    np.testing.assert_allclose(kernel(q, k, v), want, atol=3e-5,
                               rtol=3e-5)
    g_want = jax.grad(loss(lambda *a: _gqa_oracle(*a, window)),
                      (0, 1, 2))(q, k, v)
    g_got = jax.grad(loss(kernel), (0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), g_got, g_want):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4,
                                   err_msg=name)
    # a byte short of what has to wait: the two kernels, the same bits
    dq_budget(pa.resident_dq_bytes(True, t, 32 if window is None else 16,
                                   window, **seen) - 1)
    assert _kernel_names(jax.grad(loss(kernel), (0, 1, 2)), q, k, v) \
        == apart
    for name, a, b in zip(("dq", "dk", "dv"), g_got,
                          jax.grad(loss(kernel), (0, 1, 2))(q, k, v)):
        np.testing.assert_array_equal(a, b, err_msg=name)


@pytest.mark.parametrize("t,overwork", [(16384, 1.125), (8192, 1.125)])
def test_a_band_of_4096_under_tiles_of_512_runs_an_eighth_over(t,
                                                                overwork):
    """What ``flash_band_overwork`` reads in ``smallthinker_train_1of8``:
    a row block past row 4,096 visits nine K tiles for a band of eight
    tiles' pairs, the first eight row blocks 36 tiles for 32 tiles' worth
    — where Laguna's band of 512 reads 2.0."""
    from znicz_tpu.ops import pallas_attention as pa
    assert pa.band_blocks(t) == (512, 512)
    assert pa.band_steps(t, 512, 512, 4096) == (9, 9)
    counts = pa.causal_tile_counts(t, t, 512, 512, 512, 512, window=4096)
    assert counts["executed_share"] / pa.band_share(t, 4096) \
        == pytest.approx(overwork, rel=2e-3)
    laguna = pa.causal_tile_counts(4096, 4096, 512, 512, 512, 512,
                                   window=512)
    assert laguna["executed_share"] / pa.band_share(4096, 512) \
        == pytest.approx(2.0, rel=2e-2)
    # seven of a row block's nine tiles lie wholly inside the band: no
    # mask code (the diagonal's and the lower edge's are masked)
    assert counts["interior"] > 3 * (counts["crossing"]
                                     + counts["band_edge"])

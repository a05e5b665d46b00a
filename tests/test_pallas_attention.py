"""Fused flash-attention Pallas kernels vs the local_attention oracle.

Runs the REAL kernels in interpret mode on CPU (same pattern as
test_pallas_kernels.py): forward and every gradient must match the
plain-XLA oracle to float32 tolerance, causal and not, across block
geometries including partial diagonal tiles.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from znicz_tpu.ops.pallas_attention import flash_attention
from znicz_tpu.parallel.ring_attention import local_attention


def _rand(shape, seed):
    return jnp.asarray(np.random.default_rng(seed)
                       .normal(0, 1, shape).astype(np.float32))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("blocks", [(128, 128), (256, 128), (128, 256)])
def test_flash_matches_oracle_fwd_and_grads(causal, blocks):
    b, t, h, d = 2, 256, 4, 64
    q, k, v = (_rand((b, t, h, d), s) for s in (0, 1, 2))
    dy = _rand((b, t, h, d), 3)
    bq, bk = blocks

    ref = local_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=bq,
                          block_k=bk, interpret=True)
    np.testing.assert_allclose(out, ref, atol=2e-5)

    g_ref = jax.grad(
        lambda *a: jnp.vdot(local_attention(*a, causal=causal), dy),
        argnums=(0, 1, 2))(q, k, v)
    g_new = jax.grad(
        lambda *a: jnp.vdot(flash_attention(
            *a, causal=causal, block_q=bq, block_k=bk,
            interpret=True), dy),
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


def test_offsets_diagonal_mid_tile_and_masked_rows_fwd_and_grads():
    """The hard offset geometry: q rows 8…71 vs k cols 40…103 — the
    diagonal crosses mid-tile AND rows 8…39 are FULLY masked (no
    visible key in this hop at all).  Masked rows must come out
    exactly 0 (hop weight 0 in the ring combination, not NaN), and
    every gradient must match the oracle on the visible rows."""
    b, t, h, d = 2, 64, 2, 16
    q, k, v = (_rand((b, t, h, d), s) for s in (3, 4, 5))
    q_off, k_off = 8, 40
    vis = (q_off + np.arange(t)) >= k_off
    ref = _offset_oracle(q, k, v, q_off, k_off)
    out = flash_attention(q, k, v, causal=True, block_q=16,
                          block_k=16, interpret=True, q_offset=q_off,
                          k_offset=k_off)
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
        lambda *a: jnp.vdot(flash_attention(
            *a, causal=True, block_q=16, block_k=16, interpret=True,
            q_offset=q_off, k_offset=k_off), dy),
        argnums=(0, 1, 2))(q, k, v)
    for name, a, b_ in zip("qkv", g_ref, g_new):
        np.testing.assert_allclose(np.asarray(b_), np.asarray(a),
                                   atol=5e-5, err_msg=f"grad d{name}")


@pytest.mark.parametrize("causal", [False, True])
def test_head_pack_matches_unpacked_fwd_and_grads(causal):
    """head_pack=2 (pairs of heads in one 128-lane program) is exact
    per-head math: must equal the unpacked kernel AND the oracle,
    forward and every gradient."""
    b, t, h, d = 2, 128, 4, 16
    q, k, v = (_rand((b, t, h, d), s) for s in (7, 8, 9))
    dy = _rand((b, t, h, d), 10)
    kw = dict(causal=causal, block_q=32, block_k=32, interpret=True)
    ref = local_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, head_pack=2, **kw)
    np.testing.assert_allclose(out, ref, atol=2e-5)
    np.testing.assert_allclose(out, flash_attention(q, k, v, **kw),
                               atol=2e-5)
    g_ref = jax.grad(
        lambda *a: jnp.vdot(local_attention(*a, causal=causal), dy),
        argnums=(0, 1, 2))(q, k, v)
    g_new = jax.grad(
        lambda *a: jnp.vdot(flash_attention(*a, head_pack=2, **kw),
                            dy),
        argnums=(0, 1, 2))(q, k, v)
    for name, a, b_ in zip("qkv", g_ref, g_new):
        np.testing.assert_allclose(np.asarray(b_), np.asarray(a),
                                   atol=5e-5, err_msg=f"grad d{name}")


def test_resolve_head_pack_rules():
    from znicz_tpu.ops.pallas_attention import resolve_head_pack
    assert resolve_head_pack(False, 8, 64) == 1     # gated off
    assert resolve_head_pack(True, 8, 64) == 2      # the dh=64 case
    assert resolve_head_pack(True, 7, 64) == 1      # odd head count
    assert resolve_head_pack(True, 8, 128) == 1     # already full-lane
    assert resolve_head_pack(True, 8, 4) == 1       # lane-illegal dh


def test_causal_block_autopick_deepens_small_t_grids():
    from znicz_tpu.ops.pallas_attention import causal_block_for
    # T=2048 at 1024² is a 2×2 grid (one skippable tile) → 512
    assert causal_block_for(2048, 1024, 1024) == (512, 512)
    assert causal_block_for(4096, 1024, 1024) == (1024, 1024)
    # already deep grids keep the chip-swept default
    assert causal_block_for(16384, 1024, 1024) == (1024, 1024)
    # the floor: never below 256
    assert causal_block_for(512, 1024, 1024) == (256, 256)


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


"""The round-6 ring KERNEL fold: each ring hop is one fused
flash-attention Pallas pass at its global offset
(`pallas_attention.ring_hop`), composed across hops by the online-
softmax (out, lse) algebra — kernel-rate sequence parallelism.

Everything runs the REAL kernels in interpret mode on the virtual
8-device CPU mesh (the test_pallas_attention pattern) and must equal
BOTH the scan-fold ring and the local oracle — forward and every
gradient, causal and not, including geometries where the causal
diagonal falls mid-ring (hops whose tiles the offset mask splits and
hops that are entirely above the diagonal, i.e. fully masked)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from znicz_tpu.parallel.ring_attention import (local_attention,
                                               make_seq_mesh,
                                               ring_fold_choice,
                                               sequence_sharded_attention)


def _rand(shape, seed):
    return jnp.asarray(np.random.default_rng(seed)
                       .normal(0, 1, shape).astype(np.float32))


def _assert_fold(mesh, shape, want, **kw):
    fold, _, _ = ring_fold_choice(mesh, shape, pallas_fold=True, **kw)
    assert fold == want, fold


@pytest.mark.slow
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("n_shards", [2, 8])
def test_kernel_fold_equals_scan_fold_and_oracle(causal, n_shards):
    """ring-with-kernel-fold ≡ ring-with-scan-fold ≡ local oracle,
    fwd + every grad.  With causal and n_shards devices, the hops
    below/above the diagonal exercise the fully-visible and
    fully-masked offset geometries; the local hop holds the
    diagonal."""
    mesh = make_seq_mesh(n_shards)
    B, T, H, D = 2, 16 * n_shards, 2, 8
    q, k, v = (_rand((B, T, H, D), s) for s in (1, 2, 3))
    _assert_fold(mesh, q.shape, "pallas")
    with jax.default_matmul_precision("highest"):
        ref = local_attention(q, k, v, causal=causal)
        scan = sequence_sharded_attention(mesh, q, k, v, causal=causal)
        got = sequence_sharded_attention(
            mesh, q, k, v, causal=causal, pallas_fold=True,
            pallas_interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(scan),
                                   rtol=2e-4, atol=2e-5)
        ct = _rand(ref.shape, 9)
        _, vjp_ref = jax.vjp(
            lambda *a: local_attention(*a, causal=causal), q, k, v)
        _, vjp_got = jax.vjp(
            lambda *a: sequence_sharded_attention(
                mesh, *a, causal=causal, pallas_fold=True,
                pallas_interpret=True), q, k, v)
        for name, gr, gg in zip("qkv", vjp_ref(ct), vjp_got(ct)):
            np.testing.assert_allclose(np.asarray(gg), np.asarray(gr),
                                       rtol=3e-4, atol=3e-4,
                                       err_msg=f"grad d{name}")


@pytest.mark.slow
@pytest.mark.parametrize("sub", [None, (4, 4), (8, 4)])
def test_kernel_fold_diagonal_mid_hop_tiles(sub, monkeypatch):
    """Kernel tiles SMALLER than the per-device shard: the causal
    diagonal crosses inside the local hop's tile grid (partial tiles)
    while remote hops run at pure offset geometry — the q_offset /
    k_offset case the scan fold gets for free.  With ``sub`` the
    kernels walk compute sub-tiles inside each 8² tile (the chooser is
    steered here, in the test: the ring has no parameter for it), so
    the local hop's diagonal tiles hold interior, crossing and skipped
    sub-tiles, the hops below the diagonal only interior ones and the
    hops above it none."""
    from znicz_tpu.ops import pallas_attention
    if sub is not None:
        monkeypatch.setattr(pallas_attention, "sub_tile_for",
                            lambda causal, *a: sub)
    mesh = make_seq_mesh(4)
    B, T, H, D = 1, 64, 2, 8           # t_local 16, tiles 8×8
    q, k, v = (_rand((B, T, H, D), s) for s in (4, 5, 6))
    with jax.default_matmul_precision("highest"):
        ref = local_attention(q, k, v, causal=True)
        got = sequence_sharded_attention(
            mesh, q, k, v, causal=True, pallas_fold=True,
            pallas_interpret=True, pallas_block_q=8, block_k=8)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)
        ct = _rand(ref.shape, 7)
        _, vjp_ref = jax.vjp(
            lambda *a: local_attention(*a, causal=True), q, k, v)
        _, vjp_got = jax.vjp(
            lambda *a: sequence_sharded_attention(
                mesh, *a, causal=True, pallas_fold=True,
                pallas_interpret=True, pallas_block_q=8, block_k=8),
            q, k, v)
        for name, gr, gg in zip("qkv", vjp_ref(ct), vjp_got(ct)):
            np.testing.assert_allclose(np.asarray(gg), np.asarray(gr),
                                       rtol=3e-4, atol=3e-4,
                                       err_msg=f"grad d{name}")


@pytest.mark.slow
@pytest.mark.parametrize("causal", [False, True])
def test_kernel_fold_head_packed(causal):
    """Head packing through the ring, as the shapes give it (pairs of
    dh-64 heads, ``pallas_attention.head_pack_for``): two heads in one
    128-lane kernel program per hop, head-major around the ring, exact
    per-head math — fwd + grads."""
    from znicz_tpu.ops.pallas_attention import head_pack_for
    mesh = make_seq_mesh(4)
    B, T, H, D = 2, 64, 4, 64
    assert head_pack_for(H, D) == 2
    q, k, v = (_rand((B, T, H, D), s) for s in (7, 8, 9))
    with jax.default_matmul_precision("highest"):
        ref = local_attention(q, k, v, causal=causal)
        got = sequence_sharded_attention(
            mesh, q, k, v, causal=causal, pallas_fold=True,
            pallas_interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)
        ct = _rand(ref.shape, 10)
        _, vjp_ref = jax.vjp(
            lambda *a: local_attention(*a, causal=causal), q, k, v)
        _, vjp_got = jax.vjp(
            lambda *a: sequence_sharded_attention(
                mesh, *a, causal=causal, pallas_fold=True,
                pallas_interpret=True), q, k, v)
        for name, gr, gg in zip("qkv", vjp_ref(ct), vjp_got(ct)):
            np.testing.assert_allclose(np.asarray(gg), np.asarray(gr),
                                       rtol=3e-4, atol=3e-4,
                                       err_msg=f"grad d{name}")


def test_kernel_fold_on_data_model_mesh():
    """DP × SP: batch over data, time around the model-axis ring,
    hops folding through the kernel — the composition the dryrun
    trains."""
    from znicz_tpu.parallel import make_mesh
    from znicz_tpu.parallel.axis import MODEL_AXIS
    mesh = make_mesh(n_data=2, n_model=4)
    B, T, H, D = 4, 32, 2, 8
    q, k, v = (_rand((B, T, H, D), s) for s in (11, 12, 13))
    with jax.default_matmul_precision("highest"):
        ref = local_attention(q, k, v, causal=True)
        got = sequence_sharded_attention(
            mesh, q, k, v, causal=True, axis_name=MODEL_AXIS,
            pallas_fold=True, pallas_interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)


def test_illegal_shapes_fall_back_to_scan_fold():
    """The scan fold survives as the gated fallback: lane-illegal
    head dims (dh % 8) and indivisible tilings silently keep the old
    fold — same philosophy as the unit gates."""
    mesh = make_seq_mesh(2)
    _assert_fold(mesh, (2, 32, 2, 4), "scan")      # dh = 4
    _assert_fold(mesh, (2, 12, 2, 8), "scan")      # t_local = 6
    _assert_fold(mesh, (2, 32, 2, 8), "pallas")
    # an odd head count at dh 64 keeps one head per program: legal
    _assert_fold(mesh, (2, 32, 3, 64), "pallas")
    _assert_fold(mesh, (2, 32, 3, 4), "scan")
    q = _rand((2, 32, 2, 4), 1)
    ref = local_attention(q, q, q, causal=True)
    got = sequence_sharded_attention(mesh, q, q, q, causal=True,
                                     pallas_fold=True,
                                     pallas_interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)

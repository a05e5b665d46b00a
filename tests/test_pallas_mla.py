"""The two-width flash kernels of ``ops/pallas_mla.py`` alone, at the
depth a long context gives them (PR 52): a K grid EIGHT tiles deep at a
toy tile in interpret mode — the forward's online softmax over eight K
tiles, ``dq`` accumulated over eight K tiles a Q tile, ``dk_nope`` /
``dv`` / ``dk_r`` over eight Q tiles a K tile and ``dk_r`` summed over
the pairs outside — against ``latent_attention_plain`` (K assembled in
full): the values and all five cotangents; the statistics' layout
(``_STAT`` lanes a head, not a head's 128); the cotangents' dtype
(their operand's: no (B, T, H·128) array is f32 in HBM under bf16
operands).  ``tests/test_ling_reference.py`` holds the same kernels at
a 2 × 2 walk; ``tests/test_integrity.py`` compiles them through Mosaic
for a described v5e at T 16,384."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from znicz_tpu.ops import attention, pallas_mla

HEADS, TILE = 4, 128
OUTPUTS = ["o", "dq_nope", "dq_rope", "dk_nope",
           "dk_rope_summed_over_pairs", "dv"]


def rel(got, want) -> float:
    want = np.asarray(want, np.float32)
    return float(np.abs(np.asarray(got, np.float32) - want).max()
                 / (np.abs(want).max() + 1e-30))


def _rows(t: int, dtype):
    rng = np.random.default_rng(52)

    def draw(width):
        return jnp.asarray(rng.normal(size=(1, t, width)) * 0.3, dtype)
    return (draw(HEADS * 128), draw(HEADS * 64), draw(HEADS * 128),
            draw(64), draw(HEADS * 128)), draw(HEADS * 128)


def _both(rule, rows, weight):
    def run(*args):
        with jax.default_matmul_precision("highest"):
            return rule(*args), jax.grad(
                lambda *a: jnp.sum(rule(*a).astype(jnp.float32)
                                   * weight.astype(jnp.float32)),
                (0, 1, 2, 3, 4))(*args)
    o, grads = jax.jit(run)(*rows)
    return (o,) + tuple(grads)


def _kernels(rows, weight, monkeypatch):
    monkeypatch.setattr(pallas_mla, "BLOCK", TILE)
    return _both(lambda *a: pallas_mla.latent_flash_attention(
        *a, interpret=True), rows, weight)


@pytest.fixture(scope="module")
def deep_calls():
    """T = 8 tiles (and 9: a length that is whole tiles but no power of
    two), f32: kernels and plain core, o and the five cotangents."""
    out = {}
    patch = pytest.MonkeyPatch()
    try:
        for tiles in (8, 9):
            rows, weight = _rows(tiles * TILE, jnp.float32)
            out[tiles] = (
                _kernels(rows, weight, patch),
                _both(lambda *a: attention.latent_attention_plain(
                    *a, HEADS), rows, weight))
    finally:
        patch.undo()
    return out


@pytest.mark.parametrize("tiles", [8, 9])
@pytest.mark.parametrize("which", range(6), ids=OUTPUTS)
def test_a_k_grid_eight_tiles_deep_against_the_plain_core(deep_calls,
                                                          tiles, which):
    kernels, plain = deep_calls[tiles]
    assert kernels[which].shape == plain[which].shape
    assert rel(kernels[which], plain[which]) < 2e-5
    # the shared key's cotangent is ONE key's, whatever the pairs
    assert kernels[4].shape == (1, tiles * TILE, 64)


def test_the_statistics_take_stat_lanes_a_head(monkeypatch):
    """``lse`` lies (B, pairs, T, 2 · _STAT) — 8 lanes a head, the
    value repeated over them — and is the log-sum-exp of the plain
    scores."""
    monkeypatch.setattr(pallas_mla, "BLOCK", TILE)
    t = 4 * TILE
    (qn, qr, kn, kr, v), _ = _rows(t, jnp.float32)
    o, lse = pallas_mla._forward(qn, qr, kn, kr, v, True)
    assert pallas_mla._STAT == 8
    assert lse.shape == (1, HEADS // 2, t, 2 * pallas_mla._STAT)
    assert lse.dtype == jnp.float32
    lse = np.asarray(lse).reshape(HEADS // 2, t, 2, pallas_mla._STAT)
    assert (lse == lse[..., :1]).all()
    q = np.concatenate([np.asarray(qn).reshape(t, HEADS, 128),
                        np.asarray(qr).reshape(t, HEADS, 64)], -1)
    k = np.concatenate([np.asarray(kn).reshape(t, HEADS, 128),
                        np.broadcast_to(np.asarray(kr)[0, :, None, :],
                                        (t, HEADS, 64))], -1)
    s = np.einsum("qhd,khd->hqk", q, k).astype(np.float64)
    s = np.where(np.tril(np.ones((t, t), bool))[None], s, -np.inf)
    want = np.log(np.exp(s - s.max(-1, keepdims=True)).sum(-1)) \
        + s.max(-1)
    got = lse[..., 0].transpose(0, 2, 1).reshape(HEADS, t)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("which", range(6), ids=OUTPUTS)
def test_bf16_operands_get_bf16_cotangents(which, monkeypatch):
    """Under bf16 operands every per-head cotangent leaves its kernel
    in bf16 (the f32 accumulator cast at the one write) and is the f32
    call's within bf16's rounding."""
    t = 8 * TILE
    rows32, weight = _rows(t, jnp.float32)
    rows16 = tuple(a.astype(jnp.bfloat16) for a in rows32)
    monkeypatch.setattr(pallas_mla, "BLOCK", TILE)
    raw = pallas_mla._backward(
        *rows16, *pallas_mla._forward(*rows16, True),
        weight.astype(jnp.bfloat16), True)
    for grad, name in zip(raw, OUTPUTS[1:]):
        want = jnp.float32 if name.startswith("dk_rope") else jnp.bfloat16
        assert grad.dtype == want, name
    got = _kernels(rows16, weight, monkeypatch)
    exact = _both(lambda *a: attention.latent_attention_plain(*a, HEADS),
                  tuple(a.astype(jnp.float32) for a in rows16), weight)
    assert got[which].dtype == jnp.bfloat16
    assert rel(got[which], exact[which]) < 3e-2


def test_what_tiles_at_a_long_context():
    assert pallas_mla.kernel_legal(16384, 32, 128, 64, 128)
    assert pallas_mla.kernel_legal(8192, 32, 128, 64, 128)
    assert not pallas_mla.kernel_legal(16384 + 256, 32, 128, 64, 128)

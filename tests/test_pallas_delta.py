"""The gated delta rule in its chunked form (``ops/pallas_delta.py``,
PR 31) against something plain: the plain scan AND the
``znicz_delta_state_*`` kernels in interpret mode against the
token-by-token recurrence, for d_k ≠ d_v, α near 0 and near 1, β near 0
and near 2 — the output and the gradient of q, k, v, log α and β; the
kernels against the plain walk chunk by chunk; the blocked inverse
against ``solve_triangular``.  (The unit around it:
``tests/test_delta_net.py``.)

Every comparison is ONE compiled program per path (op by op the same
arithmetic is hundreds of small compilations), and the file stays
under a dozen cases: ``--dist loadfile`` starts files largest first,
so a small file starts after the files the tree already had and leaves
their schedule — and the timing tests among them — as it was."""

import functools

import jax
import jax.numpy as jnp
import jax.scipy.linalg as jsl
import numpy as np
import pytest

from znicz_tpu.ops import pallas_delta as pd


def recurrence(q, k, v, log_alpha, beta):
    """S_t = α S + β k (v − α Sᵀk)ᵀ, o_t = S_tᵀ q_t, one token at a
    time."""
    b, _, h, dk = q.shape

    def token(s, row):
        q_t, k_t, v_t, a_t, b_t = row
        s = s * jnp.exp(a_t)[..., None, None]
        seen = jnp.einsum("bhkv,bhk->bhv", s, k_t)
        s = s + b_t[..., None, None] * jnp.einsum(
            "bhk,bhv->bhkv", k_t, v_t - seen)
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    rows = tuple(jnp.moveaxis(a, 1, 0)
                 for a in (q, k, v, log_alpha, beta))
    start = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    return jnp.moveaxis(jax.lax.scan(token, start, rows)[1], 0, 1)


#: (log α range, β range)
REGIMES = {
    "mixed": ((-3.0, -1e-3), (0.0, 2.0)),
    "alpha_near_0": ((-30.0, -12.0), (0.2, 1.8)),
    "alpha_near_1": ((-1e-4, -1e-6), (0.2, 1.8)),
    "beta_near_0": ((-1.0, -1e-2), (0.0, 1e-3)),
    "beta_near_2": ((-1.0, -1e-2), (1.99, 2.0)),
}
B, T, H, DK, DV, CHUNK = 2, 48, 3, 12, 20, 16


def _inputs(regime):
    (a_lo, a_hi), (b_lo, b_hi) = REGIMES[regime]
    rng = np.random.default_rng(7)

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    k = draw(B, T, H, DK)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    return (draw(B, T, H, DK), k, draw(B, T, H, DV),
            jnp.asarray(rng.uniform(a_lo, a_hi, (B, T, H)), jnp.float32),
            jnp.asarray(rng.uniform(b_lo, b_hi, (B, T, H)), jnp.float32),
            draw(B, T, H, DV))


def _value_and_gradients(rule):
    """``rule``'s output and the gradient of q, k, v, log α and β under
    a fixed weighting of the output, as ONE compiled program (op by op
    the same arithmetic is hundreds of small compilations)."""
    def both(q, k, v, log_alpha, beta, weight):
        with jax.default_matmul_precision("highest"):
            return rule(q, k, v, log_alpha, beta), jax.grad(
                lambda *a: jnp.sum(rule(*a) * weight),
                (0, 1, 2, 3, 4))(q, k, v, log_alpha, beta)
    return jax.jit(both)


#: compiled once per path; the regimes share the shapes
_RULES = {"recurrence": _value_and_gradients(recurrence)}
_RULES.update({
    kernels: _value_and_gradients(functools.partial(
        pd.gated_delta_rule, chunk=CHUNK, kernel=kernels, interpret=True))
    for kernels in (False, True)})


@pytest.mark.parametrize("regime", list(REGIMES))
def test_chunked_form_is_the_recurrence(regime):
    """The plain scan AND the kernels (interpreted) against the
    token-by-token recurrence: the output and the gradient of q, k, v,
    log α and β."""
    args = _inputs(regime)
    want, g_want = _RULES["recurrence"](*args)
    scale = float(jnp.abs(want).max())
    assert scale > 0
    for kernels in (False, True):
        got, g_got = _RULES[kernels](*args)
        assert float(jnp.abs(got - want).max()) <= 2e-5 * scale, kernels
        for name, a, b in zip(("q", "k", "v", "log_alpha", "beta"),
                              g_got, g_want):
            top = float(jnp.abs(b).max())
            assert float(jnp.abs(a - b).max()) <= 1e-4 * top + 1e-9, (
                name, kernels)


def test_kernels_and_plain_scan_walk_the_same_states():
    rng = np.random.default_rng(3)
    g, n, c = 4, 5, 8
    w, k_hat = (jnp.asarray(rng.normal(size=(g, n, c, DK)), jnp.float32)
                for _ in range(2))
    u = jnp.asarray(rng.normal(size=(g, n, c, DV)), jnp.float32)
    decay = jnp.asarray(rng.uniform(0.1, 1.0, (g, n)), jnp.float32)
    plain = pd.state_scan(w, k_hat, u, decay)
    kernel = pd.state_scan(w, k_hat, u, decay, kernel=True,
                           interpret=True)
    assert plain[1].shape == (g, n, DK, DV)
    assert not np.asarray(plain[1][:, 0]).any()        # S_0 = 0
    for a, b in zip(kernel, plain):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_a_sequence_of_broken_chunks_is_refused_by_the_rule_itself():
    *args, _ = _inputs("mixed")
    with pytest.raises(ValueError, match="whole chunks"):
        pd.gated_delta_rule(*args, chunk=32)


@pytest.mark.parametrize("c", [8, 16, 64])
def test_blocked_inverse_against_solve_triangular(c):
    """Keys as alike as a convolution leaves them: the series alone
    loses four digits at 64 rows, the blocked form none."""
    rng = np.random.default_rng(c)
    k = rng.normal(size=(6, c, 8)) + 1.5
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    lower = jnp.asarray(np.tril(1.9 * k @ k.swapaxes(-1, -2), -1),
                        jnp.float32)
    eye = jnp.broadcast_to(jnp.eye(c), lower.shape)
    want = jsl.solve_triangular(eye + lower, eye, lower=True,
                                unit_diagonal=True)
    got = pd.unit_lower_inverse(lower)
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) <= 1e-5 * scale
    assert not np.triu(np.asarray(got), 1).any()
    np.testing.assert_allclose(
        jnp.matmul(eye + lower, got, precision="highest"), eye,
        atol=1e-4 * scale)


def test_padded_share_counts_whole_lane_tiles():
    assert pd.padded_share(128, 256) == 1.0
    assert pd.padded_share(96, 192) == pytest.approx(
        128 * 256 / (96 * 192))
    assert pd.padded_share(96, 192) == pytest.approx(1.78, abs=5e-3)

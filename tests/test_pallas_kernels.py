"""Pallas kernel tests, run in interpreter mode on the CPU platform
(the kernels compile for real on TPU; the numpy oracle is the spec —
reference test strategy, SURVEY.md §4)."""

import numpy as np

import jax
import jax.numpy as jnp


def test_layer_norm_forward_matches_reference():
    from znicz_tpu.ops.pallas_kernels import layer_norm_forward
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(0, 1, (2, 37, 64)).astype(np.float32))
    g = jnp.asarray(rng.normal(1, 0.1, 64).astype(np.float32))
    b = jnp.asarray(rng.normal(0, 0.1, 64).astype(np.float32))
    eps = 1e-5
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    want = (x - mu) / jnp.sqrt(var + eps) * g + b
    got = layer_norm_forward(x, g, b, eps, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6)
    # beta=None (no-shift) variant
    got0 = layer_norm_forward(x, g, None, eps, interpret=True)
    np.testing.assert_allclose(np.asarray(got0),
                               np.asarray(want - b), atol=2e-6)


def test_layer_norm_backward_matches_autodiff():
    """dx + cross-row γ/β grads vs jax.grad of the reference — the
    M=74 geometry exercises the tail-tile masking (74 % 512 != 0)."""
    from znicz_tpu.ops.pallas_kernels import layer_norm_backward
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(0, 1, (2, 37, 64)).astype(np.float32))
    g = jnp.asarray(rng.normal(1, 0.1, 64).astype(np.float32))
    b = jnp.asarray(rng.normal(0, 0.1, 64).astype(np.float32))
    err = jnp.asarray(rng.normal(0, 1, (2, 37, 64)).astype(np.float32))
    eps = 1e-5

    def ref(x, g, b):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return jnp.vdot((x - mu) / jnp.sqrt(var + eps) * g + b, err)

    want = jax.grad(ref, argnums=(0, 1, 2))(x, g, b)
    dx, gg, gb = layer_norm_backward(x, err, g, eps, interpret=True)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(want[0]),
                               atol=5e-6)
    np.testing.assert_allclose(np.asarray(gg), np.asarray(want[1]),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(want[2]),
                               atol=2e-5)

"""Root-mean-square normalization (Zhang & Sennrich 2019) — the norm of
the pre-norm residual block (ROADMAP R0).

``y = g · x / √(mean(x²) + ε)`` over the LAST (feature) axis per
position: no mean subtraction, no shift, one learned gain ``g`` (D,).
The gain lives in the standard ``weights`` Vector, so the GD base's
update rule, the exporter and the publisher apply unchanged.

:func:`rms_norm` / :func:`rms_norm_backward` are xp-generic (numpy
oracle and ``jax.numpy``) and are what the attention unit's
``pre_norm`` / ``qk_norm`` and the expert layer's ``pre_norm`` call:
one definition of the norm for the whole block.  Statistics are taken
in f32 whatever the activation storage dtype.
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from znicz_tpu.ops.nn_units import Forward, GradientDescentBase


def rms_norm(xp, x, gain, eps: float):
    """``gain · x / √(mean(x², −1) + eps)``."""
    ms = (x * x).mean(axis=-1, keepdims=True)
    return gain * (x / xp.sqrt(ms + eps))


def rms_norm_backward(xp, x, gain, eps: float, err):
    """``(dx, dgain)`` of :func:`rms_norm` for the cotangent ``err``:

    .. code-block:: text

        r = 1/√(mean(x²)+ε)    x̂ = x·r    dgain = Σ err·x̂
        dx̂ = err·gain          dx = r·(dx̂ − x̂·mean(dx̂·x̂))
    """
    ms = (x * x).mean(axis=-1, keepdims=True)
    r = 1.0 / xp.sqrt(ms + eps)
    xhat = x * r
    grad_gain = (err * xhat).sum(axis=tuple(range(x.ndim - 1)))
    dxhat = err * gain
    dx = r * (dxhat - xhat * (dxhat * xhat).mean(axis=-1, keepdims=True))
    return dx, grad_gain


def one_norm_placement(unit) -> None:
    """A block of this unit has ONE norm (gain ``gain_norm``): before
    the sublayer (``pre_norm``) or on its output inside the skip
    (``post_norm``).  (Attention and the gated MLP take both, with a
    gain each: :func:`norm_gains`.)"""
    if unit.pre_norm and unit.post_norm:
        raise ValueError(f"{unit}: pre_norm and post_norm are two "
                         f"placements of the block's one norm; set one")


def norm_gains(unit, g_norm, g_post=None) -> tuple:
    """``(gain of the norm before the sublayer, gain of the norm on its
    output)`` of a unit with ``pre_norm`` / ``post_norm``, ``None`` for
    a norm it has not: ``gain_norm`` is the block's one norm's gain
    whichever its placement, and the pre-norm's where the block has
    both (x + RMSNorm_post(f(RMSNorm_pre(x))), the sandwich norm), the
    output norm's then being ``gain_post``."""
    if unit.pre_norm and unit.post_norm:
        return g_norm, g_post
    return (None, g_norm) if unit.post_norm else (g_norm, None)


def post_gain(unit):
    """The Vector of a unit's ``post_norm`` gain (:func:`norm_gains`)."""
    return unit.gain_post if unit.pre_norm else unit.gain_norm


class RMSNorm(Forward):
    """Per-position RMS normalization with a learned gain."""

    #: may be a member of a looped span (``znicz_tpu.pass_span``)
    PASS_SAFE = True

    def __init__(self, workflow, eps: float = 1e-5, name=None,
                 **kwargs) -> None:
        kwargs["include_bias"] = False     # the norm has no shift
        super().__init__(workflow, name=name, **kwargs)
        self.eps = float(eps)

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device=device, **kwargs)
        if self.input is None or not self.input:
            raise AttributeError(f"{self}: input not linked yet")
        if not self.weights:
            self.weights.reset(np.ones(self.input.shape[-1], np.float32))
        self.output.reset(np.zeros(self.input.shape,
                                   dtype=self.output_store_dtype))
        self.inherit_model_shard(self.output)
        self.init_vectors(self.input, self.output, self.weights)

    def numpy_run(self) -> None:
        self.input.map_read()
        self.weights.map_read()
        self.output.map_invalidate()
        self.output.mem[...] = rms_norm(
            np, self.input.mem.astype(np.float32), self.weights.mem,
            self.eps)

    def xla_run(self) -> None:
        self.output.devmem = rms_norm(
            jnp, self.input.devmem.astype(jnp.float32),
            self.weights.devmem, self.eps)


class GDRMSNorm(GradientDescentBase):
    """Analytic RMS-norm backward (:func:`rms_norm_backward`, the same
    math on both paths)."""

    MATCHES = (RMSNorm,)
    REQUIRES_FORWARD_UNIT = True
    REQUIRES_INPUT = True

    def __init__(self, workflow, name=None, **kwargs):
        super().__init__(workflow, name=name, **kwargs)
        self.forward_unit: RMSNorm | None = None

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device=device, **kwargs)
        self.init_vectors(self.err_input, self.err_output, self.input,
                          self.output, self.weights)

    def numpy_run(self) -> None:
        for vec in (self.err_output, self.input):
            vec.map_read()
        self.weights.map_write()
        dx, grad_g = rms_norm_backward(
            np, self.input.mem.astype(np.float32), self.weights.mem,
            self.forward_unit.eps,
            self.err_output.mem.astype(np.float32))
        if self.need_err_input:
            self.err_input.map_invalidate()
            self.err_input.mem[...] = dx
        self._apply_weights_np(grad_g)

    def xla_run(self) -> None:
        dx, grad_g = rms_norm_backward(
            jnp, self.input.devmem.astype(jnp.float32),
            self.weights.devmem, self.forward_unit.eps,
            self.err_output.devmem.astype(jnp.float32))
        if self.need_err_input:
            self.err_input.devmem = dx
        self._apply_weights_xla(grad_g)

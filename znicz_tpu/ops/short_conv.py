"""Gated short convolution as the token mixer (the ``conv`` layers of
LFM2-8B-A1B, ``model_type`` lfm2_moe, PR 43): a depthwise causal
convolution of a few taps over time, gated on both sides by the
sublayer's own projections — no activation, no recurrence, no bias.

.. code-block:: text

    m = x                       (pre_norm="rms": RMSNorm(x))
    [B | C | x̃] = m W_in        ``weights`` (D, 3·D): three D-column blocks
    u_t = B_t ⊙ x̃_t
    c_t = Σ_{j<J} taps[:, j] ⊙ u_{t−J+1+j}     each channel its own J taps
                                (``conv_kernel``), zeros before the
                                sequence
    y = (C ⊙ c) W_out           ``weights_out`` (D, D)
    out = x + y                 (residual=True)

``delta_net.causal_conv`` is taps + SiLU INSIDE the delta-rule unit,
over q ‖ k ‖ v; here the convolution is the mixer itself.  Between the
two projections everything is memory-bound (three reads and one write
of T × D for some 20 FLOPs an element), so on a TPU the chain B ⊙ x̃ →
taps → C ⊙ is ONE kernel each way (``ops/pallas_short_conv.py``:
``znicz_short_conv_fwd`` reads the projection where it lies and writes
y at the width W_out's matmul takes; ``znicz_short_conv_bwd`` keeps the
projection and the taps only).  Elsewhere — no TPU, a mesh, a shape the
kernels do not tile — the same lines in ``jax.numpy`` (:func:`chain`).
``_resolve_path`` decides once at ``initialize`` and the unit holds
that one value; the gauge
``znicz_short_conv{unit,stat="path"}`` and the info line say which.
The gate is ``engine.delta_scan_kernel`` ("auto" = on a TPU), the
linear mixers' one: what it governs in ``delta_net`` (``qkv_prep``) is
this chain's sibling.

Precision: the two projections take the unit's matmul inputs (bf16 in
mixed precision) with f32 accumulation; the norm, both gates and the
taps' sum stay f32 on every path.

Parameters: ``weights`` W_in (D, 3·D), ``weights_conv`` the taps
(D, J), ``weights_out`` (D, D), ``gain_norm`` (D,) with ``pre_norm``.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from znicz_tpu.memory import Vector
from znicz_tpu.observe import metrics as _metrics
from znicz_tpu.ops import pallas_short_conv
from znicz_tpu.ops.delta_net import GDGatedDeltaNet, causal_conv
from znicz_tpu.ops.nn_units import Forward
from znicz_tpu.ops.rms_norm import rms_norm


def chain(xp, projected, taps):
    """What lies between the two projections, in ``xp`` (numpy or
    ``jax.numpy``), f32: (B, T, 3·D) = [B | C | x̃] and taps (D, J) →
    (C ⊙ taps(B ⊙ x̃)) (B, T, D) — the oracle, and the path off a
    TPU."""
    d = projected.shape[-1] // 3
    return projected[..., d:2 * d] * causal_conv(
        xp, projected[..., :d] * projected[..., 2 * d:], taps)


class ShortConv(Forward):
    """A gated short-convolution mixer block (module docstring)."""

    EXPORT_PARAMS = ("weights", "weights_conv", "weights_out",
                     "gain_norm")

    def __init__(self, workflow, conv_kernel: int = 3,
                 pre_norm: str | None = None, residual: bool = False,
                 norm_eps: float = 1e-5, name=None, **kwargs) -> None:
        kwargs.setdefault("weights_filling", "xavier")
        kwargs["include_bias"] = False
        super().__init__(workflow, name=name, **kwargs)
        if pre_norm not in (None, "rms"):
            raise ValueError(f"pre_norm must be None or 'rms', got "
                             f"{pre_norm!r}")
        self.conv_kernel = int(conv_kernel)
        if self.conv_kernel < 2:
            raise ValueError(f"{self}: conv_kernel {conv_kernel}: a "
                             f"convolution over time has 2 taps or more")
        self.pre_norm = pre_norm
        self.residual = bool(residual)
        self.norm_eps = float(norm_eps)
        for attr in self.EXPORT_PARAMS[1:]:
            setattr(self, attr, Vector(name=f"{self.name}.{attr}"))
        self._traced_vjp = None
        #: the chain's form, decided once at ``initialize``: the kernel
        #: pair (and whether interpreted), or ``jax.numpy``
        self._kernels = False
        self._interpret = False

    def unserved(self) -> str | None:
        return super().unserved() or (
            "is a gated short-convolution mixer (short_conv); serving "
            "has no rolling state for the convolution's last taps yet "
            "— the mixer exists on the training path only (ROADMAP R1, "
            "serving half)")

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device=device, **kwargs)
        if self.input is None or not self.input:
            raise AttributeError(f"{self}: input not linked yet")
        if len(self.input.shape) != 3:
            raise ValueError(f"{self}: expected (batch, time, features) "
                             f"input, got {self.input.shape}")
        b, t, d = self.input.shape
        for vec, shape in ((self.weights, (d, 3 * d)),
                           (self.weights_out, (d, d))):
            if not vec:
                vec.reset(self.fill_array(shape, self.weights_filling,
                                          self.weights_stddev,
                                          fan_in=shape[0]))
        if not self.weights_conv:
            self.weights_conv.reset(self.fill_array(
                (d, self.conv_kernel), "uniform", None,
                fan_in=self.conv_kernel))
        if self.pre_norm and not self.gain_norm:
            self.gain_norm.reset(np.ones(d, np.float32))
        self.output.reset(np.zeros((b, t, d),
                                   dtype=self.output_store_dtype))
        self.inherit_model_shard(self.output)
        self._resolve_path(t, d)
        self.init_vectors(self.input, self.output,
                          *(getattr(self, a) for a in self.EXPORT_PARAMS))

    def _resolve_path(self, t: int, d: int) -> None:
        """The kernel pair or ``jax.numpy``, once per ``initialize``."""
        from znicz_tpu.ops import pallas_kernels
        from znicz_tpu.utils.config import root
        interpret = bool(root.common.engine.get("pallas_interpret",
                                                False))
        refused = pallas_kernels.kernel_refusal(
            self.device, "delta_scan_kernel", interpret)
        mesh = getattr(self.device, "mesh", None)
        if refused is None and mesh is not None and mesh.size > 1:
            refused = (f"a mesh of {mesh.size} devices: the kernels "
                       f"have no sharding rule")
        if refused is None:
            refused = pallas_short_conv.legal(t, d, self.conv_kernel)
        self._kernels, self._interpret = refused is None, interpret
        for stat, value in (("path", float(self._kernels)),
                            ("taps", self.conv_kernel),
                            ("channels", d)):
            _metrics.short_conv(self.name, stat).set(value)
        self.info(
            "%s: gated short convolution of %d taps over %d channels: %s",
            self.name, self.conv_kernel, d,
            "znicz_short_conv_fwd / _bwd kernels from the projection "
            "where it lies to W_out's input"
            + (" (interpreted)" if interpret else "")
            if self._kernels else
            f"both gates and the taps in jax.numpy ({refused})")

    # -- pure forward ---------------------------------------------------
    def forward_args(self) -> tuple:
        return (self.input.devmem,) + tuple(
            getattr(self, attr).devmem if getattr(self, attr) else None
            for attr in self.EXPORT_PARAMS)

    def xla_forward(self, x, w_in, taps, w_out, g_norm=None):
        b, t, d = x.shape
        x32 = x.astype(jnp.float32)
        m = x32 if g_norm is None \
            else rms_norm(jnp, x32, g_norm, self.norm_eps)
        projected = self.mxu_dot(jnp, m.reshape(b * t, d),
                                 w_in).reshape(b, t, 3 * d)
        if self._kernels:
            gated = pallas_short_conv.short_conv(
                projected, taps, self.mxu_dtype or jnp.float32,
                interpret=self._interpret)
        else:
            # the backward keeps the PROJECTION and makes u and c again
            # (elementwise), as the kernels do, not three more T × D
            gated = jax.checkpoint(
                lambda p, w: chain(jnp, p, w))(projected, taps)
        y = self.mxu_dot(jnp, gated.reshape(b * t, d),
                         w_out).reshape(b, t, d)
        return x32 + y if self.residual else y

    def xla_run(self) -> None:
        args = self.forward_args()
        if not self.output._tracing:
            self._traced_vjp = None
            self.output.devmem = self.xla_forward(*args)
            return
        self.output.devmem, self._traced_vjp = jax.vjp(
            self.xla_forward, *args)

    # -- numpy oracle ---------------------------------------------------
    def _forward_np(self, x):
        b, t, d = x.shape
        m = rms_norm(np, x, self.gain_norm.mem, self.norm_eps) \
            if self.pre_norm else x
        projected = (m.reshape(b * t, d) @ self.weights.mem).reshape(
            b, t, 3 * d)
        gated = chain(np, projected, self.weights_conv.mem)
        y = (gated.reshape(b * t, d) @ self.weights_out.mem).reshape(
            b, t, d)
        return x + y if self.residual else y

    def numpy_run(self) -> None:
        self.input.map_read()
        for attr in self.EXPORT_PARAMS:
            if getattr(self, attr):
                getattr(self, attr).map_read()
        self.output.map_invalidate()
        self.output.mem[...] = self._forward_np(
            self.input.mem.astype(np.float32))


class GDShortConv(GDGatedDeltaNet):
    """Backward of :class:`ShortConv`, on ``GDGatedDeltaNet``'s
    pattern: the forward's stashed pullback (autodiff around the
    ``custom_vjp`` of the kernel pair), every parameter through the
    base's update rule; the numpy path differentiates the XLA forward
    on the host (the mixer is held to ``znbench/reference/lfm2.py``)."""

    MATCHES = (ShortConv,)
    EXTRA = ShortConv.EXPORT_PARAMS[1:]

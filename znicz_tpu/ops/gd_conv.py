"""Convolution backward units (reference: ``znicz/gd_conv.py``).

The reference hand-wrote col2im scatter + GEMM kernels.  TPU-first,
the XLA path builds the two gradient convolutions with
``jax.linear_transpose`` of the forward's bare conv (``conv_raw``) —
exactly XLA's conv transpose rules (SURVEY.md §2.3: "lax.conv
transpose rules / autodiff") WITHOUT re-evaluating the forward the way
``jax.vjp`` of the full forward would; the activation derivative comes
from the forward unit's saved output, like the numpy oracle's.  The
oracle is the explicit im2col/col2im math, independently implemented,
so the transpose path is *tested against* the reference-style
computation.

The weight/bias gradients feed the shared base update
(``GradientDescentBase._apply_param_xla``) — on data-parallel meshes
that means the ZeRO-1 reduce-scatter → sharded-momentum → all-gather
form; conv kernels pick their data-shard dim like any other parameter
(largest non-model dim, usually ``n_kernels``).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from znicz_tpu.ops.conv import (
    Conv,
    ConvRELU,
    ConvSigmoid,
    ConvStrictRELU,
    ConvTanh,
    col2im,
    im2col,
)
from znicz_tpu.ops.nn_units import GradientDescentBase


class GradientDescentConv(GradientDescentBase):
    MATCHES = (Conv,)

    def __init__(self, workflow, name=None, **kwargs):
        super().__init__(workflow, name=name, **kwargs)
        self.forward_unit: Conv | None = None  # set by link_gds

    def initialize(self, device=None, **kwargs) -> None:
        if self.input is None or not self.input:
            raise AttributeError(f"{self}: input not linked yet")
        super().initialize(device=device, **kwargs)
        self.init_vectors(self.err_input, self.err_output, self.input,
                          self.output, self.weights, self.bias)

    # -- numpy oracle: explicit col2im/GEMM -----------------------------
    def numpy_run(self) -> None:
        fwd = self.forward_unit
        for vec in (self.err_output, self.input, self.output):
            vec.map_read()
        self.weights.map_write()
        x = self.input.mem.astype(np.float32)
        w = self.weights.mem
        n = x.shape[0]
        y = self.output.mem
        delta = self.err_output.mem * fwd.activation.derivative(
            np, y, None)  # conv activations are output-expressed
        oh, ow, k = delta.shape[1:]
        delta2d = delta.reshape(-1, k)
        cols = im2col(x, fwd.ky, fwd.kx, *fwd.sliding, fwd.padding)
        cols2d = cols.reshape(-1, cols.shape[-1])
        grad_w = (cols2d.T @ delta2d).reshape(w.shape)
        if self.need_err_input:
            err_cols = (delta2d @ w.reshape(-1, k).T).reshape(cols.shape)
            self.err_input.map_invalidate()
            self.err_input.mem[...] = col2im(
                err_cols, x.shape, fwd.ky, fwd.kx, *fwd.sliding,
                fwd.padding)
        self._apply_weights_np(grad_w)
        if self.bias is not None and self.bias:
            self.bias.map_write()
            self._apply_bias_np(delta2d.sum(axis=0))

    # -- XLA path: explicit transposed convs ----------------------------
    def xla_run(self) -> None:
        """Gradients via ``jax.linear_transpose`` of the bare conv —
        exactly XLA's conv transpose rules, but WITHOUT re-evaluating
        the forward the way ``jax.vjp`` of the full forward would
        (XLA's CSE does not reliably merge the recomputed convs; the
        recompute cost ~35% extra conv FLOPs per step, measured on the
        AlexNet region HLO).  The activation derivative comes from the
        forward unit's saved OUTPUT, mirroring the numpy oracle."""
        fwd = self.forward_unit
        x = self.input.devmem
        w = self.weights.devmem
        y = self.output.devmem
        err = self.err_output.devmem
        delta = err * fwd.activation.derivative(jnp, y, None)
        cotangent = delta if fwd.mxu_dtype is None \
            else delta.astype(fwd.mxu_dtype)
        if self.need_err_input:
            t_x = jax.linear_transpose(
                lambda xx: fwd.conv_raw(xx, w),
                jax.ShapeDtypeStruct(x.shape, x.dtype))
            (grad_x,) = t_x(cotangent)
            self.err_input.devmem = grad_x.astype(jnp.float32)
        t_w = jax.linear_transpose(
            lambda ww: fwd.conv_raw(x, ww),
            jax.ShapeDtypeStruct(w.shape, w.dtype))
        (grad_w,) = t_w(cotangent)
        self._apply_weights_xla(grad_w.astype(jnp.float32))
        if self.bias is not None and self.bias:
            self._apply_bias_xla(
                delta.astype(jnp.float32).sum(axis=(0, 1, 2)))


class GDTanhConv(GradientDescentConv):
    MATCHES = (ConvTanh,)


class GDRELUConv(GradientDescentConv):
    MATCHES = (ConvRELU,)


class GDStrictRELUConv(GradientDescentConv):
    MATCHES = (ConvStrictRELU,)


class GDSigmoidConv(GradientDescentConv):
    MATCHES = (ConvSigmoid,)

"""Convolution forward units (reference: ``znicz/conv.py``).

The reference lowered conv as im2col ("unpack") + GEMM with custom
OpenCL/CUDA kernels.  TPU-first, the XLA path is a single
``lax.conv_general_dilated`` (native HLO conv, tiled onto the MXU by
XLA — SURVEY.md §2.3: "do NOT replicate im2col"), with bias +
activation fused by the jit region.  The numpy oracle *does* use
im2col — an independent implementation that doubles as the spec.

Layouts are TPU-native: NHWC data, HWIO weights.

Constructor geometry follows the reference: ``n_kernels``, ``kx``/``ky``
(kernel width/height), ``sliding`` (stride ``(sy, sx)``), ``padding``
(int, ``(v, h)``, or ``(top, bottom, left, right)``).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from znicz_tpu.memory import Vector  # noqa: F401  (typing/docs)
from znicz_tpu.ops import activations_math
from znicz_tpu.ops.nn_units import Forward

DIMNUMS = ("NHWC", "HWIO", "NHWC")


def normalize_padding(padding) -> tuple[int, int, int, int]:
    """→ (top, bottom, left, right)."""
    if isinstance(padding, (int, np.integer)):
        return (int(padding),) * 4
    padding = tuple(int(p) for p in padding)
    if len(padding) == 2:
        v, h = padding
        return (v, v, h, h)
    if len(padding) == 4:
        return padding
    raise ValueError(f"bad padding spec {padding!r}")


def im2col(x: np.ndarray, ky: int, kx: int, sy: int, sx: int,
           pad: tuple[int, int, int, int]) -> np.ndarray:
    """NHWC patches → (N, oh, ow, ky*kx*C).  The numpy oracle's
    'unpack' (reference kernel family: conv forward unpack)."""
    pt, pb, pl, pr = pad
    xp = np.pad(x, ((0, 0), (pt, pb), (pl, pr), (0, 0)))
    n, h, w, c = xp.shape
    oh = (h - ky) // sy + 1
    ow = (w - kx) // sx + 1
    cols = np.zeros((n, oh, ow, ky, kx, c), dtype=x.dtype)
    for i in range(ky):
        for j in range(kx):
            cols[:, :, :, i, j, :] = \
                xp[:, i:i + oh * sy:sy, j:j + ow * sx:sx, :]
    return cols.reshape(n, oh, ow, ky * kx * c)


def col2im(cols: np.ndarray, x_shape, ky: int, kx: int, sy: int, sx: int,
           pad: tuple[int, int, int, int]) -> np.ndarray:
    """Scatter-add patches back (the oracle's col2im, reference kernel
    family: conv gradient)."""
    pt, pb, pl, pr = pad
    n, h, w, c = x_shape
    hp, wp = h + pt + pb, w + pl + pr
    out = np.zeros((n, hp, wp, c), dtype=cols.dtype)
    oh = (hp - ky) // sy + 1
    ow = (wp - kx) // sx + 1
    cols6 = cols.reshape(n, oh, ow, ky, kx, c)
    for i in range(ky):
        for j in range(kx):
            out[:, i:i + oh * sy:sy, j:j + ow * sx:sx, :] += \
                cols6[:, :, :, i, j, :]
    return out[:, pt:pt + h, pl:pl + w, :]


class Conv(Forward):
    """2-D convolution (linear flavor)."""

    ACTIVATION = "linear"

    def __init__(self, workflow, n_kernels: int, kx: int, ky: int,
                 sliding=(1, 1), padding=0, name=None, **kwargs) -> None:
        super().__init__(workflow, name=name, **kwargs)
        self.n_kernels = int(n_kernels)
        self.kx, self.ky = int(kx), int(ky)
        self.sliding = (int(sliding[0]), int(sliding[1]))  # (sy, sx)
        self.padding = normalize_padding(padding)
        self.activation = activations_math.get(self.ACTIVATION)

    def output_spatial(self, h: int, w: int) -> tuple[int, int]:
        pt, pb, pl, pr = self.padding
        sy, sx = self.sliding
        return ((h + pt + pb - self.ky) // sy + 1,
                (w + pl + pr - self.kx) // sx + 1)

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device=device, **kwargs)
        if self.input is None or not self.input:
            raise AttributeError(f"{self}: input not linked/allocated yet")
        if len(self.input.shape) != 4:
            raise ValueError(f"{self}: expected NHWC input, got shape "
                             f"{self.input.shape}")
        n, h, w, c = self.input.shape
        fan_in = self.ky * self.kx * c
        if not self.weights:
            self.weights.reset(self.fill_array(
                (self.ky, self.kx, c, self.n_kernels),
                self.weights_filling, self.weights_stddev, fan_in=fan_in))
        if self.include_bias and not self.bias:
            self.bias.reset(self.fill_array(
                (self.n_kernels,), self.bias_filling, self.bias_stddev,
                fan_in=fan_in))
        oh, ow = self.output_spatial(h, w)
        self.output.reset(np.zeros((n, oh, ow, self.n_kernels),
                                   dtype=self.output_store_dtype))
        self.init_vectors(self.input, self.output, self.weights, self.bias)

    # -- pure forward (jnp; the backward unit transposes conv_raw) ------
    def conv_raw(self, x, w):
        """The bare conv at MXU precision: bf16 in → bf16 out in bf16
        mode (single-dtype, so ``jax.linear_transpose``'d gradient
        convs stay single-dtype — the casts' own transposes move the
        cotangent between f32 and bf16)."""
        pt, pb, pl, pr = self.padding
        dt = self.mxu_dtype
        if dt is not None:
            x, w = x.astype(dt), w.astype(dt)
        return jax.lax.conv_general_dilated(
            x, w, window_strides=self.sliding,
            padding=((pt, pb), (pl, pr)),
            dimension_numbers=DIMNUMS)

    def xla_forward(self, x, w, b):
        y = self.conv_raw(x, w)
        if y.dtype != jnp.float32:
            y = y.astype(jnp.float32)
        if b is not None:
            y = y + b
        return self.activation.fwd(jnp, y)

    def numpy_run(self) -> None:
        self.input.map_read()
        self.weights.map_read()
        x = self.input.mem.astype(np.float32)
        w = self.weights.mem
        cols = im2col(x, self.ky, self.kx, *self.sliding, self.padding)
        y = cols @ w.reshape(-1, self.n_kernels)
        if self.include_bias:
            self.bias.map_read()
            y = y + self.bias.mem
        self.output.map_invalidate()
        self.output.mem[...] = self.activation.fwd(np, y)

    def xla_run(self) -> None:
        b = self.bias.devmem if self.include_bias else None
        self.output.devmem = self.xla_forward(
            self.input.devmem, self.weights.devmem, b)


class ConvTanh(Conv):
    """Fused scaled-tanh conv (reference: ``ConvTanh``)."""
    ACTIVATION = "tanh"


class ConvRELU(Conv):
    """Fused smooth-RELU conv (reference: ``ConvRELU``)."""
    ACTIVATION = "relu"


class ConvStrictRELU(Conv):
    """Fused max(x,0) conv (reference: ``ConvStrictRELU``)."""
    ACTIVATION = "strict_relu"


class ConvSigmoid(Conv):
    ACTIVATION = "sigmoid"

"""Plain reference of the ``alexnet`` configuration: the one-tower
AlexNet forward (Krizhevsky, Sutskever, Hinton 2012) in float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")`` — no
kernels, no fusion tricks, no storage casts.  Independent of the code
under test: it reads only the layer table and the parameters.

Departures from the paper, all upstream Znicz's: one tower (no
two-GPU grouping); the LRN denominator is ``k + alpha * sum(x^2)``
(alpha is not divided by the window size); inverted dropout (the mask
the system drew, already scaled, is given in and multiplied).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _pad4(padding) -> tuple:
    """Znicz padding: an int or (top, bottom, left, right)."""
    if isinstance(padding, int):
        return (padding,) * 4
    return tuple(padding) if padding else (0, 0, 0, 0)


def _conv(x, w, b, spec):
    top, bottom, left, right = _pad4(spec.get("padding", 0))
    y = jax.lax.conv_general_dilated(
        x, w, window_strides=tuple(spec.get("sliding", (1, 1))),
        padding=((top, bottom), (left, right)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return jnp.maximum(y + b, 0.0)


def _lrn(x, spec):
    n, alpha = int(spec.get("n", 5)), float(spec.get("alpha", 1e-4))
    beta, k = float(spec.get("beta", 0.75)), float(spec.get("k", 2.0))
    c = x.shape[-1]
    low = n // 2
    sq = jnp.pad(x * x, ((0, 0),) * 3 + ((low, n - 1 - low),))
    window = sum(sq[..., off:off + c] for off in range(n))
    return x * (k + alpha * window) ** (-beta)


def _max_pool(x, spec):
    ky, kx = int(spec["ky"]), int(spec["kx"])
    sy, sx = spec.get("sliding", (ky, kx))
    h, w = x.shape[1], x.shape[2]
    # tail windows are truncated (ceil division), as upstream
    oh = -(-(h - ky) // sy) + 1 if h > ky else 1
    ow = -(-(w - kx) // sx) + 1 if w > kx else 1
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, ky, kx, 1), (1, sy, sx, 1),
        ((0, 0), (0, (oh - 1) * sy + ky - h),
         (0, (ow - 1) * sx + kx - w), (0, 0)))


def forward(params: dict, layers: list, x, masks: dict | None = None
            ) -> list:
    """Every layer's output for the batch ``x`` (NHWC, already
    normalized as the loader does).  ``params`` is keyed as a bundle
    is: ``layer<i>_weights`` / ``layer<i>_bias``.  ``masks[i]`` is the
    dropout mask layer ``i`` applied (train mode); without it the layer
    is the identity (eval mode)."""
    masks = masks or {}
    outs = []
    with jax.default_matmul_precision("highest"):
        h = jnp.asarray(x, jnp.float32)
        for i, layer in enumerate(layers):
            kind, spec = layer["type"], layer.get("->", {})
            w = params.get(f"layer{i}_weights")
            b = params.get(f"layer{i}_bias")
            if w is not None:
                w = jnp.asarray(w, jnp.float32)
                b = jnp.asarray(b, jnp.float32)
            if kind == "conv_str":
                h = _conv(h, w, b, spec)
            elif kind == "norm":
                h = _lrn(h, spec)
            elif kind == "max_pooling":
                h = _max_pool(h, spec)
            elif kind == "all2all_str":
                h = jnp.maximum(h.reshape(h.shape[0], -1) @ w + b, 0.0)
            elif kind == "dropout":
                if i in masks:
                    h = h * jnp.asarray(masks[i], jnp.float32)
            elif kind == "softmax":
                h = jax.nn.softmax(h.reshape(h.shape[0], -1) @ w + b,
                                   axis=-1)
            else:
                raise ValueError(f"reference/alexnet: no layer {kind!r}")
            outs.append(np.asarray(h))
    return outs

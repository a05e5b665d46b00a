"""Activation functions and their derivatives, numpy + jnp.

Semantics follow the reference's activation family (reference:
``znicz/activation.py``, ``znicz/all2all.py``, ``znicz/conv.py``):

- ``tanh`` is the scaled LeCun tanh ``y = 1.7159·tanh(0.6666·x)``;
- ``relu`` is the reference's *smooth* RELU ``y = log(1 + exp(x))``
  (softplus);
- ``strict_relu`` is ``max(x, 0)``;
- ``sigmoid``, ``log`` (``log(x + sqrt(x²+1))``, i.e. asinh), ``mul``
  (scale by a constant) complete the set.

Derivatives are expressed in terms of the *output* ``y`` where the
reference does so (cheap in the fused backward units); ``log`` needs
the input ``x``.  One table serves numpy and jnp because the math is
written against the array-API surface both share.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import numpy as np

import jax.numpy as jnp


_TANH_A = 1.7159
_TANH_B = 0.6666


@dataclass(frozen=True)
class Activation:
    """fwd(xp, x) -> y;  derivative(xp, y, x) -> dy/dx."""
    name: str
    fwd: Callable
    derivative: Callable
    needs_input: bool = False


def _softplus(xp, x):
    # log(1+exp(x)) stably: max(x,0) + log1p(exp(-|x|))
    return xp.maximum(x, 0) + xp.log1p(xp.exp(-xp.abs(x)))


ACTIVATIONS: dict[str, Activation] = {
    "linear": Activation(
        "linear",
        fwd=lambda xp, x: x,
        derivative=lambda xp, y, x: xp.ones_like(y)),
    "tanh": Activation(
        "tanh",
        fwd=lambda xp, x: _TANH_A * xp.tanh(_TANH_B * x),
        # dy/dx = A·B·(1−tanh²) = (B/A)·(A²−y²)
        derivative=lambda xp, y, x: (_TANH_B / _TANH_A) * (
            _TANH_A * _TANH_A - y * y)),
    "relu": Activation(
        "relu",
        fwd=_softplus,
        # y = log(1+eˣ) ⇒ dy/dx = 1 − e^{−y}
        derivative=lambda xp, y, x: 1.0 - xp.exp(-y)),
    "strict_relu": Activation(
        "strict_relu",
        fwd=lambda xp, x: xp.maximum(x, 0),
        derivative=lambda xp, y, x: (y > 0).astype(y.dtype)),
    "sigmoid": Activation(
        "sigmoid",
        fwd=lambda xp, x: 1.0 / (1.0 + xp.exp(-x)),
        derivative=lambda xp, y, x: y * (1.0 - y)),
    "log": Activation(
        "log",
        fwd=lambda xp, x: xp.log(x + xp.sqrt(x * x + 1.0)),
        derivative=lambda xp, y, x: 1.0 / xp.sqrt(x * x + 1.0),
        needs_input=True),
}


def _logistic(xp, x):
    return 1.0 / (1.0 + xp.exp(-x))


#: the gate functions of a gated MLP, ``W_down (gate(W_gate m) ⊙ W_up
#: m)`` (``ops/moe.py``: the experts, the shared expert, ``GatedMLP``) —
#: the ONE home of each function and its derivative, by the name the
#: layers' ``act`` option takes: ``silu`` (SwiGLU) and ``relu`` (ReGLU:
#: ``max(x, 0)``, the table's ``strict_relu`` — the reference's
#: ``relu`` is a softplus)
GATES: dict[str, Activation] = {
    "silu": Activation(
        "silu",
        fwd=lambda xp, x: x / (1.0 + xp.exp(-x)),
        # with σ = σ(x): d/dx x·σ = σ·(1 + x·(1 − σ))
        derivative=lambda xp, y, x: _logistic(xp, x) * (
            1.0 + x * (1.0 - _logistic(xp, x))),
        needs_input=True),
    "relu": Activation(
        "relu",
        fwd=lambda xp, x: xp.maximum(x, 0),
        derivative=lambda xp, y, x: (x > 0).astype(x.dtype),
        needs_input=True),
}


def gate(name: str) -> Activation:
    """The gate function a gated MLP's ``act`` option names."""
    try:
        return GATES[name]
    except KeyError:
        raise ValueError(
            f"act must be one of {sorted(GATES)}, got {name!r}") from None


def get(name: str) -> Activation:
    try:
        return ACTIVATIONS[name]
    except KeyError:
        raise ValueError(
            f"unknown activation '{name}' "
            f"(have {sorted(ACTIVATIONS)})") from None


def np_ns():
    return np


def jnp_ns():
    return jnp

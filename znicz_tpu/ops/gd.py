"""Gradient-descent backward units for the fully-connected family
(reference: ``znicz/gd.py``).

Math (weights stored ``(in, out)``; see ``nn_units.py``):

.. code-block:: text

    δ_act       = err_output ⊙ act'(output)
    err_input   = δ_act @ Wᵀ
    dL/dW       = xᵀ @ δ_act          (GEMM on MXU)
    dL/db       = Σ_batch δ_act

followed by the shared momentum/decay/clip update in
:class:`~znicz_tpu.ops.nn_units.GradientDescentBase`.  The evaluator
emits ``err_output`` already normalized by batch size, so no ``1/N``
appears here.  On data-parallel meshes the update path (gradient fold
included) runs ZeRO-1 sharded over the data axis by default — the
family units only PRODUCE ``dL/dW``; the reduce-scatter / sharded
momentum / all-gather plumbing lives entirely in the base's
``_apply_param_xla``.

``GDSoftmax`` is the linear case: ``EvaluatorSoftmax`` produces the
combined softmax+cross-entropy derivative (``p − t``), exactly as the
reference's evaluator does.
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from znicz_tpu.ops import activations_math
from znicz_tpu.ops.all2all import (
    All2All,
    All2AllRELU,
    All2AllSigmoid,
    All2AllSoftmax,
    All2AllStrictRELU,
    All2AllTanh,
)
from znicz_tpu.ops.nn_units import GradientDescentBase


class GradientDescent(GradientDescentBase):
    """Backward for linear ``All2All`` (reference: ``GradientDescent``)."""

    MATCHES = (All2All,)
    ACTIVATION = "linear"

    def __init__(self, workflow, name=None, **kwargs):
        super().__init__(workflow, name=name, **kwargs)
        self.activation = activations_math.get(self.ACTIVATION)

    def initialize(self, device=None, **kwargs) -> None:
        if self.input is None or not self.input:
            raise AttributeError(f"{self}: input not linked yet")
        super().initialize(device=device, **kwargs)
        self.init_vectors(self.err_input, self.err_output, self.input,
                          self.output, self.weights, self.bias)

    # -- shared math ----------------------------------------------------
    def _rows(self, x) -> int:
        """Rows of the GEMM: the batch, or batch × time under the
        forward's ``per_position``."""
        fwd = getattr(self, "forward_unit", None)
        if getattr(fwd, "per_position", False):
            return int(np.prod(x.shape[:-1]))
        return x.shape[0]

    def _delta(self, xp, err_output, output, x2d):
        """Activation-derivative folding: δ_act over flat (N, out)."""
        batch = x2d.shape[0]
        d = err_output.reshape(batch, -1)
        y = output.reshape(batch, -1)
        deriv = self.activation.derivative(
            xp, y, x2d if self.activation.needs_input else None)
        return d * deriv

    def numpy_run(self) -> None:
        for vec in (self.err_output, self.input, self.output):
            vec.map_read()
        self.weights.map_write()
        x = self.input.mem.astype(np.float32)
        x2d = x.reshape(self._rows(x), -1)
        delta = self._delta(np, self.err_output.mem, self.output.mem, x2d)
        if self.need_err_input:
            self.err_input.map_invalidate()
            ei = delta @ self.weights.mem.T
            self.err_input.mem[...] = ei.reshape(self.input.shape)
        grad_w = x2d.T @ delta
        self._apply_weights_np(grad_w)
        if self.bias is not None and self.bias:
            self.bias.map_write()
            self._apply_bias_np(delta.sum(axis=0))

    def xla_run(self) -> None:
        x = self.input.devmem
        x2d = x.reshape(self._rows(x), -1)
        w = self.weights.devmem
        delta = self._delta(jnp, self.err_output.devmem, self.output.devmem,
                            x2d)
        if self.need_err_input:
            self.err_input.devmem = self.mxu_dot(
                jnp, delta, w.T).reshape(x.shape)
        grad_w = self.mxu_dot(jnp, x2d.T, delta)
        self._apply_weights_xla(grad_w)
        if self.bias is not None and self.bias:
            self._apply_bias_xla(
                delta.astype(jnp.float32).sum(axis=0))


class GDTanh(GradientDescent):
    MATCHES = (All2AllTanh,)
    ACTIVATION = "tanh"


class GDRELU(GradientDescent):
    MATCHES = (All2AllRELU,)
    ACTIVATION = "relu"


class GDStrictRELU(GradientDescent):
    MATCHES = (All2AllStrictRELU,)
    ACTIVATION = "strict_relu"


class GDSigmoid(GradientDescent):
    MATCHES = (All2AllSigmoid,)
    ACTIVATION = "sigmoid"


class GDSoftmax(GradientDescent):
    """Linear backward: evaluator already folded the softmax+CE
    derivative into ``err_output`` (reference: ``GDSoftmax``)."""
    MATCHES = (All2AllSoftmax,)
    ACTIVATION = "linear"

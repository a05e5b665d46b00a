"""Layer normalization — companion to the attention family (beyond
the 2015 reference, whose normalizers are cross-channel LRN and
mean-dispersion; SURVEY.md §5.7 marks sequence machinery as this
framework's extension).

``y = γ · (x − μ) / √(σ² + ε) + β`` with statistics over the LAST
(feature) axis per position.  γ/β live in the standard
``weights``/``bias`` Vectors (shape (D,)), so the GD base's momentum/
decay update rule, the exporter, and the publisher all apply
unchanged.

Statistics are computed in f32 even under bf16 activation storage
(the variance of near-equal values cancels catastrophically in bf16);
the normalized output is stored back at the activation dtype.
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from znicz_tpu.ops.nn_units import Forward, GradientDescentBase


class LayerNorm(Forward):
    """Per-position feature normalization with learned scale/shift."""

    def __init__(self, workflow, eps: float = 1e-5, name=None,
                 **kwargs) -> None:
        super().__init__(workflow, name=name, **kwargs)
        self.eps = float(eps)

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device=device, **kwargs)
        if self.input is None or not self.input:
            raise AttributeError(f"{self}: input not linked yet")
        d = self.input.shape[-1]
        if not self.weights:
            self.weights.reset(np.ones(d, np.float32))   # γ
        if self.include_bias and not self.bias:
            self.bias.reset(np.zeros(d, np.float32))     # β
        self.output.reset(np.zeros(self.input.shape,
                                   dtype=self.output_store_dtype))
        self.inherit_model_shard(self.output)
        # fused Pallas layer norm (one VMEM pass vs the XLA
        # composition's materialized xhat + f32 upcasts): default ON
        # for real TPU devices per the round-5 in-graph A/B (PERF.md);
        # opt out with engine.pallas_layer_norm = False.
        from znicz_tpu.ops import pallas_kernels
        from znicz_tpu.parallel.mesh import kernel_shard_spec, \
            spec_divides
        from znicz_tpu.utils.config import root
        interpret = bool(root.common.engine.get("pallas_interpret",
                                                False))
        mesh = getattr(self.device, "mesh", None)
        self._ln_interpret = interpret
        self._ln_mesh = None
        self._ln_spec = None
        msd = getattr(self.input, "model_shard_dim", None)
        msd_axis = getattr(self.input, "model_shard_axis", None)
        ndim = len(self.input.shape)
        #: why the kernel did not engage (None = it did)
        refused = pallas_kernels.kernel_refusal(
            self.device, "pallas_layer_norm", interpret)
        if refused is None and mesh is not None and mesh.size > 1:
            # mesh-native path: a pallas_call has no GSPMD sharding
            # rule — un-shard_mapped it would gather the sharded
            # operand onto every device.  Run per-shard under
            # shard_map instead: batch rides the data axis, a ring-
            # sharded time axis (model_shard_dim) rides the model
            # axis; γ/β grad sums psum in the backward.
            # ``engine.pallas_shard_map = False`` restores the old
            # conservative gate (kernel off on multi-device meshes).
            spec, _ = kernel_shard_spec(
                mesh, ndim, model_shard_dim=msd,
                **({"model_axis": msd_axis} if msd_axis else {}))
            if not root.common.engine.get("pallas_shard_map", True):
                refused = "engine.pallas_shard_map is off"
            elif msd == ndim - 1:
                refused = "the feature axis is model-sharded"
            elif not spec_divides(mesh, self.input.shape, spec):
                refused = (f"shape {tuple(self.input.shape)} does not "
                           f"divide over mesh {dict(mesh.shape)}")
            else:
                self._ln_mesh, self._ln_spec = mesh, spec
        elif refused is None and msd is not None:
            # single device: a (trivially) model-sharded input keeps
            # the XLA path as before
            refused = "the input is model-sharded"
        self._pallas_ln = refused is None
        if self._pallas_ln:
            self.info("%s: layer-norm kernel%s%s", self.name,
                      ", per shard under shard_map"
                      if self._ln_mesh is not None else "",
                      ", INTERPRETED" if interpret else "")
        else:
            self.info("%s: XLA layer norm — %s", self.name, refused)
        self.init_vectors(self.input, self.output, self.weights,
                          self.bias)

    # xp-generic cores (shared by the oracle, XLA path and backward)
    def _normalize(self, xp, x):
        mu = x.mean(axis=-1, keepdims=True)
        var = ((x - mu) ** 2).mean(axis=-1, keepdims=True)
        return (x - mu) / xp.sqrt(var + self.eps), var

    def _forward(self, xp, x, gamma, beta):
        xhat, var = self._normalize(xp, x)
        y = gamma * xhat
        if beta is not None:
            y = y + beta
        return y, xhat, var

    def numpy_run(self) -> None:
        self.input.map_read()
        self.weights.map_read()
        beta = None
        if self.include_bias:
            self.bias.map_read()
            beta = self.bias.mem
        y, _, _ = self._forward(np, self.input.mem.astype(np.float32),
                                self.weights.mem, beta)
        self.output.map_invalidate()
        self.output.mem[...] = y

    def xla_run(self) -> None:
        beta = self.bias.devmem if self.include_bias else None
        if getattr(self, "_pallas_ln", False):
            from znicz_tpu.ops import pallas_kernels
            self.output.devmem = pallas_kernels.layer_norm_forward(
                self.input.devmem, self.weights.devmem, beta, self.eps,
                interpret=getattr(self, "_ln_interpret", False),
                mesh=getattr(self, "_ln_mesh", None),
                spec=getattr(self, "_ln_spec", None))
            return
        x = self.input.devmem.astype(jnp.float32)  # f32 statistics
        y, _, _ = self._forward(jnp, x, self.weights.devmem, beta)
        self.output.devmem = y


class GDLayerNorm(GradientDescentBase):
    """Analytic layer-norm backward (identical math on both paths):

    .. code-block:: text

        dβ = Σ err          dγ = Σ err·x̂
        dx̂ = err·γ
        dx = (dx̂ − mean(dx̂) − x̂·mean(dx̂·x̂)) / √(σ² + ε)
    """

    MATCHES = (LayerNorm,)
    REQUIRES_FORWARD_UNIT = True
    REQUIRES_INPUT = True

    def __init__(self, workflow, name=None, **kwargs):
        super().__init__(workflow, name=name, **kwargs)
        self.forward_unit: LayerNorm | None = None

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device=device, **kwargs)
        self.init_vectors(self.err_input, self.err_output, self.input,
                          self.output, self.weights, self.bias)

    def _backward(self, xp, x, err, gamma, has_bias: bool):
        fwd = self.forward_unit
        xhat, var = fwd._normalize(xp, x)
        reduce_axes = tuple(range(x.ndim - 1))
        grad_b = err.sum(axis=reduce_axes) if has_bias else None
        grad_g = (err * xhat).sum(axis=reduce_axes)
        dxhat = err * gamma
        dx = (dxhat - dxhat.mean(axis=-1, keepdims=True)
              - xhat * (dxhat * xhat).mean(axis=-1, keepdims=True)) \
            / xp.sqrt(var + fwd.eps)
        return dx, grad_g, grad_b

    def numpy_run(self) -> None:
        for vec in (self.err_output, self.input):
            vec.map_read()
        self.weights.map_write()
        has_bias = self.bias is not None and self.bias
        if has_bias:
            self.bias.map_write()
        dx, grad_g, grad_b = self._backward(
            np, self.input.mem.astype(np.float32),
            self.err_output.mem.astype(np.float32), self.weights.mem,
            has_bias)
        if self.need_err_input:
            self.err_input.map_invalidate()
            self.err_input.mem[...] = dx
        self._apply_weights_np(grad_g)
        if has_bias:
            self._apply_bias_np(grad_b)

    def xla_run(self) -> None:
        has_bias = self.bias is not None and self.bias
        if getattr(self.forward_unit, "_pallas_ln", False):
            from znicz_tpu.ops import pallas_kernels
            fwd = self.forward_unit
            dx, grad_g, grad_b = pallas_kernels.layer_norm_backward(
                self.input.devmem, self.err_output.devmem,
                self.weights.devmem, fwd.eps,
                with_beta=bool(has_bias),
                interpret=getattr(fwd, "_ln_interpret", False),
                mesh=getattr(fwd, "_ln_mesh", None),
                spec=getattr(fwd, "_ln_spec", None))
        else:
            dx, grad_g, grad_b = self._backward(
                jnp, self.input.devmem.astype(jnp.float32),
                self.err_output.devmem.astype(jnp.float32),
                self.weights.devmem, has_bias)
        if self.need_err_input:
            self.err_input.devmem = dx
        self._apply_weights_xla(grad_g)
        if has_bias:
            self._apply_bias_xla(grad_b)

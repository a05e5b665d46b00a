"""Local response normalization, AlexNet-style across-channel
(reference: ``znicz/normalization.py`` — ``LRNormalizerForward`` /
``LRNormalizerBackward``).

.. code-block:: text

    d_i = (k + α·Σ_{j∈window(i)} x_j²)        (window = n channels)
    y_i = x_i · d_i^{−β}

Defaults match the reference/AlexNet: α=1e-4, β=0.75, k=2, n=5.

The backward unit uses the exact analytic gradient on both paths
(numpy oracle and XLA) — XLA fuses the elementwise/window-sum chain
into the jit region, which benchmarking in the reference survey flags
as the right first choice before reaching for a Pallas kernel
(SURVEY.md §2.3).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from znicz_tpu.ops.nn_units import Forward, GradientDescentBase


def _band_matrix(c: int, n: int, half_low: int) -> np.ndarray:
    """(C, C) 0/1 matrix with ``M[j, i] = 1`` iff channel j is in
    channel i's window — ``arr @ M`` IS the sliding window sum."""
    idx = np.arange(c)
    lo = idx - half_low
    hi = idx + (n - 1 - half_low)
    j = idx[:, None]
    return ((j >= lo[None, :]) & (j <= hi[None, :])).astype(np.float32)


def _window_sum(xp, arr, n: int, half_low: int | None = None):
    """Sliding sum over the LAST (channel) axis:
    ``out_i = Σ_{k=i−half_low}^{i+(n−1−half_low)} arr_k`` (zero-padded).

    Default ``half_low = n//2`` (the forward's centered window).  The
    operator's adjoint — needed by the backward for even ``n``, where
    the window is asymmetric — is the same sum with
    ``half_low = n−1−n//2``.

    XLA path: the window is a matmul with the constant (C, C) band
    matrix — it rides the MXU in the conv-native layout instead of
    lowering to a sublane-crossing shifted-add chain (the profiled
    ~44%-of-step LRN fusions, profiles/r03_b384; at C=96 the GEMM is
    ~0.1 ms where the shift chain marshalled for milliseconds).  The
    numpy oracle keeps the explicit shifted-add form — an independent
    spec the matmul is tested against."""
    c = arr.shape[-1]
    if half_low is None:
        half_low = n // 2
    if xp is jnp:
        band = jnp.asarray(_band_matrix(c, n, half_low))
        return jnp.matmul(arr, band,
                          preferred_element_type=jnp.float32)
    half_high = n - 1 - half_low
    padded = xp.concatenate(
        [xp.zeros(arr.shape[:-1] + (half_low,), arr.dtype), arr,
         xp.zeros(arr.shape[:-1] + (half_high,), arr.dtype)], axis=-1)
    out = xp.zeros_like(arr)
    for off in range(n):
        out = out + padded[..., off:off + c]
    return out


def _pow_neg_beta(xp, d, beta: float):
    """``d ** (-beta)`` with sqrt/rsqrt chains for the quarter-power
    betas (0.25/0.5/0.75/1.0 — AlexNet's is 0.75).  The generic pow
    lowers to an exp·log chain on the TPU VPU; profiling the AlexNet
    step (profiles/r03_b384) put the LRN fusions at 0.2–0.4 effective
    TF/s, transcendental-bound.  sqrt and reciprocal are single fast
    VPU ops, and the chain is mathematically exact (same value up to
    rounding)."""
    if beta == 0.75:
        return (d * xp.sqrt(d)) ** -0.5 if xp is np \
            else jax.lax.rsqrt(d * xp.sqrt(d))
    if beta == 0.5:
        return d ** -0.5 if xp is np else jax.lax.rsqrt(d)
    if beta == 0.25:
        return xp.sqrt(d) ** -0.5 if xp is np \
            else jax.lax.rsqrt(xp.sqrt(d))
    if beta == 1.0:
        return 1.0 / d
    return d ** (-beta)


def _store_d(xp, d):
    """STORAGE cast for the LRN denominator tensor.

    The round-5 profile (profiles/bench_default) shows the four LRN
    band fusions at 27% of the AlexNet step, dominated by the f32
    ``d`` tensors XLA materializes and shares between forward and
    backward (446 MB + 287 MB at batch 384, written once, read once
    ≈ 1.5 GB/step at the bandwidth roof).  ``engine.lrn_d_bf16``
    stores them bf16 (the upcast fuses in-register): d = k + α·Σx²
    with k = 2 dominating, so bf16 rounding perturbs y by ≲ β·2⁻⁹ —
    the same order as the (already convergence-validated) bf16
    activation storage.  A/B lever; default follows the PERF.md
    round-5 measurement + BF16_CONVERGENCE band."""
    if xp is not jnp:
        return d
    from znicz_tpu.utils.config import root
    flag = root.common.engine.get("lrn_d_bf16", None)
    if flag is None:  # auto: ride the configured mixed-precision mode
        flag = str(root.common.precision_type) == "bfloat16"
    if not flag:
        return d
    return d.astype(jnp.bfloat16).astype(jnp.float32)


class LRNormalizerForward(Forward):
    """Across-channel LRN (weightless forward)."""

    def __init__(self, workflow, alpha: float = 1e-4, beta: float = 0.75,
                 k: float = 2.0, n: int = 5, name=None, **kwargs) -> None:
        super().__init__(workflow, name=name, **kwargs)
        self.alpha = float(alpha)
        self.beta = float(beta)
        self.k = float(k)
        self.n = int(n)

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device=device, **kwargs)
        if self.input is None or not self.input:
            raise AttributeError(f"{self}: input not linked yet")
        self.output.reset(np.zeros(self.input.shape,
                                   dtype=self.output_store_dtype))
        self.inherit_model_shard(self.output)
        self.init_vectors(self.input, self.output)

    def _forward(self, xp, x):
        d = self.k + self.alpha * _window_sum(xp, x * x, self.n)
        d = _store_d(xp, d)
        return x * _pow_neg_beta(xp, d, self.beta)

    def numpy_run(self) -> None:
        self.input.map_read()
        self.output.map_invalidate()
        self.output.mem[...] = self._forward(np, self.input.mem)

    def xla_run(self) -> None:
        # math in f32 even when activations are stored bf16: d is
        # k + tiny·Σx², all resolution is in low-order bits.  The
        # upcast fuses (in-register), costs no HBM traffic; the
        # devmem setter casts the result back to the storage dtype.
        x = self.input.devmem.astype(jnp.float32)
        self.output.devmem = self._forward(jnp, x)


class LRNormalizerBackward(GradientDescentBase):
    MATCHES = (LRNormalizerForward,)

    def __init__(self, workflow, name=None, **kwargs):
        kwargs.pop("learning_rate", None)  # weightless
        super().__init__(workflow, name=name, **kwargs)
        self.forward_unit: LRNormalizerForward | None = None

    def initialize(self, device=None, **kwargs) -> None:
        if self.input is None or not self.input:
            raise AttributeError(f"{self}: input not linked yet")
        super().initialize(device=device, **kwargs)
        self.init_vectors(self.err_input, self.err_output, self.input,
                          self.output)

    def numpy_run(self) -> None:
        """Analytic gradient (the oracle/spec):

        dy_i/dx_j = δ_ij·d_i^{−β} − 2αβ·x_i·x_j·d_i^{−β−1}·[j∈win(i)]
        """
        fwd = self.forward_unit
        for vec in (self.err_output, self.input):
            vec.map_read()
        x = self.input.mem.astype(np.float32)
        err = self.err_output.mem
        d = fwd.k + fwd.alpha * _window_sum(np, x * x, fwd.n)
        dmb = d ** (-fwd.beta)
        # t_i = err_i · x_i · d_i^{−β−1}; err_input_j gets
        # −2αβ·x_j·Σ_{i: j∈win(i)} t_i — the window operator's ADJOINT
        # (identical to the forward sum only for odd n)
        t = err * x * d ** (-fwd.beta - 1.0)
        self.err_input.map_invalidate()
        self.err_input.mem[...] = (
            err * dmb - 2.0 * fwd.alpha * fwd.beta * x
            * _window_sum(np, t, fwd.n, half_low=fwd.n - 1 - fwd.n // 2))

    def xla_run(self) -> None:
        fwd = self.forward_unit
        # f32 math on bf16-stored operands — see the forward's note
        x = self.input.devmem.astype(jnp.float32)
        err = self.err_output.devmem.astype(jnp.float32)
        d = fwd.k + fwd.alpha * _window_sum(jnp, x * x, fwd.n)
        d = _store_d(jnp, d)  # identical expression to the forward's
        # — XLA CSE shares ONE materialized d between fwd and bwd
        p = _pow_neg_beta(jnp, d, fwd.beta)
        t = err * x * (p / d)  # d^{−β−1} without a second pow
        self.err_input.devmem = (
            err * p - 2.0 * fwd.alpha * fwd.beta * x
            * _window_sum(jnp, t, fwd.n, half_low=fwd.n - 1 - fwd.n // 2))

"""An exit at every pass of a looped span, under a learned exit
distribution (Ouro, arXiv:2510.25741; PR 35).

The R states h¹ … h^R a looped span leaves (``PassSpan.states``,
(B, R, T, D), sequence leading) go through ONE head and ONE exit gate
as rows:

.. code-block:: text

    p^r = softmax(h^r W)                       (B, R, T, V)   ``output``
    λ^r = σ(h^r w_exit + b_exit)               per position, f32
    q_1 = λ¹;  q_r = λ^r ∏_{j<r}(1 − λ^j);  q_R = ∏_{j<R}(1 − λ^j)   ``exit_q``
    ℓ   = Σ_r q_r · CE(p^r, y) − β · H(q),     H(q) = −Σ_r q_r log q_r

Three units, as the softmax head has three:

- :class:`All2AllExits` (layer type ``loop_exits``) — head and gate.  The
  head's product takes the configured matmul inputs (bf16 in bf16
  mode); the gate, the exit distribution and the probabilities are f32.
  ``max_idx`` is the LAST exit's arg-max (what ``n_err`` counts).  A
  (B, T, D) input — a plain chain before it — is one exit;
- :class:`EvaluatorLoopExits` — the loss ℓ in f32, its derivative by
  the logits (``err_output`` = q_r (p^r − onehot) / rows, the combined
  softmax + cross-entropy form the repo's evaluators emit) and by the
  exit distribution (``err_exit_q`` = (CE_r + β (log q_r + 1)) / rows);
  ``epoch_loss`` accumulates Σ ℓ, so ``Decision.epoch_loss`` is its
  mean per position.  Per-exit Σ CE, Σ q_r, Σ H and the rows are
  summed on the device in ``All2AllExits.exit_stats`` and read ONCE per
  epoch by :meth:`All2AllExits.on_epoch_ended` into the gauges
  ``znicz_loop_exit{unit,exit,stat}``;
- :class:`GDAll2AllExits` — the pullback of (logits, q) by the two
  cotangents: the states' error (B, R, T, D), which the span joins to
  the passes' own, and the gradients of W, w_exit, b_exit — over all R
  exits at once, so each is updated once.

The numpy eager chain does not run a looped span, so none of the three
has a numpy path (refused by name).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from znicz_tpu.memory import Vector
from znicz_tpu.ops.evaluator import EvaluatorSoftmax
from znicz_tpu.ops.moe import GDMoE
from znicz_tpu.ops.nn_units import Forward


def exit_distribution(xp, lam):
    """q over axis 1 of the per-exit stop probabilities ``lam``
    (B, R, …): q_r = λ_r ∏_{j<r}(1 − λ_j), the last exit taking what
    is left."""
    keep = xp.cumprod(1.0 - lam, axis=1)
    before = xp.concatenate([xp.ones_like(keep[:, :1]), keep[:, :-1]],
                            axis=1)
    return xp.concatenate([(lam * before)[:, :-1], before[:, -1:]], axis=1)


def _numpy_refused(unit):
    raise NotImplementedError(
        f"{unit}: the exits of a looped span ('passes') have no numpy "
        f"path — the numpy eager chain does not run the passes")


class All2AllExits(Forward):
    """Head and exit gate over every pass's state (module docstring).
    ``weights`` is the head W (D, V)."""

    EXPORT_PARAMS = ("weights", "weights_exit", "bias_exit")
    #: the unit after a looped span that reads ALL its passes' states
    TAKES_PASSES = True
    #: probabilities stay f32 (they feed the evaluator's logarithms)
    output_store_dtype = np.dtype(np.float32)

    def __init__(self, workflow, output_sample_shape,
                 entropy_weight: float = 0.1, name=None, **kwargs) -> None:
        kwargs["include_bias"] = False          # the head has no bias
        super().__init__(workflow, name=name, **kwargs)
        self.neurons = int(np.prod(output_sample_shape))
        #: β, the weight of the exit distribution's entropy in the loss
        self.entropy_weight = float(entropy_weight)
        self.weights_exit = Vector(name=f"{self.name}.weights_exit")
        self.bias_exit = Vector(name=f"{self.name}.bias_exit")
        self.exit_q = Vector(name=f"{self.name}.exit_q", batch_major=True)
        self.max_idx = Vector(name=f"{self.name}.max_idx",
                              batch_major=True)
        #: [Σ CE_r (R), Σ q_r (R), Σ H, rows], kept on the device by the
        #: evaluator, read once per epoch
        self.exit_stats = Vector(name=f"{self.name}.exit_stats")
        #: the last epoch's means, for whoever asks after the gauges
        self.last_exit_stats: dict | None = None
        self._traced_vjp = None

    @property
    def n_exits(self) -> int:
        return self.input.shape[1] if len(self.input.shape) == 4 else 1

    def unserved(self) -> str | None:
        return super().unserved() or (
            "is a multi-exit head (loop_exits); serving has no exit "
            "rule yet — the exit gate and the exit distribution exist "
            "on the training path only (ROADMAP R7, serving half)")

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device=device, **kwargs)
        if self.input is None or not self.input:
            raise AttributeError(f"{self}: input not linked yet")
        if self.device.is_host_only:
            _numpy_refused(self)
        if len(self.input.shape) not in (3, 4):
            raise ValueError(f"{self}: expected (batch, passes, time, "
                             f"features) states, got {self.input.shape}")
        b, t, d = (self.input.shape[0], *self.input.shape[-2:])
        r = self.n_exits
        if not self.weights:
            self.weights.reset(self.fill_array(
                (d, self.neurons), self.weights_filling,
                self.weights_stddev, fan_in=d))
        if not self.weights_exit:
            self.weights_exit.reset(self.fill_array(
                (d,), self.weights_filling, self.weights_stddev,
                fan_in=d))
        if not self.bias_exit:
            self.bias_exit.reset(np.zeros(1, np.float32))
        self.output.reset(np.zeros((b, r, t, self.neurons), np.float32))
        self.exit_q.reset(np.zeros((b, r, t), np.float32))
        self.max_idx.reset(np.zeros((b, t), np.int32))
        if not self.exit_stats:
            self.exit_stats.reset(np.zeros(2 * r + 2, np.float32))
        # the slots the default partition rules do not name
        from znicz_tpu.parallel import partition
        self.partition_leaf("exit_q", partition.BATCH)
        for slot in ("bias_exit", "exit_stats"):
            self.partition_leaf(slot, partition.REPLICATED)
        self.init_vectors(self.input, self.output, self.weights,
                          self.weights_exit, self.bias_exit, self.exit_q,
                          self.max_idx, self.exit_stats)

    def forward_args(self) -> tuple:
        return (self.input.devmem, self.weights.devmem,
                self.weights_exit.devmem, self.bias_exit.devmem)

    def xla_forward(self, h, w, w_exit, b_exit):
        """``(logits, q)``: (B, R, T, V) and (B, R, T), f32."""
        b, t, d = (h.shape[0], *h.shape[-2:])
        h32 = h.astype(jnp.float32).reshape(b, -1, t, d)
        logits = self.mxu_dot(jnp, h32.reshape(-1, d), w).reshape(
            h32.shape[:3] + (-1,)).astype(jnp.float32)
        # the gate is a matrix-vector product of f32 by f32
        z = jnp.einsum("brtd,d->brt", h32, w_exit,
                       precision=jax.lax.Precision.HIGHEST) + b_exit[0]
        return logits, exit_distribution(jnp, jax.nn.sigmoid(z))

    def xla_run(self) -> None:
        args = self.forward_args()
        if self.output._tracing:
            (logits, q), self._traced_vjp = jax.vjp(self.xla_forward,
                                                    *args)
        else:
            self._traced_vjp = None
            logits, q = self.xla_forward(*args)
        self.output.devmem = jax.nn.softmax(logits, axis=-1)
        self.exit_q.devmem = q
        self.max_idx.devmem = jnp.argmax(logits[:, -1], axis=-1).astype(
            jnp.int32)

    def numpy_run(self) -> None:
        _numpy_refused(self)

    def on_epoch_ended(self) -> None:
        """Read the device totals once, publish them, start over."""
        from znicz_tpu.observe import metrics as obs_metrics
        stats = self.exit_stats
        stats.map_read()
        r = self.n_exits
        total = np.asarray(stats.mem, np.float64)
        rows = total[-1]
        if rows:
            self.last_exit_stats = {
                "loss": (total[:r] / rows).tolist(),
                "mass": (total[r:2 * r] / rows).tolist(),
                "entropy": float(total[2 * r] / rows)}
            if obs_metrics.enabled():
                for stat in ("loss", "mass"):
                    for i, value in enumerate(self.last_exit_stats[stat]):
                        obs_metrics.loop_exit(self.name, i, stat).set(
                            value)
                obs_metrics.loop_exit(self.name, "entropy", "value").set(
                    self.last_exit_stats["entropy"])
        stats.map_invalidate()
        stats.mem[...] = 0.0      # uploaded on the next region fire


class GDAll2AllExits(GDMoE):
    """Backward of :class:`All2AllExits`: the stashed pullback of
    (logits, q) by the evaluator's two cotangents."""

    MATCHES = (All2AllExits,)
    EXTRA = ("weights_exit", "bias_exit")
    HAS_AUX = False

    def __init__(self, workflow, name=None, **kwargs):
        super().__init__(workflow, name=name, **kwargs)
        self.err_exit_q: Vector | None = None    # linked: the evaluator's

    def _cotangent(self, xp, err):
        return err, self.err_exit_q.devmem.astype(jnp.float32)

    def numpy_run(self) -> None:
        _numpy_refused(self)


class EvaluatorLoopExits(EvaluatorSoftmax):
    """The expected cross-entropy under the exit distribution less β
    times its entropy (module docstring)."""

    def __init__(self, workflow, name: str | None = None, **kwargs) -> None:
        super().__init__(workflow, name=name, **kwargs)
        self.exit_q: Vector | None = None        # linked: the head's
        self.exit_stats: Vector | None = None
        #: the head, for β
        self.exits_unit: All2AllExits | None = None
        self.err_exit_q = Vector(name=f"{self.name}.err_exit_q",
                                 batch_major=True)

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device=device, **kwargs)
        if self.compute_confusion:
            raise NotImplementedError(
                f"{self}: no confusion matrix over the exits of a looped "
                f"span")
        if not self.err_exit_q:
            self.err_exit_q.reset(np.zeros(self.exit_q.shape, np.float32))
        from znicz_tpu.parallel import partition
        self.partition_leaf("err_exit_q", partition.BATCH)
        self.init_vectors(self.err_exit_q, self.exit_q, self.exit_stats)

    def numpy_run(self) -> None:
        _numpy_refused(self)

    def xla_run(self) -> None:
        p = self.output.devmem                        # (B, R, T, V) f32
        q = self.exit_q.devmem                        # (B, R, T)
        t = self.labels.devmem                        # (B, T)
        b, r, n_t, _ = p.shape
        beta = np.float32(self.exits_unit.entropy_weight)
        mask, valid = self._valid_mask(jnp, b * n_t, n_t)
        mask = mask.reshape(b, 1, n_t)
        denom = jnp.maximum(valid, 1).astype(jnp.float32)
        hit = t[:, None, :, None] == jnp.arange(p.shape[-1])
        ce = -jnp.log(jnp.maximum(jnp.take_along_axis(
            p, jnp.broadcast_to(t[:, None, :, None], (b, r, n_t, 1)),
            axis=-1)[..., 0], 1e-30))                 # (B, R, T)
        log_q = jnp.log(jnp.maximum(q, 1e-30))
        entropy = -(q * log_q).sum(axis=1, keepdims=True)   # (B, 1, T)
        err = mask[..., None] * q[..., None] \
            * jnp.where(hit, p - 1.0, p) / denom
        err_q = mask * (ce + beta * (log_q + 1.0)) / denom
        grad_inj = self._inject(jnp, 1)
        if grad_inj is not None:
            err = err + grad_inj.astype(err.dtype)
        self.err_output.devmem = err
        self.err_exit_q.devmem = err_q
        max_idx = self.max_idx.devmem
        n_err = jnp.sum((max_idx != t) & mask[:, 0]).astype(jnp.int32)
        self.n_err.devmem = n_err
        cls = int(self.minibatch_class)
        self.epoch_n_err.devmem = self.epoch_n_err.devmem.at[cls].add(n_err)
        loss_sum = jnp.sum(mask * ((q * ce).sum(axis=1, keepdims=True)
                                   - beta * entropy)).astype(jnp.float32)
        loss_inj = self._inject(jnp, 0)
        if loss_inj is not None:
            loss_sum = loss_sum + loss_inj
        loss_ok = jnp.isfinite(loss_sum)
        # a non-finite step must not poison the epoch accumulators
        self.epoch_loss.devmem = self.epoch_loss.devmem.at[cls].add(
            jnp.where(loss_ok, loss_sum, 0.0))
        stats = jnp.concatenate([
            (mask * ce).sum(axis=(0, 2)), (mask * q).sum(axis=(0, 2)),
            jnp.stack([(mask * entropy).sum(),
                       mask.sum().astype(jnp.float32)])])
        self.exit_stats.devmem = self.exit_stats.devmem + jnp.where(
            loss_ok, stats, 0.0)
        self._seed_step_flags(jnp, loss_ok)

"""Sparse mixture-of-experts feed-forward layer (ROADMAP R0/R1; OLMoE,
arXiv:2409.02060) — beyond the 2015 reference, like the attention
family it follows.

``MoE`` maps (B, T, D) → (B, T, D):

.. code-block:: text

    m   = RMSNorm(x)                        (pre_norm="rms", else x)
    p   = softmax_E(m @ W_r)                 router, float32 throughout
    top = the top_k largest p_e per token    (NOT renormalised unless
                                              norm_topk)
    f   = Σ_{e ∈ top} p_e · W_down,e (silu(W_gate,e m) ⊙ W_up,e m)
    y   = x + f                              (residual=True, else f)

**Dropless**: every token is computed by all ``top_k`` of its experts —
no capacity factor, no dropped token.  Shapes stay static although the
rows an expert sees change every step: the N·k (token, expert) pairs
are sorted by expert, the rows gathered in that order, and three
GROUPED matmuls (one weight slab per expert, ``group_sizes`` = rows
per expert, a traced value) run over them; the results are put back in
token order, weighted and summed.  The grouped matmul
(:func:`grouped_matmul`) is JAX's own Pallas kernel
(``jax.experimental.pallas.ops.tpu.megablox``) on a TPU — 27% faster
than ``jax.lax.ragged_dot`` at the OLMoE shapes on the chip (PERF.md
§6, PR 25) — and ``ragged_dot``, the XLA core, elsewhere; the gate is
``engine.moe_grouped_matmul`` ("auto", like the flash kernels').  Both
permutations are GATHERS in both directions (:func:`_dispatch`,
:func:`_unpermute`: a permutation's adjoint is its inverse), so no
scatter-add runs in the step.

Two auxiliary losses of the router come out of the pure forward beside
``y``: the load-balancing loss ``E · Σ_e (rows_e / N) · mean_n p_ne``
(the model's own ``load_balancing_loss_func``: 8 = ``top_k`` under
uniform routing) and the z-loss ``mean_n logsumexp(logits_n)²``.  The
backward feeds ``aux_loss_weight`` and ``z_loss_weight`` in as their
cotangents, so the chain's ``err_output`` protocol is unchanged and the
evaluator's loss stays the cross-entropy.

What routing did is kept ON THE DEVICE (``moe_stats``: rows per expert,
per-step extremes, both losses, summed over steps) and read once per
epoch by :meth:`MoE.on_epoch_ended` into the gauges
``znicz_moe_expert_tokens`` and ``znicz_moe_aux_loss`` — no host read
per step.

Backward (``GDMoE``): the stashed ``jax.vjp`` of the forward on the XLA
path (as the attention unit does), validated against the analytic
numpy oracle, which loops over the experts.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from znicz_tpu.memory import Vector
from znicz_tpu.ops.nn_units import Forward, GradientDescentBase
from znicz_tpu.ops.rms_norm import rms_norm, rms_norm_backward

#: slots of ``moe_stats`` after the E per-expert row totals
_LB, _Z, _STEPS, _MAX, _MIN = range(5)


# ----------------------------------------------------------------------
# the device path's three primitives
# ----------------------------------------------------------------------
#: (rows, contraction, columns) tile of the Pallas grouped matmul: the
#: fastest of nine on the chip at the OLMoE shapes, forward and both
#: gradients (PERF.md §6, PR 25); larger tiles overflow scoped VMEM
GMM_TILING = (256, 1024, 1024)


def gmm_tiling(rows: int, k: int, n: int) -> tuple:
    """``GMM_TILING`` cut to a small problem (the tests'): the kernels
    want the row tile to divide the rows."""
    return (min(GMM_TILING[0], rows), min(GMM_TILING[1], k),
            min(GMM_TILING[2], n))


def _megablox():
    # the package's ``gmm`` attribute is its custom-vjp wrapper, which
    # hides the module of that name — and whose backward would hand
    # back the weight gradient in the weights' bf16
    import importlib
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm_pallas(lhs, rhs, group_sizes, interpret):
    return _megablox().gmm(lhs, rhs.astype(lhs.dtype), group_sizes,
                           jnp.float32, gmm_tiling(*lhs.shape,
                                                   rhs.shape[2]),
                           interpret=interpret)


def _gmm_pallas_fwd(lhs, rhs, group_sizes, interpret):
    return (_gmm_pallas(lhs, rhs, group_sizes, interpret),
            (lhs, rhs, group_sizes))


def _gmm_pallas_bwd(interpret, residual, grad):
    lhs, rhs, group_sizes = residual
    kernels = _megablox()
    grad = grad.astype(lhs.dtype)
    tiling = gmm_tiling(*lhs.shape, rhs.shape[2])
    d_lhs = kernels.gmm(grad, rhs.astype(lhs.dtype), group_sizes,
                        jnp.float32, tiling, transpose_rhs=True,
                        interpret=interpret)
    d_rhs = kernels.tgmm(lhs.swapaxes(0, 1), grad, group_sizes,
                         jnp.float32, tiling,
                         num_actual_groups=rhs.shape[0],
                         interpret=interpret)
    return d_lhs.astype(lhs.dtype), d_rhs.astype(rhs.dtype), None


_gmm_pallas.defvjp(_gmm_pallas_fwd, _gmm_pallas_bwd)


@functools.partial(jax.jit, static_argnums=(3, 4))
def grouped_matmul(lhs, rhs, group_sizes, kernel: bool = False,
                   interpret: bool = False):
    """(M, K) rows in E contiguous groups × (E, K, N) f32 slabs →
    (M, N) f32: row r of group e is multiplied by ``rhs[e]``, the slabs
    cast to the rows' dtype on the way in.  ``kernel`` runs JAX's
    Pallas grouped matmul (``megablox``; the weight gradient comes back
    in f32), else ``jax.lax.ragged_dot``, the XLA core.  Jitted so that
    the three call sites of a layer, and every layer, lower it once
    (PERF.md §6, PR 24: lowering is a set-up cost the compile cache
    does not hide)."""
    if kernel:
        return _gmm_pallas(lhs, rhs, group_sizes, interpret)
    return jax.lax.ragged_dot(lhs, rhs.astype(lhs.dtype), group_sizes,
                              preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(m, order, inverse, dtype):
    """Rows of ``m`` (N, D) in expert order: row j is token
    ``order[j] // k`` (``order`` sorts the N·k flat (token, slot)
    pairs by expert), cast to ``dtype``."""
    k = order.shape[0] // m.shape[0]
    return jnp.take(m.astype(dtype), order // k, axis=0)


def _dispatch_fwd(m, order, inverse, dtype):
    return _dispatch(m, order, inverse, dtype), (order, inverse, m.shape)


def _dispatch_bwd(dtype, residual, grad):
    order, inverse, (n, d) = residual
    k = order.shape[0] // n
    # the adjoint of a gather by a permutation is the gather by its
    # inverse; a token's k copies then sum, in f32
    back = jnp.take(grad, inverse, axis=0).astype(jnp.float32)
    return back.reshape(n, k, d).sum(axis=1), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _unpermute(rows, inverse, order):
    """``rows`` (N·k, D) back from expert order to (token, slot)
    order."""
    return jnp.take(rows, inverse, axis=0)


def _unpermute_fwd(rows, inverse, order):
    return _unpermute(rows, inverse, order), (inverse, order)


def _unpermute_bwd(residual, grad):
    _, order = residual
    return jnp.take(grad, order, axis=0), None, None


_unpermute.defvjp(_unpermute_fwd, _unpermute_bwd)


def _silu(xp, x):
    return x / (1.0 + xp.exp(-x))


class MoE(Forward):
    """Dropless top-k mixture of SwiGLU experts (module docstring)."""

    EXPORT_PARAMS = ("weights", "weights_gate", "weights_up",
                     "weights_down", "gain_norm")

    def __init__(self, workflow, n_experts: int, top_k: int, width: int,
                 norm_topk: bool = False, pre_norm: str | None = None,
                 residual: bool = False, aux_loss_weight: float = 0.0,
                 z_loss_weight: float = 0.0, norm_eps: float = 1e-5,
                 name=None, **kwargs) -> None:
        kwargs.setdefault("weights_filling", "xavier")
        kwargs["include_bias"] = False
        super().__init__(workflow, name=name, **kwargs)
        self.n_experts, self.top_k = int(n_experts), int(top_k)
        self.width = int(width)
        if not 1 <= self.top_k <= self.n_experts:
            raise ValueError(f"{self}: top_k {top_k} of {n_experts} "
                             f"experts")
        if pre_norm not in (None, "rms"):
            raise ValueError(f"pre_norm must be None or 'rms', got "
                             f"{pre_norm!r}")
        self.norm_topk = bool(norm_topk)
        self.pre_norm = pre_norm
        self.residual = bool(residual)
        self.aux_loss_weight = float(aux_loss_weight)
        self.z_loss_weight = float(z_loss_weight)
        self.norm_eps = float(norm_eps)
        self.weights_gate = Vector(name=f"{self.name}.weights_gate")
        self.weights_up = Vector(name=f"{self.name}.weights_up")
        self.weights_down = Vector(name=f"{self.name}.weights_down")
        self.gain_norm = Vector(name=f"{self.name}.gain_norm")
        #: [rows per expert (E) | lb loss, z loss, steps, per-step max
        #: and min rows of an expert], summed on the device
        self.moe_stats = Vector(name=f"{self.name}.moe_stats")
        #: what the router did in the last step: its (N, E) logits and
        #: the (N, top_k) experts chosen — what a check against a plain
        #: reference needs, which must not re-decide near-ties
        self.router_logits = Vector(name=f"{self.name}.router_logits",
                                    batch_major=True)
        self.last_choice = Vector(name=f"{self.name}.last_choice",
                                  batch_major=True)
        #: pullback stashed by xla_run for the GD pair (as attention)
        self._traced_vjp = None

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device=device, **kwargs)
        if self.input is None or not self.input:
            raise AttributeError(f"{self}: input not linked yet")
        if len(self.input.shape) != 3:
            raise ValueError(f"{self}: expected (batch, time, features) "
                             f"input, got {self.input.shape}")
        b, t, d = self.input.shape
        e, f = self.n_experts, self.width
        if not self.weights:                       # the router
            self.weights.reset(self.fill_array(
                (d, e), self.weights_filling, self.weights_stddev,
                fan_in=d))
        for vec, shape, fan_in in (
                (self.weights_gate, (e, d, f), d),
                (self.weights_up, (e, d, f), d),
                (self.weights_down, (e, f, d), f)):
            if not vec:
                vec.reset(self.fill_array(shape, self.weights_filling,
                                          self.weights_stddev,
                                          fan_in=fan_in))
        if self.pre_norm and not self.gain_norm:
            self.gain_norm.reset(np.ones(d, np.float32))
        if not self.moe_stats:
            self.moe_stats.reset(np.zeros(e + 5, np.float32))
        self.output.reset(np.zeros((b, t, d),
                                   dtype=self.output_store_dtype))
        self.router_logits.reset(np.zeros((b, t, e), np.float32))
        self.last_choice.reset(np.zeros((b, t, self.top_k), np.int32))
        self.inherit_model_shard(self.output)
        from znicz_tpu.ops import pallas_kernels
        from znicz_tpu.utils.config import root
        interpret = bool(root.common.engine.get("pallas_interpret",
                                                False))
        #: why the Pallas grouped matmul did not engage (None = it did)
        refused = pallas_kernels.kernel_refusal(
            self.device, "moe_grouped_matmul", interpret)
        mesh = getattr(self.device, "mesh", None)
        if refused is None and mesh is not None and mesh.size > 1:
            refused = "expert parallelism over a mesh is not built"
        rows = b * t * self.top_k
        if refused is None and rows % min(GMM_TILING[0], rows):
            refused = (f"{rows} rows do not divide by the kernel's "
                       f"row tile {GMM_TILING[0]}")
        self._gmm_kernel = refused is None
        self._gmm_interpret = interpret
        self.info("%s: %d experts top %d, dropless; grouped matmul %s "
                  "over %d rows in %d groups, %d x %d (gate, up) and "
                  "%d x %d (down)", self.name, e, self.top_k,
                  f"megablox kernel, tiles {GMM_TILING}"
                  + (", INTERPRETED" if interpret else "")
                  if self._gmm_kernel
                  else f"jax.lax.ragged_dot ({refused})",
                  rows, e, d, f, f, d)
        self.init_vectors(self.input, self.output, self.weights,
                          self.weights_gate, self.weights_up,
                          self.weights_down, self.gain_norm,
                          self.moe_stats, self.router_logits,
                          self.last_choice)

    # -- pure forward (jnp; the backward vjp's this) --------------------
    def forward_args(self) -> tuple:
        return (self.input.devmem, self.weights.devmem,
                self.weights_gate.devmem, self.weights_up.devmem,
                self.weights_down.devmem,
                self.gain_norm.devmem if self.gain_norm else None)

    def route(self, xp, m, w_r):
        """``(logits, p, top_p, top_e)`` for (N, D) rows — float32
        throughout, on the device at the highest matmul precision, so
        that the choice of experts does not ride bf16 rounding."""
        if xp is jnp:
            logits = jnp.dot(m, w_r, precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
            p = jax.nn.softmax(logits, axis=-1)
            top_p, top_e = jax.lax.top_k(p, self.top_k)
        else:
            logits = m @ w_r
            z = np.exp(logits - logits.max(axis=-1, keepdims=True))
            p = z / z.sum(axis=-1, keepdims=True)
            # ties go to the lower index, as lax.top_k
            top_e = np.argsort(-p, axis=-1, kind="stable")[:, :self.top_k]
            top_p = np.take_along_axis(p, top_e, axis=-1)
        return logits, p, top_p, top_e

    def aux_losses(self, xp, logits, p, counts):
        """(load-balancing loss, z-loss); ``counts`` rows per expert."""
        n = logits.shape[0]
        lb = self.n_experts * ((counts / n) * p.mean(axis=0)).sum()
        top = logits.max(axis=-1)
        lse = top + xp.log(xp.exp(logits - top[:, None]).sum(axis=-1))
        return lb, (lse * lse).mean()

    def xla_forward(self, x, w_r, w_g, w_u, w_d, g_norm=None):
        """``((y, (lb, z)), (counts, logits, top_e))``: the output and
        the two auxiliary losses (differentiable); rows per expert, the
        router's logits and its choice (not)."""
        b, t, d = x.shape
        n, k, e = b * t, self.top_k, self.n_experts
        x32 = x.astype(jnp.float32)
        m = (x32 if g_norm is None
             else rms_norm(jnp, x32, g_norm, self.norm_eps)).reshape(n, d)
        logits, p, top_p, top_e = self.route(jnp, m, w_r)
        if self.norm_topk:
            top_p = top_p / top_p.sum(axis=-1, keepdims=True)
        flat_e = top_e.reshape(n * k)
        order = jnp.argsort(flat_e, stable=True).astype(jnp.int32)
        inverse = jnp.argsort(order).astype(jnp.int32)
        # rows per expert by comparison, not by a scatter-add (which a
        # TPU serialises)
        sizes = (flat_e[:, None] == jnp.arange(e)[None, :]).sum(
            axis=0, dtype=jnp.int32)
        dt = self.mxu_dtype or jnp.float32
        path = (getattr(self, "_gmm_kernel", False),
                getattr(self, "_gmm_interpret", False))
        rows = _dispatch(m, order, inverse, dt)
        gate = grouped_matmul(rows, w_g, sizes, *path)
        up = grouped_matmul(rows, w_u, sizes, *path)
        hidden = (_silu(jnp, gate) * up).astype(dt)
        out = grouped_matmul(hidden, w_d, sizes, *path)
        out = _unpermute(out, inverse, order).reshape(n, k, d)
        y = (out * top_p[..., None]).sum(axis=1).reshape(b, t, d)
        if self.residual:
            y = x32 + y
        counts = jax.lax.stop_gradient(sizes.astype(jnp.float32))
        return ((y, self.aux_losses(jnp, logits, p, counts)),
                (counts, jax.lax.stop_gradient(logits), top_e))

    def _record(self, lb, z, counts, logits, top_e) -> None:
        self.router_logits.devmem = logits.reshape(
            self.router_logits.shape)
        self.last_choice.devmem = top_e.astype(jnp.int32).reshape(
            self.last_choice.shape)
        tail = jnp.stack([lb, z, jnp.float32(1.0), counts.max(),
                          counts.min()])
        self.moe_stats.devmem = self.moe_stats.devmem \
            + jnp.concatenate([counts, tail]).astype(jnp.float32)

    def xla_run(self) -> None:
        args = self.forward_args()
        if not self.output._tracing:
            self._traced_vjp = None
            (y, (lb, z)), routed = self.xla_forward(*args)
        else:
            (y, (lb, z)), self._traced_vjp, routed = jax.vjp(
                self.xla_forward, *args, has_aux=True)
        self.output.devmem = y
        self._record(lb, z, *routed)

    # -- the epoch-end read ---------------------------------------------
    def on_epoch_ended(self) -> None:
        """Read the device totals once, publish them, start over."""
        from znicz_tpu.observe import metrics as obs_metrics
        stats = self.moe_stats
        if not stats:
            return
        stats.map_read()
        e = self.n_experts
        tail = np.asarray(stats.mem[e:], np.float64)
        steps = max(float(tail[_STEPS]), 1.0)
        if obs_metrics.enabled() and tail[_STEPS]:
            per_step = float(np.sum(stats.mem[:e])) / steps / e
            for stat, value in (("max", tail[_MAX] / steps),
                                ("mean", per_step),
                                ("min", tail[_MIN] / steps)):
                obs_metrics.moe_expert_tokens(self.name, stat).set(value)
            obs_metrics.moe_aux_loss(self.name, "load_balance").set(
                tail[_LB] / steps)
            obs_metrics.moe_aux_loss(self.name, "z").set(
                tail[_Z] / steps)
        stats.map_invalidate()
        stats.mem[...] = 0.0      # uploaded on the next region fire

    # -- numpy oracle ---------------------------------------------------
    def _forward_np(self, x):
        """``(y, cache)``; a loop over the experts, each computing the
        rows routed to it."""
        b, t, d = x.shape
        n = b * t
        m = (rms_norm(np, x, self.gain_norm.mem, self.norm_eps)
             if self.pre_norm else x).reshape(n, d)
        logits, p, raw_p, top_e = self.route(np, m, self.weights.mem)
        top_p = raw_p / raw_p.sum(axis=-1, keepdims=True) \
            if self.norm_topk else raw_p
        f = np.zeros((n, d), np.float32)
        per_expert = []
        for e in range(self.n_experts):
            rows, slots = np.nonzero(top_e == e)
            me = m[rows]
            gate = me @ self.weights_gate.mem[e]
            up = me @ self.weights_up.mem[e]
            hidden = _silu(np, gate) * up
            out = hidden @ self.weights_down.mem[e]
            np.add.at(f, rows, out * top_p[rows, slots][:, None])
            per_expert.append((rows, slots, me, gate, up, hidden, out))
        y = f.reshape(b, t, d)
        if self.residual:
            y = x + y
        counts = np.asarray([len(pe[0]) for pe in per_expert],
                            np.float32)
        return y, (m, logits, p, raw_p, top_p, top_e, per_expert, counts)

    def numpy_run(self) -> None:
        for vec in (self.input, self.weights, self.weights_gate,
                    self.weights_up, self.weights_down, self.gain_norm):
            if vec:
                vec.map_read()
        y, cache = self._forward_np(self.input.mem.astype(np.float32))
        self.output.map_invalidate()
        self.output.mem[...] = y
        logits, p, counts = cache[1], cache[2], cache[-1]
        for vec, value in ((self.router_logits, logits),
                           (self.last_choice, cache[5])):
            vec.map_invalidate()
            vec.mem[...] = value.reshape(vec.shape)
        lb, z = self.aux_losses(np, logits, p, counts)
        self.moe_stats.map_write()
        self.moe_stats.mem[...] += np.concatenate(
            [counts, [lb, z, 1.0, counts.max(), counts.min()]]
        ).astype(np.float32)


class GDMoE(GradientDescentBase):
    """Expert-layer backward: the forward's stashed ``jax.vjp`` with
    ``(err_output, (aux_loss_weight, z_loss_weight))`` as the
    cotangent; the numpy oracle is analytic."""

    MATCHES = (MoE,)
    REQUIRES_FORWARD_UNIT = True
    REQUIRES_INPUT = True
    #: parameters beside the router (``weights``, the base's own pair)
    EXTRA = ("weights_gate", "weights_up", "weights_down", "gain_norm")

    def __init__(self, workflow, name=None, **kwargs):
        super().__init__(workflow, name=name, **kwargs)
        self.forward_unit: MoE | None = None
        for attr in self.EXTRA:
            setattr(self, f"accumulated_gradient_{attr}",
                    Vector(name=f"{self.name}.acc_{attr}"))

    def _extra_pairs(self) -> list:
        """``(attr, parameter Vector, its accumulator)`` beside the
        router."""
        fwd = self.forward_unit
        return [(attr, getattr(fwd, attr),
                 getattr(self, f"accumulated_gradient_{attr}"))
                for attr in self.EXTRA if getattr(fwd, attr)]

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device=device, **kwargs)
        pairs = self._extra_pairs()
        if self.gradient_moment:
            for _, param, acc in pairs:
                self._alloc_accumulator(acc, param)
        self.init_vectors(self.err_input, self.err_output, self.input,
                          self.output, self.weights,
                          *(v for _, param, acc in pairs
                            for v in (param, acc)))

    def _micro_accum_params(self):
        pairs = super()._micro_accum_params()
        if self.forward_unit is not None:
            pairs.extend((attr, param)
                         for attr, param, _ in self._extra_pairs())
        return pairs

    def region_vectors(self):
        vecs = super().region_vectors()
        seen = {id(v) for v in vecs}
        for _, param, acc in self._extra_pairs():
            for vec in (param, acc):
                if vec and id(vec) not in seen:
                    vecs.append(vec)
        return vecs

    def _cotangent(self, xp, err):
        fwd = self.forward_unit
        return (err, (xp.float32(fwd.aux_loss_weight),
                      xp.float32(fwd.z_loss_weight)))

    def xla_run(self) -> None:
        fwd = self.forward_unit
        # the stash is valid only inside the trace that made it (see
        # GDMultiHeadAttention.xla_run)
        vjp = fwd._traced_vjp if self.err_output._tracing else None
        fwd._traced_vjp = None
        if vjp is None:
            _, vjp, _ = jax.vjp(fwd.xla_forward, *fwd.forward_args(),
                                has_aux=True)
        gx, g_router, *g_extra = vjp(self._cotangent(
            jnp, self.err_output.devmem.astype(jnp.float32)))
        if self.need_err_input:
            self.err_input.devmem = gx
        self._apply_weights_xla(g_router)
        grads = dict(zip(self.EXTRA, g_extra))
        for attr, param, acc in self._extra_pairs():
            self._apply_weights_xla(grads[attr], vec=param, acc_vec=acc)

    def numpy_run(self) -> None:
        """Analytic backward (the oracle/spec)."""
        fwd = self.forward_unit
        for vec in (self.err_output, self.input):
            vec.map_read()
        self.weights.map_write()
        for _, param, _ in self._extra_pairs():
            param.map_write()
        x = self.input.mem.astype(np.float32)
        b, t, d = x.shape
        n, e_n = b * t, fwd.n_experts
        _, (m, logits, p, raw_p, top_p, top_e, per_expert, counts) = \
            fwd._forward_np(x)
        dy = self.err_output.mem.astype(np.float32).reshape(n, d)
        grads = {attr: np.zeros_like(getattr(fwd, attr).mem)
                 for attr in ("weights_gate", "weights_up",
                              "weights_down")}
        dm = np.zeros_like(m)
        dtop = np.zeros_like(top_p)
        for e, (rows, slots, me, gate, up, hidden, out) in \
                enumerate(per_expert):
            dye = dy[rows]
            dtop[rows, slots] = (out * dye).sum(axis=-1)
            dout = dye * top_p[rows, slots][:, None]
            grads["weights_down"][e] = hidden.T @ dout
            dhidden = dout @ fwd.weights_down.mem[e].T
            sig = 1.0 / (1.0 + np.exp(-gate))
            dgate = dhidden * up * sig * (1.0 + gate * (1.0 - sig))
            dup = dhidden * gate * sig
            grads["weights_gate"][e] = me.T @ dgate
            grads["weights_up"][e] = me.T @ dup
            np.add.at(dm, rows, dgate @ fwd.weights_gate.mem[e].T
                      + dup @ fwd.weights_up.mem[e].T)
        if fwd.norm_topk:      # back through top_p = raw_p / Σ raw_p
            total = raw_p.sum(axis=-1, keepdims=True)
            dtop = (dtop - (dtop * top_p).sum(axis=-1, keepdims=True)) \
                / total
        dp = np.zeros_like(p)
        np.put_along_axis(dp, top_e, dtop, axis=-1)
        # the load-balancing loss reaches p through its column means
        dp += fwd.aux_loss_weight * e_n * (counts / n)[None, :] / n
        dlogits = p * (dp - (dp * p).sum(axis=-1, keepdims=True))
        top = logits.max(axis=-1)
        lse = top + np.log(np.exp(logits - top[:, None]).sum(axis=-1))
        dlogits += fwd.z_loss_weight * (2.0 * lse / n)[:, None] * p
        grad_router = m.T @ dlogits
        dm += dlogits @ self.weights.mem.T
        dx = dm.reshape(b, t, d)
        if fwd.pre_norm:
            dx, grads["gain_norm"] = rms_norm_backward(
                np, x, fwd.gain_norm.mem, fwd.norm_eps, dx)
        if fwd.residual:
            dx = dx + dy.reshape(b, t, d)
        if self.need_err_input:
            self.err_input.map_invalidate()
            self.err_input.mem[...] = dx
        self._apply_weights_np(grad_router)
        for attr, param, acc in self._extra_pairs():
            self._apply_weights_np(grads[attr], vec=param, acc_vec=acc)

"""NN base classes: Forward, GradientDescentBase, fwd↔bwd pairing.

Rebuilds the reference's ``znicz/nn_units.py``:

- :class:`Forward` — base of all forward units: ``input`` (linked),
  ``output``, ``weights``, ``bias`` Vectors; weight-init fill schemes;
- :class:`GradientDescentBase` — base of all backward units:
  ``err_output`` (from the next unit / evaluator), ``err_input`` (to
  the previous one), shared ``weights``/``bias``, learning rate,
  momentum (``gradient_moment``), L1/L2 decay (``weights_decay``,
  ``l1_vs_l2``), and momentum accumulators;
- the ``MatchingObject`` pairing: backward classes declare
  ``MATCHES = (ForwardClass, …)`` and a registry lets
  ``StandardWorkflow`` auto-build the backward chain
  (reference: the ``MatchingObject`` metaclass).

TPU-first deltas:

- weights are stored ``(in_features, out_features)`` so the forward
  GEMM is ``x @ W`` with no transpose (the reference stored
  ``(out, in)`` for its OpenCL tiles; XLA prefers plain layouts and
  fuses the rest);
- the parameter update runs on device inside the jit region, and the
  gradient is folded across the data-parallel mesh axis with
  ``lax.pmean`` exactly where the reference called
  ``generate_data_for_master``/``apply_data_from_slave``
  (see :mod:`znicz_tpu.parallel`).
"""

from __future__ import annotations

from typing import Type

import numpy as np

import jax
import jax.numpy as jnp

from znicz_tpu.accelerated_units import AcceleratedUnit
from znicz_tpu.memory import Vector
from znicz_tpu.parallel.axis import maybe_pmean
from znicz_tpu.utils import prng


# ----------------------------------------------------------------------
# fwd ↔ bwd pairing registry (reference: MatchingObject metaclass)
# ----------------------------------------------------------------------
_GD_FOR_FORWARD: dict[type, type] = {}


class MatchingObject(type):
    """Metaclass registering backward units against their forwards via
    a ``MATCHES`` tuple on the backward class."""

    def __init__(cls, name, bases, namespace) -> None:
        super().__init__(name, bases, namespace)
        for fwd_cls in namespace.get("MATCHES", ()):
            _GD_FOR_FORWARD[fwd_cls] = cls


def gd_for(forward_cls: type) -> Type["GradientDescentBase"]:
    """The backward class paired with ``forward_cls`` (walks the MRO so
    subclasses inherit their parent's pairing unless they override)."""
    for klass in forward_cls.__mro__:
        gd = _GD_FOR_FORWARD.get(klass)
        if gd is not None:
            return gd
    raise KeyError(f"no gradient unit registered for {forward_cls.__name__}")


def family_of(unit) -> tuple[str, bool]:
    """``(family, backward)`` of a region member, for
    ``observe.op_scopes()``: a backward unit's family is the forward
    class it is paired with (``MATCHES``), a forward unit's the class
    the pairing registered (its own, or the parent it inherits the
    pairing from), so a layer's two units share one; any other unit
    (loader, evaluator, guard) is its own family.  Units of several
    classes that are one mechanism (the stream units) name it
    themselves (``FAMILY``)."""
    backward = isinstance(unit, GradientDescentBase)
    named = getattr(type(unit), "FAMILY", None)
    if named:
        return named, backward
    for klass in type(unit).__mro__:
        if backward and klass.__dict__.get("MATCHES"):
            return klass.MATCHES[0].__name__, True
        if not backward and klass in _GD_FOR_FORWARD:
            return klass.__name__, False
    return type(unit).__name__, backward


def phases_of(unit) -> tuple:
    """``((scope, how), …)``: the phases inside a region member's own
    scope, for ``observe.op_scopes()`` — its class's ``PHASES``, a
    backward unit's those of the forward unit it walks back: the
    pullback's operations carry the forward's scopes
    (``transpose(jvp(<scope>))``), and what only the backward unit
    opens is declared with its layer all the same."""
    if isinstance(unit, GradientDescentBase) \
            and getattr(unit, "forward_unit", None) is not None:
        unit = unit.forward_unit
    return tuple(type(unit).PHASES.items())


# ----------------------------------------------------------------------
# Forward base
# ----------------------------------------------------------------------
class Forward(AcceleratedUnit):
    """Base forward unit (reference: ``znicz/nn_units.py`` Forward).

    Subclasses set ``self.output`` from ``self.input`` in their run
    methods; parameters live in ``weights``/``bias`` Vectors shared
    with the paired backward unit.
    """

    #: Vector attributes the exporter serializes; units with extra
    #: parameter pairs (attention's output projection) extend this
    EXPORT_PARAMS: tuple = ("weights", "bias")
    #: the looped span this unit is a member of
    #: (``StandardWorkflow.link_forwards`` sets it), else None
    pass_span = None

    def __init__(self, workflow, name: str | None = None,
                 weights_filling: str = "uniform",
                 weights_stddev: float | None = None,
                 bias_filling: str = "uniform",
                 bias_stddev: float | None = None,
                 include_bias: bool = True,
                 **kwargs) -> None:
        super().__init__(workflow, name=name, **kwargs)
        self.input: Vector | None = None  # usually replaced by link_attrs
        self.output = Vector(name=f"{self.name}.output", batch_major=True)
        self.weights = Vector(name=f"{self.name}.weights")
        self.bias = Vector(name=f"{self.name}.bias")
        self.weights_filling = weights_filling
        self.weights_stddev = weights_stddev
        self.bias_filling = bias_filling
        self.bias_stddev = bias_stddev
        self.include_bias = include_bias

    # -- weight init ----------------------------------------------------
    def fill_array(self, arr_shape, filling: str, stddev: float | None,
                   fan_in: int) -> np.ndarray:
        gen = prng.get()
        if stddev is None:
            stddev = 1.0 / max(1.0, np.sqrt(fan_in))
        if filling == "uniform":
            return gen.fill_uniform(arr_shape, -stddev, stddev,
                                    dtype=np.float32)
        if filling == "gaussian":
            return gen.fill_normal(arr_shape, 0.0, stddev, dtype=np.float32)
        if filling == "constant":
            return np.full(arr_shape, stddev, dtype=np.float32)
        # variance-preserving fillings (stddev argument ignored):
        # the reference's fixed-stddev fillings assume shallow nets or
        # ImageNet-scale horizons; deep ReLU stacks need fan-scaled
        # init to keep forward/backward variance O(1)
        if filling == "he":  # ReLU family
            return gen.fill_normal(arr_shape, 0.0,
                                   float(np.sqrt(2.0 / max(1, fan_in))),
                                   dtype=np.float32)
        if filling == "xavier":  # tanh/sigmoid/linear family
            return gen.fill_normal(arr_shape, 0.0,
                                   float(np.sqrt(1.0 / max(1, fan_in))),
                                   dtype=np.float32)
        raise ValueError(f"unknown filling '{filling}'")

    # -- serving ----------------------------------------------------------
    def unserved(self) -> str | None:
        """Why serving cannot run this unit — the rest of the sentence
        ``<caller>: layer <i> …`` that ``export.refuse_unserved`` raises,
        from "is a …" or "sets …" to the ROADMAP item that would serve
        it — or ``None``: the unit is exported through the generic
        manifest and served as it trained.  A layer type that has no
        prefill / decode step says so HERE, reading its own options; a
        member of a looped span answers for its span."""
        return None if self.pass_span is None \
            else self.pass_span.unserved()

    def unserved_beside(self) -> str | None:
        """Likewise for an edge beside the chain's that ends in this
        unit.  ``export.refuse_unserved`` asks this of every layer
        before it asks any for itself: such an edge reaches past the
        layer before, which would else be refused first, for itself."""
        return None

    @property
    def current_batch(self) -> int:
        return self.input.shape[0]

    @property
    def output_store_dtype(self) -> np.dtype:
        """Storage dtype for this unit's ``output`` — the activation
        policy (:attr:`AcceleratedUnit.act_store_dtype`) unless a
        subclass pins f32 (e.g. softmax probabilities feeding the
        evaluator)."""
        return self.act_store_dtype

    def inherit_model_shard(self, *vectors) -> None:
        """Declare that same-shaped output vectors shard like the
        input.  Every shape-preserving (elementwise) forward should
        call this after allocating its outputs so tensor-parallel
        feature sharding passes through instead of silently degrading
        to replicated (which would make GSPMD all-gather the
        activations between a column and row layer every step).
        Declarative since round 17: each vector gets an exact-path
        rule in the workflow's partition table derived from the
        input's resolved placement (``partition.like``)."""
        from znicz_tpu.parallel import partition
        for vec in vectors:
            placement = partition.like(self.input,
                                       batch_major=vec.batch_major)
            partition.declare(self, vec, placement)


# ----------------------------------------------------------------------
# GradientDescent base
# ----------------------------------------------------------------------
class GradientDescentBase(AcceleratedUnit, metaclass=MatchingObject):
    """Base backward unit (reference: ``znicz/nn_units.py``
    GradientDescentBase).

    Update rule (matching the reference's momentum + L1/L2 decay, plus
    optional per-tensor gradient-norm clipping):

    .. code-block:: text

        ĝ   = dL/dW · min(1, gradient_clip / ‖dL/dW‖₂)      (clip > 0)
        g   = ĝ + weights_decay·((1−l1_vs_l2)·W + ½·l1_vs_l2·sign(W))
        acc = gradient_moment·acc − learning_rate·g
        W  += acc

    In data-parallel runs ``dL/dW`` is folded over the ``data`` mesh
    axis before the update — the synchronous SPMD replacement for the
    reference's master-side gradient fold.  On meshes with a data axis
    of size > 1 the fold+update pair runs **ZeRO-1 sharded** by
    default (``root.common.engine.zero1``, auto): gradients are
    reduce-scattered, the update and the STORED momentum state live on
    each chip's 1/N shard, and updated params are all-gathered back —
    same math, half the update-path comm bytes, optimizer memory cut
    by the mesh size (:meth:`_apply_param_zero1`).
    """

    MATCHES: tuple = ()
    #: subclasses that require a paired forward / a linked input set
    #: these to get the labeled error instead of a raw AttributeError
    REQUIRES_FORWARD_UNIT = False
    REQUIRES_INPUT = False

    def __init__(self, workflow, name: str | None = None,
                 learning_rate: float = 0.01,
                 learning_rate_bias: float | None = None,
                 weights_decay: float = 0.0,
                 weights_decay_bias: float = 0.0,
                 l1_vs_l2: float = 0.0,
                 gradient_moment: float = 0.0,
                 gradient_moment_bias: float | None = None,
                 gradient_clip: float = 0.0,
                 need_err_input: bool = True,
                 **kwargs) -> None:
        super().__init__(workflow, name=name, **kwargs)
        self.learning_rate = learning_rate
        self.learning_rate_bias = (learning_rate if learning_rate_bias is None
                                   else learning_rate_bias)
        self.weights_decay = weights_decay
        self.weights_decay_bias = weights_decay_bias
        self.l1_vs_l2 = l1_vs_l2
        self.gradient_moment = gradient_moment
        self.gradient_moment_bias = (gradient_moment
                                     if gradient_moment_bias is None
                                     else gradient_moment_bias)
        #: max L2 norm per parameter tensor for the (mesh-folded) raw
        #: gradient; 0 disables.  Applied before decay, so the clip
        #: bounds the DATA term only — the regularizer stays exact.
        self.gradient_clip = gradient_clip
        self.need_err_input = need_err_input
        #: a cotangent of this unit's INPUT that reaches it beside the
        #: chain — made by a GD further on than the next one (an expert
        #: layer whose router reads this block's input: ``ops/moe.py``,
        #: ``route_from``), parked in that unit's turn
        #: (:meth:`park_beside`) and joined to ``err_input`` after this
        #: unit's own run (:meth:`join_beside`): an ndarray, a jax
        #: array or a tracer, for one eager step or one trace
        self._err_beside = None
        #: resolved at initialize (parallel.mesh.zero1_choice): True =
        #: the update runs ZeRO-1 sharded over the mesh's data axis
        self._zero1 = False
        self._grad_comms_bf16 = False
        #: anomaly-guard flag vector ([running_ok, loss_ok], linked by
        #: StandardWorkflow to the AnomalyGuard's step_flags); when
        #: set, every parameter update folds isfinite(‖grad‖²) into
        #: the running flag and applies through where(ok, new, old) —
        #: a non-finite step leaves weights and momentum untouched.
        #: None (the default for standalone units) = exact seed path.
        self.anomaly_flag: Vector | None = None
        #: round 19 SDC sentinel hooks (linked by StandardWorkflow to
        #: the guard's vectors): ``sdc_fingerprint`` receives this
        #: unit's sub-sampled gradient + post-update parameter
        #: checksums; ``sdc_inject`` is the chaos leaf arming the
        #: ``sdc.flip_param`` / ``sdc.flip_grad`` corruptions (an
        #: exact ×1.0 identity when disarmed — never recompiles).
        self.sdc_fingerprint: Vector | None = None
        self.sdc_inject: Vector | None = None
        #: exact Vector set the fingerprint fold covered, in fold
        #: order — the sentinel's host recompute and the shadow audit
        #: enumerate the SAME tensors from this (populated on both
        #: backends whether or not the fingerprint vector is linked)
        self._fp_folded: dict[int, Vector] = {}
        # linked from the paired forward unit by StandardWorkflow:
        self.input: Vector | None = None
        self.output: Vector | None = None
        self.weights: Vector | None = None
        self.bias: Vector | None = None
        # linked from the next backward unit / evaluator:
        self.err_output: Vector | None = None
        self.err_input = Vector(name=f"{self.name}.err_input",
                                batch_major=True)
        # momentum slots
        self.accumulated_gradient_weights = Vector(
            name=f"{self.name}.acc_grad_w")
        self.accumulated_gradient_bias = Vector(
            name=f"{self.name}.acc_grad_b")
        #: the microbatch gradient-accumulation buffers, keyed by
        #: parameter Vector identity (:meth:`_whole_gradient` says what
        #: they are for, :meth:`_alloc_micro_accum` when they exist)
        self._micro_accum: dict[int, Vector] = {}
        # device-resident [lr, lr_bias]; only populated when a
        # LearningRateAdjust unit schedules this GD unit — a region
        # leaf, so schedule changes never recompile the step program
        self.lr_state = Vector(name=f"{self.name}.lr_state")

    def park_beside(self, grad) -> None:
        """``grad``: a further cotangent of this unit's input, from a
        unit that read it beside the chain."""
        self._err_beside = grad

    def join_beside(self) -> None:
        """``err_input`` plus what was parked, which is then gone.
        The engine calls this after the unit's run, eager or traced
        (``AcceleratedUnit.run``, ``JitRegion._trace_members``)."""
        grad, self._err_beside = self._err_beside, None
        if grad is None or not self.need_err_input:
            return
        if self.device.is_host_only:
            self.err_input.map_write()
            self.err_input.mem[...] += np.asarray(grad).reshape(
                self.err_input.shape)
        else:
            self.err_input.devmem = self.err_input.devmem \
                + grad.reshape(self.err_input.shape)

    def forget_trace(self) -> None:
        self._err_beside = None

    def initialize(self, device=None, **kwargs) -> None:
        if self.REQUIRES_FORWARD_UNIT \
                and getattr(self, "forward_unit", None) is None:
            raise ValueError(
                f"{self}: forward_unit not set — assign the paired "
                f"forward unit before initialize (link_attrs does not "
                f"do this)")
        if self.REQUIRES_INPUT and (self.input is None
                                    or not self.input):
            raise AttributeError(f"{self}: input not linked yet")
        super().initialize(device=device, **kwargs)
        # err_input allocation lives here (post-super, device resolved)
        # so its dtype can follow the activation storage policy
        if (self.need_err_input and self.input is not None
                and self.input and not self.err_input):
            self.err_input.reset(np.zeros(self.input.shape,
                                          dtype=self.act_store_dtype))
            # the error cotangent shards like the tensor it's the
            # gradient of (tensor parallelism: feature-sharded
            # activations get feature-sharded errors) — declared as a
            # rule derived from the input's resolved placement
            from znicz_tpu.parallel import partition
            partition.declare(self, self.err_input,
                              partition.like(self.input,
                                             batch_major=True),
                              slot="err_input")
        if not self.need_err_input and (self.weights is None
                                        or not self.weights):
            # weightless AND nothing upstream wants the error: the unit
            # has no observable effect — skip it entirely (scheduler
            # and jit region both honor gate_skip)
            from znicz_tpu.mutable import Bool
            self.gate_skip = Bool(True)
        from znicz_tpu.parallel.mesh import zero1_choice
        from znicz_tpu.utils.config import root
        self._zero1 = zero1_choice(self.device)
        # second convergence-gated comms lever: reduce-scatter the
        # weight gradients in bf16 (half the ICI bytes again).
        # Default OFF until a multi-chip A/B + convergence band lands
        # (BF16_CONVERGENCE.json, `bfloat16_gradcomms` arm).
        self._grad_comms_bf16 = (
            self._zero1
            and bool(root.common.engine.get("bf16_grad_comms", False)))
        # round 21: fp8 matmul lever (engine.fp8_matmul, default OFF
        # until the QUANT_BENCH fp8 convergence A/B and the FP8_TPU
        # chip arm clear it — same gating shape as bf16_grad_comms).
        # Forward/backward matmuls take float8_e4m3fn inputs via
        # mxu_dot (f32 accumulation) and the weight gradient
        # round-trips through fp8 before the optimizer sees it.
        self._fp8_matmul = bool(
            root.common.engine.get("fp8_matmul", False))
        if self.gradient_moment or self.gradient_moment_bias:
            if self.weights is not None and self.weights:
                self._alloc_accumulator(self.accumulated_gradient_weights,
                                        self.weights)
            if (self.bias is not None and self.bias
                    and self.gradient_moment_bias):
                self._alloc_accumulator(self.accumulated_gradient_bias,
                                        self.bias)
            self.init_vectors(self.accumulated_gradient_weights,
                              self.accumulated_gradient_bias)
        self._alloc_micro_accum()

    def _micro_accum_params(self) -> list:
        """``(suffix, parameter Vector)`` pairs that
        :meth:`_alloc_micro_accum` gives a buffer; units with extra
        parameter pairs (attention's output projection) extend this
        the same way they extend ``EXPORT_PARAMS``."""
        return [("w", self.weights), ("b", self.bias)]

    def _alloc_micro_accum(self) -> None:
        """Allocate the microbatch accumulation buffers of
        :meth:`_whole_gradient` when ``root.common.engine.grad_accum``
        > 1: one f32 zero buffer per parameter tensor, a region leaf
        (the ``micro_accum_*`` attribute makes ``region_vectors`` pick
        it up), found by the parameter's identity.  Replicated
        placement (the ``acc_\\w+`` default rule): the buffer holds
        the logically-global microbatch gradient sum; ZeRO-1's
        reduce-scatter engages once, at apply.  (The passes of a looped
        span need none: their sum lives inside one traced step.)"""
        from znicz_tpu.utils.config import root
        n_micro = int(root.common.engine.get("grad_accum", 1) or 1)
        if (n_micro < 2 or self.device is None
                or self.device.is_host_only):
            return
        if self.weights is None or not self.weights:
            return  # weightless backward: nothing accumulates
        for suffix, param in self._micro_accum_params():
            if param is None or not param:
                continue
            attr = f"micro_accum_{suffix}"
            vec = getattr(self, attr, None)
            if vec is None:
                vec = Vector(name=f"{self.name}.acc_micro_{suffix}")
                setattr(self, attr, vec)
            if not vec:
                vec.reset(np.zeros(tuple(param.shape),
                                   dtype=np.float32))
            self._micro_accum[id(param)] = vec
            self.init_vectors(vec)

    def _alloc_accumulator(self, acc_vec: Vector, param_vec: Vector) -> None:
        """Allocate a momentum accumulator for ``param_vec``: storage
        dtype from the bf16-optimizer-state policy, model-axis sharding
        inherited, and — under ZeRO-1 — a data-sharded dim plus zero
        padding so each chip STORES only 1/N of the state.  The
        (dim, pad) choice is a RULE CONSEQUENCE now: the unit declares
        a :class:`~znicz_tpu.parallel.partition.Zero1` placement for
        the accumulator's leaf path and the engine derives the
        sharded layout from the logical shape; units with extra
        parameter pairs (attention's output projection) call this for
        their own accumulators so every lever composes identically."""
        from znicz_tpu.parallel import partition
        shape = tuple(param_vec.shape)
        from znicz_tpu.parallel.axis import MODEL_AXIS
        model_dim = getattr(param_vec, "model_shard_dim", None)
        model_axis = getattr(param_vec, "model_shard_axis",
                             MODEL_AXIS) or MODEL_AXIS
        if self._zero1:
            placement = partition.Zero1(model_dim)
        elif model_dim is None:
            placement = partition.REPLICATED
        else:
            placement = partition.model_sharded(model_dim,
                                                axis=model_axis)
        resolved = partition.declare(self, acc_vec, placement,
                                     logical_shape=shape)
        acc_vec.reset(np.zeros(resolved.padded_shape(),
                               dtype=self.opt_state_dtype))
        partition.stamp(self, acc_vec, resolved,
                        pad_applied=bool(resolved.data_shard_pad))

    @property
    def opt_state_dtype(self) -> np.dtype:
        """STORAGE dtype for the momentum accumulators.

        In bf16 mode the update fusions over the big FC state are
        bandwidth-bound on ~600 MB/step of optimizer-state traffic
        (PERF.md round 4: measured +1.0% img/s from halving it; round
        5 validated the precision against moving error curves —
        BF16_CONVERGENCE.json's ``bfloat16_optstate`` arm).  The
        momentum MATH stays f32 (the accumulator is upcast in the
        update expression; only its storage rounds) — same
        storage-vs-compute split as ``act_store_dtype``.  Opt out:
        ``root.common.engine.bf16_optimizer_state = False``.
        """
        from znicz_tpu.utils.config import root
        if (self.device is not None
                and not self.device.is_host_only
                and self.device.compute_dtype == np.dtype("bfloat16")
                and bool(root.common.engine.get("bf16_optimizer_state",
                                                True))):
            import jax.numpy as jnp
            return np.dtype(jnp.bfloat16)
        return np.dtype(np.float32)

    # -- learning-rate source (scheduled vector or static float) --------
    def _lr(self, xla: bool):
        if self.lr_state:
            return (self.lr_state.devmem[0] if xla
                    else float(self.lr_state.mem[0]))
        return self.learning_rate

    def _lr_bias(self, xla: bool):
        if self.lr_state:
            return (self.lr_state.devmem[1] if xla
                    else float(self.lr_state.mem[1]))
        return self.learning_rate_bias

    # -- shared update math (xp = np or jnp) ----------------------------
    def _regularized(self, xp, grad, weights, decay: float):
        if not decay:
            return grad
        l1 = self.l1_vs_l2
        reg = (1.0 - l1) * weights
        if l1:
            reg = reg + 0.5 * l1 * xp.sign(weights)
        return grad + decay * reg

    def _clipped(self, xp, grad, grad_sq=None):
        """Per-tensor L2 gradient-norm clipping (``gradient_clip``).
        The norm is a full-tensor reduction: under ZeRO-1 it runs on
        the scattered shard (partial sums + one scalar all-reduce),
        so clipping does not resurrect the full-gradient all-reduce.
        ``grad_sq``: Σ grad², where the caller has it."""
        clip = self.gradient_clip
        if not clip:
            return grad
        if grad_sq is None:
            g32 = grad.astype(np.float32) if xp is np \
                else grad.astype(jnp.float32)
            grad_sq = xp.sum(g32 * g32)
        norm = xp.sqrt(grad_sq)
        scale = xp.minimum(1.0, clip / xp.maximum(norm, 1e-30))
        return grad * scale

    # -- round 19: SDC fingerprint fold + seeded corruption ------------
    def _fp_register(self, vec: Vector) -> None:
        """Record that ``vec`` is covered by the fingerprint fold (the
        sentinel's host recompute and the shadow audit enumerate
        exactly this set, in this order)."""
        self._fp_folded.setdefault(id(vec), vec)

    def _sdc_scales(self, xla: bool):
        """The armed ``(param_scale, grad_scale)`` multiplier deltas,
        or None when the chaos leaf is absent (the common case)."""
        inj = self.sdc_inject
        if inj is None or not inj:
            return None
        return inj.devmem if xla else inj.mem

    def _fold_fingerprint(self, xp, slot: int, value) -> None:
        """Fold one tensor's sub-sampled checksum into the guard's
        shared fingerprint (slot 0 = post-update params, slot 1 =
        folded gradients).  A no-op unless StandardWorkflow linked the
        vector — standalone units keep the exact seed path."""
        fpv = self.sdc_fingerprint
        if fpv is None or not fpv:
            return
        from znicz_tpu.resilience.integrity import tensor_fingerprint
        if xp is np:
            fpv.mem[slot] += np.float32(tensor_fingerprint(xp, value))
            return
        # the fold's device work has a scope of its own inside
        # ``update``: ``observe.op_scopes()`` phase ``fingerprint``
        with jax.named_scope("fingerprint"):
            fpv.devmem = fpv.devmem.at[slot].add(
                tensor_fingerprint(xp, value))

    def _np_grad_ok(self, grad: np.ndarray) -> bool:
        """Numpy-path mirror of the guard's on-device finite check:
        AND this gradient's ‖g‖² finiteness into the shared flag and
        return whether the update may apply."""
        guard = self.anomaly_flag
        if guard is None or not guard:
            return True
        own = bool(np.isfinite(
            np.sum(np.square(grad, dtype=np.float64))))
        ok = own and guard.mem[0] > 0.5
        if not own:
            guard.mem[0] = 0.0
        return ok

    # ``vec``/``acc`` parameters let units with EXTRA parameter pairs
    # (e.g. attention's output projection) reuse the exact update rule
    # instead of copy-pasting the momentum/decay/clip math
    def _apply_weights_np(self, grad_w: np.ndarray, vec=None,
                          acc_vec=None) -> None:
        vec = vec if vec is not None else self.weights
        acc_vec = acc_vec if acc_vec is not None \
            else self.accumulated_gradient_weights
        self._fp_register(vec)
        self._fold_fingerprint(np, 2, vec.mem)
        sdc = self._sdc_scales(xla=False)
        if sdc is not None:
            grad_w = grad_w.copy()
            grad_w.ravel()[0] *= 1.0 + sdc[1]
        self._fold_fingerprint(np, 1, grad_w)
        if not self._np_grad_ok(grad_w):
            # skipped update: the claimed fp still covers the (kept)
            # value, or the next step's refold would false-alarm
            self._fold_fingerprint(np, 0, vec.mem)
            return  # anomaly guard: skip, don't poison
        w = vec.mem
        g = self._regularized(np, self._clipped(np, grad_w), w,
                              self.weights_decay)
        lr = self._lr(xla=False)
        if self.gradient_moment:
            acc = acc_vec.mem
            acc *= self.gradient_moment
            acc -= lr * g
            w += acc
        else:
            w -= lr * g
        self._fold_fingerprint(np, 0, w)

    def _apply_bias_np(self, grad_b: np.ndarray, vec=None,
                       acc_vec=None) -> None:
        vec = vec if vec is not None else self.bias
        acc_vec = acc_vec if acc_vec is not None \
            else self.accumulated_gradient_bias
        if vec is None or not vec:
            return
        self._fp_register(vec)
        self._fold_fingerprint(np, 2, vec.mem)
        self._fold_fingerprint(np, 1, grad_b)
        if not self._np_grad_ok(grad_b):
            self._fold_fingerprint(np, 0, vec.mem)
            return  # anomaly guard: skip, don't poison
        b = vec.mem
        g = self._regularized(np, self._clipped(np, grad_b), b,
                              self.weights_decay_bias)
        lr = self._lr_bias(xla=False)
        if self.gradient_moment_bias:
            acc = acc_vec.mem
            acc *= self.gradient_moment_bias
            acc -= lr * g
            b += acc
        else:
            b -= lr * g
        self._fold_fingerprint(np, 0, b)

    def _apply_weights_xla(self, grad_w, vec=None, acc_vec=None,
                           grad_sq=None) -> bool:
        """``grad_sq``: Σ grad_w² where whoever made the gradient has
        it (a kernel that summed the squares as it stored them); the
        return says whether the guard took it
        (:meth:`_update_param_xla`)."""
        vec = vec if vec is not None else self.weights
        acc_vec = acc_vec if acc_vec is not None \
            else self.accumulated_gradient_weights
        return self._apply_param_xla(
            grad_w, vec, acc_vec, self.weights_decay, self._lr(xla=True),
            self.gradient_moment, grad_sq)

    def _apply_bias_xla(self, grad_b, vec=None, acc_vec=None) -> None:
        vec = vec if vec is not None else self.bias
        acc_vec = acc_vec if acc_vec is not None \
            else self.accumulated_gradient_bias
        if vec is None or not vec:
            return
        self._apply_param_xla(grad_b, vec, acc_vec,
                              self.weights_decay_bias,
                              self._lr_bias(xla=True),
                              self.gradient_moment_bias)

    @jax.named_scope("update")
    def _apply_param_xla(self, grad, vec: Vector, acc_vec, decay: float,
                         lr, moment: float, grad_sq=None) -> bool:
        """One parameter tensor's update on the XLA path, traced under
        the scope ``update`` (either form; the fingerprint folds inside
        it under ``fingerprint``): ``observe.op_scopes()`` reads the
        two as phases of the unit.

        Two forms, same math (``tests/test_zero1.py`` pins parity):

        - replicated (the historical path): the gradient is all-reduced
          (implicitly by GSPMD from the data-sharded contraction, or by
          ``maybe_pmean`` under an explicit mapped axis) and the
          identical momentum/decay/clip update runs on every chip;
        - ZeRO-1 (``engine.zero1``, auto-on for data axes > 1): see
          :meth:`_apply_param_zero1`.

        With :attr:`anomaly_flag` linked (the default under
        ``StandardWorkflow``'s anomaly guard) the whole update —
        either form — is applied through ``where(ok, new, old)``,
        where ``ok`` = the step's running flag (loss finite, every
        previously-checked gradient finite) AND ``isfinite(‖grad‖²)``
        of THIS tensor.  A non-finite step leaves the parameter and
        its momentum bitwise untouched; finite steps are bitwise
        identical to the unguarded path (``where`` with a true
        predicate selects the new value exactly).

        A gradient that is not whole yet — a pass of a looped span, a
        microbatch of an accumulated step — never reaches the update:
        :meth:`_whole_gradient` is the one home of both, and this
        method passes on only the whole one, ONCE per parameter and
        optimizer step (:meth:`_update_param_xla`).

        ``grad_sq`` is Σ grad² of ``grad`` as it was handed in, from
        whoever made it, or None; :meth:`_update_param_xla` holds the
        one rule for it and returns whether the guard read it.
        """
        whole = self._whole_gradient(grad, vec)
        return whole is not None and self._update_param_xla(
            whole, vec, acc_vec, decay, lr, moment,
            None if grad_sq is None else (grad, grad_sq))

    def _whole_gradient(self, grad, vec: Vector):
        """The ONE home of "this gradient is not whole yet": returns the
        whole gradient of ``vec`` for this optimizer step, or ``None``
        where ``grad`` is a part that was put by.  Two ways a gradient
        arrives in parts, and they compose — a looped table under
        ``run_accumulated`` gives (Σ over microbatches of Σ over
        passes) / M:

        - **the passes of a looped span** (``znicz_tpu.pass_span``,
          inside ONE traced step): the span walks its R passes back
          from the last to the first and sets
          :func:`~znicz_tpu.accelerated_units.current_pass_phase`;
          a ``"partial"`` pass adds its gradient to the span's
          trace-local sum (f32; no buffer on the device outlives the
          step) and returns ``None``, the ``"whole"`` pass (pass 0,
          the last walked) returns the SUM.  The adds trace under the
          scope ``pass_sum`` (``observe.op_scopes()`` phase
          ``pass_sum``);
        - **the microbatches of an accumulated step** (round 20,
          ``JitRegion.run_accum``; across traced bodies, so through
          device buffers): :func:`~znicz_tpu.accelerated_units.
          current_accum_phase` ``("accum", M)`` sums the gradient into
          the f32 micro-accumulation buffer of ``vec``
          (:meth:`_alloc_micro_accum`: the ``acc_micro_*`` leaves,
          allocated where ``engine.grad_accum`` > 1, one per tensor of
          :meth:`_micro_accum_params`) and returns ``None`` — no
          pmean, no fingerprint fold, no guard gate, no parameter
          write; ``("apply", M)`` returns the MEAN ``(Σ grads)/M`` and
          zeroes the buffer.

        A non-finite part propagates through either sum, so the guard's
        finite check on the whole skips the whole step; the buffer's
        zeroing is unconditional, so a skipped step cannot poison the
        next one.
        """
        from znicz_tpu.accelerated_units import (current_accum_phase,
                                                 current_pass_phase)
        passes = current_pass_phase()
        if passes is not None:
            mode, span = passes
            with jax.named_scope("pass_sum"):
                part = span.partial.pop(id(vec), None)
                if part is not None:
                    grad = part + grad.astype(jnp.float32)
                elif span.passes > 1:
                    grad = grad.astype(jnp.float32)
            if mode == "partial":
                span.partial[id(vec)] = grad
                return None
            assert mode == "whole", passes
        phase = current_accum_phase()
        if phase is not None:
            mode, n_micro = phase
            acc = self._micro_accum.get(id(vec))
            if acc is None or not acc:
                raise RuntimeError(
                    f"{self}: gradient accumulation phase {phase} but "
                    f"no micro-accumulation buffer for '{vec.name}' — "
                    f"set root.common.engine.grad_accum before "
                    f"initialize (and cover the tensor in "
                    f"_micro_accum_params for extra parameter pairs)")
            if mode == "accum":
                acc.devmem = acc.devmem + grad.astype(jnp.float32)
                return None
            assert mode == "apply", phase
            grad = (acc.devmem + grad.astype(jnp.float32)) \
                / np.float32(n_micro)
            acc.devmem = jnp.zeros_like(acc.devmem)
        return grad

    def _update_param_xla(self, grad, vec: Vector, acc_vec, decay: float,
                          lr, moment: float, known=None) -> bool:
        """The update proper, from a WHOLE gradient (see
        :meth:`_apply_param_xla`, whose scope it traces under).

        ``known`` = ``(tensor, Σ tensor²)`` from whoever made a
        gradient, or None.  ONE rule decides whether the guard's (and
        the clip's) Σ g² is that number or a pass over ``grad``: it is
        the number exactly where the gradient the update applies IS
        that tensor — the same traced value, which nothing between its
        maker and here has replaced.  Every step that changes a
        gradient makes a new value, so the rule needs no list of them:
        a looped span's passes and an accumulated step's microbatches
        (:meth:`_whole_gradient` returns their sum or mean), the mean
        over a mapped axis, the fp8 round trip and the seeded SDC flip
        below all fail it, and the pass over ``grad`` stays.  Returns
        whether the guard read ``known``'s number."""
        from znicz_tpu.parallel.axis import current_data_axis
        grad = maybe_pmean(grad)
        if getattr(self, "_fp8_matmul", False):
            # fp8 gradient round-trip (round 21): the optimizer sees
            # the gradient at the precision the fp8 training arm would
            # communicate/store it — applied BEFORE the fingerprint
            # fold so the SDC sentinel checks what is actually applied
            f8 = self.fp8_dtype
            if f8 is not None:
                grad = grad.astype(f8).astype(jnp.float32)
        self._fp_register(vec)
        # round 19: refold the STORED parameter before the update
        # (slot 2) — the guard compares it against last step's
        # post-update claimed fp, so a between-step memory mutation
        # (sdc.flip_param) self-identifies on the corrupting chip
        self._fold_fingerprint(jnp, 2, vec.devmem)
        # seeded gradient corruption (sdc.flip_grad) rides a device
        # leaf — ``×(1 + scale)`` is an exact identity when disarmed,
        # an exponent-scale flip of one element when armed; applied
        # to the unit's main weight gradient only.  (sdc.flip_param
        # is injected host-side between dispatches — see
        # AnomalyGuard._host_flip_param.)
        sdc = self._sdc_scales(xla=True)
        if sdc is not None and vec is self.weights:
            idx = (0,) * grad.ndim
            grad = grad.at[idx].multiply(1.0 + sdc[1])
        self._fold_fingerprint(jnp, 1, grad)
        guard = self.anomaly_flag \
            if self.anomaly_flag is not None and self.anomaly_flag else None
        grad_sq = known[1] if known is not None and known[0] is grad \
            else None
        if guard is not None:
            if grad_sq is None:
                g32 = grad.astype(jnp.float32)
                own_ok = jnp.isfinite(jnp.sum(g32 * g32))
            else:
                own_ok = jnp.isfinite(grad_sq)
            flags = guard.devmem
            step_ok = (flags[0] > 0.5) & own_ok
            guard.devmem = flags.at[0].set(
                jnp.where(own_ok, flags[0], 0.0))
            w_before = vec.devmem
            acc_before = (acc_vec.devmem
                          if moment and acc_vec is not None and acc_vec
                          else None)
        if self._zero1 and current_data_axis() is None:
            self._apply_param_zero1(grad, vec, acc_vec, decay, lr, moment)
        else:
            w = vec.devmem
            g = self._regularized(
                jnp, self._clipped(jnp, grad, grad_sq), w, decay)
            if moment:
                # momentum math in f32 regardless of the accumulator's
                # STORAGE dtype (opt_state_dtype); the setter rounds
                # the store back down
                acc = moment * acc_vec.devmem.astype(jnp.float32) - lr * g
                acc_vec.devmem = acc
                vec.devmem = w + acc
            else:
                vec.devmem = w - lr * g
        if guard is not None:
            vec.devmem = jnp.where(step_ok, vec.devmem, w_before)
            if acc_before is not None:
                acc_vec.devmem = jnp.where(step_ok, acc_vec.devmem,
                                           acc_before)
        # the param fingerprint folds the COMMITTED value — a
        # between-step memory mutation (sdc.flip_param, host-injected)
        # makes the NEXT step's pre-update refold disagree with this
        # claimed checksum, which is what the guard's sticky
        # self-check detects
        self._fold_fingerprint(jnp, 0, vec.devmem)
        # the committed value once more in its readers' dtype, where
        # a cast of it is kept (``Vector.keep_cast``): one more result
        # of the fusion that holds it, where the next step would have
        # read all of it again to cast it
        vec.recast()
        return guard is not None and grad_sq is not None

    def _apply_param_zero1(self, grad, vec: Vector, acc_vec,
                           decay: float, lr, moment: float) -> None:
        """ZeRO-1 form of the update (Rajbhandari et al., 2020, stage
        1), expressed as GSPMD sharding constraints on the existing
        math so XLA derives the collectives:

        1. the weight gradient is constrained to the data-axis-sharded
           layout — GSPMD fuses the data-parallel reduction with the
           constraint into a reduce-scatter (half the bytes of the
           replicated path's all-reduce);
        2. momentum/decay/clip run on each chip's 1/N shard, and the
           momentum accumulator is STORED sharded (its Vector carries
           ``data_shard_dim`` — per-chip optimizer state shrinks by
           the data-axis size);
        3. the updated shard is constrained back to the gathered
           layout — one all-gather returns the params every forward
           expects.

        Indivisible dims are zero-padded to a multiple of the axis
        size (the accumulator is stored padded; grads/params pad and
        slice in flight — pad rows carry exact zeros through every
        step).  Model-axis sharding (TP) composes: the spec pair keeps
        ``model_shard_dim`` on the model axis in both layouts.
        """
        from jax.sharding import NamedSharding
        from znicz_tpu.parallel.mesh import zero1_partition, zero1_specs
        mesh = self.device.mesh
        model_dim = getattr(vec, "model_shard_dim", None)
        if acc_vec is not None and acc_vec \
                and acc_vec.data_shard_dim is not None:
            dim, pad = acc_vec.data_shard_dim, acc_vec.data_shard_pad
        else:
            dim, pad = zero1_partition(vec.shape,
                                       self.device.n_data_shards,
                                       model_dim)
        if dim is None:  # nothing shardable: keep the replicated form
            w = vec.devmem
            g = self._regularized(jnp, self._clipped(jnp, grad), w, decay)
            if moment:
                acc = moment * acc_vec.devmem.astype(jnp.float32) - lr * g
                acc_vec.devmem = acc
                vec.devmem = w + acc
            else:
                vec.devmem = w - lr * g
            return
        sharded_spec, gathered_spec = zero1_specs(
            mesh, len(vec.shape), dim, model_dim)
        sharded = NamedSharding(mesh, sharded_spec)
        gathered = NamedSharding(mesh, gathered_spec)
        w = vec.devmem
        if self._grad_comms_bf16:
            # the reduce-scatter moves bf16 bytes; shard math upcasts
            grad = grad.astype(jnp.bfloat16)
        if pad:
            widths = [(0, 0)] * grad.ndim
            widths[dim] = (0, pad)
            grad = jnp.pad(grad, widths)
            w = jnp.pad(w, widths)
        g = jax.lax.with_sharding_constraint(grad, sharded)
        g = g.astype(jnp.float32)
        w_shard = jax.lax.with_sharding_constraint(w, sharded)
        g = self._regularized(jnp, self._clipped(jnp, g), w_shard, decay)
        if moment:
            acc = moment * acc_vec.devmem.astype(jnp.float32) - lr * g
            acc_vec.devmem = jax.lax.with_sharding_constraint(acc, sharded)
            new_w = w_shard + acc
        else:
            new_w = w_shard - lr * g
        new_w = jax.lax.with_sharding_constraint(new_w, gathered)
        if pad:
            idx = [slice(None)] * new_w.ndim
            idx[dim] = slice(0, vec.shape[dim])
            new_w = new_w[tuple(idx)]
        vec.devmem = new_w


# ----------------------------------------------------------------------
# Weightless backward base
# ----------------------------------------------------------------------
class WeightlessGradientUnit(GradientDescentBase):
    """Base for backward units of weightless forwards (pooling, dropout,
    cutter, depooling, normalizers, joiners): no learning-rate state,
    ``err_output → err_input`` only.

    Handles the shared lifecycle: tolerating optimizer kwargs from
    ``"<-"`` configs, requiring a linked ``input``, allocating
    ``err_input`` to match it, and registering the standard region
    leaves.  Subclasses that need their paired forward at initialize
    time set ``REQUIRES_FORWARD_UNIT = True`` to get a labeled error
    instead of a mid-training ``NoneType`` crash.
    """

    REQUIRES_FORWARD_UNIT = True
    REQUIRES_INPUT = True

    def __init__(self, workflow, name=None, **kwargs):
        kwargs.pop("learning_rate", None)  # weightless; tolerate configs
        super().__init__(workflow, name=name, **kwargs)
        self.forward_unit = None  # set by link_gds / the sample

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device=device, **kwargs)
        self.init_vectors(self.err_input, self.err_output, self.input,
                          self.output)

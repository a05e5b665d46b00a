"""Gated-delta-rule linear attention (Yang, Kautz, Hatamizadeh 2024,
arXiv:2412.06464 §3–4; the recurrent layers of Olmo-Hybrid-7B, PR 31):
a token mixer whose memory is a d_k × d_v matrix per head, carried
along the sequence instead of a cache of keys.

.. code-block:: text

    m = x                       (pre_norm="rms": RMSNorm(x))
    q̃, k̃, ṽ = m W_q, m W_k, m W_v          H heads of d_k, d_k, d_v
    u_t[c] = silu(Σ_{j<J} taps[c, j] · ũ_{t−J+1+j}[c])
                                a causal convolution over time, each
                                channel its own J taps, zeros before the
                                sequence — over q̃, k̃ and ṽ alike
    q_t = q_t / √(‖q_t‖² + ε) · d_k^(−1/2)   k_t = k_t / √(‖k_t‖² + ε)
    β_t = 2 σ(m W_b)            the write strength, one per head; the 2
                                (``allow_neg_eigval``) lets I − β k kᵀ
                                reach eigenvalue −1
    α_t = exp(−exp(A) softplus(m W_a + b))    the decay, one per head;
                                A, b learned per head
    S_t = α_t S_{t−1} + β_t k_t (v_t − α_t S_{t−1}ᵀ k_t)ᵀ,   S_0 = 0
    o_t = S_tᵀ q_t
    y = concat_h(RMSNorm(o_t,h) g_o ⊙ silu((m W_g)_h)) W_o
    out = x + y                 (post_norm="rms": x + RMSNorm(y))

The recurrence runs in the chunked form of ``ops/pallas_delta.py``
(chunks of 64 positions; exactly the recurrence in exact arithmetic).
On a TPU the rule is four kernels: ``znicz_gdr_chunk_fwd`` / ``_bwd``
compute what is local to a chunk (Γ, the triangular inverse, W, U, K̂,
Qc, P — every (64, 64) matrix in VMEM), ``znicz_delta_state_fwd`` /
``_bwd`` walk the state from chunk to chunk; elsewhere, or on a mesh,
the same algebra in ``jax.numpy`` and a ``lax.scan``.  A sequence that
is not whole chunks is padded with positions that write nothing (β 0,
α 1) on either path.  Never another formula.

What lies between the q ‖ k ‖ v projection and the rule — the taps,
the SiLU, the two L2 norms, d_k^(−1/2) — is two more kernels on that
path WHERE d_k and d_v are whole 128-lane tiles (PR 40):
``znicz_qkv_prep_fwd`` reads a head's columns of the projection where
the matmul wrote them and writes q, k, v head-major (B, H, T, ·) and
padded to whole chunks, the rows the rule's kernels read, so the rule
is handed them without a move (log α, β and o keep theirs);
``znicz_qkv_prep_bwd`` makes c, a and the norms again in VMEM from the
projection and the taps — all its ``custom_vjp`` keeps — and writes the
projection's cotangent and the taps'.  Elsewhere (96 × 192, no TPU, a
mesh) the same lines in ``jax.numpy`` and a ``moveaxis`` a tensor;
``_resolve_path`` decides once, from what it can see, and the gauge
``znicz_delta_scan{stat="prep_path"}`` says which.

Precision, the same on every path: the projections take the unit's
matmul inputs (bf16 in mixed precision) with f32 accumulation; the
convolution, the SiLU, the norms (in the prep kernels too: u is read
and du written at the f32 the matmuls hand over and take), the gates,
the decay's logarithms and their
sums, Γ, K Kᵀ, the triangular inverse (f32 matmuls at the highest
precision, in the kernels too) and the state stay f32.  In mixed
precision the products into W, U, P, V′, O and the state's update take
bf16 INPUTS with f32 accumulation: the state itself is accumulated and
stored f32.

Both decay shapes run through ONE family of kernels; the shape of
log α — (B, T, H) or (B, T, H, d_k) — is a static property of the call
and picks the body.  ``decay="channel"`` (Kimi Delta Attention,
arXiv:2510.26692; Ling-3.0-flash, PR 37) gives every KEY CHANNEL its
own decay, S_t = Diag(α_t) S_{t−1} + β_t k_t (v_t − (Diag(α_t)
S_{t−1})ᵀ k_t)ᵀ, in the bounded form

.. code-block:: text

    log α_t = lower_bound · σ(exp(A_h) · (m W_f + b))   ∈ (lower_bound, 0)

W_f (D, H·d_k), A per head, b per channel (``weights_ba`` is then
W_b ‖ W_f, (D, H + H·d_k)); ``gate="sigmoid"`` makes the output gate
σ((m W_g)_h).  The chunked algebra then has Γ inside the contraction
and computes it by sub-blocks of ``pallas_delta.SUB_BLOCK`` positions
(kernels ``znicz_kda_chunk_fwd`` / ``_bwd``, ``znicz_kda_state_fwd`` /
``_bwd``); the exponent bound it relies on is stated ONCE, at
``pallas_delta.MAX_EXPONENT``, and ``initialize`` refuses a
``lower_bound`` that could pass it.

Parameters: ``weights`` (D, H·(2 d_k + d_v)) = W_q ‖ W_k ‖ W_v,
``weights_conv`` (H·(2 d_k + d_v), J), ``weights_gate`` (D, H·d_v),
``weights_ba`` (D, 2H) = W_b ‖ W_a, ``decay_log`` A (H,),
``decay_bias`` b (H,), ``gain_out`` g_o (d_v,), ``weights_out``
(H·d_v, D), ``gain_norm`` (D,) with a block norm.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from znicz_tpu.memory import Vector
from znicz_tpu.observe import metrics as _metrics
from znicz_tpu.ops import pallas_delta
from znicz_tpu.ops.moe import GDMoE, _sigmoid, _silu
from znicz_tpu.ops.nn_units import Forward
from znicz_tpu.ops.rms_norm import one_norm_placement, rms_norm
from znicz_tpu.utils import prng


def causal_conv(xp, u, taps):
    """(B, T, C) convolved causally over time with each channel's own
    ``taps`` (C, J): out_t = Σ_j taps[:, j] · u_{t−J+1+j}, zeros before
    the sequence."""
    t, width = u.shape[1], taps.shape[1]
    padded = xp.concatenate(
        [xp.zeros_like(u[:, :width - 1]), u], axis=1)
    return sum(padded[:, j:j + t] * taps[:, j] for j in range(width))


def _softplus(xp, x):
    return xp.logaddexp(x, 0.0)


def _logistic(xp, x):
    """σ(x) whose gradient stays finite where exp(−x) leaves the f32
    range (``_sigmoid``'s does not): the bounded decay's argument is
    exp(A) · logits, tens in either direction."""
    if xp is jnp:
        return jax.nn.sigmoid(x)
    e = np.exp(-np.abs(x))
    return np.where(x >= 0, 1.0, e) / (1.0 + e)


def l2_normalize(xp, x, eps: float):
    return x / xp.sqrt((x * x).sum(axis=-1, keepdims=True) + eps)


class GatedDeltaNet(Forward):
    """A gated-delta-rule mixer block (module docstring)."""

    EXPORT_PARAMS = ("weights", "weights_conv", "weights_gate",
                     "weights_ba", "decay_log", "decay_bias", "gain_out",
                     "weights_out", "gain_norm")

    def __init__(self, workflow, n_heads: int, key_dim: int,
                 value_dim: int, conv_kernel: int = 4,
                 allow_neg_eigval: bool = False,
                 decay: str = "head", lower_bound: float | None = None,
                 gate: str = "silu",
                 pre_norm: str | None = None,
                 post_norm: str | None = None, residual: bool = False,
                 norm_eps: float = 1e-5,
                 chunk: int = pallas_delta.CHUNK, name=None,
                 **kwargs) -> None:
        kwargs.setdefault("weights_filling", "xavier")
        kwargs["include_bias"] = False
        super().__init__(workflow, name=name, **kwargs)
        for option, value in (("pre_norm", pre_norm),
                              ("post_norm", post_norm)):
            if value not in (None, "rms"):
                raise ValueError(f"{option} must be None or 'rms', got "
                                 f"{value!r}")
        if decay not in ("head", "channel"):
            raise ValueError(f"decay must be 'head' or 'channel', got "
                             f"{decay!r}")
        if gate not in ("silu", "sigmoid"):
            raise ValueError(f"gate must be 'silu' or 'sigmoid', got "
                             f"{gate!r}")
        if lower_bound is not None and not lower_bound < 0:
            raise ValueError(f"lower_bound {lower_bound} is not below 0")
        #: one decay a head, or one per key channel (module docstring)
        self.decay = decay
        #: log α ∈ (lower_bound, 0) by a sigmoid; None: −exp(A)·softplus
        self.lower_bound = None if lower_bound is None \
            else float(lower_bound)
        self.gate = gate
        self.n_heads = int(n_heads)
        self.key_dim, self.value_dim = int(key_dim), int(value_dim)
        self.conv_kernel = int(conv_kernel)
        #: β ∈ (0, 2) instead of (0, 1)
        self.allow_neg_eigval = bool(allow_neg_eigval)
        self.pre_norm, self.post_norm = pre_norm, post_norm
        self.residual = bool(residual)
        self.norm_eps = float(norm_eps)
        #: positions per chunk of the scan: the program's choice, not
        #: a model's
        self.chunk = int(chunk)
        for attr in self.EXPORT_PARAMS[1:]:
            setattr(self, attr, Vector(name=f"{self.name}.{attr}"))
        self._traced_vjp = None
        self._kernels = False
        self._prep = False
        self._interpret = False

    # -- parameters -----------------------------------------------------
    @property
    def decay_channels(self) -> int:
        """Decays a head: 1, or d_k."""
        return self.key_dim if self.decay == "channel" else 1

    def _decay_init(self) -> tuple:
        """A and b as the layer of arXiv:2412.06464 draws them: exp(A)
        uniform in (0, 16); softplus(b) log-uniform in (1e-3, 0.1)."""
        gen, h = prng.get(), self.n_heads
        a = gen.fill_uniform((h,), 1e-3, 16.0, dtype=np.float32)
        dt = np.exp(gen.fill_uniform(
            (h * self.decay_channels,), np.log(1e-3), np.log(0.1),
            dtype=np.float32))
        dt = np.maximum(dt, 1e-4)
        return np.log(a).astype(np.float32), \
            (dt + np.log(-np.expm1(-dt))).astype(np.float32)

    def unserved(self) -> str | None:
        channel = self.decay == "channel"
        return super().unserved() or (
            f"is a gated-delta-rule linear-attention layer "
            f"(gated_delta_net"
            f"{', decay=channel: a decay per key channel' if channel else ''}"
            f"); serving has no state slot yet — the recurrent state and "
            f"the convolution's tail exist on the training path only "
            f"(ROADMAP R6, serving half)")

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device=device, **kwargs)
        if self.input is None or not self.input:
            raise AttributeError(f"{self}: input not linked yet")
        if len(self.input.shape) != 3:
            raise ValueError(f"{self}: expected (batch, time, features) "
                             f"input, got {self.input.shape}")
        one_norm_placement(self)
        b, t, d = self.input.shape
        h, dk, dv = self.n_heads, self.key_dim, self.value_dim
        wide = h * (2 * dk + dv)
        for vec, shape in ((self.weights, (d, wide)),
                           (self.weights_gate, (d, h * dv)),
                           (self.weights_ba,
                            (d, h * (1 + self.decay_channels))),
                           (self.weights_out, (h * dv, d))):
            if not vec:
                vec.reset(self.fill_array(shape, self.weights_filling,
                                          self.weights_stddev,
                                          fan_in=shape[0]))
        if not self.weights_conv:
            self.weights_conv.reset(self.fill_array(
                (wide, self.conv_kernel), "uniform", None,
                fan_in=self.conv_kernel))
        if not self.decay_log:
            a_log, bias = self._decay_init()
            self.decay_log.reset(a_log)
            self.decay_bias.reset(bias)
        if not self.gain_out:
            self.gain_out.reset(np.ones(dv, np.float32))
        if (self.pre_norm or self.post_norm) and not self.gain_norm:
            self.gain_norm.reset(np.ones(d, np.float32))
        self.output.reset(np.zeros((b, t, d),
                                   dtype=self.output_store_dtype))
        self.inherit_model_shard(self.output)
        from znicz_tpu.parallel import partition
        for attr in ("decay_log", "decay_bias"):   # a scalar per head
            self.partition_leaf(attr, partition.REPLICATED)
        self._resolve_path(t)
        self.init_vectors(self.input, self.output,
                          *(getattr(self, a) for a in self.EXPORT_PARAMS))

    def _resolve_path(self, t: int) -> None:
        """Kernels or ``jax.numpy`` and the plain scan, once per
        ``initialize`` and by one rule for what is local to a chunk
        (``chunk_path``) and the walk over the chunks (``path``); on
        that path the taps, the SiLU and the norms are kernels too
        where a head's columns are whole lane tiles (``prep_path``);
        the gauge ``znicz_delta_scan`` and the info line say which."""
        from znicz_tpu.ops import pallas_kernels
        from znicz_tpu.utils.config import root
        interpret = bool(root.common.engine.get("pallas_interpret",
                                                False))
        refused = pallas_kernels.kernel_refusal(
            self.device, "delta_scan_kernel", interpret)
        mesh = getattr(self.device, "mesh", None)
        if refused is None and mesh is not None and mesh.size > 1:
            refused = (f"a mesh of {mesh.size} devices: the kernels "
                       f"have no sharding rule")
        if refused is None and not pallas_delta.kernel_legal(
                self.chunk):
            refused = f"a chunk of {self.chunk} is not whole sublanes"
        sub = pallas_delta.sub_block(self.chunk)
        if self.decay == "channel":
            # the sub-block algebra exponentiates up to sub · |bound|
            # (pallas_delta.MAX_EXPONENT: the bound's one statement)
            reach = float("inf") if self.lower_bound is None \
                else sub * -self.lower_bound
            if reach > pallas_delta.MAX_EXPONENT:
                raise ValueError(
                    f"{self}: decay='channel' with lower_bound "
                    f"{self.lower_bound} over sub-blocks of {sub} "
                    f"positions exponentiates up to {reach:g}, past "
                    f"pallas_delta.MAX_EXPONENT "
                    f"{pallas_delta.MAX_EXPONENT:g}: the f32 range")
        self._kernels, self._interpret = refused is None, interpret
        dk, dv, chunk = self.key_dim, self.value_dim, self.chunk
        # the taps, the SiLU and the norms as kernels too where a head's
        # columns are whole lane tiles of the projection
        self._prep = self._kernels and pallas_delta.prep_legal(dk, dv)
        chunks = -(-t // chunk)
        b = self.input.shape[0]
        # what a chunk's kernels multiply, forward + backward: products
        # of two real f32 factors (six bf16 passes) and products with a
        # 0 / ±1 matrix (the f32 factor's three parts, one contraction)
        exact = masks = 0
        if self._kernels:
            products = pallas_delta.chunk_products(
                self.decay == "channel", chunk, sub, self.mxu_dtype)
            exact = products["exact_fwd"] + products["exact_bwd"]
            masks = products["mask_fwd"] + products["mask_bwd"]
        stats = {
            "chunk": chunk, "chunks": chunks, "key_dim": dk,
            "value_dim": dv,
            "padded_share": pallas_delta.padded_share(dk, dv)
            if self._kernels else 1.0,
            "state_mb": b * self.n_heads * chunks * dk * dv * 4 / 1e6,
            "path": 1.0 if self._kernels else 0.0,
            "chunk_path": 1.0 if self._kernels else 0.0,
            "prep_path": 1.0 if self._prep else 0.0,
            "exact_products": exact, "mask_products": masks,
            "decay_channels": self.decay_channels,
            "sub_block": sub if self.decay == "channel" else chunk}
        for stat, value in stats.items():
            _metrics.delta_scan(self.name, stat).set(value)
        self.info(
            "%s: gated delta rule over %d chunks of %d (%d heads, "
            "d_k %d, d_v %d%s): %s; %s; %.1f MB of per-chunk states kept "
            "for the backward, tiles hold %.2f x d_k x d_v",
            self.name, chunks, chunk, self.n_heads, dk, dv,
            f", {chunks * chunk - t} positions of padding"
            if t % chunk else "",
            ("znicz_kda_chunk_fwd / _bwd kernels for what is local to a "
             f"chunk (a decay per key channel, sub-blocks of {sub}), "
             "znicz_kda_state_fwd / _bwd for the walk"
             if self.decay == "channel" else
             "znicz_gdr_chunk_fwd / _bwd kernels for what is local to a "
             "chunk, znicz_delta_state_fwd / _bwd for the walk")
            + f", {exact} f32 products at six passes and {masks} with a "
              "0/1 matrix at three a chunk"
            + (" (interpreted)" if interpret else "")
            if self._kernels else f"plain scan ({refused})",
            "znicz_qkv_prep_fwd / _bwd kernels from the projection to "
            "head-major q, k, v" if self._prep else
            "taps, SiLU and norms in jax.numpy (" + (
                f"heads of {dk} x {dv} are not whole 128-lane tiles"
                if self._kernels else "no kernels") + ")",
            stats["state_mb"], stats["padded_share"])

    # -- pure forward ---------------------------------------------------
    def forward_args(self) -> tuple:
        return (self.input.devmem,) + tuple(
            getattr(self, attr).devmem if getattr(self, attr) else None
            for attr in self.EXPORT_PARAMS)

    def _gates(self, xp, ba, a_log, bias):
        """(…, H + H·channels) logits → β (…, H) and log α, (…, H) or
        (…, H, d_k) with a decay per key channel, f32."""
        h = self.n_heads
        beta = _sigmoid(xp, ba[..., :h])
        if self.allow_neg_eigval:
            beta = 2.0 * beta
        if self.decay == "head" and self.lower_bound is None:
            # the scalar-decay program as it was, equation for equation
            # (its persisted key does not move)
            return beta, \
                -xp.exp(a_log) * _softplus(xp, ba[..., h:] + bias)
        rate, logits = xp.exp(a_log), ba[..., h:] + bias
        if self.decay == "channel":
            logits = logits.reshape(logits.shape[:-1]
                                    + (h, self.key_dim))
            rate = rate[:, None]
        if self.lower_bound is None:
            return beta, -rate * _softplus(xp, logits)
        return beta, self.lower_bound * _logistic(xp, rate * logits)

    def _gate_of(self, xp, gate):
        return _silu(xp, gate) if self.gate == "silu" \
            else _sigmoid(xp, gate)

    def _heads(self, xp, mixed):
        """The convolved q ‖ k ‖ v (B, T, ·) → q, k (B, T, H, d_k)
        normed and scaled, v (B, T, H, d_v)."""
        b, t, _ = mixed.shape
        h, dk, dv = self.n_heads, self.key_dim, self.value_dim
        q = mixed[..., :h * dk].reshape(b, t, h, dk)
        k = mixed[..., h * dk:2 * h * dk].reshape(b, t, h, dk)
        v = mixed[..., 2 * h * dk:].reshape(b, t, h, dv)
        return (l2_normalize(xp, q, self.norm_eps) * dk ** -0.5,
                l2_normalize(xp, k, self.norm_eps), v)

    def xla_forward(self, x, w_qkv, w_conv, w_gate, w_ba, a_log, bias,
                    g_out, w_out, g_norm=None):
        b, t, d = x.shape
        h, dv = self.n_heads, self.value_dim
        x32 = x.astype(jnp.float32)
        m = x32 if g_norm is None or self.post_norm \
            else rms_norm(jnp, x32, g_norm, self.norm_eps)
        rows = m.reshape(b * t, d)

        def heads(projected, taps):
            return self._heads(jnp, _silu(jnp, causal_conv(
                jnp, projected, taps)))

        pad = -t % self.chunk
        projected = self.mxu_dot(jnp, rows, w_qkv).reshape(b, t, -1)
        if self._prep:
            # ONE kernel each way from the projection where it lies to
            # q, k, v (B, H, T + pad, ·), the rows the rule's kernels
            # read; its backward keeps the projection and the taps
            q, k, v = pallas_delta.qkv_prep(
                projected, w_conv, h, self.key_dim, dv, self.norm_eps,
                pad=pad, interpret=self._interpret)
        else:
            if self.decay == "channel":
                # the backward keeps the PROJECTION and runs the taps,
                # the SiLU and the two norms again (elementwise: a pass
                # over 3 · T · H · d values) — under plain autodiff the
                # convolved and the activated copies are kept (2 · 192
                # MB a layer at T 4,096 × 32 heads of 128) and XLA,
                # short of memory, makes the projection again as a
                # MATMUL
                heads = jax.checkpoint(heads)
            q, k, v = heads(projected, w_conv)
        beta, log_alpha = self._gates(
            jnp, self.mxu_dot(jnp, rows, w_ba).reshape(b, t, -1),
            a_log, bias)
        if pad:        # positions that write nothing and decay nothing
            def padded(*arrays):
                return (jnp.pad(a, ((0, 0), (0, pad))
                                + ((0, 0),) * (a.ndim - 2))
                        for a in arrays)
            if not self._prep:          # the kernel wrote them padded
                q, k, v = padded(q, k, v)
            beta, log_alpha = padded(beta, log_alpha)
        o = pallas_delta.gated_delta_rule(
            q, k, v, log_alpha, beta, chunk=self.chunk,
            kernel=self._kernels, interpret=self._interpret,
            dot_dtype=self.mxu_dtype, head_major=self._prep)[:, :t]
        gate = self.mxu_dot(jnp, rows, w_gate).reshape(b, t, h, dv)
        o = rms_norm(jnp, o, g_out, self.norm_eps) \
            * self._gate_of(jnp, gate)
        y = self.mxu_dot(jnp, o.reshape(b * t, h * dv),
                         w_out).reshape(b, t, d)
        if self.post_norm:
            y = rms_norm(jnp, y, g_norm, self.norm_eps)
        return x32 + y if self.residual else y

    def xla_run(self) -> None:
        args = self.forward_args()
        if not self.output._tracing:
            self._traced_vjp = None
            self.output.devmem = self.xla_forward(*args)
            return
        self.output.devmem, self._traced_vjp = jax.vjp(
            self.xla_forward, *args)

    # -- numpy oracle: the recurrence itself, token by token ------------
    def _forward_np(self, x):
        b, t, d = x.shape
        h, dk, dv = self.n_heads, self.key_dim, self.value_dim
        gain = self.gain_norm.mem if self.gain_norm else None
        m = rms_norm(np, x, gain, self.norm_eps) if self.pre_norm else x
        rows = m.reshape(b * t, d)
        mixed = _silu(np, causal_conv(
            np, (rows @ self.weights.mem).reshape(b, t, -1),
            self.weights_conv.mem))
        q, k, v = self._heads(np, mixed)
        beta, log_alpha = self._gates(
            np, (rows @ self.weights_ba.mem).reshape(b, t, -1),
            self.decay_log.mem, self.decay_bias.mem)
        if self.decay == "head":
            log_alpha = log_alpha[..., None]
        state = np.zeros((b, h, dk, dv), np.float32)
        o = np.zeros((b, t, h, dv), np.float32)
        for i in range(t):
            state = state * np.exp(log_alpha[:, i])[..., None]
            seen = np.einsum("bhkv,bhk->bhv", state, k[:, i])
            state = state + beta[:, i][..., None, None] * np.einsum(
                "bhk,bhv->bhkv", k[:, i], v[:, i] - seen)
            o[:, i] = np.einsum("bhkv,bhk->bhv", state, q[:, i])
        gate = (rows @ self.weights_gate.mem).reshape(b, t, h, dv)
        o = rms_norm(np, o, self.gain_out.mem, self.norm_eps) \
            * self._gate_of(np, gate)
        y = (o.reshape(b * t, h * dv) @ self.weights_out.mem).reshape(
            b, t, d)
        if self.post_norm:
            y = rms_norm(np, y, gain, self.norm_eps)
        return x + y if self.residual else y

    def numpy_run(self) -> None:
        self.input.map_read()
        for attr in self.EXPORT_PARAMS:
            if getattr(self, attr):
                getattr(self, attr).map_read()
        self.output.map_invalidate()
        self.output.mem[...] = self._forward_np(
            self.input.mem.astype(np.float32))


class GDGatedDeltaNet(GDMoE):
    """Backward of :class:`GatedDeltaNet`: the forward's stashed
    pullback (autodiff around the ``custom_vjp`` of the prep kernels,
    of the chunk-local kernels and of the state kernels),
    every parameter through the base's update rule.  There is no
    analytic numpy backward: the numpy path differentiates the XLA
    forward on the host (the recurrence is checked against
    ``znbench/reference/olmo_hybrid.py`` instead)."""

    MATCHES = (GatedDeltaNet,)
    EXTRA = GatedDeltaNet.EXPORT_PARAMS[1:]
    HAS_AUX = False

    def __init__(self, workflow, name=None, **kwargs):
        super().__init__(workflow, name=name, **kwargs)
        self._host_pullback = None

    def _cotangent(self, xp, err):
        return err

    def numpy_run(self) -> None:
        fwd = self.forward_unit
        for vec in (self.err_output, self.input):
            vec.map_read()
        self.weights.map_write()
        for _, param, _ in self._extra_pairs():
            param.map_write()
        args = (self.input.mem.astype(np.float32),) + tuple(
            getattr(fwd, attr).mem if getattr(fwd, attr) else None
            for attr in fwd.EXPORT_PARAMS)
        if self._host_pullback is None:   # one host program, built once
            self._host_pullback = jax.jit(
                lambda err, *args: jax.vjp(fwd.xla_forward, *args)[1](err))
        with jax.default_matmul_precision("highest"):
            gx, g_own, *g_extra = self._host_pullback(
                jnp.asarray(self.err_output.mem, jnp.float32), *args)
        if self.need_err_input:
            self.err_input.map_invalidate()
            self.err_input.mem[...] = np.asarray(gx)
        self._apply_weights_np(np.asarray(g_own))
        grads = dict(zip(self.EXTRA, g_extra))
        for attr, param, acc in self._extra_pairs():
            self._apply_weights_np(np.asarray(grads[attr]), vec=param,
                                   acc_vec=acc)

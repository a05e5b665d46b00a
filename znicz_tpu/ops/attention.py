"""Multi-head attention units — the long-context op family.

The 2015 reference has no attention (SURVEY.md §5.7), but this
framework treats long-context machinery as first-class: this module
makes the :mod:`znicz_tpu.parallel.ring_attention` primitive
consumable from the unit graph.

``MultiHeadAttention`` maps (B, T, D) → (B, T, D):

.. code-block:: text

    qkv  = x @ W_qkv + b_qkv          (D, 3·D) packed projection
    q,k,v split → (B, T, H, D/H)
    o    = softmax(q·kᵀ/√dₕ [+causal]) · v
    y    = concat(o) @ W_out + b_out   (D, D)

``seq_parallel=True`` runs the attention core **blockwise around the
ICI ring** over the device mesh's ``model`` axis (K/V shards rotate
via ``ppermute``, online-softmax accumulation; no device materializes
the (T, T) score matrix) — sequences longer than one chip's HBM shard
over the mesh exactly like the scaling-book recipe.  The unit's
output Vector carries ``model_shard_dim=1`` (the time axis) so the
sharding annotation flows through the graph.

The pre-norm residual block (ROADMAP R0; OLMoE, PR 25) is options of
this unit, all off by default so that a bare attention layer's program
does not change: ``pre_norm="rms"`` attends over ``RMSNorm(x)``,
``residual=True`` adds the input back (``y = x + f(norm(x))`` is ONE
unit with ONE GD pair, so the workflow's graph stays a chain),
``qk_norm="rms"`` normalizes the whole D-wide q and k projections (not
per head) with a gain each — ``qk_norm="rms_head"`` (LFM2, PR 43) each
HEAD of q and of k over its own dh dims, one gain of dh shared by the
heads —, ``rope={"theta": …}`` rotates q and k by
position over the full head in the half-split ("rotate_half")
convention.  Norms and rotation run in f32 between the QKV projection
and the attention core; the kernels are untouched.

``post_norm="rms"`` (Olmo-Hybrid-7B, PR 31) is the family's other
placement of the block's norm: on the sublayer's OUTPUT, inside the
skip — ``y = x + RMSNorm(f(x))``.  Alone, its gain is ``gain_norm``
too, so a layer moves between the placements by one word of the layer
table.  BOTH at once (Ouro's sandwich norm, PR 35) is
``y = x + RMSNorm_post(f(RMSNorm_pre(x)))`` with a gain each:
``gain_norm`` the norm's before the sublayer, ``gain_post`` the one's
on its output (:func:`~znicz_tpu.ops.rms_norm.norm_gains`).

Grouped queries, a window and a per-head gate (Laguna-S-2.1, PR 29)
are options of the same kind, per unit, so that one model mixes layers
of different head counts, windows and rotary rules: ``n_kv_heads`` (K
and V have fewer heads than q; query head h reads K/V head
h // (n_heads / n_kv_heads)), ``head_dim`` (the head size where
``n_heads · head_dim`` is not the model's width: the fused projection
is (D, (H + 2·H_kv)·dh) and the out-projection (H·dh, D)), ``window``
(causal only: position r attends to (r − window, r]), ``head_gate``
(``o_h`` is multiplied by ``σ(n · W_g)_h``, W_g (D, H), n the
sublayer's normed input — the head-wise sigmoid output gate of
arXiv:2505.06708) and, inside ``rope``, ``rotary_dim`` (only the first
``rotary_dim`` of a head rotate) and ``yarn`` (arXiv:2309.00071: blended
inverse frequencies, cos and sin scaled by ``attention_factor``).  The
flash kernels take the group and the window (``pallas_attention``); a
shape they cannot tile takes the plain core WITH the band mask; the
ring and the scan-blocked core refuse them at ``initialize``.

A latent K/V (multi-head latent attention, arXiv:2405.04434 §2.1
without the query latent; Ling-3.0-flash's full layers, PR 37) is one
more set of per-unit options: ``kv_latent`` (the compressed K/V's
width, with its own RMSNorm, gain ``gain_latent``), ``qk_nope`` (a
key's per-head width, up-projected from the latent), ``qk_rope`` (the
rotary width: ONE key of that width is shared by all heads) and
``v_head_dim``:

.. code-block:: text

    [q_nope | q_rope | c | k_r] = m W        ``weights`` (D, H·nope + H·rope
                                             + latent + rope): all heads'
                                             q_nope, all heads' q_rope, c, k_r
    c = RMSNorm(c) ;  [k_nope | v] = c W_up  ``weights_kv_up`` (latent,
                                             H·nope + H·v): all heads'
                                             k_nope, then all heads' v
    q_rope, k_r rotated (``rope``, over those ``qk_rope`` only)
    s_h = (q_nope,h·k_nope,h + q_rope,h·k_r) / √(nope + rope), causal
    o_h = softmax(s_h) v_h ;  y = concat_h(σ((m W_gate)_h) o_h) W_out

With ``q_latent`` (the query latent of the same paper, which Ling
leaves out; Xing4.0-29B-A4B, PR 46) the queries are up-projected from a
latent of their own, and ``weights`` holds the two DOWN-projections
only:

.. code-block:: text

    [c_q | c | k_r] = m W                    ``weights`` (D, q_latent + latent
                                             + rope)
    c_q = RMSNorm(c_q)                       gain ``gain_q_latent``
    [q_nope | q_rope] = c_q W_uq             ``weights_q_up`` (q_latent,
                                             H·nope + H·rope): all heads'
                                             q_nope, then all heads' q_rope

``score_scale`` replaces the factor 1/√(nope + rope) of the scores (a
family that scales the whole score by the square of YaRN's factor and
leaves the tables alone gives ``rope.yarn`` with ``attention_factor``
1 and the product here); both absent leave the layer as it was.

The column order of ``weights`` (parts side by side, not per head) is a
fixed permutation of the published one; the rotation is this module's
half-split convention, which differs from an interleaved one by a
fixed permutation of the rotary columns of W on BOTH sides of the
product, so the scores are the same
(``tests/test_ling_reference.py``).  On a TPU the core is
``ops/pallas_mla.py`` — keys of two widths, values not padded, nothing
of shape (B, T, H, nope + rope) written; elsewhere, and for shapes
those kernels do not tile, the plain core with K assembled in full.
``head_layout``'s head-major fallback for dh 192 is never reached by
such a layer.

Backward (``GDMultiHeadAttention``): ``jax.vjp`` of the forward on
the XLA path — this differentiates THROUGH the shard_map/ppermute
ring, so sequence-parallel training needs no hand-written collective
gradients — validated against the explicit analytic numpy oracle.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from znicz_tpu.memory import Vector
from znicz_tpu.observe import scopes as _scopes
from znicz_tpu.ops.nn_units import Forward, GradientDescentBase
from znicz_tpu.ops.rms_norm import (norm_gains, post_gain, rms_norm,
                                    rms_norm_backward)
from znicz_tpu.parallel.axis import DATA_AXIS, MODEL_AXIS, SEQ_AXIS


def _split_heads(qkv, n_heads: int):
    """(B, T, 3D) → three (B, T, H, D/H) (pure slicing/reshape —
    backend-agnostic)."""
    b, t, d3 = qkv.shape
    d = d3 // 3
    dh = d // n_heads
    q, k, v = qkv[..., :d], qkv[..., d:2 * d], qkv[..., 2 * d:]
    reshape = (b, t, n_heads, dh)
    return q.reshape(reshape), k.reshape(reshape), v.reshape(reshape)


def yarn_inv_freq(dim: int, theta: float, yarn: dict) -> np.ndarray:
    """YaRN's (arXiv:2309.00071) ``dim/2`` inverse frequencies, float64,
    as ``transformers``' ``_compute_yarn_parameters``: the plain
    ``theta^(−2i/dim)`` and the same ÷ ``factor``, blended over the
    linear ramp between the two correction dims (where a frequency
    turns ``beta_fast`` and ``beta_slow`` times over the original
    context)."""
    factor = float(yarn["factor"])
    original = float(yarn["original_max_position_embeddings"])

    def correction_dim(rotations: float) -> float:
        return dim * np.log(original / (rotations * 2 * np.pi)) \
            / (2 * np.log(theta))

    low = max(np.floor(correction_dim(float(yarn.get("beta_fast", 32)))),
              0)
    high = min(np.ceil(correction_dim(float(yarn.get("beta_slow", 1)))),
               dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    plain = 1.0 / np.power(float(theta),
                           np.arange(0, dim, 2, dtype=np.float64) / dim)
    return plain / factor * ramp + plain * (1.0 - ramp)


def yarn_attention_factor(yarn: dict) -> float:
    given = yarn.get("attention_factor")
    return float(given) if given is not None \
        else 0.1 * float(np.log(float(yarn["factor"]))) + 1.0


def rope_tables(xp, t: int, dh: int, theta: float, yarn=None):
    """(T, dh/2) cosines and sines of ``pos · theta^(−2i/dh)``, f32;
    with ``yarn`` the blended frequencies, both tables scaled by its
    ``attention_factor``.  ``dh`` is the ROTATED width."""
    if yarn:
        inv_freq = yarn_inv_freq(dh, float(theta), yarn)
        scale = yarn_attention_factor(yarn)
    else:
        inv_freq = 1.0 / np.power(
            float(theta), np.arange(0, dh, 2, dtype=np.float64) / dh)
        scale = 1.0
    angle = np.arange(t, dtype=np.float64)[:, None] * inv_freq[None, :]
    return (xp.asarray(np.cos(angle) * scale, dtype=xp.float32),
            xp.asarray(np.sin(angle) * scale, dtype=xp.float32))


def apply_rope(xp, x, cos, sin, inverse: bool = False):
    """Rotate (B, T, H, dh) by position, half-split convention:
    ``(x₁, x₂) → (x₁·cos − x₂·sin, x₂·cos + x₁·sin)`` with x₁ / x₂ the
    two halves of the head.  ``inverse`` rotates back — the adjoint,
    which is what the backward applies to the cotangent."""
    return _rotate(xp, x, cos[None, :, None, :], sin[None, :, None, :],
                   inverse)


def _rotate(xp, x, c, s, inverse: bool = False):
    """The half-split rotation of (…, dh) by tables that broadcast
    against (…, rot/2): the first ``rot`` of a head rotate (its two
    halves being x₁, x₂), the rest pass (``rot`` = dh: the whole
    head)."""
    half = c.shape[-1]
    x1, x2 = x[..., :half], x[..., half:2 * half]
    if inverse:
        s = -s
    return xp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s]
                          + ([x[..., 2 * half:]]
                             if 2 * half < x.shape[-1] else []), axis=-1)


def apply_rope_rows(xp, x, cos, sin, n_heads: int):
    """:func:`apply_rope` over (B, T, H·dh) rows without leaving their
    layout.  On a TPU a (B, T, D) array lies in tiles of 8 rows × 128
    columns, and its (B, T, H, dh) view in tiles of 8 HEADS × 128: no
    bitcast, so slicing heads there costs a relayout each way (compiled
    for a described v5e: eight of B·T·D elements per layer and step;
    PR 28).  The view (B, T/8, H, 8, dh) keeps 8 rows of one head
    together — the tiles the rows already lie in — and there the
    rotation is fusions over them and nothing else."""
    b, t, d = x.shape
    dh = d // n_heads
    sub = 8 if t % 8 == 0 else 1
    tiles = x.reshape(b, t // sub, sub, n_heads, dh) \
        .transpose(0, 1, 3, 2, 4)
    table = (t // sub, 1, sub, cos.shape[-1])
    out = _rotate(xp, tiles, cos.reshape(table), sin.reshape(table))
    return out.transpose(0, 1, 3, 2, 4).reshape(b, t, d)


def band_mask(xp, tq: int, tk: int, window=None):
    """(tq, tk) causal visibility; with a ``window`` row r also stops
    seeing columns ≤ r − window."""
    rows, cols = xp.arange(tq)[:, None], xp.arange(tk)[None, :]
    mask = rows >= cols
    return mask if window is None else mask & (cols > rows - window)


def _local_attention_np(q, k, v, causal: bool, window=None):
    """Numpy oracle core (mirrors parallel.ring_attention's
    local_attention); K/V heads are repeated over their group."""
    d = q.shape[-1]
    group = q.shape[2] // k.shape[2]
    if group > 1:
        k, v = np.repeat(k, group, axis=2), np.repeat(v, group, axis=2)
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    if causal:
        mask = band_mask(np, q.shape[1], k.shape[1], window)
        s = np.where(mask[None, None], s, -1e30)
    s = s - s.max(axis=-1, keepdims=True)
    p = np.exp(s)
    p = p / p.sum(axis=-1, keepdims=True)
    o = np.einsum("bhqk,bkhd->bqhd", p, v)
    return o, p


def latent_attention_plain(q_nope, q_rope, k_nope, k_rope, v,
                           n_heads: int, xp=jnp):
    """The plain core of a latent-K/V layer, causal: rows as the
    kernels take them (q scaled already), K assembled in full — the
    shared rotary key repeated for every head — and the (T, T) scores
    in memory."""
    b, t, _ = q_nope.shape
    h = n_heads

    def heads(a):
        return a.reshape(b, t, h, -1)

    q = xp.concatenate([heads(q_nope), heads(q_rope)], axis=-1)
    k = xp.concatenate(
        [heads(k_nope), xp.broadcast_to(
            k_rope[:, :, None, :], (b, t, h, k_rope.shape[-1]))], axis=-1)
    if xp is jnp:
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32)
    else:
        s = np.einsum("bqhd,bkhd->bhqk", q, k)
    s = xp.where(band_mask(xp, t, t)[None, None], s, -1e30)
    s = s - s.max(axis=-1, keepdims=True)
    p = xp.exp(s)
    p = p / p.sum(axis=-1, keepdims=True)
    if xp is jnp:
        o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), heads(v),
                       preferred_element_type=jnp.float32)
    else:
        o = np.einsum("bhqk,bkhd->bqhd", p, heads(v))
    return o.reshape(b, t, -1)


class MultiHeadAttention(Forward):
    """Weighted multi-head self-attention layer."""

    EXPORT_PARAMS = ("weights", "bias", "weights_out", "bias_out",
                     "gain_norm", "gain_q", "gain_k", "weights_head_gate",
                     "gain_post", "weights_kv_up", "gain_latent",
                     "weights_q_up", "gain_q_latent")
    #: may be a member of a looped span (``znicz_tpu.pass_span``): the
    #: backward needs the forward's input and pullback only
    PASS_SAFE = True
    #: the scopes inside a latent-K/V layer's two units
    #: (:meth:`_latent_forward`): its matmuls outside the kernels, read
    #: by the products — on a TPU hardly one stands in a fusion of its
    #: own scope alone — and the element-wise passes at activation size
    #: around them
    PHASES = {"project": _scopes.PRODUCTS, "rotate_norm": _scopes.ALL}
    #: the options of the pre-norm residual block (ROADMAP R0), which
    #: serving has no step for (:meth:`unserved`)
    _BLOCK_OPTIONS = ("pre_norm", "post_norm", "qk_norm", "rope_theta",
                      "residual", "head_dim", "window", "head_gate")

    def __init__(self, workflow, n_heads: int, causal: bool = False,
                 seq_parallel: bool = False,
                 flash_block_k: int | None = None,
                 pre_norm: str | None = None, residual: bool = False,
                 qk_norm: str | None = None, rope: dict | None = None,
                 norm_eps: float = 1e-5,
                 n_kv_heads: int | None = None,
                 head_dim: int | None = None,
                 window: int | None = None, head_gate: bool = False,
                 post_norm: str | None = None,
                 kv_latent: int | None = None, qk_nope: int = 0,
                 qk_rope: int = 0, v_head_dim: int = 0,
                 q_latent: int | None = None,
                 score_scale: float | None = None,
                 name=None, **kwargs) -> None:
        # attention defaults to fan-scaled init (the reference's
        # fixed-stddev fillings predate attention entirely)
        kwargs.setdefault("weights_filling", "xavier")
        super().__init__(workflow, name=name, **kwargs)
        self.n_heads = int(n_heads)
        self.causal = bool(causal)
        #: flash-style blocked local attention: scan over K/V blocks
        #: of this size with the ring's online-softmax fold, so the
        #: (T, T) score matrix never materializes in HBM (None = the
        #: plain form; long sequences want T×T HBM traffic gone —
        #: measured A/B in SEQ_BENCH.json)
        self.flash_block_k = (None if flash_block_k is None
                              else int(flash_block_k))
        #: ring attention over the mesh's model axis (time-sharded).
        #: This is the CONFIGURED request and is never mutated;
        #: :attr:`ring_active` is the per-initialize resolution (a mesh
        #: without a model axis falls back to local attention, but
        #: re-initializing on a capable mesh re-engages the ring).
        self.seq_parallel = bool(seq_parallel)
        self._ring_active = False
        for option, value in (("pre_norm", pre_norm),
                              ("post_norm", post_norm)):
            if value not in (None, "rms"):
                raise ValueError(f"{option} must be None or 'rms', got "
                                 f"{value!r}")
        if qk_norm not in (None, "rms", "rms_head"):
            raise ValueError(f"qk_norm must be None, 'rms' or "
                             f"'rms_head', got {qk_norm!r}")
        #: the pre-norm residual block (module docstring); all off =
        #: the bare layer, whose program these options leave untouched
        self.pre_norm = pre_norm
        #: the norm on the sublayer's OUTPUT, inside the skip:
        #: x + RMSNorm(f(x)) (OLMo 2's placement); its gain is
        #: ``gain_norm`` where it is the block's one norm, ``gain_post``
        #: beside a ``pre_norm`` (module docstring)
        self.post_norm = post_norm
        self.residual = bool(residual)
        self.qk_norm = qk_norm
        self.rope_theta = None if rope is None else float(rope["theta"])
        #: how many dims of a head rotate (None: all) and the YaRN
        #: scaling of the frequencies (None: none)
        self.rotary_dim = None if not rope or not rope.get("rotary_dim") \
            else int(rope["rotary_dim"])
        self.rope_yarn = dict(rope["yarn"]) \
            if rope and rope.get("yarn") else None
        self.norm_eps = float(norm_eps)
        #: grouped queries, a head size of its own, the window and the
        #: per-head gate (module docstring); all unset = the layer as
        #: it was
        self.n_kv_heads = self.n_heads if n_kv_heads is None \
            else int(n_kv_heads)
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"{n_heads} query heads do not divide over "
                             f"{n_kv_heads} K/V heads")
        self.head_dim = None if head_dim is None else int(head_dim)
        self.window = None if window is None else int(window)
        if self.window is not None and (self.window < 1 or not causal):
            raise ValueError(f"window {window} needs causal=True and "
                             f"≥ 1 position")
        self.head_gate = bool(head_gate)
        #: a latent K/V (module docstring): None = the layer as it was
        self.kv_latent = None if kv_latent is None else int(kv_latent)
        self.qk_nope, self.qk_rope = int(qk_nope), int(qk_rope)
        self.v_head_dim = int(v_head_dim)
        if self.kv_latent is not None:
            if not (causal and rope and self.qk_nope > 0
                    and self.qk_rope > 0 and self.qk_rope % 2 == 0
                    and self.v_head_dim > 0 and self.kv_latent > 0):
                raise ValueError(
                    f"kv_latent {kv_latent} needs causal=True, rope and "
                    f"qk_nope, qk_rope (even), v_head_dim > 0")
            refused = [name for name, on in (
                ("seq_parallel", seq_parallel),
                ("flash_block_k", flash_block_k), ("qk_norm", qk_norm),
                ("n_kv_heads", n_kv_heads), ("head_dim", head_dim),
                ("window", window), ("post_norm", post_norm),
                ("rope.rotary_dim", rope.get("rotary_dim")),
                ("include_bias", kwargs.get("include_bias", True)))
                if on]
            if refused:
                raise ValueError(
                    f"kv_latent does not combine with "
                    f"{', '.join(refused)}")
        elif q_latent is not None or score_scale is not None:
            raise ValueError("q_latent and score_scale are options of a "
                             "layer with kv_latent")
        #: the query latent's width and the scores' factor (module
        #: docstring): None = the layer as it was
        self.q_latent = None if q_latent is None else int(q_latent)
        self.score_scale = None if score_scale is None \
            else float(score_scale)
        self.weights_q_up = Vector(name=f"{self.name}.weights_q_up")
        self.gain_q_latent = Vector(name=f"{self.name}.gain_q_latent")
        self.weights_kv_up = Vector(name=f"{self.name}.weights_kv_up")
        self.gain_latent = Vector(name=f"{self.name}.gain_latent")
        self.weights_head_gate = Vector(
            name=f"{self.name}.weights_head_gate")
        self.gain_norm = Vector(name=f"{self.name}.gain_norm")
        self.gain_post = Vector(name=f"{self.name}.gain_post")
        self.gain_q = Vector(name=f"{self.name}.gain_q")
        self.gain_k = Vector(name=f"{self.name}.gain_k")
        #: pullback of the LAST application, stashed by xla_run for the
        #: GD pair (same trace; transient — never pickled, cleared by
        #: the consumer; a looped span keeps one per pass on its tape)
        self._traced_vjp = None
        #: how the flash kernels run this layer's call, decided once at
        #: ``initialize`` (``pallas_attention.plan``; ``pallas_mla.plan``
        #: for a latent K/V): the ONE value the unit holds of them
        self._flash = None
        self.weights_out = Vector(name=f"{self.name}.weights_out")
        self.bias_out = Vector(name=f"{self.name}.bias_out")

    def unserved(self) -> str | None:
        """Serving knows the bare attention layer only: a bundle's
        manifest carries no block option, the decode plan has no
        incremental step for rotary positions, the q/k norms or the
        pre-norm residual, no latent page."""
        said = super().unserved()
        if said is not None:
            return said
        if self.kv_latent is not None:
            more = [name for name in ("q_latent", "score_scale")
                    if getattr(self, name) is not None]
            return (
                f"sets kv_latent (a latent K/V with qk_nope, qk_rope, "
                f"v_head_dim{'; ' + ', '.join(more) if more else ''}); "
                f"serving has no latent page and no absorbed "
                f"projections yet — the prefill / decode steps cache "
                f"whole keys and values"
                + (", and the manifest lacks the query latent's two "
                   "projections, its norm's gain and the scores' factor"
                   if more else "")
                + " (ROADMAP R5, serving half)")
        if self.qk_norm == "rms_head":
            return ("sets qk_norm=rms_head (a norm over each head of q "
                    "and k, one gain of the head's size); the manifest "
                    "and the prefill / decode steps lack the gains and "
                    "the norm of a cached key (ROADMAP R1, serving half)")
        used = [name for name in self._BLOCK_OPTIONS
                if getattr(self, name)]
        if self.n_kv_heads != self.n_heads:
            used.append("n_kv_heads")
        if not used:
            return None
        return (
            f"sets {', '.join(n.replace('_theta', '') for n in used)}; "
            f"serving runs the bare attention layer only — the manifest "
            f"and the prefill / decode steps lack the norm gains, the "
            f"rotation by cached position and the residual (ROADMAP R1, "
            f"serving half)")

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device=device, **kwargs)
        if self.input is None or not self.input:
            raise AttributeError(f"{self}: input not linked yet")
        if len(self.input.shape) != 3:
            raise ValueError(f"{self}: expected (batch, time, features) "
                             f"input, got {self.input.shape}")
        b, t, d = self.input.shape
        if self.kv_latent is not None:
            return self._initialize_latent(b, t, d)
        if self.head_dim is None and d % self.n_heads:
            raise ValueError(f"{self}: features {d} not divisible by "
                             f"{self.n_heads} heads")
        #: widths of q (= the core's output) and of k, v
        q_width, kv_width, dh = self._widths(d)
        wide = q_width + 2 * kv_width
        if self.flash_block_k and t % self.flash_block_k:
            raise ValueError(
                f"{self}: time axis {t} not divisible by "
                f"flash_block_k {self.flash_block_k}")
        if not self.weights:
            self.weights.reset(self.fill_array(
                (d, wide), self.weights_filling,
                self.weights_stddev, fan_in=d))
        if not self.weights_out:
            self.weights_out.reset(self.fill_array(
                (q_width, d), self.weights_filling,
                self.weights_stddev, fan_in=q_width))
        if self.head_gate and not self.weights_head_gate:
            self.weights_head_gate.reset(self.fill_array(
                (d, self.n_heads), self.weights_filling,
                self.weights_stddev, fan_in=d))
        if self.include_bias:
            if not self.bias:
                self.bias.reset(np.zeros(wide, np.float32))
            if not self.bias_out:
                self.bias_out.reset(np.zeros(d, np.float32))
        for gain, width in ((self.gain_norm,
                             d if self.pre_norm or self.post_norm else 0),
                            (self.gain_post,
                             d if self.pre_norm and self.post_norm else 0),
                            (self.gain_q, self._qk_gain_width(q_width, dh)),
                            (self.gain_k,
                             self._qk_gain_width(kv_width, dh))):
            if width and not gain:
                gain.reset(np.ones(width, np.float32))
        rotated = self.rotary_dim or dh
        if self.rope_theta is not None and (rotated % 2 or rotated > dh):
            raise ValueError(f"{self}: rope needs an even rotated width "
                             f"within the head, got {rotated} of {dh}")
        #: the options the ring and the scan-blocked core do not know
        grouped = (self.n_kv_heads != self.n_heads
                   or self.window is not None)
        if grouped and (self.seq_parallel or self.flash_block_k):
            raise ValueError(
                f"{self}: grouped queries and a window run on the flash "
                f"kernels or the plain core, not on the ring or the "
                f"scan-blocked core (seq_parallel, flash_block_k)")
        self.output.reset(np.zeros((b, t, d),
                                   dtype=self.output_store_dtype))
        from jax.sharding import PartitionSpec as P
        from znicz_tpu.parallel import partition
        mesh = getattr(self.device, "mesh", None)
        self._ring_active = False
        #: mesh axis the ring rotates over: a 3-D (data × model × seq)
        #: mesh gives sequence parallelism its OWN axis so DP × TP ×
        #: SP compose; 2-D meshes keep the historical model-axis ring
        self._ring_axis = (SEQ_AXIS if mesh is not None
                           and mesh.shape.get(SEQ_AXIS, 1) > 1
                           else MODEL_AXIS)
        # the default (non-ring) placement replaces any stale
        # time-sharding rule from a prior ring-engaged initialize;
        # the ring branch below re-declares when it actually engages
        self.partition_leaf("output", partition.BATCH)
        if self.seq_parallel:
            ring_n = 1 if mesh is None \
                else mesh.shape.get(self._ring_axis, 1)
            if ring_n < 2:
                # no ring to ride — fall back to local attention (the
                # math is identical; seq_parallel is a layout choice).
                # The configured flag stays intact so a later
                # re-initialize on a capable mesh engages the ring.
                pass
            else:
                if t % ring_n:
                    raise ValueError(
                        f"{self}: time axis {t} not divisible by the "
                        f"{self._ring_axis}-axis size {ring_n}")
                self._ring_active = True
                # time rides the ring: declared, not hand-set
                self.partition_leaf(
                    "output", P(DATA_AXIS, self._ring_axis))
        # the core: the fused flash kernels where their plan says they
        # run this call (``pallas_attention.plan``), the RING folding
        # its hops with the same kernels (``engine.ring_pallas_fold``,
        # auto = TPU/interpret), else the XLA cores (local) or the scan
        # fold (ring)
        from znicz_tpu.ops import pallas_attention, pallas_kernels
        from znicz_tpu.utils.config import root
        self._flash = pallas_attention.plan(
            self.device, b, t, self.n_heads, self.n_kv_heads, dh,
            self.causal, self.window, self.flash_block_k,
            model_sharded=getattr(self.input, "model_shard_dim", None)
            is not None, ring=self._ring_active)
        #: which fold the ring runs ("pallas"/"scan"; None = no ring)
        #: — the multichip dryrun attests this
        self._ring_fold = None
        self._ring_block_q = None
        self._ring_block_k = self.flash_block_k
        if self._ring_active:
            from znicz_tpu.parallel.ring_attention import \
                ring_fold_choice
            rflag = root.common.engine.get("ring_pallas_fold", "auto")
            if rflag == "auto":
                rflag = (pallas_kernels.is_tpu_device(self.device)
                         or self._flash.interpret)
            self._ring_fold, self._ring_block_q, self._ring_block_k \
                = ring_fold_choice(
                    mesh, (b, t, self.n_heads, dh),
                    axis_name=self._ring_axis,
                    block_k=self.flash_block_k,
                    pallas_fold=bool(rflag))
            self.info("%s: ring attention over '%s', %s fold",
                      self.name, self._ring_axis, self._ring_fold)
        else:
            self.info("%s: %s", self.name, self._flash.line())
        if self._flash.runs:
            from znicz_tpu.observe import metrics as obs_metrics
            for stat, value in (
                    ("passes", self._flash.backward),
                    ("resident_dq_bytes", self._flash.resident_dq)):
                obs_metrics.flash_backward(self.name, stat).set(value)
        if self._flash.runs and self._flash.window is not None:
            for stat, value in (
                    ("window", self._flash.window),
                    ("band_share", self._flash.band_share),
                    ("executed_share",
                     self._flash.tiles["executed_share"])):
                obs_metrics.flash_band(self.name, stat).set(value)
        self.init_vectors(self.input, self.output, self.weights,
                          self.bias, self.weights_out, self.bias_out,
                          self.gain_norm, self.gain_q, self.gain_k,
                          self.weights_head_gate, self.gain_post)

    def _initialize_latent(self, b: int, t: int, d: int) -> None:
        """``initialize`` of a layer with a latent K/V (module
        docstring): its parameters, and the core — the two-width flash
        kernels where they tile the call, else the plain core."""
        h, nope, rope = self.n_heads, self.qk_nope, self.qk_rope
        latent, dv = self.kv_latent, self.v_head_dim
        q_wide = h * (nope + rope)
        for vec, shape in (
                (self.weights, (d, (self.q_latent or q_wide)
                                + latent + rope)),
                (self.weights_q_up, (self.q_latent or 0, q_wide)),
                (self.weights_kv_up, (latent, h * (nope + dv))),
                (self.weights_out, (h * dv, d)),
                (self.weights_head_gate, (d, h if self.head_gate else 0))):
            if all(shape) and not vec:
                vec.reset(self.fill_array(shape, self.weights_filling,
                                          self.weights_stddev,
                                          fan_in=shape[0]))
        if not self.gain_latent:
            self.gain_latent.reset(np.ones(latent, np.float32))
        if self.q_latent and not self.gain_q_latent:
            self.gain_q_latent.reset(np.ones(self.q_latent, np.float32))
        if self.pre_norm and not self.gain_norm:
            self.gain_norm.reset(np.ones(d, np.float32))
        self.output.reset(np.zeros((b, t, d),
                                   dtype=self.output_store_dtype))
        from znicz_tpu.observe import metrics as obs_metrics
        from znicz_tpu.ops import pallas_mla
        from znicz_tpu.parallel import partition
        self.partition_leaf("output", partition.BATCH)
        for attr in ("weights_kv_up", "gain_latent") + (
                ("weights_q_up", "gain_q_latent") if self.q_latent else ()):
            self.partition_leaf(attr, partition.REPLICATED)
        self._ring_active = False
        self._flash = pallas_mla.plan(self.device, t, h, nope, rope, dv)
        for stat, value in (("latent", latent), ("qk_nope", nope),
                            ("qk_rope", rope), ("v", dv)) + (
                (("q_latent", self.q_latent),) if self.q_latent else ()) + (
                (("backward_passes", self._flash.backward_passes),)
                if self._flash.runs else ()):
            obs_metrics.attention_latent(self.name, stat).set(value)
        self.info("%s: latent K/V of %d (+ %d shared rotary)%s, %d heads, "
                  "keys %d + %d, values %d: %s", self.name, latent, rope,
                  f", query latent of {self.q_latent}"
                  if self.q_latent else "",
                  h, nope, rope, dv, self._flash.line())
        self.init_vectors(self.input, self.output, self.weights,
                          self.weights_out, self.gain_norm,
                          self.weights_head_gate, self.weights_kv_up,
                          self.gain_latent, self.weights_q_up,
                          self.gain_q_latent)

    def _latent_scale(self) -> float:
        """The factor of a latent layer's scores."""
        return (self.qk_nope + self.qk_rope) ** -0.5 \
            if self.score_scale is None else self.score_scale

    def _latent_forward(self, x, w, w_out, g_norm, w_gate, w_up,
                        g_latent, w_q_up=None, g_q_latent=None):
        """A latent-K/V layer's forward.  Two phases are named for the
        program's map (``observe/scopes.py``), forward and pullback:
        ``project`` — the matmuls outside the kernels (the fused
        down-projection, the query's and the K/V's up-projections, the
        out-projection) — and ``rotate_norm`` — the element-wise passes
        at activation size around them (the pre-norm, the latents'
        norms, the rotations, the scale, the casts to the kernels'
        dtype); the kernels are their own names.  What the compiler
        fuses around a product of ``project`` reads ``project`` with
        it (the map's rule for this one phase)."""
        b, t, d = x.shape
        h, nope, rope = self.n_heads, self.qk_nope, self.qk_rope
        latent, dv = self.kv_latent, self.v_head_dim
        with jax.named_scope("rotate_norm"):
            x32 = x.astype(jnp.float32)
            m = x32 if g_norm is None \
                else rms_norm(jnp, x32, g_norm, self.norm_eps)
            rows = m.reshape(b * t, d)
        with jax.named_scope("project"):
            proj = self.mxu_dot(jnp, rows, w).reshape(b, t, -1)
        at = h * nope
        # the queries: columns of the one projection, or up-projected
        # from their own normed latent
        wide = h * (nope + rope)
        if w_q_up is None:
            q, below = proj, wide
        else:
            below = w_q_up.shape[0]
            with jax.named_scope("rotate_norm"):
                c_q = rms_norm(jnp, proj[..., :below], g_q_latent,
                               self.norm_eps).reshape(b * t, below)
            with jax.named_scope("project"):
                q = self.mxu_dot(jnp, c_q, w_q_up).reshape(b, t, wide)
        with jax.named_scope("rotate_norm"):
            c = rms_norm(jnp, proj[..., below:below + latent],
                         g_latent, self.norm_eps).reshape(b * t, latent)
            cos, sin = rope_tables(jnp, t, rope, self.rope_theta,
                                   self.rope_yarn)
            scale = self._latent_scale()
            q_nope = q[..., :at] * scale
            # the heads' rotary parts turn where they lie (PR 28's view)
            q_rope = apply_rope_rows(
                jnp, q[..., at:wide], cos, sin, h) * scale
            k_rope = apply_rope(jnp, proj[..., None, -rope:], cos,
                                sin).reshape(b, t, rope)
        # two products over the up-projection's column ranges, so that
        # neither k_nope ‖ v nor its cotangent is ever cut or joined at
        # activation size
        with jax.named_scope("project"):
            k_nope = self.mxu_dot(jnp, c, w_up[:, :at]).reshape(b, t, at)
            v = self.mxu_dot(jnp, c, w_up[:, at:]).reshape(b, t, h * dv)
        arrays = (q_nope, q_rope, k_nope, k_rope, v)
        if self.mxu_dtype is not None:
            with jax.named_scope("rotate_norm"):
                arrays = tuple(a.astype(self.mxu_dtype) for a in arrays)
        if self._flash is not None and self._flash.runs:
            o = self._flash.attend(*arrays)
        else:
            o = latent_attention_plain(*arrays, h)
        with jax.named_scope("rotate_norm"):
            o = o.astype(jnp.float32)
        with jax.named_scope("project"):
            return self._project_out(x32, m, o, w_out, None, w_gate, None)

    @property
    def ring_active(self) -> bool:
        """True when THIS initialization actually rides the ring
        (``seq_parallel`` requested AND the mesh has a model axis)."""
        return self._ring_active

    # -- pure forward (jnp; the backward vjp's this) --------------------
    def forward_args(self) -> tuple:
        """The arguments of :meth:`xla_forward` from this unit's
        Vectors (``None`` for what an option leaves out)."""
        def dev(vec):
            return vec.devmem if vec else None
        return (self.input.devmem, self.weights.devmem,
                self.bias.devmem if self.include_bias else None,
                self.weights_out.devmem,
                self.bias_out.devmem if self.include_bias else None,
                dev(self.gain_norm), dev(self.gain_q), dev(self.gain_k)) \
            + ((self.weights_head_gate.devmem,) if self.head_gate
               else (None,) if self.gain_post or self.kv_latent else ()) \
            + ((self.gain_post.devmem,) if self.gain_post
               else (None,) if self.kv_latent else ()) \
            + ((self.weights_kv_up.devmem, self.gain_latent.devmem)
               if self.kv_latent else ()) \
            + ((self.weights_q_up.devmem, self.gain_q_latent.devmem)
               if self.q_latent else ())

    def _widths(self, d: int) -> tuple:
        """(q width, k/v width, head size) for a model width ``d``."""
        dh = self.head_dim or d // self.n_heads
        return self.n_heads * dh, self.n_kv_heads * dh, dh

    def _qk_gain_width(self, width: int, dh: int) -> int:
        """A q/k norm's gain: the projection's width, a head's under
        ``rms_head``, none without the norm."""
        return {None: 0, "rms": width, "rms_head": dh}[self.qk_norm]

    def _qk_normed(self, xp, rows, gain, dh: int):
        """(B, T, width) normed over the whole projection (``rms``) or
        over each head's ``dh`` (``rms_head``)."""
        if self.qk_norm == "rms":
            return rms_norm(xp, rows, gain, self.norm_eps)
        b, t, width = rows.shape
        return rms_norm(xp, rows.reshape(b, t, width // dh, dh), gain,
                        self.norm_eps).reshape(b, t, width)

    def _rope_tables(self, xp, t: int, dh: int):
        return rope_tables(xp, t, self.rotary_dim or dh, self.rope_theta,
                           self.rope_yarn)

    def _normed_rotated(self, xp, qkv, g_q, g_k, rows: bool = False):
        """(B, T, q + 2·kv widths) f32 projections → q (B, T, H, dh),
        k, v (B, T, H_kv, dh) with the q/k norms (whole-projection or
        per head) and the rotation applied; ``rows`` keeps them (B, T, width), where
        the flash kernels read them."""
        b, t, wide = qkv.shape
        h, h_kv = self.n_heads, self.n_kv_heads
        dh = wide // (h + 2 * h_kv)
        qw, kw = h * dh, h_kv * dh
        q, k, v = qkv[..., :qw], qkv[..., qw:qw + kw], qkv[..., qw + kw:]
        if self.qk_norm:
            q = self._qk_normed(xp, q, g_q, dh)
            k = self._qk_normed(xp, k, g_k, dh)
        if not rows:
            q = q.reshape(b, t, h, dh)
            k, v = k.reshape(b, t, h_kv, dh), v.reshape(b, t, h_kv, dh)
        if self.rope_theta is not None:
            cos, sin = self._rope_tables(xp, t, dh)
            if rows:
                q = apply_rope_rows(xp, q, cos, sin, h)
                k = apply_rope_rows(xp, k, cos, sin, h_kv)
            else:
                q = apply_rope(xp, q, cos, sin)
                k = apply_rope(xp, k, cos, sin)
        return q, k, v

    def xla_forward(self, x, w_qkv, b_qkv, w_out, b_out,
                    g_norm=None, g_q=None, g_k=None, w_gate=None,
                    g_post=None, w_kv_up=None, g_latent=None,
                    w_q_up=None, g_q_latent=None):
        if w_kv_up is not None:
            return self._latent_forward(x, w_qkv, w_out, g_norm, w_gate,
                                        w_kv_up, g_latent, w_q_up,
                                        g_q_latent)
        b, t, d = x.shape
        wide = w_qkv.shape[1]
        grouped = self.n_kv_heads != self.n_heads
        x32 = x.astype(jnp.float32)
        g_pre, g_post = norm_gains(self, g_norm, g_post)
        h = x32 if g_pre is None \
            else rms_norm(jnp, x32, g_pre, self.norm_eps)
        qkv = self.mxu_dot(jnp, h.reshape(b * t, d), w_qkv)
        if b_qkv is not None:
            qkv = qkv + b_qkv
        # attention-core GEMM/storage dtype: the repo-wide bf16-inputs/
        # f32-accumulation convention (profiled: the core's (T, T)
        # tensors are the step's HBM-bandwidth sink — PERF.md round 5).
        # Cast ONCE here so q/k/v reach the core at half width.
        dot_dtype = self.mxu_dtype
        fused = not self.qk_norm and self.rope_theta is None
        # never the ring; a unit that was not initialized has no plan
        flash = self._flash is not None and self._flash.runs
        if fused:
            # q, k, v are column ranges of ONE projection result
            if dot_dtype is not None:
                qkv = qkv.astype(dot_dtype)
            arrays = (qkv.reshape(b, t, wide),)
        else:
            # norms and rotation in f32, THEN the cast
            arrays = self._normed_rotated(
                jnp, qkv.reshape(b, t, wide), g_q, g_k, rows=flash)
            if dot_dtype is not None:
                arrays = tuple(a.astype(dot_dtype) for a in arrays)
        if flash:
            # the kernels read q, k, v and write o where the
            # projections have them: a head (a pair at dh 64) is a
            # column block of (B, T, ·), so neither a transpose nor a
            # slice stands between a projection and a kernel, forward
            # or backward (24 copies of 0.6 ms a step in the LM cell
            # before; PERF.md §6, PR 28)
            o = self._flash.attend(arrays, dot_dtype)
            return self._project_out(x32, h, o, w_out, b_out, w_gate, g_post)
        q, k, v = self._normed_rotated(jnp, arrays[0], None, None) \
            if fused else arrays      # fused: neither norm nor rotation
        if self.ring_active:
            from znicz_tpu.parallel.ring_attention import \
                sequence_sharded_attention
            o = sequence_sharded_attention(
                self.device.mesh, q, k, v, causal=self.causal,
                axis_name=getattr(self, "_ring_axis", MODEL_AXIS),
                dot_dtype=dot_dtype,
                block_k=self.flash_block_k,
                # round 6: the per-hop fold is the flash KERNEL when
                # the gate resolved it legal (initialize); the scan
                # fold is the gated fallback
                pallas_fold=(getattr(self, "_ring_fold", None)
                             == "pallas"),
                pallas_interpret=self._flash.interpret,
                pallas_block_q=getattr(self, "_ring_block_q", None))
        elif self.flash_block_k:
            from znicz_tpu.parallel.ring_attention import \
                local_attention_blocked
            o = local_attention_blocked(q, k, v, causal=self.causal,
                                        block_k=self.flash_block_k,
                                        dot_dtype=dot_dtype)
        else:
            # also the core of a grouped or windowed layer whose shape
            # the kernels cannot tile: it never attends outside its
            # window (ring and scan refuse both at initialize)
            from znicz_tpu.parallel.ring_attention import local_attention
            o = local_attention(q, k, v, causal=self.causal,
                                dot_dtype=dot_dtype, window=self.window)
        return self._project_out(x32, h, o, w_out, b_out, w_gate, g_post)

    def _project_out(self, x32, h, o, w_out, b_out, w_gate=None,
                     g_post=None):
        """The out-projection over the core's result — (B, T, H, dh)
        or (B, T, H·dh), (B·T, H·dh) by a free reshape either way —
        and the residual; with ``w_gate`` every head's output first
        multiplied by its sigmoid gate, computed from the sublayer's
        (normed) input ``h``; with ``post_norm`` the projection's
        result normed (gain ``g_post``) before the skip adds it."""
        b, t, d = x32.shape
        o = o.reshape(b * t, w_out.shape[0])
        if w_gate is not None:
            gate = jax.nn.sigmoid(
                self.mxu_dot(jnp, h.reshape(b * t, d), w_gate))
            o = o.astype(jnp.float32) * jnp.repeat(
                gate, w_out.shape[0] // self.n_heads, axis=-1)
        y = self.mxu_dot(jnp, o, w_out)
        if b_out is not None:
            y = y + b_out
        y = y.reshape(b, t, d)
        if self.post_norm:
            y = rms_norm(jnp, y, g_post, self.norm_eps)
        return x32 + y if self.residual else y

    def xla_run(self) -> None:
        args = self.forward_args()
        if not self.output._tracing:
            # eager (non-region) execution: plain forward.  Stashing a
            # pullback here would pin the forward residuals — for the
            # plain core that includes the (B, H, T, T) probability
            # tensor — in HBM across steps of forward-only workflows.
            self._traced_vjp = None
            self.output.devmem = self.xla_forward(*args)
            return
        # region trace: compute through jax.vjp and STASH the pullback
        # for this unit's GD pair — both are traced into one program,
        # and re-deriving the vjp there would re-run the forward.  XLA
        # CSE merges the duplicated einsums of the plain core, but an
        # opaque pallas_call (the fused flash kernel) is never CSE'd,
        # so the kernel executed twice per step (measured +3.4 ms at
        # T=2048 — PERF.md round 5).  In eval-mode region variants the
        # unused pullback is dead code and XLA drops it.
        out, self._traced_vjp = jax.vjp(self.xla_forward, *args)
        self.output.devmem = out

    # -- autoregressive decode (round 12, serving.decode) ---------------
    # Pure functions of their arguments (weights ride in as leaves, no
    # Vector state) so the decode engine can AOT-compile them exactly
    # like export's forward programs.  Math is plain f32 einsum — the
    # decode-side GEMMs are (B,1,·) slivers where the flash kernel's
    # tiling has nothing to win, and f32 keeps the incremental path
    # numerically aligned with the full-forward oracle.
    def xla_prefill(self, x, w_qkv, b_qkv, w_out, b_out):
        """Causal forward over a (possibly right-padded) prompt that
        also returns the per-position K/V: (B, T, D) →
        ``(y, k, v)`` with k/v shaped (B, T, H, Dh) for the cache.

        Padded tail positions produce garbage k/v rows — harmless by
        construction: causal masking keeps them out of every real
        position's softmax here, and the decode step overwrites row
        ``pos`` before its mask (``<= pos``) ever admits it.
        """
        b, t, d = x.shape
        qkv = x.astype(jnp.float32).reshape(b * t, d) @ w_qkv
        if b_qkv is not None:
            qkv = qkv + b_qkv
        q, k, v = _split_heads(qkv.reshape(b, t, 3 * d), self.n_heads)
        dh = d // self.n_heads
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
            jnp.float32(dh))
        if self.causal:
            mask = (jnp.arange(t)[:, None] >= jnp.arange(t)[None, :])
            s = jnp.where(mask[None, None], s, -1e30)
        s = s - s.max(axis=-1, keepdims=True)
        p = jnp.exp(s)
        p = p / p.sum(axis=-1, keepdims=True)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
        y = o.reshape(b * t, d) @ w_out
        if b_out is not None:
            y = y + b_out
        return y.reshape(b, t, d), k, v

    def xla_decode_step(self, x, k_cache, v_cache, pos,
                        w_qkv, b_qkv, w_out, b_out):
        """One incremental token: write this position's K/V into the
        cache, attend the new query over the cached prefix.

        ``x``: (B, 1, D) current-token features; ``k_cache``/
        ``v_cache``: (B, Tmax, H, Dh) per-sequence cache pages;
        ``pos``: (B,) int32 position index of THIS token per sequence
        (ragged — sequences in one decode batch sit at different
        depths).  Returns ``(y, k_cache, v_cache)`` with the caches
        functionally updated at ``pos`` — under input donation the
        update is in-place in HBM, so a warmed decode loop allocates
        nothing per token and compiles nothing (shapes pinned by the
        live-batch bucket).
        """
        b, one, d = x.shape
        t_max = k_cache.shape[1]
        qkv = x.astype(jnp.float32).reshape(b, d) @ w_qkv
        if b_qkv is not None:
            qkv = qkv + b_qkv
        q, k, v = _split_heads(qkv.reshape(b, 1, 3 * d), self.n_heads)
        dh = d // self.n_heads
        rows = jnp.arange(b)
        k_cache = k_cache.at[rows, pos].set(k[:, 0])
        v_cache = v_cache.at[rows, pos].set(v[:, 0])
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k_cache) / jnp.sqrt(
            jnp.float32(dh))
        # length mask: the prefix [0, pos] is live, everything beyond
        # is stale garbage from a prior tenant of the slot or the
        # prefill's padded tail — never admitted
        mask = jnp.arange(t_max)[None, :] <= pos[:, None]
        s = jnp.where(mask[:, None, None, :], s, -1e30)
        s = s - s.max(axis=-1, keepdims=True)
        p = jnp.exp(s)
        p = p / p.sum(axis=-1, keepdims=True)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v_cache)
        y = o.reshape(b, d) @ w_out
        if b_out is not None:
            y = y + b_out
        return y.reshape(b, 1, d), k_cache, v_cache

    # -- paged decode (round 15, serving.decode) ------------------------
    # Same math as the flat steps above, but K/V live in a shared page
    # POOL (P, ptok, H, Dh) addressed through a per-sequence block
    # table instead of per-slot (maxT, H, Dh) strips.  Three wins the
    # flat layout cannot express: (1) attention reads only the pages a
    # sequence actually occupies (the nb block bucket), not the full
    # maxT reservation; (2) full pages are SHARED between sequences
    # with a common prompt prefix (refcounted, copy-on-write at
    # divergence — host-side, serving/decode.py); (3) live capacity is
    # bounded by tokens, not slots.  Tables carry nb+1 entries: the
    # last is the trash page, where padded lanes/positions scatter
    # their garbage writes.
    def _project_qkv(self, x, w_qkv, b_qkv):
        """(B, W, D) → q, k, v each (B, W, H, Dh)."""
        b, w, d = x.shape
        qkv = x.astype(jnp.float32).reshape(b * w, d) @ w_qkv
        if b_qkv is not None:
            qkv = qkv + b_qkv
        return _split_heads(qkv.reshape(b, w, 3 * d), self.n_heads)

    def _out_proj(self, o, w_out, b_out):
        b, w, h, dh = o.shape
        y = o.reshape(b * w, h * dh) @ w_out
        if b_out is not None:
            y = y + b_out
        return y.reshape(b, w, h * dh)

    def _kv_quantize(self, rows):
        """(B, W, H, Dh) f32 K/V rows → ``(q int8, scale f32
        (B, W, H))`` — symmetric absmax over each row's head vector
        (round 21).  Dequantization ``q.astype(f32) * s`` is exact on
        representable values, so the quantize/dequantize pair adds one
        rounding step per element and nothing else."""
        s = jnp.maximum(jnp.max(jnp.abs(rows), axis=-1), 1e-8) / 127.0
        q = jnp.clip(jnp.round(rows / s[..., None]),
                     -127, 127).astype(jnp.int8)
        return q, s

    def _paged_attend(self, q, k_pool, v_pool, tables, q_pos,
                      k_scale=None, v_scale=None):
        """Attend (B, W, H, Dh) queries at global positions ``q_pos``
        (B, W) over the pages in ``tables`` (B, nb+1; last = trash).
        Key position ``p`` is admitted iff ``p <= q_pos`` — stale rows
        from a prior page tenant and this window's padded tail sit
        beyond every real query's position by construction.

        With ``k_scale``/``v_scale`` pools (round 21) the K/V pools
        hold int8 rows dequantized on gather — the HBM-resident cache
        is int8 + one f32 scale per (token, head)."""
        nb = tables.shape[1] - 1
        ptok = k_pool.shape[1]
        dh = q.shape[-1]
        # (B, nb, ptok, H, Dh) → (B, nb·ptok, H, Dh): the gather is
        # bounded by the BLOCK BUCKET nb, not maxT — a short sequence
        # attends over exactly the pages it occupies
        k_rows = k_pool[tables[:, :nb]].reshape(
            q.shape[0], nb * ptok, self.n_heads, dh)
        v_rows = v_pool[tables[:, :nb]].reshape(
            q.shape[0], nb * ptok, self.n_heads, dh)
        if k_scale is not None:
            ks = k_scale[tables[:, :nb]].reshape(
                q.shape[0], nb * ptok, self.n_heads)
            vs = v_scale[tables[:, :nb]].reshape(
                q.shape[0], nb * ptok, self.n_heads)
            k_rows = k_rows.astype(jnp.float32) * ks[..., None]
            v_rows = v_rows.astype(jnp.float32) * vs[..., None]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k_rows) / jnp.sqrt(
            jnp.float32(dh))
        mask = jnp.arange(nb * ptok)[None, None, :] \
            <= q_pos[:, :, None]
        s = jnp.where(mask[:, None], s, -1e30)
        s = s - s.max(axis=-1, keepdims=True)
        p = jnp.exp(s)
        p = p / p.sum(axis=-1, keepdims=True)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v_rows)

    def _paged_write(self, pool, rows, tables, positions, live):
        """Scatter (B, W, H, Dh) K or V rows through the block table at
        global ``positions`` (B, W); lanes/positions with ``live``
        False write into the trash page (last table entry)."""
        ptok = pool.shape[1]
        nb = tables.shape[1] - 1
        block = positions // ptok
        # trash entry for dead lanes/positions AND for live positions
        # past the table — an overflow (host bookkeeping slip) must
        # discard the write, not overwrite the last allocated page
        block = jnp.where(live & (block < nb), block, nb)
        page = jnp.take_along_axis(tables, block, axis=1)
        off = jnp.where(live, positions % ptok, 0)
        return pool.at[page, off].set(rows.astype(pool.dtype))

    def _paged_update(self, k, v, k_pool, v_pool, k_scale, v_scale,
                      tables, positions, live):
        """Scatter this window's K/V through the table; when scale
        pools ride along (int8 pages, round 21) the rows quantize on
        WRITE — scale rows share the page index/offset/trash
        semantics of their data rows (``_paged_write`` is generic
        over trailing dims), so COW and the trash page need no new
        code."""
        if k_scale is not None:
            k, ks = self._kv_quantize(k)
            v, vs = self._kv_quantize(v)
            k_scale = self._paged_write(k_scale, ks, tables,
                                        positions, live)
            v_scale = self._paged_write(v_scale, vs, tables,
                                        positions, live)
        k_pool = self._paged_write(k_pool, k, tables, positions, live)
        v_pool = self._paged_write(v_pool, v, tables, positions, live)
        return k_pool, v_pool, k_scale, v_scale

    def xla_prefill_paged(self, x, k_pool, v_pool, table, start,
                          length, w_qkv, b_qkv, w_out, b_out,
                          k_scale=None, v_scale=None):
        """Causal forward over a prompt WINDOW against the paged
        cache: ``x`` (1, W, D) features of positions
        ``start..start+W-1`` (right-padded past ``length`` real
        tokens), ``table`` (nb+1,) the sequence's block row.  Writes
        the window's K/V through the table, attends each window
        position over the cached prefix PLUS the window itself
        (``<= q_pos``), returns ``(y, k_pool, v_pool)``.

        ``start=0`` is a fresh prefill; ``start>0`` is the tail
        prefill after a prefix-cache hit — the shared pages below
        ``start`` are read, never written (the window's writes begin
        at ``start``, past every shared full block)."""
        one, w, d = x.shape
        q, k, v = self._project_qkv(x, w_qkv, b_qkv)
        idx = jnp.arange(w)
        positions = (start + idx)[None, :]
        live = (idx < length)[None, :]
        tables = table[None, :]
        k_pool, v_pool, k_scale, v_scale = self._paged_update(
            k, v, k_pool, v_pool, k_scale, v_scale, tables, positions,
            live)
        o = self._paged_attend(q, k_pool, v_pool, tables, positions,
                               k_scale, v_scale)
        y = self._out_proj(o, w_out, b_out)
        if k_scale is not None:
            return y, k_pool, v_pool, k_scale, v_scale
        return y, k_pool, v_pool

    def xla_decode_step_paged(self, x, k_pool, v_pool, tables, pos,
                              w_qkv, b_qkv, w_out, b_out,
                              k_scale=None, v_scale=None):
        """One incremental token through the page table: ``x``
        (B, 1, D), ``tables`` (B, nb+1), ``pos`` (B,) the position of
        THIS token per lane (padded lanes carry the trash table and
        write harmlessly there)."""
        q, k, v = self._project_qkv(x, w_qkv, b_qkv)
        positions = pos[:, None]
        live = jnp.ones_like(positions, bool)
        k_pool, v_pool, k_scale, v_scale = self._paged_update(
            k, v, k_pool, v_pool, k_scale, v_scale, tables, positions,
            live)
        o = self._paged_attend(q, k_pool, v_pool, tables, positions,
                               k_scale, v_scale)
        y = self._out_proj(o, w_out, b_out)
        if k_scale is not None:
            return y, k_pool, v_pool, k_scale, v_scale
        return y, k_pool, v_pool

    def xla_window_paged(self, x, k_pool, v_pool, tables, pos,
                         lengths, w_qkv, b_qkv, w_out, b_out,
                         k_scale=None, v_scale=None):
        """Batched multi-token WINDOW through the page table — the op
        behind both speculative verification (window = last accepted
        token + K drafts, ``lengths`` = K+1 everywhere) and batched
        tail prefill (window = each lane's unshared prompt tail,
        right-padded; admission coalescing for prefix-hit traffic).

        ``x`` (B, W, D) window features starting at per-lane position
        ``pos`` (B,); positions past ``lengths`` (B,) write into the
        trash page.  Writes all live K/V, attends each window
        position causally over prefix+window in ONE batched forward.
        Stale/overflow rows beyond a lane's real positions sit past
        the position mask exactly like a reused slot's rows."""
        b, w, d = x.shape
        q, k, v = self._project_qkv(x, w_qkv, b_qkv)
        idx = jnp.arange(w)[None, :]
        positions = pos[:, None] + idx
        live = idx < lengths[:, None]
        k_pool, v_pool, k_scale, v_scale = self._paged_update(
            k, v, k_pool, v_pool, k_scale, v_scale, tables, positions,
            live)
        o = self._paged_attend(q, k_pool, v_pool, tables, positions,
                               k_scale, v_scale)
        y = self._out_proj(o, w_out, b_out)
        if k_scale is not None:
            return y, k_pool, v_pool, k_scale, v_scale
        return y, k_pool, v_pool

    # -- numpy oracle ---------------------------------------------------
    def _forward_np(self, x):
        """``(y, (h, qkv, q, k, v, o, p, gate, raw))``: ``h`` is what
        the QKV projection saw (the input, or its pre-norm), ``qkv`` the
        raw projections, ``q``/``k`` what the core saw (normed,
        rotated), ``o`` the core's output BEFORE the per-head ``gate``
        (None without one), ``raw`` the out-projection's result BEFORE
        the ``post_norm`` (None without one)."""
        b, t, d = x.shape
        if self.kv_latent is not None:
            return self._latent_forward_np(x), None
        h = rms_norm(np, x, self.gain_norm.mem, self.norm_eps) \
            if self.pre_norm else x
        qkv = h.reshape(b * t, d) @ self.weights.mem
        if self.include_bias:
            qkv = qkv + self.bias.mem
        q, k, v = self._normed_rotated(
            np, qkv.reshape(b, t, -1),
            self.gain_q.mem if self.qk_norm else None,
            self.gain_k.mem if self.qk_norm else None)
        o, p = _local_attention_np(q, k, v, self.causal, self.window)
        gate, out = None, o
        if self.head_gate:
            gate = 1.0 / (1.0 + np.exp(
                -(h.reshape(b * t, d) @ self.weights_head_gate.mem)))
            out = o * gate.reshape(b, t, self.n_heads, 1)
        y = out.reshape(b * t, -1) @ self.weights_out.mem
        if self.include_bias:
            y = y + self.bias_out.mem
        y, raw = y.reshape(b, t, d), None
        if self.post_norm:
            raw, y = y, rms_norm(np, y, post_gain(self).mem,
                                 self.norm_eps)
        if self.residual:
            y = x + y
        return y, (h, qkv, q, k, v, o, p, gate, raw)

    def _latent_forward_np(self, x):
        """:meth:`_latent_forward` in numpy, the plain core."""
        b, t, d = x.shape
        h, nope, rope = self.n_heads, self.qk_nope, self.qk_rope
        latent, dv = self.kv_latent, self.v_head_dim
        m = rms_norm(np, x, self.gain_norm.mem, self.norm_eps) \
            if self.pre_norm else x
        proj = (m.reshape(b * t, d) @ self.weights.mem).reshape(b, t, -1)
        at, wide = h * nope, h * (nope + rope)
        below = self.q_latent or wide
        q = proj
        if self.q_latent:
            q = (rms_norm(np, proj[..., :below], self.gain_q_latent.mem,
                          self.norm_eps).reshape(b * t, below)
                 @ self.weights_q_up.mem).reshape(b, t, wide)
        c = rms_norm(np, proj[..., below:below + latent],
                     self.gain_latent.mem, self.norm_eps)
        cos, sin = rope_tables(np, t, rope, self.rope_theta,
                               self.rope_yarn)
        scale = self._latent_scale()
        q_rope = apply_rope(
            np, q[..., at:wide].reshape(b, t, h, rope),
            cos, sin).reshape(b, t, h * rope) * scale
        k_rope = apply_rope(np, proj[..., None, -rope:], cos,
                            sin).reshape(b, t, rope)
        kv = c.reshape(b * t, latent) @ self.weights_kv_up.mem
        o = latent_attention_plain(
            q[..., :at] * scale, q_rope, kv[:, :at].reshape(b, t, at),
            k_rope, kv[:, at:].reshape(b, t, h * dv), h, xp=np)
        if self.head_gate:
            gate = 1.0 / (1.0 + np.exp(
                -(m.reshape(b * t, d) @ self.weights_head_gate.mem)))
            o = (o.reshape(b, t, h, dv)
                 * gate.reshape(b, t, h, 1)).reshape(b, t, h * dv)
        y = (o.reshape(b * t, h * dv) @ self.weights_out.mem).reshape(
            b, t, d)
        return x + y if self.residual else y

    def numpy_run(self) -> None:
        self.input.map_read()
        self.weights.map_read()
        self.weights_out.map_read()
        if self.include_bias:
            self.bias.map_read()
            self.bias_out.map_read()
        for gain in (self.gain_norm, self.gain_q, self.gain_k,
                     self.weights_head_gate, self.gain_post,
                     self.weights_kv_up, self.gain_latent,
                     self.weights_q_up, self.gain_q_latent):
            if gain:
                gain.map_read()
        y, _ = self._forward_np(self.input.mem.astype(np.float32))
        self.output.map_invalidate()
        self.output.mem[...] = y


class GDMultiHeadAttention(GradientDescentBase):
    """Attention backward: analytic numpy oracle vs ``jax.vjp`` of the
    forward (which differentiates through the ring when
    ``seq_parallel``)."""

    MATCHES = (MultiHeadAttention,)
    REQUIRES_FORWARD_UNIT = True
    REQUIRES_INPUT = True

    def __init__(self, workflow, name=None, **kwargs):
        super().__init__(workflow, name=name, **kwargs)
        self.forward_unit: MultiHeadAttention | None = None
        self.accumulated_gradient_weights_out = Vector(
            name=f"{self.name}.acc_gw_out")
        self.accumulated_gradient_bias_out = Vector(
            name=f"{self.name}.acc_gb_out")
        # the block's three gains (pre-norm, q norm, k norm)
        self.accumulated_gradient_gain_norm = Vector(
            name=f"{self.name}.acc_gain_norm")
        self.accumulated_gradient_gain_q = Vector(
            name=f"{self.name}.acc_gain_q")
        self.accumulated_gradient_gain_k = Vector(
            name=f"{self.name}.acc_gain_k")
        # … and the per-head gate's projection
        self.accumulated_gradient_weights_head_gate = Vector(
            name=f"{self.name}.acc_gw_head_gate")
        # … and the output norm's gain beside a pre-norm
        self.accumulated_gradient_gain_post = Vector(
            name=f"{self.name}.acc_gain_post")
        # … and a latent K/V's up-projection and norm gain
        self.accumulated_gradient_weights_kv_up = Vector(
            name=f"{self.name}.acc_gw_kv_up")
        self.accumulated_gradient_gain_latent = Vector(
            name=f"{self.name}.acc_gain_latent")
        # … and a query latent's up-projection and norm gain
        self.accumulated_gradient_weights_q_up = Vector(
            name=f"{self.name}.acc_gw_q_up")
        self.accumulated_gradient_gain_q_latent = Vector(
            name=f"{self.name}.acc_gain_q_latent")
        self._host_pullback = None

    #: ``_gain_pairs``' suffixes, in the order ``forward_args`` hands
    #: the gains (and the latent's two parameters) to the forward
    _GAINS = ("norm", "q", "k", "head_gate", "post", "kv_up", "latent",
              "q_up", "q_latent")

    def _gain_pairs(self) -> list:
        """``(suffix, parameter Vector, its accumulator)`` for the
        gains and the gate the forward's options allocated."""
        fwd = self.forward_unit
        pairs = [(name, gain, getattr(
                    self, f"accumulated_gradient_gain_{name}"))
                 for name, gain in (("norm", fwd.gain_norm),
                                    ("q", fwd.gain_q), ("k", fwd.gain_k))
                 if gain]
        if fwd.weights_head_gate:
            pairs.append(("head_gate", fwd.weights_head_gate,
                          self.accumulated_gradient_weights_head_gate))
        if fwd.gain_post:
            pairs.append(("post", fwd.gain_post,
                          self.accumulated_gradient_gain_post))
        if fwd.weights_kv_up:
            pairs.append(("kv_up", fwd.weights_kv_up,
                          self.accumulated_gradient_weights_kv_up))
            pairs.append(("latent", fwd.gain_latent,
                          self.accumulated_gradient_gain_latent))
        if fwd.weights_q_up:
            pairs.append(("q_up", fwd.weights_q_up,
                          self.accumulated_gradient_weights_q_up))
            pairs.append(("q_latent", fwd.gain_q_latent,
                          self.accumulated_gradient_gain_q_latent))
        return pairs

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device=device, **kwargs)
        fwd = self.forward_unit
        # the shared allocator (not a bare reset) so the output
        # projection's momentum gets the same bf16-storage + ZeRO-1
        # data-sharding treatment as the base pair
        if self.gradient_moment:
            self._alloc_accumulator(self.accumulated_gradient_weights_out,
                                    fwd.weights_out)
        if self.gradient_moment_bias and fwd.include_bias:
            self._alloc_accumulator(self.accumulated_gradient_bias_out,
                                    fwd.bias_out)
        gains = self._gain_pairs()
        if self.gradient_moment:
            for _, gain, acc in gains:
                self._alloc_accumulator(acc, gain)
        self.init_vectors(self.err_input, self.err_output, self.input,
                          self.output, self.weights, self.bias,
                          fwd.weights_out, fwd.bias_out,
                          self.accumulated_gradient_weights_out,
                          self.accumulated_gradient_bias_out,
                          *(v for _, gain, acc in gains
                            for v in (gain, acc)))

    def _micro_accum_params(self):
        # round 20: the output projection pair accumulates too — the
        # base enumeration only covers the fused QKV weights/bias
        pairs = super()._micro_accum_params()
        fwd = self.forward_unit
        if fwd is not None:
            pairs.extend([("wo", fwd.weights_out), ("bo", fwd.bias_out)])
            pairs.extend((f"g{name}", gain)
                         for name, gain, _ in self._gain_pairs())
        return pairs

    def region_vectors(self):
        vecs = super().region_vectors()
        seen = {id(v) for v in vecs}
        fwd = self.forward_unit
        for vec in (fwd.weights_out, fwd.bias_out,
                    self.accumulated_gradient_weights_out,
                    self.accumulated_gradient_bias_out,
                    *(v for _, gain, acc in self._gain_pairs()
                      for v in (gain, acc))):
            if vec and id(vec) not in seen:
                vecs.append(vec)
        return vecs

    def xla_run(self) -> None:
        fwd = self.forward_unit
        has_bias = fwd.include_bias
        # consume the stashed pullback ONLY when this GD is tracing
        # into the same region program the forward just traced into
        # (the region schedules forward before backward, and the
        # forward overwrites the stash at the top of every trace, so a
        # tracing consumer can never see a stale trace's closure); an
        # EAGER backward must rebuild — a stash from some earlier
        # trace would hold escaped tracers.  A member of a looped span
        # is applied R times a step: the span keeps the R pullbacks and
        # puts an application's back here before this unit fires for
        # that pass (``pass_span.PassSpan.trace_backward``)
        vjp = fwd._traced_vjp if self.err_output._tracing else None
        fwd._traced_vjp = None   # single-use: one backward per
        #                          application, never a stale one
        if vjp is None:          # forward ran outside this trace
            _, vjp = jax.vjp(fwd.xla_forward, *fwd.forward_args())
        gx, gwq, gbq, gwo, gbo, *ggains = vjp(
            self.err_output.devmem.astype(jnp.float32))
        if self.need_err_input:
            self.err_input.devmem = gx
        self._apply_weights_xla(gwq)
        if has_bias:
            self._apply_bias_xla(gbq)
        # second pair through the SAME parameterized base update rule
        self._apply_weights_xla(
            gwo, vec=fwd.weights_out,
            acc_vec=self.accumulated_gradient_weights_out)
        if has_bias:
            self._apply_bias_xla(
                gbo, vec=fwd.bias_out,
                acc_vec=self.accumulated_gradient_bias_out)
        grads = dict(zip(self._GAINS, ggains))
        for name, gain, acc in self._gain_pairs():
            self._apply_weights_xla(grads[name], vec=gain, acc_vec=acc)

    def _latent_backward_np(self, x) -> None:
        """A latent-K/V layer has no analytic numpy backward: the numpy
        path differentiates the plain XLA forward on the host, as
        ``GDGatedDeltaNet`` does (the layer is held to
        ``znbench/reference/ling.py`` instead)."""
        fwd = self.forward_unit
        plan, fwd._flash = fwd._flash, None      # the plain core
        try:
            if self._host_pullback is None:   # one host program
                self._host_pullback = jax.jit(
                    lambda err, *args: jax.vjp(fwd.xla_forward,
                                               *args)[1](err))
            args = (x,) + tuple(
                None if a is None else np.asarray(a)
                for a in (getattr(fwd, attr).mem
                          if getattr(fwd, attr) else None
                          for attr in fwd.EXPORT_PARAMS))
            with jax.default_matmul_precision("highest"):
                gx, gwq, _, gwo, _, *ggains = self._host_pullback(
                    jnp.asarray(self.err_output.mem, jnp.float32), *args)
        finally:
            fwd._flash = plan
        if self.need_err_input:
            self.err_input.map_invalidate()
            self.err_input.mem[...] = np.asarray(gx)
        self._apply_weights_np(np.asarray(gwq))
        self._apply_weights_np(
            np.asarray(gwo), vec=fwd.weights_out,
            acc_vec=self.accumulated_gradient_weights_out)
        grads = dict(zip(self._GAINS, ggains))
        for name, gain, acc in self._gain_pairs():
            self._apply_weights_np(np.asarray(grads[name]), vec=gain,
                                   acc_vec=acc)

    def numpy_run(self) -> None:
        """Analytic attention backward (the oracle/spec)."""
        fwd = self.forward_unit
        for vec in (self.err_output, self.input):
            vec.map_read()
        self.weights.map_write()
        fwd.weights_out.map_write()
        if fwd.include_bias:
            self.bias.map_write()
            fwd.bias_out.map_write()
        for _, gain, _ in self._gain_pairs():
            gain.map_write()
        x = self.input.mem.astype(np.float32)
        if fwd.kv_latent is not None:
            return self._latent_backward_np(x)
        b, t, d = x.shape
        h, h_kv = fwd.n_heads, fwd.n_kv_heads
        qw, kw, dh = fwd._widths(d)
        _, (hidden, qkv, q, k, v, o, p, gate, raw) = fwd._forward_np(x)
        err = self.err_output.mem.astype(np.float32).reshape(b, t, d)
        grad_gains = {}
        dy = err
        if fwd.post_norm:                 # back through the output norm
            dy, grad_gains["post" if fwd.pre_norm else "norm"] = \
                rms_norm_backward(np, raw, post_gain(fwd).mem,
                                  fwd.norm_eps, err)
        dy = dy.reshape(b * t, d)
        # output projection (over the gated heads, where there is a gate)
        do = (dy @ fwd.weights_out.mem.T).reshape(b, t, h, dh)
        d_hidden = 0.0
        if gate is None:
            grad_wo = o.reshape(b * t, qw).T @ dy
        else:
            g4 = gate.reshape(b, t, h, 1)
            grad_wo = (o * g4).reshape(b * t, qw).T @ dy
            d_logit = ((do * o).sum(axis=-1) * gate.reshape(b, t, h)
                       * (1.0 - gate.reshape(b, t, h))).reshape(b * t, h)
            grad_gains["head_gate"] = hidden.reshape(b * t, d).T @ d_logit
            d_hidden = d_logit @ fwd.weights_head_gate.mem.T
            do = do * g4
        grad_bo = dy.sum(axis=0)
        # attention core: dv, softmax jacobian, dq/dk — K/V heads
        # repeated over their group, their gradients summed over it
        group = h // h_kv
        k_all, v_all = np.repeat(k, group, axis=2), \
            np.repeat(v, group, axis=2)
        dv = np.einsum("bhqk,bqhd->bkhd", p, do)
        dp = np.einsum("bqhd,bkhd->bhqk", do, v_all)
        ds = p * (dp - (dp * p).sum(axis=-1, keepdims=True))
        ds = ds / np.sqrt(dh)
        dq = np.einsum("bhqk,bkhd->bqhd", ds, k_all)
        dk = np.einsum("bhqk,bqhd->bkhd", ds, q)
        dk, dv = (a.reshape(b, t, h_kv, group, dh).sum(axis=3)
                  for a in (dk, dv))
        if fwd.rope_theta is not None:    # the rotation's adjoint
            # (with YaRN's scaled tables too: a·Rᵀ, not the inverse)
            cos, sin = fwd._rope_tables(np, t, dh)
            dq = apply_rope(np, dq, cos, sin, inverse=True)
            dk = apply_rope(np, dk, cos, sin, inverse=True)
        if fwd.qk_norm:                   # back through the q/k norms
            raw = qkv.reshape(b, t, -1)
            # over the whole projection, or over each head's dh
            over = (lambda a: a.reshape(b, t, -1, dh)) \
                if fwd.qk_norm == "rms_head" \
                else (lambda a: a.reshape(b, t, -1))
            dq, grad_gains["q"] = rms_norm_backward(
                np, over(raw[..., :qw]), fwd.gain_q.mem, fwd.norm_eps,
                over(dq))
            dk, grad_gains["k"] = rms_norm_backward(
                np, over(raw[..., qw:qw + kw]), fwd.gain_k.mem,
                fwd.norm_eps, over(dk))
        dqkv = np.concatenate(
            [a.reshape(b, t, -1) for a in (dq, dk, dv)],
            axis=-1).reshape(b * t, qw + 2 * kw)
        # input projection
        grad_wq = hidden.reshape(b * t, d).T @ dqkv
        grad_bq = dqkv.sum(axis=0)
        dx = (dqkv @ self.weights.mem.T + d_hidden).reshape(b, t, d)
        if fwd.pre_norm:
            dx, grad_gains["norm"] = rms_norm_backward(
                np, x, fwd.gain_norm.mem, fwd.norm_eps, dx)
        if fwd.residual:
            dx = dx + err
        if self.need_err_input:
            self.err_input.map_invalidate()
            self.err_input.mem[...] = dx
        self._apply_weights_np(grad_wq)
        if fwd.include_bias:
            self._apply_bias_np(grad_bq)
        self._apply_weights_np(
            grad_wo, vec=fwd.weights_out,
            acc_vec=self.accumulated_gradient_weights_out)
        if fwd.include_bias:
            self._apply_bias_np(
                grad_bo, vec=fwd.bias_out,
                acc_vec=self.accumulated_gradient_bias_out)
        for name, gain, acc in self._gain_pairs():
            self._apply_weights_np(grad_gains[name], vec=gain,
                                   acc_vec=acc)

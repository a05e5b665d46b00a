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
(:func:`grouped_matmul`) is the repo's own pair of Pallas kernels on a
TPU (``ops/pallas_gmm.py``: ``znicz_gmm`` forward and row gradient,
``znicz_tgmm`` weight gradient; their grids follow the groups, so a
slab is read once per group and only a tile that straddles a group
boundary pays for a mask — PERF.md §6, PR 34, which has them beside
JAX's library kernel, the path from PR 25 to PR 33, and beside
``jax.lax.ragged_dot`` on the chip) and ``ragged_dot``, the XLA core,
elsewhere; the gate is ``engine.moe_grouped_matmul`` ("auto", like the
flash kernels').  What the grids did is counted beside the routing
totals (rows the visits cover over rows that are real: the gauge
``znicz_moe_gmm_rows``).  ``znicz_tgmm`` has a second result: Σ g² of
the slabs it writes, summed as each finished block lay in VMEM — what
the update's anomaly guard otherwise reads the whole (E, K, N) f32
gradient from HBM once more for (PERF.md §6, PR 44).  The number
leaves the layer's ONE pullback as the "cotangent" of a zero scalar
per slab that ``xla_forward`` takes (``taps``) and adds nowhere: a
TAP, not a derivative — it carries no gradient of anything
(:func:`_gmm_kernels`).  ``GDMoE`` hands it to the update with the
slab's gradient, and the update uses it under its one rule
(``GradientDescentBase._update_param_xla``: the gradient it applies
IS the tensor the sum was made of); the gauge
``znicz_moe_guard_sum{stat="from_kernel"}`` says for how many of the
layer's tensors it did (3, or 0: ``ragged_dot``, accumulation).  Both
permutations are GATHERS in both directions (:func:`_dispatch`,
:func:`_unpermute`: a permutation's adjoint is its inverse), so no
scatter-add runs in the step.  The slabs are stored in f32 and
multiplied in the rows' dtype: where the two differ the layer keeps
each slab once more in that dtype, a leaf the update writes beside the
slab, and the matmuls of either path read it — no step casts a slab
(:meth:`MoE._keep_slab_copies`, PR 47; the gauge
``znicz_moe_slab_copy``).

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

Options of the same layer (Laguna-S-2.1, PR 29; all unset = the layer
above, whose program they leave untouched): ``score="sigmoid"`` scores
an expert by ``σ(logit)`` instead of the softmax (the load-balancing
loss then runs over the scores normalised to sum 1); ``routed_scale``
multiplies the routed sum; ``shared_width`` adds an always-on gated MLP
of that width, ``Shared(m)``, beside it; and **``held``** names the
experts whose weights live on THIS chip — one chip's share of an
expert-parallel deployment.  The router keeps all its outputs and its
top k; slabs, momentum and gradients exist for the held experts only;
the layer computes the (token, expert) pairs routed to them and nothing
stands in for the others: ``y = x + Shared(m) + Σ_{e ∈ top_k ∩ held}
w_e · Expert_e(m)``.  The pairs here are a traced number under a static
shape: they are sorted to the front and the first rows of that order
go through the grouped matmuls, whose work follows the rows that are
real.  The two ways between the (N, D) tokens and that buffer are each
other's adjoint, and where the share held is a tenth of the pairs or
more — N · k ≤ ``HELD_GATHER`` (8) times the fit size, a rule of the
static shapes alone — BOTH are gathers in both directions, as the
dropless layer's are (PR 51): the rows come by a gather at their
tokens (:func:`_gather_rows`) and go back, weighted, by k gathers of N
rows through the pairs' inverse map, added up slot by slot in f32
(:func:`_combine`; a buffer above ``GATHER_OPERAND_BYTES`` a block of
columns at a time, so that the gathers read their operand from the
chip's fast memory); each one's pullback is the other's gather (the
rows' cotangent gathered in the rows' dtype, a token's k copies summed
in f32), so no scatter-add runs in the step — a TPU runs one serially,
0.49 µs a row of 2,560 into 16,384 tokens, where the gathers of a
direction take a third of that pass (PERF.md §6, PR 51).  The map is no second sort: a pair here lies at
its expert's offset plus its rank among that expert's pairs, a
cumulative sum over the comparison that counts the groups
(:func:`_positions`).  The gathers read a row for every one of a
token's k slots, here or not, so a share below the rule (8 of 256
experts: one slot in thirty-two points at a row) keeps the form the
layer had: a gather and a scatter-add over the buffer's rows, under
autodiff, and no map is made.  The gauge ``znicz_moe_combine{form}``
names the form; either way the operations lie under the scope
``combine`` (``observe.op_scopes()``: phase ``combine``), the dropless
layer's un-permutation and weighted sum too.  The gathers, the
weighting and the way back run over every row of the buffer, real or
not (PERF.md §6, PR 29), so the buffer has TWO static lengths and the
device picks one a step, from the count it has (PR 45).  ONE rule
sizes the longer, the **capacity**: ``HELD_SLACK`` (4) times the share
uniform routing sends here, N · k · |held| / E, and never more than
the N · min(k, |held|) pairs that CAN arrive — as an expert-parallel
exchange buffer is sized (the worst case is 25.6 times the uniform
share at 8 of 256 experts, top 10).  The shorter, the **fit** size, is
``HELD_FIT`` (1.25) times the uniform share, in whole row tiles and no
more than the capacity: what a step needs whose routing is near
uniform.  A step whose pairs here fit it runs at it (ONE ``cond``
forward and one backward, :func:`_fit_or_capacity`); a step that
routes more here runs the whole capacity — as windows of the fit
size's rows, through the same kernels — and makes the routed part's
forward again in its backward, once or twice (the forward keeps for
the backward what the FIT branch made, nothing of the capacity's: a
step at the fit size writes nothing it would not write if the buffer
had that one length);
where the two lengths are one the layer is the plain body under plain
autodiff.  Nothing is dropped and no precision changes either way.  A
step that routes more pairs here than the capacity is not computed
short: its output is NaN, so the anomaly guard refuses the whole step.
What a user sees: ``znicz_moe_held{stat="fit_steps"}`` beside
``steps`` — equal while routing stays near uniform; ``fit_steps``
under ``steps`` with ``rows_here`` over ``fit``: the router sends this
chip more than its share, the steps are right and cost a buffer of the
whole capacity and a forward or two more (raise ``aux_loss_weight``);
``guard_skipped_steps`` rising WITH ``rows_over`` above 0 and
``rows_here`` near ``capacity``: the router has collapsed onto this
chip's experts (the same cure, or hold fewer tokens a step); skipped
steps with ``rows_over`` 0 have another cause.

``select_bias`` and ``groups`` (Ling-3.0-flash, PR 37; DeepSeek-V3's
router, arXiv:2412.19437 §2.1.2) change the CHOICE only, beside
``held``: with ``select_bias`` the top k are taken by ``score + b``
while the weights stay the unbiased scores' (b enters no output and no
gradient); with ``groups=(n, m)`` the experts stand in n groups, a
group's score is the sum of its 2 largest (biased) scores, the m best
groups are kept and the top k taken among their experts.  b (E,) is a
leaf of the third kind — neither moved by a gradient nor fixed: once a
step, inside the step program, ``GDMoE`` moves it by ``b_e += γ ·
sign(mean load − load_e)`` (``bias_rate`` γ; load = this step's rows
routed to e, all E counted) under the scope ``router_bias``, gated by
the anomaly guard's running flag like every update (a skipped step
moves no bias).  It is a Vector of the unit, so the snapshotter saves
and restores it; it lies OUTSIDE the SDC fingerprint, which folds what
``_apply_param_xla`` updates: a flipped bit in b moves a choice, not a
value, and the next steps' rule pulls it back.

``act`` and ``route_from`` (SmallThinker-21BA3B, PR 50;
arXiv:2507.20984).  ``act`` names the gate function of every gated MLP
of the layer — the experts', the shared expert's; ``GatedMLP`` takes it
too: ``"silu"`` (SwiGLU, the layer above) or ``"relu"`` (ReGLU:
``W_down (max(W_gate m, 0) ⊙ W_up m)``).  The function and its
derivative have ONE home, ``activations_math.GATES``; the XLA path
differentiates the function, the numpy oracle uses the derivative.
About half of a ReLU layer's hidden is zeros, which the layer COUNTS —
``hidden_stats``, two numbers kept on the device beside ``moe_stats``
and read with it (``znicz_moe_hidden{stat="live"|"total"}``; of a held
share, over the steps that ran at the fit size) — and does not exploit.
**``route_from="block_input"``** takes the router's logits from another
tensor than the experts read: the INPUT of the residual sublayer before
this one (the block's input — ``residual`` is inside that sublayer's
unit), as it is, before that sublayer's norm and before this one's:
``r = x W_r`` where the experts read ``m = RMSNorm(a)``, ``a = x +
Attn(RMSNorm(x))``.  The choice is then known a sublayer ahead of the
experts, which a deployment uses to send or fetch by it while attention
runs; here nothing overlaps, the option is the model's mathematics.
It is a second forward edge into the unit (``route_input``, linked by
``StandardWorkflow._link_route``, which refuses a table with no
residual sublayer there) and a second backward edge out of it: the
pullback's cotangent of ``x_route``, ``∂L/∂r · W_rᵀ``, does not go down
the chain but is parked on that sublayer's GD unit
(``GradientDescentBase.park_beside``) and joined to its ``err_input``
after its own run, inside the one step program.  The logits, the
scores, the top k, its weights and the sort that plans the dispatch run
under the scope ``route`` (forward and pullback:
``observe.op_scopes()`` gives such an operation the phase ``route``).

``GatedMLP`` is the same gated MLP with no router — a model's dense
feed-forward block (``y = x + W_down (act(W_gate m) ⊙ W_up m)``).
"""

from __future__ import annotations

import functools
import math
import typing

import numpy as np

import jax
import jax.numpy as jnp

from znicz_tpu.memory import Vector
from znicz_tpu.observe import scopes as _scopes
from znicz_tpu.ops import activations_math, pallas_gmm
from znicz_tpu.ops.nn_units import Forward, GradientDescentBase
from znicz_tpu.ops.rms_norm import (norm_gains, post_gain, rms_norm,
                                    rms_norm_backward)

#: slots of ``moe_stats`` after the E per-expert row totals
_LB, _Z, _STEPS, _MAX, _MIN = range(5)
#: … and, of a layer with ``select_bias``, just before the grouped
#: matmuls' two at the end: steps the bias's rule ran, steps the guard
#: held it back, the largest |b_e| it left
_BIAS = slice(-5, -2)


# ----------------------------------------------------------------------
# the device path's three primitives
# ----------------------------------------------------------------------
#: a held share's row buffer, as a multiple of what uniform routing
#: sends to the experts held (module docstring): the CAPACITY, the most
#: a step may route here …
HELD_SLACK = 4
#: … and the FIT size, which holds a step whose routing is near
#: uniform: the length the buffer runs at whenever the pairs here fit
HELD_FIT = 1.25


@jax.custom_vjp
def _kept(made, kept):
    """``kept``, a value made and saved elsewhere, where the same value
    is made again (``made``): every reader takes ``kept``, the
    cotangent goes the way ``made`` came — what made it again is dead
    code, and only its pullback runs."""
    return kept


_kept.defvjp(lambda made, kept: (kept, None),
             lambda _, grad: (grad, None))


def _cast(rhs, dtype):
    """The slabs in the rows' dtype.  ``rhs`` may be a pair: the slabs
    and the cast of them that is kept beside them, a leaf the update
    writes (``MoE._keep_slab_copies``: a cast made here is a pass over
    the slabs, 235 MB in Laguna, at every call) — the cast is read,
    and a cotangent goes to the slabs as if they had been cast
    here."""
    if isinstance(rhs, tuple):
        return _kept(rhs[0].astype(dtype), rhs[1])
    return rhs.astype(dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _gmm_kernels(lhs, rhs, group_sizes, tap, interpret):
    """The grouped matmul through ``ops/pallas_gmm.py``; the groups may
    end before the rows do (one chip's share of the pairs, under a
    static capacity): the kernels' work follows the groups, and the
    rows past them come back zero, forward and backward.

    ``tap`` is a scalar the forward adds nowhere.  It is a TAP, not an
    operand: what the backward returns as its "cotangent" is no
    derivative of anything but Σ d_rhs² — the sum of the squares of
    the weight gradient this very backward returns, as ``znicz_tgmm``
    summed them while the slabs lay in VMEM.  A pullback can hand out
    nothing but cotangents, so that is how the number leaves it."""
    return pallas_gmm.znicz_gmm(lhs, _cast(rhs, lhs.dtype), group_sizes,
                                interpret=interpret)


def _gmm_kernels_fwd(lhs, rhs, group_sizes, tap, interpret):
    return (_gmm_kernels(lhs, rhs, group_sizes, tap, interpret),
            (lhs, rhs, group_sizes))


def _gmm_kernels_bwd(interpret, residual, grad):
    lhs, rhs, group_sizes = residual
    grad = grad.astype(lhs.dtype)
    # the row gradient leaves the kernel in the rows' dtype: one
    # rounding of its f32 accumulator; the weight gradient stays f32
    d_lhs = pallas_gmm.znicz_gmm(grad, _cast(rhs, lhs.dtype), group_sizes,
                                 transpose_rhs=True, out_dtype=lhs.dtype,
                                 interpret=interpret)
    d_rhs, squares = pallas_gmm.znicz_tgmm(lhs, grad, group_sizes,
                                           interpret=interpret)
    if isinstance(rhs, tuple):       # (the slabs, their cast): no
        d_rhs = (d_rhs.astype(rhs[0].dtype), None)    # cotangent to it
    else:
        d_rhs = d_rhs.astype(rhs.dtype)
    return d_lhs, d_rhs, None, squares.sum()


_gmm_kernels.defvjp(_gmm_kernels_fwd, _gmm_kernels_bwd)


@functools.partial(jax.jit, static_argnums=(3, 4))
def grouped_matmul(lhs, rhs, group_sizes, kernel: bool = False,
                   interpret: bool = False, tap=None):
    """(M, K) rows in E contiguous groups × (E, K, N) f32 slabs →
    (M, N) f32: row r of group e is multiplied by ``rhs[e]``, the slabs
    cast to the rows' dtype on the way in (or, ``rhs`` a pair, read
    from the cast their caller made: :func:`_cast`).  ``kernel`` runs
    the repo's Pallas kernels (``znicz_gmm`` / ``znicz_tgmm``; the
    weight gradient comes back in f32, the row gradient in the rows'
    dtype), else
    ``jax.lax.ragged_dot``, the XLA core.  Jitted so that the three
    call sites of a layer, and every layer, lower it once (PERF.md §6,
    PR 24: lowering is a set-up cost the compile cache does not hide).
    Rows may follow the last group; they come back zero on either
    path.

    ``tap``, a zero scalar that enters no result: on the kernel path
    the pullback's "cotangent" of it is Σ (weight gradient)², made
    where the gradient is made (:func:`_gmm_kernels`: a tap, not a
    derivative); on the ``ragged_dot`` path it is 0 and means
    nothing — only a caller that knows which path ran may read it."""
    if kernel:
        return _gmm_kernels(
            lhs, rhs, group_sizes,
            jnp.zeros((), jnp.float32) if tap is None else tap, interpret)
    return jax.lax.ragged_dot(lhs, _cast(rhs, lhs.dtype), group_sizes,
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


#: a held share's rows come from their tokens and go back to them BY
#: GATHERS where the N · k pairs are at most this many times the fit
#: size's rows (the share held is a tenth of the pairs or more), else
#: by a gather and a scatter-add over the buffer's rows (module
#: docstring): the gathers read a row for every one of a token's k
#: slots, here or not, the scatter-add walks the buffer's rows one
#: after the other
HELD_GATHER = 8


def _by_gathers(n: int, k: int, fit: int) -> bool:
    return n * k <= HELD_GATHER * fit


def _positions(mask, sizes):
    """Where each of the N·k flat (token, slot) pairs lies in the order
    that sorts them by the expert held (``mask`` (N·k, |held|): which
    pair is which expert's; ``sizes`` its column sums): a pair here at
    its expert's offset plus its rank among that expert's pairs, a
    pair of an absent expert past them all — the inverse of the
    order's head without a second sort."""
    mask = mask.astype(jnp.int32)
    rank = jnp.cumsum(mask, axis=0) - mask
    first = jnp.cumsum(sizes) - sizes
    return jnp.where(mask.any(axis=1),
                     (mask * (first[None, :] + rank)).sum(axis=1),
                     mask.shape[0])


#: a row gather reads its operand from the chip's fast memory where
#: the compiler can hold it there and fetches it row by row from HBM
#: where it cannot (PERF.md §6, PR 51: 16,384 rows of 10 KB out of a
#: 157 MB operand take 1.1 ms, six times what they take out of one
#: that fits), so a larger operand than this is gathered a block of
#: columns at a time — a share of a v5e's 128 MiB that leaves room for
#: the next block's prefetch
GATHER_OPERAND_BYTES = 48 << 20


def _column_blocks(rows: int, columns: int, itemsize: int) -> int:
    """Into how many blocks of whole 128-lane tiles a (rows, columns)
    gather operand is cut so that a block holds at most
    ``GATHER_OPERAND_BYTES`` (1: as it is)."""
    tiles, ragged = divmod(columns, 128)
    least = -(-rows * columns * itemsize // GATHER_OPERAND_BYTES)
    return 1 if ragged else next(
        (blocks for blocks in range(least, tiles + 1)
         if tiles % blocks == 0), 1)


def _slots_sum(rows, at, ok, scale=None):
    """(N, D) f32: Σ over a token's k slots of the buffer's row
    ``at[n, s]`` where ``ok[n, s]`` — k gathers of N rows added up
    slot by slot, never an (N·k, D) buffer; by column blocks where the
    buffer is large (:func:`_column_blocks`).  ``scale``: a factor a
    row of the buffer, applied where the rows lie, a block at a time
    (one pass over the buffer with the cut)."""
    blocks = _column_blocks(*rows.shape, rows.dtype.itemsize)
    out = []
    for part in jnp.split(rows, blocks, axis=1):
        if scale is not None:
            part = part * scale[:, None]
        f = 0.0
        for s in range(at.shape[1]):
            f = f + jnp.where(
                ok[:, s, None],
                jnp.take(part, at[:, s], axis=0, mode="clip"),
                0).astype(jnp.float32)
        out.append(f)
    return out[0] if blocks == 1 else jnp.concatenate(out, axis=1)


def _rows_of(m, dtype, token, live):
    """Rows of ``m`` (N, D) in the buffer's order: row j is token
    ``token[j]`` where ``live[j]``, else zero, cast to ``dtype``."""
    return jnp.where(live[:, None], jnp.take(m, token, axis=0),
                     0.0).astype(dtype)


def _weights_of_rows(top_p, pair, live):
    """The weight of each of the buffer's rows: ``top_p`` (N, k) at its
    flat (token, slot) pair where ``live``, else zero."""
    return jnp.where(live, jnp.take(top_p.reshape(-1), pair), 0.0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _gather_rows(m, dtype, token, live, at, ok):
    """:func:`_rows_of` whose pullback gathers too: ``at`` (N, k) is
    the row of each (token, slot) pair, ``ok`` whether it has one."""
    return _rows_of(m, dtype, token, live)


def _gather_rows_fwd(m, dtype, token, live, at, ok):
    return _gather_rows(m, dtype, token, live, at, ok), (at, ok)


def _gather_rows_bwd(dtype, residual, grad):
    # gathered in the rows' dtype; a token's k copies then sum, in f32
    # (the values a scatter-add of the f32 cast adds up)
    return (_slots_sum(grad, *residual),) + (None,) * 4


_gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


@jax.custom_vjp
def _combine(out, top_p, pair, live, at, ok):
    """(N, D) f32: each token's rows of ``out`` (the buffer's, f32)
    times their weights ``top_p`` (N, k), summed: the rows weighted
    where they lie — ``pair`` and ``live``, the buffer's side of the
    same map: the products the scatter-add's form makes — and then
    gathered (:func:`_slots_sum`)."""
    return _slots_sum(out, at, ok, _weights_of_rows(top_p, pair, live))


def _combine_fwd(out, top_p, pair, live, at, ok):
    return _combine(out, top_p, pair, live, at, ok), \
        (out, top_p, pair, live, at, ok)


def _combine_bwd(residual, grad):
    out, top_p, pair, live, at, ok = residual
    back = jnp.take(grad, pair // top_p.shape[1], axis=0)
    d_weight = jnp.where(live, (out * back).sum(axis=1), 0.0)
    return (back * _weights_of_rows(top_p, pair, live)[:, None],
            jnp.where(ok, jnp.take(d_weight, at, mode="clip"), 0.0),
            None, None, None, None)


_combine.defvjp(_combine_fwd, _combine_bwd)


def _hidden_stats(live, total):
    """``[elements of the hidden that are not zero, elements there
    are]`` as a ReLU layer's ``hidden_stats`` adds them up."""
    return jax.lax.stop_gradient(
        jnp.stack([live, total]).astype(jnp.float32))


class _Held(typing.NamedTuple):
    """What the routed sum of a held share reads of its layer beside
    the arrays: hashable, so that the layers of a model that agree on
    it share ONE traced and lowered program a direction
    (:func:`_fit_or_capacity`)."""
    top_k: int
    dtype: object              # of the rows the matmuls take
    kernel: bool               # ``grouped_matmul``'s path
    interpret: bool
    fit: int                   # the buffer's two static lengths
    capacity: int
    act: str = "silu"          # the experts' gate function


def _cut(sizes, length):
    """The groups' sizes cut to a buffer of ``length`` rows, in the
    groups' order (all of them fit, unless the step is over its
    capacity: then :meth:`MoE._held_experts` poisons it)."""
    return jnp.minimum(sizes, jnp.maximum(
        length - (jnp.cumsum(sizes) - sizes), 0))


def _held_rows(plan, length, m, top_p, w_g, w_u, w_d, taps, casts, order,
               inverse, sizes, here, kept=None):
    """``(f, sizes, (rows, gate, up, hidden, out))``: the routed sum
    of the first ``length`` (static) pairs of ``order``, of which
    ``here`` are real; the groups cut to that buffer; and what a
    pullback of it reads.  ``inverse``: each pair's position in
    ``order`` — the rows then come and go by gathers
    (:func:`_gather_rows`, :func:`_combine`) — or None: a gather and a
    scatter-add under autodiff (``HELD_GATHER``).  ``casts``: the three
    slabs in the rows' dtype, the layer's kept copies, or None (cast
    at each call); ``kept``: the five results from an earlier run of
    the same function, which then stand in for the ones made here
    (:func:`_kept`)."""
    n, d = m.shape
    k, dt = plan.top_k, plan.dtype
    keep = iter(kept or ())
    pair = order[:length]
    live = jnp.arange(length) < here
    token = pair // k
    sizes = _cut(sizes, length)
    path = (plan.kernel, plan.interpret)
    w_g, w_u, w_d = (w_g, w_u, w_d) if casts is None \
        else zip((w_g, w_u, w_d), casts)

    def saved(made):
        return made if kept is None else _kept(made, next(keep))

    with jax.named_scope("combine"):
        if inverse is None:
            rows = _rows_of(m, dt, token, live)
        else:
            at = inverse.reshape(n, k)
            ok = (at >= 0) & (at < jnp.minimum(here, length))
            rows = _gather_rows(m, dt, token, live, at, ok)
    rows = saved(rows)
    gate = saved(grouped_matmul(rows, w_g, sizes, *path, tap=taps[0]))
    up = saved(grouped_matmul(rows, w_u, sizes, *path, tap=taps[1]))
    hidden = saved((_gate(jnp, plan.act, gate) * up).astype(dt))
    out = saved(grouped_matmul(hidden, w_d, sizes, *path, tap=taps[2]))
    with jax.named_scope("combine"):
        if inverse is None:
            weight = _weights_of_rows(top_p, pair, live)
            f = jnp.zeros((n, d), jnp.float32).at[token].add(
                out * weight[:, None])
        else:
            f = _combine(out, top_p, pair, live, at, ok)
    return f, sizes, (rows, gate, up, hidden, out)


#: the windows of a capacity branch written out one after the other;
#: more of them are a scan
UNROLLED_WINDOWS = 2


def _held_windows(plan, m, top_p, w_g, w_u, w_d, taps, casts, order,
                  inverse, sizes, here):
    """``(f, sizes)`` of a buffer of the capacity's rows, made by
    :func:`_held_rows` over ⌈capacity / fit⌉ windows of ``fit`` rows of
    the order, one after the other: each window takes what is left of
    the groups, and the results add up.  So the SAME kernels at the
    same shapes run as in a step at the fit size — a second set, at
    the capacity's length, would be traced, lowered and compiled for
    steps that near-uniform routing never takes, at every start of the
    program (PERF.md §6, PR 45: 8 s of a 60 s set-up).

    Two windows are written out: they trace as fast as a scan, and
    their pullback is the body's own.  From three on the traces grow
    with the windows (3–8 s of a 49 s set-up at three), so they are a
    scan whose body is traced once; it keeps nothing of a window for
    its pullback but what went in (``jax.checkpoint``: a window's
    forward is made again where its pullback runs — kept, the
    windows' results are stacked, and the chip's compiler refused the
    fusion that wrote a kernel's result into the stack)."""
    fit, capacity = plan.fit, plan.capacity
    # as one buffer of the capacity's rows would cut them
    left = sizes = _cut(sizes, capacity)
    here = jnp.minimum(here, capacity)
    starts = range(0, capacity, fit)
    order = jnp.pad(order, (0, max(starts[-1] + fit - order.shape[0], 0)))

    def window(f, left, start, pairs):
        part, took, _ = _held_rows(
            plan, fit, m, top_p, w_g, w_u, w_d, taps, casts, pairs,
            None if inverse is None else inverse - start, left,
            here - start)
        return f + part, left - took

    if len(starts) <= UNROLLED_WINDOWS:
        f = 0.0
        for start in starts:
            f, left = window(f, left, start, order[start:start + fit])
        return f, sizes

    @jax.checkpoint
    def step(carry, start):
        return window(*carry, start, jax.lax.dynamic_slice(
            order, (start,), (fit,))), None

    (f, _), _ = jax.lax.scan(
        step, (jnp.zeros(m.shape, jnp.float32), sizes),
        jnp.arange(len(starts), dtype=jnp.int32) * fit)
    return f, sizes


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fit_or_capacity(plan, *args):
    """:func:`_held_rows` at the fit size where the pairs here fit it,
    :func:`_held_windows` over the capacity where they do not
    (``args``: those functions', from ``m`` to ``here``) →
    ``(f, sizes)``.

    One ``cond``, and a pullback of its own: ``jax.vjp`` of a ``cond``
    hands out BOTH branches' residuals, the absent branch's as zeros,
    and a step at the fit size would write the capacity's every time.
    Here the forward saves what the FIT branch made — its rows, gate,
    up, hidden and out — and nothing of the capacity branch, which
    writes zeros of the fit branch's shapes when it runs; the pullback
    holds the same ``cond``: at the fit size it is ``jax.vjp`` of the
    body with the saved values standing in for the ones made again
    (:func:`_kept`), at the capacity it makes the windows' forward
    again from the inputs (and, from three windows on, each window's
    once more where its pullback runs) — one or two more forwards of
    the layer's routed part than a buffer of one length would cost, in
    the steps that take that branch only.  There the three taps are
    summed from
    the slabs' gradients (each window's kernels sum the squares of its
    own part, and the parts add up before the update sees them).
    Either direction is jitted on ``plan``: the layers of a model
    trace and lower it once, not once each (PERF.md §6, PR 24 and
    PR 45: what a warm compile cache does not hide)."""
    return _fit_or_capacity_fwd(plan, *args)[0]


@functools.partial(jax.jit, static_argnums=0)
def _fit_or_capacity_fwd(plan, *args):
    m, w_g = args[0], args[2]
    (d, width), dt = (m.shape[1], w_g.shape[2]), plan.dtype

    def at_capacity(*a):
        # zeros where the fit branch has its rows, gate, up, hidden
        # and out (``cond`` holds the two branches to one type)
        return _held_windows(plan, *a) + (tuple(
            jnp.zeros((plan.fit, columns), dtype) for columns, dtype in (
                (d, dt), (width, jnp.float32), (width, jnp.float32),
                (width, dt), (d, jnp.float32))),)

    f, sizes, kept = jax.lax.cond(
        args[-1] <= plan.fit,
        lambda *a: _held_rows(plan, plan.fit, *a), at_capacity, *args)
    out = (f, sizes)
    if plan.act == "relu":
        # … and how much of the hidden is not zero, of a step at the
        # fit size (the capacity branch keeps none: 0 there)
        out += (jnp.count_nonzero(kept[3]),)
    return out, (args, kept)


@functools.partial(jax.jit, static_argnums=0)
def _fit_or_capacity_bwd(plan, residual, grads):
    args, kept = residual
    # the last five — the slabs' casts, the pairs' order and its
    # inverse, the groups' sizes, the pairs here — take no cotangent
    diff, rest = args[:-5], args[-5:]

    def at_fit(diff, kept, grad):
        return jax.vjp(lambda *diff: _held_rows(
            plan, plan.fit, *diff, *rest, kept=kept)[0], *diff)[1](grad)

    def at_capacity(diff, kept, grad):
        *grads, taps = jax.vjp(lambda *diff: _held_windows(
            plan, *diff, *rest)[0], *diff)[1](grad)
        return (*grads, tuple(
            tap if tap is None else jnp.sum(slab * slab)
            for tap, slab in zip(taps, grads[2:])))

    return jax.lax.cond(args[-1] <= plan.fit, at_fit, at_capacity,
                        diff, kept, grads[0]) + (None,) * 5


_fit_or_capacity.defvjp(_fit_or_capacity_fwd, _fit_or_capacity_bwd)


def _gate(xp, act: str, x):
    """``act(x)``, ``act`` a name of ``activations_math.GATES``."""
    return activations_math.gate(act).fwd(xp, x)


#: (``ops/delta_net.py`` takes both from here)
_silu = activations_math.GATES["silu"].fwd


def _sigmoid(xp, x):
    return 1.0 / (1.0 + xp.exp(-x))


def gated_mlp(xp, dot, m, w_gate, w_up, w_down, act: str = "silu"):
    """``W_down (act(W_gate m) ⊙ W_up m)`` of (N, D) rows; ``dot`` is
    the unit's matmul (bf16 inputs on the device, plain in numpy),
    ``act`` a name of ``activations_math.GATES``."""
    return dot(xp, _gate(xp, act, dot(xp, m, w_gate)) * dot(xp, m, w_up),
               w_down)


def _np_dot(xp, a, b):
    return a @ b


#: the one place ``route_from`` may name: the input of the block, that
#: is of the residual sublayer before the expert layer
ROUTE_FROM = ("block_input",)


class MoE(Forward):
    """Dropless top-k mixture of gated-MLP experts (module
    docstring)."""

    EXPORT_PARAMS = ("weights", "weights_gate", "weights_up",
                     "weights_down", "gain_norm", "weights_shared_gate",
                     "weights_shared_up", "weights_shared_down")
    #: the always-on shared expert's three matrices
    SHARED = ("weights_shared_gate", "weights_shared_up",
              "weights_shared_down")
    #: the expert slabs, in the order of ``xla_forward``'s ``taps``: on
    #: the kernel path the pullback hands out Σ g² of each one's
    #: gradient beside the gradient (``_gmm_kernels``)
    TAPPED = ("weights_gate", "weights_up", "weights_down")
    #: … and each one's copy in the matmuls' dtype
    #: (:meth:`_keep_slab_copies`): made from its slab, never saved
    SLAB_COPIES = tuple(f"{attr}_cast" for attr in TAPPED)
    SNAPSHOT_EXCLUDE = SLAB_COPIES
    #: the scopes inside the layer's two units (module docstring): the
    #: selection bias's rule; the logits, scores, top k and the sort
    #: that plans the dispatch; the experts' rows gathered from their
    #: tokens and put back, weighted and summed — forward and pullback
    PHASES = {"router_bias": _scopes.ALL, "route": _scopes.ALL,
              "combine": _scopes.ALL}

    def __init__(self, workflow, n_experts: int, top_k: int, width: int,
                 norm_topk: bool = False, pre_norm: str | None = None,
                 residual: bool = False, aux_loss_weight: float = 0.0,
                 z_loss_weight: float = 0.0, norm_eps: float = 1e-5,
                 score: str = "softmax", routed_scale: float = 1.0,
                 shared_width: int = 0, held=None,
                 select_bias: bool = False, groups=None,
                 bias_rate: float = 1e-3, act: str = "silu",
                 route_from: str | None = None,
                 name=None, **kwargs) -> None:
        kwargs.setdefault("weights_filling", "xavier")
        kwargs["include_bias"] = False
        super().__init__(workflow, name=name, **kwargs)
        #: the experts' (and the shared expert's) gate function
        self.act = activations_math.gate(act).name
        if route_from not in (None,) + ROUTE_FROM:
            raise ValueError(f"route_from must be None or one of "
                             f"{ROUTE_FROM}, got {route_from!r}")
        #: where the router's logits are taken from (module docstring):
        #: None — what the experts read; "block_input" — the input of
        #: the residual sublayer before this one, as it is
        self.route_from = route_from
        #: … that tensor (``StandardWorkflow.link_forwards`` links it to
        #: the sublayer's ``input``) and the sublayer's GD unit, which
        #: joins the router's cotangent to its own (``link_gds``)
        self.route_input: Vector | None = None
        self.route_gd = None
        self.n_experts, self.top_k = int(n_experts), int(top_k)
        self.width = int(width)
        if not 1 <= self.top_k <= self.n_experts:
            raise ValueError(f"{self}: top_k {top_k} of {n_experts} "
                             f"experts")
        if pre_norm not in (None, "rms"):
            raise ValueError(f"pre_norm must be None or 'rms', got "
                             f"{pre_norm!r}")
        if score not in ("softmax", "sigmoid"):
            raise ValueError(f"score must be 'softmax' or 'sigmoid', "
                             f"got {score!r}")
        self.norm_topk = bool(norm_topk)
        self.pre_norm = pre_norm
        self.residual = bool(residual)
        self.aux_loss_weight = float(aux_loss_weight)
        self.z_loss_weight = float(z_loss_weight)
        self.norm_eps = float(norm_eps)
        #: the options of the module docstring's last part
        self.score = score
        self.routed_scale = float(routed_scale)
        self.shared_width = int(shared_width)
        self.held = None if held is None \
            else tuple(sorted({int(e) for e in held}))
        if self.held is not None and not (
                self.held and 0 <= self.held[0]
                and self.held[-1] < self.n_experts):
            raise ValueError(f"{self}: held {held} is not a set of "
                             f"experts below {n_experts}")
        #: the choice's options (module docstring): a bias on the
        #: selection and the group limit (n groups, m kept)
        self.select_bias_on = bool(select_bias)
        self._bias_steps = 0       # steps the bias's rule ran, so far
        self.bias_rate = float(bias_rate)
        self.groups = None if groups is None \
            else (int(groups[0]), int(groups[1]))
        if self.groups is not None:
            n_group, kept = self.groups
            per = self.n_experts // max(n_group, 1)
            if (n_group < 1 or self.n_experts % n_group
                    or not 1 <= kept <= n_group or per < 2
                    or kept * per < self.top_k):
                raise ValueError(
                    f"{self}: groups {groups} do not split "
                    f"{n_experts} experts into groups of at least 2 "
                    f"whose kept ones hold top_k {top_k}")
        #: the selection bias b (E,) and its last step's loads; what
        #: the rule did is three slots of ``moe_stats`` (``_BIAS``)
        self.select_bias = Vector(name=f"{self.name}.select_bias")
        self.select_load = Vector(name=f"{self.name}.select_load")
        self.weights_gate = Vector(name=f"{self.name}.weights_gate")
        self.weights_up = Vector(name=f"{self.name}.weights_up")
        self.weights_down = Vector(name=f"{self.name}.weights_down")
        self.gain_norm = Vector(name=f"{self.name}.gain_norm")
        for attr in self.SHARED + self.SLAB_COPIES:
            setattr(self, attr, Vector(name=f"{self.name}.{attr}"))
        #: [rows per expert held here (all E without ``held``) | lb
        #: loss, z loss, steps, per-step max and min rows of an expert;
        #: with ``held`` also: rows here, rows routed, rows over the
        #: capacity, steps that ran at the fit size | rows the kernels'
        #: visits cover, rows that are real (both 0 on the XLA path)],
        #: summed on the device
        self.moe_stats = Vector(name=f"{self.name}.moe_stats")
        #: of a layer of ReLU experts: [elements of the hidden that are
        #: not zero, elements there are] over the steps since the last
        #: read, counted on the device (a held share: the steps that ran
        #: at the fit size)
        self.hidden_stats = Vector(name=f"{self.name}.hidden_stats")
        #: what the router did in the last step: its (N, E) logits and
        #: the (N, top_k) experts chosen — what a check against a plain
        #: reference needs, which must not re-decide near-ties
        self.router_logits = Vector(name=f"{self.name}.router_logits",
                                    batch_major=True)
        self.last_choice = Vector(name=f"{self.name}.last_choice",
                                  batch_major=True)
        #: pullback stashed by xla_run for the GD pair (as attention)
        self._traced_vjp = None

    @property
    def n_local(self) -> int:
        """Experts whose weights live here."""
        return self.n_experts if self.held is None else len(self.held)

    def unserved(self) -> str | None:
        choice = [name for name, on in (
            ("select_bias", self.select_bias_on),
            ("groups", self.groups)) if on]
        said = choice + [f"act={self.act}"] * (self.act != "silu")
        return super().unserved() or (
            f"is a sparse-expert layer (moe"
            f"{', ' + ', '.join(said) if said else ''}); "
            f"serving has no expert dispatch yet — router, top-k"
            f"{', the selection bias and the group limit' if choice else ''} "
            f"and grouped matmul exist on the training path only "
            f"(ROADMAP R1, serving half)")

    def unserved_beside(self) -> str | None:
        return self.route_from and (
            f"takes its router's logits from the input of the sublayer "
            f"before it (moe, route_from={self.route_from}); serving "
            f"runs a chain one layer's output into the next — the edge "
            f"beside it and the expert layer exist on the training path "
            f"only (ROADMAP R1, serving half)")

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device=device, **kwargs)
        if self.input is None or not self.input:
            raise AttributeError(f"{self}: input not linked yet")
        if len(self.input.shape) != 3:
            raise ValueError(f"{self}: expected (batch, time, features) "
                             f"input, got {self.input.shape}")
        b, t, d = self.input.shape
        e, f = self.n_experts, self.width
        local = self.n_local
        if self.route_from is not None:
            if self.route_input is None or not self.route_input:
                raise AttributeError(
                    f"{self}: route_from={self.route_from!r} and no "
                    f"route_input linked (a layer table links it: "
                    f"StandardWorkflow.link_forwards)")
            if self.route_input.shape != self.input.shape:
                raise ValueError(
                    f"{self}: the router reads {self.route_input.shape}"
                    f", the experts {self.input.shape}")
        if self.act == "relu" and (
                not self.hidden_stats or self.hidden_stats.shape != (2,)):
            self.hidden_stats.reset(np.zeros(2, np.float32))
        if not self.weights:                       # the router
            self.weights.reset(self.fill_array(
                (d, e), self.weights_filling, self.weights_stddev,
                fan_in=d))
        shared = self.shared_width
        for vec, shape, fan_in in (
                (self.weights_gate, (local, d, f), d),
                (self.weights_up, (local, d, f), d),
                (self.weights_down, (local, f, d), f),
                (self.weights_shared_gate, (d, shared), d),
                (self.weights_shared_up, (d, shared), d),
                (self.weights_shared_down, (shared, d), shared)):
            if all(shape) and not vec:
                vec.reset(self.fill_array(shape, self.weights_filling,
                                          self.weights_stddev,
                                          fan_in=fan_in))
        if self.pre_norm and not self.gain_norm:
            self.gain_norm.reset(np.ones(d, np.float32))
        if self.select_bias_on:
            from znicz_tpu.parallel import partition
            for vec in (self.select_bias, self.select_load):
                if not vec or vec.shape != (e,):
                    vec.reset(np.zeros(e, np.float32))
            for attr in ("select_bias", "select_load"):
                self.partition_leaf(attr, partition.REPLICATED)
            from znicz_tpu.observe import metrics as obs_metrics
            obs_metrics.moe_router(self.name, "groups_kept").set(
                self.groups[1] if self.groups else 0)
        slots = local + 5 + (4 if self.held else 0) \
            + (3 if self.select_bias_on else 0) + 2
        if not self.moe_stats or self.moe_stats.shape != (slots,):
            # (a snapshot from before PR 34 holds two slots fewer, a
            # held layer's from before PR 45 one)
            self.moe_stats.reset(np.zeros(slots, np.float32))
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
        pairs = rows = fit = b * t * self.top_k
        if self.held is not None:
            def whole_tiles(rows, most):
                tile = min(pallas_gmm.ROW_TILE, rows)
                return min(-(-rows // tile) * tile, most)

            # the rows the step's buffers hold (module docstring), a
            # whole number of the kernels' row tiles where they fit:
            # the most a step may route here, and what a step near
            # uniform routing needs
            rows = whole_tiles(min(-(-HELD_SLACK * pairs * local // e),
                                   b * t * min(self.top_k, local)), pairs)
            fit = whole_tiles(math.ceil(HELD_FIT * pairs * local / e),
                              rows)
            self._capacity, self._fit = rows, fit
        from znicz_tpu.observe import metrics as obs_metrics
        gathers = _by_gathers(b * t, self.top_k, fit)   # (dropless: 1)
        for form in ("gather", "scatter"):
            obs_metrics.moe_combine(self.name, form).set(
                gathers == (form == "gather"))
        #: the kernels' row tile: one for the layer's nine calls (a
        #: held layer's calls all run at the fit size's length)
        self._gmm_row_tile = tile = pallas_gmm.row_tile(fit)
        if refused is None and fit % tile:
            refused = (f"{fit} rows do not divide by the kernels' "
                       f"row tile {tile}")
        self._gmm_kernel = refused is None
        self._gmm_interpret = interpret
        self.info("%s: %d experts top %d%s, dropless; grouped matmul %s "
                  "over %d rows in %d groups, %d x %d (gate, up) and "
                  "%d x %d (down)%s", self.name, e, self.top_k,
                  "" if self.held is None else
                  f", {local} held here (a step's buffer: {fit} rows "
                  f"where the pairs here fit, else the capacity {rows}, "
                  f"of {pairs} pairs)",
                  f"znicz_gmm / znicz_tgmm kernels, row tile {tile}"
                  + (", INTERPRETED" if interpret else "")
                  if self._gmm_kernel
                  else f"jax.lax.ragged_dot ({refused})",
                  fit, local, d, f, f, d,
                  (f"; {self.score} scores x {self.routed_scale:g}, "
                   f"shared expert of {shared}"
                   if (self.score, self.routed_scale, shared)
                   != ("softmax", 1.0, 0) else "")
                  + (f"; the choice by score + bias (rate "
                     f"{self.bias_rate:g}, moved in the step program "
                     f"under scope router_bias)"
                     if self.select_bias_on else "")
                  + ("; group-limited: %d groups, %d kept" % self.groups
                     if self.groups else "")
                  + (f"; {self.act} experts" if self.act != "silu" else "")
                  + (f"; the router reads the {self.route_from}, as it is"
                     if self.route_from else ""))
        self.init_vectors(self.input, self.output, self.weights,
                          self.weights_gate, self.weights_up,
                          self.weights_down, self.gain_norm,
                          self.moe_stats, self.router_logits,
                          self.last_choice, self.select_bias,
                          self.select_load, self.hidden_stats,
                          self.route_input,
                          *(getattr(self, attr) for attr in self.SHARED))
        self._keep_slab_copies()

    def _keep_slab_copies(self) -> None:
        """Each slab once more in the matmuls' dtype, as a leaf of its
        own (``Vector.keep_cast``) — exactly where that dtype is set
        and is not the slab's: what the layer sees of itself, whichever
        path the grouped matmuls take.  The grouped matmuls read the
        copy (:meth:`forward_args`) and no step casts a slab: a Pallas
        call takes no cast into its operand, so the cast was a pass of
        its own over every slab every step (4 bytes a parameter read,
        2 written, for nothing multiplied: PERF.md §6, PR 47).  Four
        rules:

        - **the update writes it**: ``_update_param_xla`` stores the
          slab it committed once more, cast — the value the next step
          would have cast, so nothing computed changes by a bit;
        - **it is no parameter**: no snapshot holds it
          (``SNAPSHOT_EXCLUDE``), no bundle (``EXPORT_PARAMS``), the
          SDC fingerprint and its vote do not fold it, no count of
          parameters counts it;
        - **it is never stale**: whatever writes a slab from the host
          reaches the device through the slab's upload, which makes
          the copy again (``Vector._upload``; counted:
          ``znicz_moe_slab_copy{stat="refreshed"}``);
        - **it exists by dtype**: no option, and under float32
          matmuls no leaf — the layer's programs are then what they
          were."""
        from znicz_tpu.observe import metrics as obs_metrics
        dtype = None if self.device.is_host_only else self.mxu_dtype
        obs_metrics.moe_slab_copy(self.name, "refreshed").set(0)
        for attr, kept in zip(self.TAPPED, self.SLAB_COPIES):
            slab = getattr(self, attr)
            if dtype is not None and np.dtype(dtype) != slab.dtype:
                slab.keep_cast(getattr(self, kept), dtype,
                               made=self._slab_copy_refreshed)
        self.init_vectors(*(getattr(self, kept)
                            for kept in self.SLAB_COPIES))

    def _slab_copy_refreshed(self) -> None:
        from znicz_tpu.observe import metrics as obs_metrics
        obs_metrics.moe_slab_copy(self.name, "refreshed").inc()

    # -- pure forward (jnp; the backward vjp's this) --------------------
    def forward_args(self) -> tuple:
        # a slab with its copy in the matmuls' dtype, where it keeps
        # one (``_keep_slab_copies``): the matmuls read the copy
        slabs = tuple(
            slab.devmem if slab.cast_copy is None
            else (slab.devmem, slab.cast_copy.devmem)
            for slab in (getattr(self, attr) for attr in self.TAPPED))
        args = (self.input.devmem, self.weights.devmem, *slabs,
                self.gain_norm.devmem if self.gain_norm else None)
        # what only some layers have, None where this one has not, the
        # Nones at the end left out; no cotangent ever reaches
        # ``select_bias``, and what comes back for ``taps`` is no
        # cotangent (``_gmm_kernels``)
        tail = [getattr(self, attr).devmem if self.shared_width else None
                for attr in self.SHARED]
        tail.append(self.select_bias.devmem if self.select_bias_on
                    else None)
        tail.append((jnp.zeros((), jnp.float32),) * len(self.TAPPED)
                    if getattr(self, "_gmm_kernel", False) else None)
        tail.append(self.route_input.devmem if self.route_from else None)
        while tail and tail[-1] is None:
            tail.pop()
        return args + tuple(tail)

    def _selection(self, xp, p, bias):
        """The numbers the top k are taken by, (N, E): the scores, plus
        the selection bias, −inf outside the groups kept (a group's
        score: the sum of its 2 largest biased scores)."""
        sel = p if bias is None else p + bias
        if self.groups is None:
            return sel
        n_group, kept = self.groups
        grouped = sel.reshape(sel.shape[0], n_group, -1)
        if xp is jnp:
            group_score = jax.lax.top_k(grouped, 2)[0].sum(axis=-1)
            best = jax.lax.top_k(group_score, kept)[1]
        else:
            group_score = np.sort(grouped, axis=-1)[..., -2:].sum(axis=-1)
            best = np.argsort(-group_score, axis=-1,
                              kind="stable")[:, :kept]
        keep = (best[:, :, None]
                == xp.arange(n_group)[None, None, :]).any(axis=1)
        return xp.where(keep[:, :, None], grouped, -xp.inf).reshape(
            sel.shape)

    def route(self, xp, m, w_r, bias=None):
        """``(logits, p, top_p, top_e)`` for (N, D) rows — float32
        throughout, on the device at the highest matmul precision, so
        that the choice of experts does not ride bf16 rounding.  ``p``
        is the score the top k are taken by: the softmax over the
        experts, or each expert's own sigmoid — or, with ``bias`` or
        ``groups``, :meth:`_selection` of it; ``top_p`` is ``p`` at
        the chosen experts either way."""
        plain = bias is None and self.groups is None
        if xp is jnp:
            logits = jnp.dot(m, w_r, precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
            p = jax.nn.softmax(logits, axis=-1) \
                if self.score == "softmax" else jax.nn.sigmoid(logits)
            if plain:
                top_p, top_e = jax.lax.top_k(p, self.top_k)
            else:
                top_e = jax.lax.top_k(
                    self._selection(jnp, jax.lax.stop_gradient(p), bias),
                    self.top_k)[1]
                top_p = jnp.take_along_axis(p, top_e, axis=-1)
        else:
            logits = m @ w_r
            if self.score == "softmax":
                z = np.exp(logits - logits.max(axis=-1, keepdims=True))
                p = z / z.sum(axis=-1, keepdims=True)
            else:
                p = _sigmoid(np, logits)
            # ties go to the lower index, as lax.top_k
            sel = p if plain else self._selection(np, p, bias)
            top_e = np.argsort(-sel, axis=-1,
                               kind="stable")[:, :self.top_k]
            top_p = np.take_along_axis(p, top_e, axis=-1)
        return logits, p, top_p, top_e

    def aux_losses(self, xp, logits, p, counts):
        """(load-balancing loss, z-loss); ``counts`` rows per expert,
        over ALL the experts the router chooses among.  Sigmoid scores
        enter the load-balancing loss normalised to sum 1."""
        n = logits.shape[0]
        if self.score == "sigmoid":
            p = p / p.sum(axis=-1, keepdims=True)
        lb = self.n_experts * ((counts / n) * p.mean(axis=0)).sum()
        top = logits.max(axis=-1)
        lse = top + xp.log(xp.exp(logits - top[:, None]).sum(axis=-1))
        return lb, (lse * lse).mean()

    def _weights_of(self, top_p):
        """The chosen experts' weights in the routed sum."""
        if self.norm_topk:
            top_p = top_p / top_p.sum(axis=-1, keepdims=True)
        if self.routed_scale != 1.0:
            top_p = top_p * self.routed_scale
        return top_p

    def _held_experts(self, m, top_p, top_e, w_g, w_u, w_d, taps):
        """``(f, local counts, (rows here, rows over, whether the step
        ran at the fit size[, of ReLU experts: the hidden's elements
        that are not zero and that there are]))``: the routed sum of
        the pairs whose expert lives here (module docstring)."""
        n = m.shape[0]
        k, local = self.top_k, self.n_local
        plan = _Held(k, self.mxu_dtype or jnp.float32,
                     getattr(self, "_gmm_kernel", False),
                     getattr(self, "_gmm_interpret", False),
                     self._fit, self._capacity, self.act)
        casts = None
        if isinstance(w_g, tuple):    # (slab, its copy): forward_args
            (w_g, w_u, w_d), casts = zip(w_g, w_u, w_d)
        with jax.named_scope("route"):
            table = np.full(self.n_experts, local, np.int32)
            table[list(self.held)] = np.arange(local, dtype=np.int32)
            slot = jnp.asarray(table)[top_e.reshape(n * k)]
            # the pairs here first, by expert and inside an expert by
            # token; the pairs of absent experts last
            order = jnp.argsort(slot, stable=True).astype(jnp.int32)
            mask = slot[:, None] == jnp.arange(local)[None, :]
            sizes = mask.sum(axis=0, dtype=jnp.int32)
            here = sizes.sum()
            # … and, where the rows go back by gathers, the order's
            # inverse
            inverse = _positions(mask, sizes) \
                if _by_gathers(n, k, plan.fit) else None
        if plan.fit == plan.capacity:
            # one length: the plain body under plain autodiff
            f, sizes, kept = _held_rows(
                plan, plan.capacity, m, top_p, w_g, w_u, w_d, taps, casts,
                order, inverse, sizes, here)
            live = (jnp.count_nonzero(kept[3]),) \
                if self.act == "relu" else ()
        else:
            f, sizes, *live = _fit_or_capacity(
                plan, m, top_p, w_g, w_u, w_d, taps, casts, order, inverse,
                sizes, here)
        over = jnp.maximum(here - plan.capacity, 0)
        # never short: the guard refuses a step that is over
        f = f + jnp.where(over > 0, jnp.float32(jnp.nan), 0.0)
        fits = here <= plan.fit
        hidden = ()
        if self.act == "relu":      # [not zero, there are], fit steps
            hidden = (_hidden_stats(
                live[0], jnp.where(fits, here, 0) * self.width),)
        return f, sizes, (here, over, fits) + hidden

    def xla_forward(self, x, w_r, w_g, w_u, w_d, g_norm=None,
                    ws_g=None, ws_u=None, ws_d=None, select_bias=None,
                    taps=None, x_route=None):
        """``((y, (lb, z)), (counts, logits, top_e))``: the output and
        the two auxiliary losses (differentiable); rows per expert, the
        router's logits and its choice (not; then the loads a selection
        bias moves by and a ReLU layer's count of its hidden, where the
        layer has them).  ``taps``: a zero scalar
        for each slab of ``TAPPED``, which enters nothing — the
        pullback returns Σ g² of that slab's gradient in its place
        (``grouped_matmul``; the kernel path only).  A slab may be a
        pair, the slab and its kept copy in the matmuls' dtype
        (:meth:`forward_args`): the matmuls read the copy, the
        cotangent is the slab's.  ``x_route`` (``route_from``): what
        the router reads in place of ``m``, as it is."""
        taps = taps or (None,) * len(self.TAPPED)
        b, t, d = x.shape
        n, k, e = b * t, self.top_k, self.n_experts
        x32 = x.astype(jnp.float32)
        m = (x32 if g_norm is None
             else rms_norm(jnp, x32, g_norm, self.norm_eps)).reshape(n, d)
        with jax.named_scope("route"):
            logits, p, top_p, top_e = self.route(
                jnp, m if x_route is None
                else x_route.astype(jnp.float32).reshape(n, d), w_r,
                None if select_bias is None
                else jax.lax.stop_gradient(select_bias))
            top_p = self._weights_of(top_p)
            flat_e = top_e.reshape(n * k)

        def rows_per_expert():
            # by comparison, not by a scatter-add (which a TPU
            # serialises)
            return (flat_e[:, None] == jnp.arange(e)[None, :]).sum(
                axis=0, dtype=jnp.int32)

        hidden_stats = None
        if self.held is not None:
            with jax.named_scope("route"):
                sizes = rows_per_expert()
            f, local, here = self._held_experts(m, top_p, top_e, w_g,
                                                w_u, w_d, taps)
            hidden_stats = here[3] if self.act == "relu" else None
            y = f.reshape(b, t, d)
            extra = jnp.stack([here[0], jnp.int32(n * k), here[1],
                               here[2].astype(jnp.int32)])
            counts = jax.lax.stop_gradient(
                jnp.concatenate([local, extra]).astype(jnp.float32))
        else:
            with jax.named_scope("route"):
                order = jnp.argsort(flat_e, stable=True).astype(jnp.int32)
                inverse = jnp.argsort(order).astype(jnp.int32)
                sizes = rows_per_expert()
            dt = self.mxu_dtype or jnp.float32
            path = (getattr(self, "_gmm_kernel", False),
                    getattr(self, "_gmm_interpret", False))
            rows = _dispatch(m, order, inverse, dt)
            gate = grouped_matmul(rows, w_g, sizes, *path, tap=taps[0])
            up = grouped_matmul(rows, w_u, sizes, *path, tap=taps[1])
            hidden = (_gate(jnp, self.act, gate) * up).astype(dt)
            out = grouped_matmul(hidden, w_d, sizes, *path, tap=taps[2])
            with jax.named_scope("combine"):
                out = _unpermute(out, inverse, order).reshape(n, k, d)
                y = (out * top_p[..., None]).sum(axis=1).reshape(b, t, d)
            counts = None
            if self.act == "relu":
                hidden_stats = _hidden_stats(jnp.count_nonzero(hidden),
                                             hidden.size)
        if ws_g is not None:
            y = y + gated_mlp(jnp, self.mxu_dot, m, ws_g, ws_u, ws_d,
                              self.act).reshape(b, t, d)
        if self.residual:
            y = x32 + y
        routed = jax.lax.stop_gradient(sizes.astype(jnp.float32))
        return ((y, self.aux_losses(jnp, logits, p, routed)),
                (routed if counts is None else counts,
                 jax.lax.stop_gradient(logits), top_e)
                + ((routed,) if self.select_bias_on else ())
                + ((hidden_stats,) if self.act == "relu" else ()))

    def _record(self, lb, z, counts, logits, top_e, *more) -> None:
        more = list(more)
        if self.select_bias_on:   # what GDMoE's rule reads this step
            self.select_load.devmem = more.pop(0)
        if self.act == "relu":
            self.hidden_stats.devmem = self.hidden_stats.devmem \
                + more.pop(0)
        self.router_logits.devmem = logits.reshape(
            self.router_logits.shape)
        self.last_choice.devmem = top_e.astype(jnp.int32).reshape(
            self.last_choice.shape)
        held = []
        if self.held is not None:   # [local rows | here, all, over, fit]
            counts, held = counts[:self.n_local], [counts[self.n_local:]]
        tail = jnp.stack([lb, z, jnp.float32(1.0), counts.max(),
                          counts.min()])
        grid = jnp.zeros(2, jnp.float32)
        if getattr(self, "_gmm_kernel", False):
            # what the kernels' grids did with these groups: an E-long
            # computation beside theirs, on the device (a straddling
            # tile is computed part by part, so a call covers what
            # tiles of a part's rows would)
            sizes = counts.astype(jnp.int32)
            grid = jnp.stack([
                pallas_gmm.visited_rows(
                    sizes, pallas_gmm.part_rows(self._gmm_row_tile)),
                sizes.sum()])
        if self.select_bias_on:    # GDMoE's rule fills them (_BIAS)
            held = held + [jnp.zeros(3, jnp.float32)]
        self.moe_stats.devmem = self.moe_stats.devmem + jnp.concatenate(
            [counts, tail] + held + [grid]).astype(jnp.float32)

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
        from znicz_tpu.observe import metrics as obs_metrics
        obs_metrics.moe_slab_copy(self.name, "slabs").set(
            sum(isinstance(arg, tuple) for arg in args[2:5]))

    # -- the epoch-end read ---------------------------------------------
    def on_epoch_ended(self) -> None:
        """Read the device totals once, publish them, start over."""
        from znicz_tpu.observe import metrics as obs_metrics
        stats = self.moe_stats
        if not stats:
            return
        stats.map_read()
        e = self.n_local
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
            if self.held is not None:
                here, routed, over = tail[5:8] / steps
                for stat, value in (
                        ("held", e), ("of", self.n_experts),
                        ("rows_here", here), ("rows_routed", routed),
                        ("capacity", getattr(self, "_capacity", 0)),
                        ("fit", getattr(self, "_fit", 0)),
                        ("rows_over", over),
                        ("fit_steps", tail[8]), ("steps", tail[_STEPS])):
                    obs_metrics.moe_held(self.name, stat).set(value)
            if tail[-1]:
                for stat, value in (("visited", tail[-2] / steps),
                                    ("real", tail[-1] / steps)):
                    obs_metrics.moe_gmm_rows(self.name, stat).set(value)
            if self.select_bias_on:
                ran, _skipped, extreme = tail[_BIAS]
                self._bias_steps += int(ran)
                obs_metrics.moe_router(self.name, "bias_steps").set(
                    self._bias_steps)
                obs_metrics.moe_router(self.name, "bias_abs_max").set(
                    extreme)
        stats.map_invalidate()
        stats.mem[...] = 0.0      # uploaded on the next region fire
        if self.hidden_stats:
            self.hidden_stats.map_read()
            live, total = (float(v) for v in self.hidden_stats.mem)
            if obs_metrics.enabled() and total:
                for stat, value in (("live", live), ("total", total)):
                    obs_metrics.moe_hidden(self.name, stat).set(value)
            self.hidden_stats.map_invalidate()
            self.hidden_stats.mem[...] = 0.0

    # -- numpy oracle ---------------------------------------------------
    def _forward_np(self, x):
        """``(y, cache)``; a loop over the experts held here, each
        computing the rows routed to it."""
        b, t, d = x.shape
        n = b * t
        m = (rms_norm(np, x, self.gain_norm.mem, self.norm_eps)
             if self.pre_norm else x).reshape(n, d)
        if self.select_bias_on:
            self.select_bias.map_read()
        logits, p, raw_p, top_e = self.route(
            np, self._routed_from_np(m), self.weights.mem,
            self.select_bias.mem if self.select_bias_on else None)
        top_p = self._weights_of(raw_p)
        f = np.zeros((n, d), np.float32)
        per_expert = []
        experts = range(self.n_experts) if self.held is None \
            else self.held
        for slot, e in enumerate(experts):
            rows, slots = np.nonzero(top_e == e)
            me = m[rows]
            gate = me @ self.weights_gate.mem[slot]
            up = me @ self.weights_up.mem[slot]
            hidden = _gate(np, self.act, gate) * up
            out = hidden @ self.weights_down.mem[slot]
            np.add.at(f, rows, out * top_p[rows, slots][:, None])
            per_expert.append((rows, slots, me, gate, up, hidden, out))
        if self.shared_width:
            f = f + gated_mlp(np, _np_dot, m, *(
                getattr(self, attr).mem for attr in self.SHARED),
                self.act)
        y = f.reshape(b, t, d)
        if self.residual:
            y = x + y
        # rows per expert over ALL the experts routed over (what the
        # load-balancing loss counts); ``per_expert`` has the held ones
        routed = np.asarray([(top_e == e).sum()
                             for e in range(self.n_experts)], np.float32)
        return y, (m, logits, p, raw_p, top_p, top_e, per_expert, routed)

    def _routed_from_np(self, m):
        """The rows the router reads: the experts' own ``m``, or the
        block's input as it is (``route_from``)."""
        if not self.route_from:
            return m
        self.route_input.map_read()
        return self.route_input.mem.astype(np.float32).reshape(m.shape)

    def numpy_run(self) -> None:
        for vec in (self.input, self.weights, self.weights_gate,
                    self.weights_up, self.weights_down, self.gain_norm,
                    *(getattr(self, attr) for attr in self.SHARED)):
            if vec:
                vec.map_read()
        y, cache = self._forward_np(self.input.mem.astype(np.float32))
        self.output.map_invalidate()
        self.output.mem[...] = y
        logits, p, per_expert, routed = cache[1], cache[2], cache[-2], \
            cache[-1]
        counts = np.asarray([len(pe[0]) for pe in per_expert], np.float32)
        for vec, value in ((self.router_logits, logits),
                           (self.last_choice, cache[5])):
            vec.map_invalidate()
            vec.mem[...] = value.reshape(vec.shape)
        lb, z = self.aux_losses(np, logits, p, routed)
        if self.select_bias_on:
            self.select_load.map_invalidate()
            self.select_load.mem[...] = routed
        held = [] if self.held is None \
            else [counts.sum(), routed.sum(), 0.0,
                  float(counts.sum() <= self._fit)]
        if self.select_bias_on:
            held = held + [0.0, 0.0, 0.0]
        self.moe_stats.map_write()
        self.moe_stats.mem[...] += np.concatenate(
            [counts, [lb, z, 1.0, counts.max(), counts.min()], held,
             [0.0, 0.0]]).astype(np.float32)
        if self.act == "relu":
            self.hidden_stats.map_write()
            self.hidden_stats.mem[...] += (
                sum(np.count_nonzero(pe[5]) for pe in per_expert),
                sum(pe[5].size for pe in per_expert))


class GDMoE(GradientDescentBase):
    """Expert-layer backward: the forward's stashed ``jax.vjp`` with
    ``(err_output, (aux_loss_weight, z_loss_weight))`` as the
    cotangent; the numpy oracle is analytic."""

    MATCHES = (MoE,)
    REQUIRES_FORWARD_UNIT = True
    REQUIRES_INPUT = True
    #: parameters beside the router (``weights``, the base's own pair),
    #: in the order of the forward's arguments
    EXTRA = ("weights_gate", "weights_up", "weights_down", "gain_norm",
             "weights_shared_gate", "weights_shared_up",
             "weights_shared_down")
    #: … and what follows them there that is no parameter of this
    #: rule (``MoE.xla_forward``)
    AFTER_EXTRA = ("select_bias", "taps", "x_route")
    #: the forward returns what routing did beside its output
    HAS_AUX = True

    def __init__(self, workflow, name=None, **kwargs):
        super().__init__(workflow, name=name, **kwargs)
        self.forward_unit = None
        for attr in self.EXTRA:
            setattr(self, f"accumulated_gradient_{attr}",
                    Vector(name=f"{self.name}.acc_{attr}"))

    def _extra_pairs(self) -> list:
        """``(attr, parameter Vector, its accumulator)`` beside the
        base's own pair."""
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
        fwd = self.forward_unit
        for _, param, acc in self._extra_pairs():
            # … and the copy the update writes beside a slab
            for vec in (param, acc, param.cast_copy):
                if vec and id(vec) not in seen:
                    vecs.append(vec)
        if getattr(fwd, "select_bias_on", False):
            # what the bias's rule reads and writes of the forward unit
            for vec in (fwd.select_bias, fwd.select_load, fwd.moe_stats):
                if vec and id(vec) not in seen:
                    vecs.append(vec)
        return vecs

    def _cotangent(self, xp, err):
        fwd = self.forward_unit
        return (err, (xp.float32(fwd.aux_loss_weight),
                      xp.float32(fwd.z_loss_weight)))

    def _pullback(self):
        """The forward's stashed pullback — valid only inside the trace
        that made it, single-use, the current pass's where a looped
        span applies the unit R times (see
        GDMultiHeadAttention.xla_run) — or a new one."""
        fwd = self.forward_unit
        vjp = fwd._traced_vjp if self.err_output._tracing else None
        fwd._traced_vjp = None
        if vjp is None:
            vjp = jax.vjp(fwd.xla_forward, *fwd.forward_args(),
                          has_aux=self.HAS_AUX)[1]
        return vjp

    def xla_run(self) -> None:
        fwd = self.forward_unit
        gx, g_own, *g_extra = self._pullback()(self._cotangent(
            jnp, self.err_output.devmem.astype(jnp.float32)))
        if self.need_err_input:
            self.err_input.devmem = gx
        self._apply_weights_xla(g_own)
        # (of a slab handed in with its copy, the slab's: the copy
        # takes no cotangent)
        grads = {attr: grad[0] if isinstance(grad, tuple) else grad
                 for attr, grad in zip(self.EXTRA, g_extra)}
        after = dict(zip(self.AFTER_EXTRA, g_extra[len(self.EXTRA):]))
        # Σ g² of a slab's gradient where the kernels made it: what the
        # pullback returns for the forward's ``taps``
        tapped = getattr(fwd, "_gmm_kernel", False)
        sums = dict(zip(fwd.TAPPED, after["taps"])) if tapped else {}
        if getattr(fwd, "route_from", None):
            # the router's share of the block input's cotangent
            fwd.route_gd.park_beside(after["x_route"])
        taken = 0
        for attr, param, acc in self._extra_pairs():
            taken += self._apply_weights_xla(
                grads[attr], vec=param, acc_vec=acc,
                grad_sq=sums.get(attr))
        if isinstance(fwd, MoE):
            from znicz_tpu.observe import metrics as obs_metrics
            obs_metrics.moe_guard_sum(fwd.name, "from_kernel").set(taken)
        if getattr(fwd, "select_bias_on", False):
            self._move_select_bias(jnp)

    def _move_select_bias(self, xp) -> None:
        """The selection bias's own rule, once per optimizer step:
        ``b_e += γ · sign(mean load − load_e)`` from this step's loads
        (module docstring) — no gradient, no momentum, gated by the
        anomaly guard's running flag as every update is.  Under
        accumulation the rule runs with the step that applies (its
        loads: the last microbatch's); a pass of a looped span is
        refused at ``initialize``."""
        from znicz_tpu.accelerated_units import current_accum_phase
        phase = current_accum_phase() if xp is jnp else None
        if phase is not None and phase[0] == "accum":
            return
        fwd = self.forward_unit
        guard = self.anomaly_flag \
            if self.anomaly_flag is not None and self.anomaly_flag else None
        if xp is np:
            for vec in (fwd.select_bias, fwd.moe_stats):
                vec.map_write()
            fwd.select_load.map_read()
            load = fwd.select_load.mem
            ok = np.float32(1.0 if guard is None
                            else guard.mem[0] > 0.5)
            fwd.select_bias.mem[...] += ok * fwd.bias_rate * np.sign(
                load.mean() - load)
            did = fwd.moe_stats.mem[_BIAS]
            did[:2] += (ok, 1.0 - ok)
            did[2] = np.abs(fwd.select_bias.mem).max()
            return
        with jax.named_scope("router_bias"):
            load = fwd.select_load.devmem
            ok = jnp.float32(1.0) if guard is None \
                else (guard.devmem[0] > 0.5).astype(jnp.float32)
            bias = fwd.select_bias.devmem \
                + ok * fwd.bias_rate * jnp.sign(load.mean() - load)
            fwd.select_bias.devmem = bias
            stats = fwd.moe_stats.devmem
            fwd.moe_stats.devmem = stats.at[_BIAS].set(jnp.stack(
                [stats[-5] + ok, stats[-4] + (1.0 - ok),
                 jnp.abs(bias).max()]))

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
        _, (m, logits, p, raw_p, top_p, top_e, per_expert, routed) = \
            fwd._forward_np(x)
        dy = self.err_output.mem.astype(np.float32).reshape(n, d)
        grads = {attr: np.zeros_like(getattr(fwd, attr).mem)
                 for attr in ("weights_gate", "weights_up",
                              "weights_down")}
        dm = np.zeros_like(m)
        dtop = np.zeros_like(top_p)     # 0 for an expert held elsewhere
        for e, (rows, slots, me, gate, up, hidden, out) in \
                enumerate(per_expert):
            dye = dy[rows]
            dtop[rows, slots] = (out * dye).sum(axis=-1)
            dout = dye * top_p[rows, slots][:, None]
            grads["weights_down"][e] = hidden.T @ dout
            dgate, dup, dme = _gated_mlp_backward(
                me, gate, up, dout, fwd.weights_gate.mem[e],
                fwd.weights_up.mem[e], fwd.weights_down.mem[e], fwd.act)
            grads["weights_gate"][e] = me.T @ dgate
            grads["weights_up"][e] = me.T @ dup
            np.add.at(dm, rows, dme)
        if fwd.shared_width:
            w_g, w_u, w_d = (getattr(fwd, attr).mem
                             for attr in fwd.SHARED)
            gate, up = m @ w_g, m @ w_u
            grads["weights_shared_down"] = (
                _gate(np, fwd.act, gate) * up).T @ dy
            dgate, dup, dme = _gated_mlp_backward(m, gate, up, dy, w_g,
                                                  w_u, w_d, fwd.act)
            grads["weights_shared_gate"] = m.T @ dgate
            grads["weights_shared_up"] = m.T @ dup
            dm += dme
        dtop = dtop * fwd.routed_scale
        if fwd.norm_topk:      # back through top_p = raw_p / Σ raw_p
            total = raw_p.sum(axis=-1, keepdims=True)
            unit = raw_p / total
            dtop = (dtop - (dtop * unit).sum(axis=-1, keepdims=True)) \
                / total
        dp = np.zeros_like(p)
        np.put_along_axis(dp, top_e, dtop, axis=-1)
        # the load-balancing loss reaches the scores through their
        # column means
        d_lb = fwd.aux_loss_weight * e_n * (routed / n)[None, :] / n
        if fwd.score == "softmax":
            dp = dp + d_lb
            dlogits = p * (dp - (dp * p).sum(axis=-1, keepdims=True))
        else:                  # … normalised to sum 1 first
            total = p.sum(axis=-1, keepdims=True)
            dp = dp + (d_lb - (d_lb * p / total).sum(
                axis=-1, keepdims=True)) / total
            dlogits = dp * p * (1.0 - p)
        top = logits.max(axis=-1)
        lse = top + np.log(np.exp(logits - top[:, None]).sum(axis=-1))
        soft = np.exp(logits - lse[:, None])
        dlogits += fwd.z_loss_weight * (2.0 * lse / n)[:, None] * soft
        grad_router = fwd._routed_from_np(m).T @ dlogits
        d_routed_from = dlogits @ self.weights.mem.T
        if fwd.route_from:     # the block input's cotangent, its share
            fwd.route_gd.park_beside(d_routed_from.reshape(b, t, d))
        else:
            dm += d_routed_from
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
        if fwd.select_bias_on:
            self._move_select_bias(np)


def _gated_mlp_backward(m, gate, up, dout, w_gate, w_up, w_down,
                        act: str = "silu"):
    """``(dgate, dup, dm)`` of ``W_down (act(gate) ⊙ up)`` given the
    cotangent ``dout`` of its output."""
    dhidden = dout @ w_down.T
    dgate = dhidden * up * activations_math.gate(act).derivative(
        np, None, gate)
    dup = dhidden * _gate(np, act, gate)
    return dgate, dup, dgate @ w_gate.T + dup @ w_up.T


class GatedMLP(Forward):
    """A dense gated feed-forward block (module docstring):
    ``y = x + W_down (silu(W_gate m) ⊙ W_up m)``, m the input or its
    RMSNorm.  ``weights`` is W_gate."""

    EXPORT_PARAMS = ("weights", "weights_up", "weights_down",
                     "gain_norm", "gain_post")
    #: may be a member of a looped span (``znicz_tpu.pass_span``)
    PASS_SAFE = True

    def __init__(self, workflow, width: int, pre_norm: str | None = None,
                 residual: bool = False, norm_eps: float = 1e-5,
                 post_norm: str | None = None, act: str = "silu",
                 name=None, **kwargs) -> None:
        kwargs.setdefault("weights_filling", "xavier")
        kwargs["include_bias"] = False
        super().__init__(workflow, name=name, **kwargs)
        #: the gate function, as the expert layer's
        self.act = activations_math.gate(act).name
        for option, value in (("pre_norm", pre_norm),
                              ("post_norm", post_norm)):
            if value not in (None, "rms"):
                raise ValueError(f"{option} must be None or 'rms', got "
                                 f"{value!r}")
        self.width = int(width)
        self.pre_norm = pre_norm
        #: the norm on the block's OUTPUT inside the skip,
        #: x + RMSNorm(f(x)): gain ``gain_norm``, or ``gain_post``
        #: beside a ``pre_norm`` (see ops/attention.py)
        self.post_norm = post_norm
        self.residual = bool(residual)
        self.norm_eps = float(norm_eps)
        self.weights_up = Vector(name=f"{self.name}.weights_up")
        self.weights_down = Vector(name=f"{self.name}.weights_down")
        self.gain_norm = Vector(name=f"{self.name}.gain_norm")
        self.gain_post = Vector(name=f"{self.name}.gain_post")
        self._traced_vjp = None

    def unserved(self) -> str | None:
        return super().unserved() or (
            f"is a gated MLP block (gated_mlp"
            f"{', act=' + self.act if self.act != 'silu' else ''}); "
            f"serving runs no feed-forward sublayer yet (ROADMAP R1, "
            f"serving half)")

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device=device, **kwargs)
        if self.input is None or not self.input:
            raise AttributeError(f"{self}: input not linked yet")
        d, f = self.input.shape[-1], self.width
        for vec, shape in ((self.weights, (d, f)),
                           (self.weights_up, (d, f)),
                           (self.weights_down, (f, d))):
            if not vec:
                vec.reset(self.fill_array(shape, self.weights_filling,
                                          self.weights_stddev,
                                          fan_in=shape[0]))
        if (self.pre_norm or self.post_norm) and not self.gain_norm:
            self.gain_norm.reset(np.ones(d, np.float32))
        if self.pre_norm and self.post_norm and not self.gain_post:
            self.gain_post.reset(np.ones(d, np.float32))
        self.output.reset(np.zeros(self.input.shape,
                                   dtype=self.output_store_dtype))
        self.inherit_model_shard(self.output)
        self.init_vectors(self.input, self.output, self.weights,
                          self.weights_up, self.weights_down,
                          self.gain_norm, self.gain_post)

    def forward_args(self) -> tuple:
        return (self.input.devmem, self.weights.devmem,
                self.weights_up.devmem, self.weights_down.devmem,
                self.gain_norm.devmem if self.gain_norm else None) \
            + ((self.gain_post.devmem,) if self.gain_post else ())

    def xla_forward(self, x, w_g, w_u, w_d, g_norm=None, g_post=None):
        x32 = x.astype(jnp.float32)
        g_pre, g_post = norm_gains(self, g_norm, g_post)
        m = x32 if g_pre is None \
            else rms_norm(jnp, x32, g_pre, self.norm_eps)
        y = gated_mlp(jnp, self.mxu_dot, m.reshape(-1, x.shape[-1]),
                      w_g, w_u, w_d, self.act).reshape(x.shape)
        if self.post_norm:
            y = rms_norm(jnp, y, g_post, self.norm_eps)
        return x32 + y if self.residual else y

    def xla_run(self) -> None:
        args = self.forward_args()
        if not self.output._tracing:
            self._traced_vjp = None
            self.output.devmem = self.xla_forward(*args)
            return
        self.output.devmem, self._traced_vjp = jax.vjp(
            self.xla_forward, *args)

    def _forward_np(self, x):
        m = (rms_norm(np, x, self.gain_norm.mem, self.norm_eps)
             if self.pre_norm else x).reshape(-1, x.shape[-1])
        gate, up = m @ self.weights.mem, m @ self.weights_up.mem
        y = ((_gate(np, self.act, gate) * up)
             @ self.weights_down.mem).reshape(x.shape)
        raw = None
        if self.post_norm:
            raw, y = y, rms_norm(np, y, post_gain(self).mem,
                                 self.norm_eps)
        return (x + y if self.residual else y), (m, gate, up, raw)

    def numpy_run(self) -> None:
        for vec in (self.input, self.weights, self.weights_up,
                    self.weights_down, self.gain_norm, self.gain_post):
            if vec:
                vec.map_read()
        self.output.map_invalidate()
        self.output.mem[...] = self._forward_np(
            self.input.mem.astype(np.float32))[0]


class GDGatedMLP(GDMoE):
    """Backward of :class:`GatedMLP`: the stashed pullback, as the
    expert layer's; the numpy oracle is analytic."""

    MATCHES = (GatedMLP,)
    EXTRA = ("weights_up", "weights_down", "gain_norm", "gain_post")
    HAS_AUX = False

    def _cotangent(self, xp, err):
        return err

    def numpy_run(self) -> None:
        fwd = self.forward_unit
        for vec in (self.err_output, self.input):
            vec.map_read()
        self.weights.map_write()
        for _, param, _ in self._extra_pairs():
            param.map_write()
        x = self.input.mem.astype(np.float32)
        _, (m, gate, up, raw) = fwd._forward_np(x)
        err = self.err_output.mem.astype(np.float32)
        grads, dy = {}, err
        if fwd.post_norm:                 # back through the output norm
            gain = post_gain(fwd)
            dy, grads["gain_post" if gain is fwd.gain_post
                      else "gain_norm"] = rms_norm_backward(
                np, raw, gain.mem, fwd.norm_eps, err)
        dy = dy.reshape(m.shape)
        grads["weights_down"] = (_gate(np, fwd.act, gate) * up).T @ dy
        dgate, dup, dm = _gated_mlp_backward(
            m, gate, up, dy, fwd.weights.mem, fwd.weights_up.mem,
            fwd.weights_down.mem, fwd.act)
        grads["weights_up"] = m.T @ dup
        dx = dm.reshape(x.shape)
        if fwd.pre_norm:
            dx, grads["gain_norm"] = rms_norm_backward(
                np, x, fwd.gain_norm.mem, fwd.norm_eps, dx)
        if fwd.residual:
            dx = dx + err
        if self.need_err_input:
            self.err_input.map_invalidate()
            self.err_input.mem[...] = dx
        self._apply_weights_np(m.T @ dgate)
        for attr, param, acc in self._extra_pairs():
            self._apply_weights_np(grads[attr], vec=param, acc_vec=acc)

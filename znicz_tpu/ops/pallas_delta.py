"""The gated delta rule in its chunked form (Yang, Kautz, Hatamizadeh
2024, arXiv:2412.06464): what is local to a chunk in plain
``jax.numpy``, the state handed from chunk to chunk as Pallas kernels.

Per head, with keys q_t, k_t of d_k, values v_t of d_v, a decay
α_t ∈ (0, 1) and a write strength β_t, the recurrence is

.. code-block:: text

    S_t = α_t S_{t−1} + β_t k_t (v_t − α_t S_{t−1}ᵀ k_t)ᵀ     o_t = S_tᵀ q_t

over a state S of d_k × d_v, S_0 = 0.  In chunks of C positions, with
c_i = Σ_{j≤i} log α_j inside a chunk, Γ_ij = exp(c_i − c_j) for i ≥ j
(only such differences are ever exponentiated: all ≤ 1) and K, Q, V
the chunk's rows:

.. code-block:: text

    A  = (I + strict_lower(diag(β)(Γ ⊙ K Kᵀ)))⁻¹ diag(β)
    W  = A (exp(c) ⊙ K)        U = A V         K̂ = K ⊙ exp(c_C − c)
    V′ = U − W S_n             S_{n+1} = exp(c_C) S_n + K̂ᵀ V′     (*)
    O  = (exp(c) ⊙ Q) S_n + (Q Kᵀ ⊙ Γ, lower triangle) V′

The matrix under the inverse is unit lower triangular, its strict part
L nilpotent.  Its inverse is built by halves from the 2 × 2 blocks of
the diagonal (:func:`unit_lower_inverse`): ten whole-chunk batched
matmuls on the MXU at C 64, differentiable, no triangular solve — and
not the series Π_p (I + (−L)^(2^p)), whose high powers cancel too many
digits.

Only (*) is sequential, and only (*) is a kernel:
``znicz_delta_state_fwd`` walks a head's chunks along the grid's last
axis with S in VMEM and writes V′ and, for the backward, every chunk's
S_n; ``znicz_delta_state_bwd`` walks them in reverse with the state's
cotangent carried.  Everything around them is ``jax.numpy`` under
autodiff (the (C, C) matrices a chunk keeps for it are 31 MB each a
layer at T 4,096 × 30 heads; the inverse keeps ONE, its result, and has
its own derivative rule).  :func:`state_scan` without the kernels is
the same algebra as a ``lax.scan`` over the chunks — the path off a
TPU.

Head sizes need not fill a 128-lane tile: a block spans a whole
(C, d_k) or (d_k, d_v) face of its array, which Mosaic lays out in
whole (8, 128) tiles — at d_k 96 × d_v 192 the state occupies
128 × 256 lanes' worth, 1.78 × its elements (:func:`padded_share`);
HBM holds no padding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: positions per chunk (the program's, not a model's)
CHUNK = 64
_LANES, _SUBLANES = 128, 8
_HIGHEST = jax.lax.Precision.HIGHEST


def kernel_legal(chunk: int = CHUNK) -> bool:
    """A chunk's rows are whole sublane tiles (and a power of two, as
    the inverse wants them)."""
    return chunk % _SUBLANES == 0 and chunk & (chunk - 1) == 0


def padded_share(dk: int, dv: int) -> float:
    """Elements of the 128-lane tiles a d_k × d_v product occupies over
    d_k · d_v: d_k is the lane axis of W and K̂ and the contraction of
    their products with the state, d_v the lane axis of S, U and V′;
    each goes to the next multiple of 128 (1.0: nothing padded; 1.78
    at 96 × 192)."""
    def whole(n: int) -> int:
        return -(-n // _LANES) * _LANES
    return whole(dk) * whole(dv) / float(dk * dv)


def _mm(a, b, dot_dtype):
    """Batched ``a @ b`` over the leading axes: inputs in ``dot_dtype``
    with f32 accumulation, or f32 at the highest precision."""
    if dot_dtype is not None:
        return jnp.matmul(a.astype(dot_dtype), b.astype(dot_dtype),
                          preferred_element_type=jnp.float32)
    return jnp.matmul(a, b, precision=_HIGHEST)


def _pair_masks(c: int) -> list:
    """Per level of the inverse, sizes 1, 2, 4, … < C: the 0/1 (C, C)
    mask of the blocks BELOW the diagonal that join two inverted blocks
    of that size into one of twice it."""
    at = np.arange(c)
    masks, size = [], 1
    while size < c:
        same_pair = at[:, None] // (2 * size) == at[None, :] // (2 * size)
        masks.append((same_pair & (at[:, None] % (2 * size) >= size)
                      & (at[None, :] % (2 * size) < size)).astype(
                          np.float32))
        size *= 2
    return masks


@jax.custom_vjp
def unit_lower_inverse(lower):
    """(I + L)⁻¹ for strictly lower triangular ``lower`` (…, C, C), C a
    power of two, in f32 matmuls at the highest precision and no
    triangular solve — by halves:

    .. code-block:: text

        [[A, 0], [B, D]]⁻¹ = [[A⁻¹, 0], [−D⁻¹ B A⁻¹, D⁻¹]]

    from the 2 × 2 blocks on the diagonal (which invert exactly: I − L,
    L² = 0 there) up.  Every level is ONE pair of whole (C, C) batched
    matmuls: with X the block-diagonal inverse so far and B the level's
    blocks below the diagonal (L under a 0/1 mask), the next is
    X − X B X — the zeros ride along, and nothing is sliced, gathered
    or concatenated (sliced into its blocks the same algebra was 2,000
    operations and 29 ms a layer on the chip; PERF.md §6, PR 31).
    2 (log₂ C − 1) matmuls in all, ten at C 64.

    The nilpotent series in product form, Π_p (I + (−L)^(2^p)), is as
    many matmuls and loses digits as C grows — its high powers are
    large and cancel: at C 64 with keys as alike as a convolution
    leaves them it read 1.4e-4 of the mixer's output against the
    recurrence, and 1.6e-2 of the inverse with keys nearly parallel,
    where this form reads 6e-7 and 3e-6 (f32).

    Its derivative is its own rule, d(M⁻¹) = −M⁻¹ dM M⁻¹: two matmuls
    from the inverse the forward kept, not the transposes of ten."""
    c = lower.shape[-1]
    if c & (c - 1):
        raise ValueError(f"unit_lower_inverse: {c} rows are not a power "
                         f"of two")
    masks = _pair_masks(c)
    inverse = jnp.eye(c, dtype=lower.dtype)
    for mask in masks:
        joint = jnp.matmul(
            jnp.matmul(inverse, lower * mask, precision=_HIGHEST),
            inverse, precision=_HIGHEST)
        inverse = inverse - joint
    return inverse


def _inverse_fwd(lower):
    inverse = unit_lower_inverse(lower)
    return inverse, inverse


def _inverse_bwd(inverse, cotangent):
    back = jnp.swapaxes(inverse, -1, -2)
    c = inverse.shape[-1]
    strictly_lower = np.tril(np.ones((c, c), np.float32), -1)
    return (-jnp.matmul(jnp.matmul(back, cotangent, precision=_HIGHEST),
                        back, precision=_HIGHEST) * strictly_lower,)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def chunk_local(q, k, v, log_alpha, beta, dot_dtype=None):
    """What a chunk computes from its own rows: ``(W, K̂, U, decay,
    Qc, P)`` of the module docstring for q, k (G, N, C, d_k),
    v (G, N, C, d_v), log α and β (G, N, C) — ``decay`` = exp(c_C)
    (G, N), ``Qc`` = exp(c) ⊙ Q, ``P`` = Q Kᵀ ⊙ Γ on and below the
    diagonal.  The logarithms, their sums, Γ and the inverse are f32;
    the products into W, U and P take ``dot_dtype`` inputs."""
    chunk = q.shape[-2]
    log_alpha = log_alpha.astype(jnp.float32)
    c = jnp.cumsum(log_alpha, axis=-1)
    rows = np.arange(chunk)[:, None]
    cols = np.arange(chunk)[None, :]
    # c_i − c_j summed from its own terms, Σ_{j<m≤i} log α_m (a 0/1
    # matmul), not as a difference of two prefixes: where the decay is
    # strong the prefixes are hundreds and their difference would
    # carry their rounding
    at = np.arange(chunk)
    between = (at[None, None, :] < at[:, None, None]) \
        & (at[:, None, None] <= at[None, :, None])  # [m, i, j]: j < m ≤ i
    between = between.astype(np.float32).reshape(chunk, -1)
    gamma = jnp.exp(jnp.where(
        rows >= cols,
        jnp.matmul(log_alpha, between, precision=_HIGHEST).reshape(
            log_alpha.shape + (chunk,)),
        -jnp.inf))
    kk = jnp.matmul(k, jnp.swapaxes(k, -1, -2), precision=_HIGHEST)
    lower = jnp.where(rows > cols, beta[..., :, None] * gamma * kk, 0.0)
    a = unit_lower_inverse(lower) * beta[..., None, :]
    grown = jnp.exp(c)[..., None]
    w = _mm(a, grown * k, dot_dtype)
    u = _mm(a, v, dot_dtype)
    # exp(c_C − c_i) from the suffix's own sum, for the same reason
    after = jnp.flip(jnp.cumsum(jnp.flip(log_alpha, -1), axis=-1), -1)
    after = jnp.concatenate(
        [after[..., 1:], jnp.zeros_like(after[..., :1])], axis=-1)
    k_hat = k * jnp.exp(after)[..., None]
    p = _mm(q, jnp.swapaxes(k, -1, -2), dot_dtype) * gamma
    return w, k_hat, u, jnp.exp(c[..., -1]), q * grown, p


# ----------------------------------------------------------------------
# the walk over the chunks: plain
# ----------------------------------------------------------------------
def _state_scan_plain(w, k_hat, u, decay, dot_dtype):
    def step(s, chunk):
        w_n, k_n, u_n, d_n = chunk
        v_new = u_n - _mm(w_n, s, dot_dtype)
        s_next = d_n[:, None, None] * s + _mm(
            jnp.swapaxes(k_n, -1, -2), v_new, dot_dtype)
        return s_next, (v_new, s)

    g, _, _, dk = w.shape
    start = jnp.zeros((g, dk, u.shape[-1]), jnp.float32)
    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (w, k_hat, u, decay))
    _, (v_new, states) = jax.lax.scan(step, start, xs)
    return jnp.moveaxis(v_new, 0, 1), jnp.moveaxis(states, 0, 1)


# ----------------------------------------------------------------------
# the walk over the chunks: kernels
# ----------------------------------------------------------------------
def _dot(a, b, dot_dtype, trans_a=False, trans_b=False):
    dims = (((0,) if trans_a else (1,), (1,) if trans_b else (0,)),
            ((), ()))
    if dot_dtype is not None:
        a, b = a.astype(dot_dtype), b.astype(dot_dtype)
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _fwd_kernel(w_ref, k_ref, u_ref, d_ref, v_ref, s_ref, state, *,
                dot_dtype):
    @pl.when(pl.program_id(1) == 0)
    def _start():
        state[...] = jnp.zeros_like(state)

    s = state[...]
    s_ref[...] = s
    v_new = u_ref[...] - _dot(w_ref[...], s, dot_dtype)
    v_ref[...] = v_new
    state[...] = d_ref[...] * s + _dot(k_ref[...], v_new, dot_dtype,
                                       trans_a=True)


def _bwd_kernel(w_ref, k_ref, d_ref, s_ref, v_ref, dv_ref, ds_ref,
                dw_ref, dk_ref, du_ref, dd_ref, carry, *, dot_dtype):
    """One chunk of the reverse walk; ``carry`` is the cotangent of
    S_{n+1}, this chunk's output state."""
    @pl.when(pl.program_id(1) == 0)
    def _start():
        carry[...] = jnp.zeros_like(carry)

    g, s = carry[...], s_ref[...]
    dv = dv_ref[...] + _dot(k_ref[...], g, dot_dtype)
    du_ref[...] = dv
    dk_ref[...] = _dot(v_ref[...], g, dot_dtype, trans_b=True)
    dw_ref[...] = -_dot(dv, s, dot_dtype, trans_b=True)
    dd_ref[...] = jnp.sum(g * s, axis=0, keepdims=True)
    carry[...] = ds_ref[...] + d_ref[...] * g - _dot(
        w_ref[...], dv, dot_dtype, trans_a=True)


def _face(rows: int, cols: int, at):
    """One chunk's (rows, cols) face of a (G, N, rows, cols) array."""
    return pl.BlockSpec((None, None, rows, cols),
                        lambda g, n: (g, at(n), 0, 0))


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))


def _lanes(decay, dv: int):
    """(G, N) → (G, N, 1, d_v): a chunk's scalar as a row of lanes."""
    return jnp.broadcast_to(decay[..., None, None],
                            decay.shape + (1, dv)).astype(jnp.float32)


def _forward_call(w, k_hat, u, decay, interpret, dot_dtype):
    g, n, c, dk = w.shape
    dv = u.shape[-1]

    def first(i):
        return i

    return pl.pallas_call(
        functools.partial(_fwd_kernel, dot_dtype=dot_dtype),
        grid=(g, n),
        in_specs=[_face(c, dk, first), _face(c, dk, first),
                  _face(c, dv, first), _face(1, dv, first)],
        out_specs=(_face(c, dv, first), _face(dk, dv, first)),
        out_shape=(jax.ShapeDtypeStruct((g, n, c, dv), jnp.float32),
                   jax.ShapeDtypeStruct((g, n, dk, dv), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=_PARAMS, interpret=interpret,
        name="znicz_delta_state_fwd",
    )(w, k_hat, u, _lanes(decay, dv))


def _backward_call(w, k_hat, decay, states, v_new, d_v, d_s, interpret,
                   dot_dtype):
    g, n, c, dk = w.shape
    dv = v_new.shape[-1]

    def back(i):
        return n - 1 - i

    f32 = jnp.float32
    dw, dk_hat, du, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, dot_dtype=dot_dtype),
        grid=(g, n),
        in_specs=[_face(c, dk, back), _face(c, dk, back),
                  _face(1, dv, back), _face(dk, dv, back),
                  _face(c, dv, back), _face(c, dv, back),
                  _face(dk, dv, back)],
        out_specs=(_face(c, dk, back), _face(c, dk, back),
                   _face(c, dv, back), _face(1, dv, back)),
        out_shape=(jax.ShapeDtypeStruct((g, n, c, dk), f32),
                   jax.ShapeDtypeStruct((g, n, c, dk), f32),
                   jax.ShapeDtypeStruct((g, n, c, dv), f32),
                   jax.ShapeDtypeStruct((g, n, 1, dv), f32)),
        scratch_shapes=[pltpu.VMEM((dk, dv), f32)],
        compiler_params=_PARAMS, interpret=interpret,
        name="znicz_delta_state_bwd",
    )(w, k_hat, _lanes(decay, dv), states, v_new, d_v, d_s)
    return dw, dk_hat, du, dd.sum(axis=(-1, -2))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _state_scan_kernels(w, k_hat, u, decay, interpret, dot_dtype):
    return _forward_call(w, k_hat, u, decay, interpret, dot_dtype)


def _scan_fwd(w, k_hat, u, decay, interpret, dot_dtype):
    v_new, states = _forward_call(w, k_hat, u, decay, interpret,
                                  dot_dtype)
    return (v_new, states), (w, k_hat, decay, states, v_new)


def _scan_bwd(interpret, dot_dtype, residual, cotangent):
    w, k_hat, decay, states, v_new = residual
    d_v, d_s = cotangent
    return _backward_call(w, k_hat, decay, states, v_new, d_v, d_s,
                          interpret, dot_dtype)


_state_scan_kernels.defvjp(_scan_fwd, _scan_bwd)


def state_scan(w, k_hat, u, decay, kernel: bool = False,
               interpret: bool = False, dot_dtype=None):
    """(*) of the module docstring over a head's chunks: W, K̂
    (G, N, C, d_k), U (G, N, C, d_v), decay (G, N) → V′ (G, N, C, d_v)
    and every chunk's starting state S_n (G, N, d_k, d_v), f32."""
    if kernel:
        return _state_scan_kernels(
            *(a.astype(jnp.float32) for a in (w, k_hat, u, decay)),
            interpret, dot_dtype)
    return _state_scan_plain(w, k_hat, u, decay, dot_dtype)


# ----------------------------------------------------------------------
# the rule
# ----------------------------------------------------------------------
def gated_delta_rule(q, k, v, log_alpha, beta, chunk: int = CHUNK,
                     kernel: bool = False, interpret: bool = False,
                     dot_dtype=None):
    """o (B, T, H, d_v) of the recurrence in its chunked form for
    q, k (B, T, H, d_k), v (B, T, H, d_v), log α ≤ 0 and β (B, T, H);
    T a multiple of ``chunk``."""
    b, t, h, _ = q.shape
    if t % chunk:
        raise ValueError(f"gated_delta_rule: {t} positions are not "
                         f"whole chunks of {chunk}")
    n = t // chunk

    def chunks(a):                # (B, T, H, ·) → (B·H, N, C, ·)
        a = jnp.moveaxis(a.astype(jnp.float32), 2, 1)
        return a.reshape((b * h, n, chunk) + a.shape[3:])

    w, k_hat, u, decay, q_grown, p = chunk_local(
        chunks(q), chunks(k), chunks(v), chunks(log_alpha), chunks(beta),
        dot_dtype)
    v_new, states = state_scan(w, k_hat, u, decay, kernel, interpret,
                               dot_dtype)
    o = _mm(q_grown, states, dot_dtype) + _mm(p, v_new, dot_dtype)
    return jnp.moveaxis(o.reshape(b, h, t, o.shape[-1]), 1, 2)

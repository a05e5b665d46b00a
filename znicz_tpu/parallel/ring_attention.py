"""Ring attention: sequence/context parallelism over the device mesh.

The 2015 reference has no attention (SURVEY.md §5.7), but this
framework treats long-context machinery as first-class: sequences too
long for one chip's HBM shard over a mesh axis, and attention runs
**blockwise around the ICI ring** — each device keeps its Q shard and
passes K/V shards to its neighbor with ``jax.lax.ppermute``, folding
every incoming block into an **online-softmax accumulator** (running
max, normalizer and weighted-value sum), the numerically stable
streaming form.  Communication overlaps compute block by block and no
device ever materializes the full (T, T) score matrix.

Layout: ``(batch, time, heads, head_dim)``; time is sharded over
:data:`SEQ_AXIS`.  :func:`sequence_sharded_attention` is the user
entry — it ``shard_map``'s :func:`ring_attention_block` over the mesh
and is validated on the virtual CPU mesh against
:func:`local_attention` (the single-device oracle).  Causal masking
uses global positions, so it is exact across shard boundaries.

Since round 6 the production TPU fold is the fused flash KERNEL: each
hop is one :func:`znicz_tpu.ops.pallas_attention.ring_hop` pass at
the hop's global offset (:func:`_ring_kernel_fold`), and the XLA scan
fold below is the portable fallback (non-TPU backends,
kernel-illegal shard geometry — :func:`ring_fold_choice` resolves).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from znicz_tpu.parallel.axis import SEQ_AXIS

_NEG_INF = -1e30


def _visibility(tq: int, tk: int, q_pos=None, k_pos=None):
    """(1, 1, tq, tk) key-visibility mask: causal when global
    positions are given (exact across shard/block boundaries — the
    one masking rule both the ring and the blocked form use), all-ones
    otherwise."""
    if q_pos is None:
        return jnp.ones((1, 1, tq, tk), bool)
    return (q_pos[:, None] >= k_pos[None, :])[None, None]


def local_attention(q, k, v, causal: bool = False, dot_dtype=None,
                    window=None):
    """Single-device softmax attention — the oracle.

    Shapes: q (B, Tq, H, D), k/v (B, Tk, H_kv, D) → (B, Tq, H, D).
    Grouped queries (H a multiple of H_kv; query head h reads K/V head
    ``h // (H / H_kv)``) go through an einsum over the group, K and V
    not repeated; a causal ``window`` also hides columns ≤ row − window.
    Both unset, the function traces what it always has.

    ``dot_dtype`` (e.g. ``jnp.bfloat16``) casts the GEMM operands AND
    the materialized (T, T) score/probability tensors to that dtype —
    the profile of the T=2048 step (PERF.md round 5) shows the six
    attention-core GEMMs + the softmax reduction pinned at the HBM
    bandwidth roof (~660–775 GB/s, 11–24 TF/s) streaming f32 (B, H,
    T, T) tensors, so halving the bytes nearly halves the step.
    Softmax statistics (row max, normalizer) still reduce in f32 via
    ``preferred_element_type`` on the reductions' inputs; ``None``
    keeps the original full-f32 math (the CPU/oracle path).
    """
    d = q.shape[-1]
    if dot_dtype is not None:
        q, k, v = (a.astype(dot_dtype) for a in (q, k, v))
    b, tq, h, _ = q.shape
    tk, h_kv = k.shape[1], k.shape[2]
    group = h // h_kv
    if group > 1:
        s = jnp.einsum("bqhgd,bkhd->bhgqk",
                       q.reshape(b, tq, h_kv, group, d), k,
                       preferred_element_type=jnp.float32
                       ).reshape(b, h, tq, tk) / np.sqrt(d)
    else:
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32) / np.sqrt(d)
    if causal:
        rows, cols = jnp.arange(tq)[:, None], jnp.arange(tk)[None, :]
        mask = rows >= cols
        if window is not None:
            mask = mask & (cols > rows - window)
        s = jnp.where(mask[None, None], s, _NEG_INF)
    elif window is not None:
        raise ValueError("a window needs causal attention")
    if dot_dtype is not None:
        # stabilized softmax with the big (T, T) tensors STORED in
        # dot_dtype; exp/normalizer math in f32
        s = s.astype(dot_dtype)
        m = jax.lax.stop_gradient(
            s.max(axis=-1, keepdims=True).astype(jnp.float32))
        e = jnp.exp(s.astype(jnp.float32) - m)
        p = (e / e.sum(axis=-1, keepdims=True)).astype(dot_dtype)
    else:
        p = jax.nn.softmax(s, axis=-1)
    if group > 1:
        return jnp.einsum("bhgqk,bkhd->bqhgd",
                          p.reshape(b, h_kv, group, tq, tk), v,
                          preferred_element_type=jnp.float32
                          ).reshape(b, tq, h, d)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v,
                     preferred_element_type=jnp.float32)
    return out


def local_attention_blocked(q, k, v, causal: bool = False,
                            block_k: int = 512, dot_dtype=None):
    """Single-device FLASH-style attention as a plain-XLA ``lax.scan``
    over K/V blocks with the same online-softmax fold the ring uses,
    so the full (T, T) score matrix never materializes in HBM — per
    scan step only a (B, H, Tq, block_k) tile exists.

    Since round 5 this is the portable FALLBACK, not the production
    path: on TPU the fused Pallas kernel
    (:func:`znicz_tpu.ops.pallas_attention.flash_attention`) is the
    measured winner at every T (SEQ_BENCH.json / PERF.md round 5) and
    is the unit's default.  The scan form remains for platforms
    without Pallas and as the shard_map-compatible fold the ring path
    shares; while (T, T) fits HBM the plain fused form beats this
    scan (the carry round-trips dominate — measured round 4), so the
    scan is only selected explicitly via
    ``MultiHeadAttention(flash_block_k=...)`` on non-TPU backends.

    Exact same math as :func:`local_attention` (tested equal, fwd and
    vjp); ``jax.checkpoint`` on the fold keeps the backward from
    storing per-block softmax residuals (it recomputes the tile —
    the standard flash-attention backward tradeoff)."""
    b, t, h, d = q.shape
    tk = k.shape[1]
    if tk % block_k:
        raise ValueError(f"T_k {tk} not divisible by block_k {block_k}")
    n_blocks = tk // block_k
    qh = q  # (B, Tq, H, D); fold consumes this layout directly
    k_blocks = k.reshape(b, n_blocks, block_k, h, d) \
        .transpose(1, 0, 2, 3, 4)
    v_blocks = v.reshape(b, n_blocks, block_k, h, d) \
        .transpose(1, 0, 2, 3, 4)
    tq = t
    q_pos = jnp.arange(tq)

    m0 = jnp.full((b, h, tq), _NEG_INF, jnp.float32)
    denom0 = jnp.zeros((b, h, tq), jnp.float32)
    acc0 = jnp.zeros((b, h, tq, d), jnp.float32)

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def fold(carry, blk):
        i, k_blk, v_blk = blk
        mask = _visibility(
            tq, block_k,
            *((q_pos, i * block_k + jnp.arange(block_k)) if causal
              else (None, None)))
        return _fold_block(carry, qh, k_blk, v_blk, mask,
                           dot_dtype=dot_dtype), None

    (m, denom, acc), _ = jax.lax.scan(
        fold, (m0, denom0, acc0),
        (jnp.arange(n_blocks), k_blocks, v_blocks))
    out = acc / jnp.maximum(denom, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def _fold_block(carry, q, k_blk, v_blk, s_mask, dot_dtype=None):
    """Online-softmax fold of one K/V block into (m, denom, acc).

    ``dot_dtype`` casts the two tile GEMMs' operands (scores stay f32
    via ``preferred_element_type``; the running statistics are always
    f32 — same convention as :func:`local_attention`)."""
    m, denom, acc = carry
    d = q.shape[-1]
    if dot_dtype is not None:
        q, k_blk = q.astype(dot_dtype), k_blk.astype(dot_dtype)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk,
                   preferred_element_type=jnp.float32) / np.sqrt(d)
    s = jnp.where(s_mask, s, _NEG_INF)
    m_blk = s.max(axis=-1)
    m_new = jnp.maximum(m, m_blk)
    # guard: rows with no visible keys anywhere yet keep m = -inf
    p = jnp.exp(s - m_new[..., None])
    p = jnp.where(s_mask, p, 0.0)
    correction = jnp.exp(m - m_new)
    if dot_dtype is not None:
        p, v_blk = p.astype(dot_dtype), v_blk.astype(dot_dtype)
    acc = acc * correction[..., None] \
        + jnp.einsum("bhqk,bkhd->bhqd", p, v_blk,
                     preferred_element_type=jnp.float32)
    denom = (denom * correction
             + p.astype(jnp.float32).sum(axis=-1))
    return m_new, denom, acc


def _ring_kernel_fold(q, k, v, offs, axis_name: str, causal: bool,
                      dot_dtype, block_q: int | None,
                      block_k: int | None, interpret: bool):
    """The round-6 ring fold: each hop IS one fused flash-kernel pass
    (:func:`znicz_tpu.ops.pallas_attention.ring_hop`) over the
    arriving K/V shard at its GLOBAL offset, and the hops compose
    through the same online-softmax (m, l, acc) algebra the scan fold
    carries — expressed as the numerically identical (out, lse) pair:
    ``combine((o₁, lse₁), (o₂, lse₂)) = ((o₁·w₁ + o₂·w₂)/(w₁+w₂),
    m + log(w₁+w₂))`` with ``wᵢ = exp(lseᵢ − m)``.  The backward
    differentiates through the combination and the per-hop custom_vjp
    (recompute-from-lse kernels, the lse cotangent folded into delta),
    so sequence-parallel training runs kernel-rate in BOTH directions.
    Causal hops entirely above the diagonal skip every tile via the
    kernel's offset-aware ``pl.when`` (they contribute lse ≈ −1e30 and
    weight 0 here).

    Operands stay head-major (and head-packed: pairs of heads at
    dh 64, ``pallas_attention.head_pack_for``) around the whole ring —
    K/V rotate in kernel layout, so the per-hop cost is exactly one
    kernel dispatch, no re-transposes.  The one-chip path addresses
    the projections' layout instead (PR 28); the ring keeps this one
    until a four-chip cell can measure the move.

    ``offs`` is this device's (1, 1) int32 global row offset, handed
    in as a SEQUENCE-SHARDED OPERAND (not ``axis_index``), and the
    arriving block's offset ROTATES with K/V via ``ppermute``.  This
    is load-bearing, not style: the offsets become custom_vjp
    residuals, i.e. shard_map OUTPUTS of the forward — and the GSPMD
    partitioner refuses a partition-id-derived value crossing that
    boundary ("PartitionId instruction is not supported for SPMD
    partitioning … ambiguous").  Deriving them from a sharded operand
    keeps the whole fold partition-id-free."""
    from znicz_tpu.ops import pallas_attention as pa

    axis_size = jax.lax.psum(1, axis_name)
    b, tq, h, dh = q.shape
    tk = k.shape[1]
    if dot_dtype is not None:
        q, k, v = (a.astype(dot_dtype) for a in (q, k, v))
    pack = pa.head_pack_for(h, dh)
    qh, kh, vh = (pa.pack_heads(a, pack) for a in (q, k, v))
    bq = min(block_q or pa.BLOCK_Q, tq)
    bk = min(block_k or pa.BLOCK_K, tk)
    q_off = offs[0, 0]                   # this device's global row 0
    dhp = pack * dh                      # packed head width

    def hop(k_t, v_t, k_off):
        return pa.ring_hop(qh, k_t, v_t, q_off, k_off, causal,
                           bq, bk, interpret, pack)

    def combine(state, o_h, lse_h):
        o, lse = state                   # o f32, lse f32 (B,Hp,Tq,pack)
        m = jnp.maximum(lse, lse_h)
        w1, w2 = jnp.exp(lse - m), jnp.exp(lse_h - m)
        l = w1 + w2
        o = o * jnp.repeat(w1 / l, dh, axis=-1) \
            + o_h.astype(jnp.float32) * jnp.repeat(w2 / l, dh, axis=-1)
        return o, m + jnp.log(l)

    # fold the local block first (it holds the causal diagonal, so
    # lse starts finite), then rotate-then-fold — the final iteration
    # folds without a trailing (wasted) ppermute
    o0, lse0 = hop(kh, vh, q_off)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def step(i, loop_state):
        o, lse, k_cur, v_cur, off_cur = loop_state
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        # the arriving block's global offset travels WITH the block
        off_cur = jax.lax.ppermute(off_cur, axis_name, perm)
        o, lse = combine((o, lse), *hop(k_cur, v_cur, off_cur[0, 0]))
        return o, lse, k_cur, v_cur, off_cur

    o, _, _, _, _ = jax.lax.fori_loop(
        1, axis_size, step, (o0.astype(jnp.float32), lse0, kh, vh,
                             offs))
    assert o.shape == (b, h // pack, tq, dhp)
    return pa.unpack_heads(o.astype(q.dtype), pack, h)  # (B,Tq,H,Dh)


def ring_attention_block(q, k, v, seq_offsets=None,
                         axis_name: str = SEQ_AXIS,
                         causal: bool = False, dot_dtype=None,
                         block_k: int | None = None,
                         pallas_fold: bool = False,
                         pallas_interpret: bool = False,
                         pallas_block_q: int | None = None):
    """The per-device body (call under ``shard_map``): q/k/v are THIS
    device's sequence shards; K/V rotate the full ring.

    ``pallas_fold`` makes each hop a fused flash-kernel pass (the
    round-6 production TPU path — see :func:`_ring_kernel_fold`);
    ``pallas_interpret`` runs those kernels in interpret mode (the
    virtual-CPU-mesh testing lever) and ``pallas_block_q`` overrides
    the kernel's q tile.  Legality (tiling/dh) is the CALLER's
    job — :func:`sequence_sharded_attention` gates on the per-shard
    shapes and falls back to the scan fold.

    ``block_k`` composes the flash-style K/V-block fold INTO each ring
    step of the SCAN fold: the arriving (tq × tk_local) tile is folded
    sub-block by sub-block under ``jax.checkpoint``, so a device never
    materializes even its per-step local score tile — the single-chip
    ``local_attention_blocked`` memory behavior, per ring hop.
    Without it, large per-device T_local hits the same (tq, tk) HBM
    wall on every hop that the blocked form was built to remove
    (round-4 verdict item 6).  On the kernel fold, ``block_k`` is the
    kernel's K tile instead.  The scan fold remains the portable
    fallback (non-TPU backends, kernel-illegal shapes).

    ``seq_offsets`` (kernel fold only): this device's (1, 1) int32
    global row offset as a sequence-sharded operand — see
    :func:`_ring_kernel_fold` for why it cannot be ``axis_index``."""
    if pallas_fold:
        if seq_offsets is None:
            raise ValueError("the kernel fold needs the sharded "
                             "seq_offsets operand (see "
                             "sequence_sharded_attention)")
        return _ring_kernel_fold(q, k, v, seq_offsets, axis_name,
                                 causal, dot_dtype, pallas_block_q,
                                 block_k, pallas_interpret)
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    b, tq, h, dim = q.shape
    tk = k.shape[1]
    # block_k >= T_local degrades to the whole-tile fold below — only
    # a PARTIAL blocking that doesn't tile evenly is an error
    if block_k is not None and block_k < tk and tk % block_k:
        raise ValueError(f"T_local {tk} not divisible by "
                         f"block_k {block_k}")
    q_pos = my_idx * tq + jnp.arange(tq)            # global positions

    def fold_tile(state, k_t, v_t, src):
        """Fold the whole K/V tile that originated on device ``src``
        — one `_fold_block` when ``block_k`` is off, a checkpointed
        sub-block scan when on."""
        k_pos0 = src * tk
        if block_k is None or block_k >= tk:
            mask = _visibility(
                tq, tk,
                *((q_pos, k_pos0 + jnp.arange(tk)) if causal
                  else (None, None)))
            return _fold_block(state, q, k_t, v_t, mask,
                               dot_dtype=dot_dtype)
        nb = tk // block_k
        k_sub = jnp.moveaxis(
            k_t.reshape(b, nb, block_k, h, dim), 1, 0)
        v_sub = jnp.moveaxis(
            v_t.reshape(b, nb, block_k, h, dim), 1, 0)

        @functools.partial(jax.checkpoint, prevent_cse=False)
        def sub_fold(carry, blk):
            i, kk, vv = blk
            mask = _visibility(
                tq, block_k,
                *((q_pos, k_pos0 + i * block_k + jnp.arange(block_k))
                  if causal else (None, None)))
            return _fold_block(carry, q, kk, vv, mask,
                               dot_dtype=dot_dtype), None

        state, _ = jax.lax.scan(sub_fold, state,
                                (jnp.arange(nb), k_sub, v_sub))
        return state

    # accumulators: derived from q so they carry its sharded/varying
    # type under shard_map, but cast to f32 — attention statistics
    # accumulate across the whole ring in f32 even with bf16 q/k/v
    # (the repo-wide bf16-inputs/f32-accumulation convention)
    zero4 = (jnp.swapaxes(q, 1, 2) * 0.0).astype(jnp.float32)
    state = (zero4[..., 0] + _NEG_INF, zero4[..., 0], zero4)
    # fold the local block first, then rotate-then-fold — the final
    # iteration folds without a trailing (wasted) ppermute
    state = fold_tile(state, k, v, my_idx)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def step(i, loop_state):
        m, denom, acc, k_cur, v_cur = loop_state
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        src = (my_idx - i) % axis_size   # origin of the arriving block
        m, denom, acc = fold_tile((m, denom, acc), k_cur, v_cur, src)
        return m, denom, acc, k_cur, v_cur

    m, denom, acc, _, _ = jax.lax.fori_loop(
        1, axis_size, step, (*state, k, v))
    denom = jnp.where(denom == 0.0, 1.0, denom)  # fully masked rows
    out = (acc / denom[..., None]).astype(q.dtype)
    return jnp.transpose(out, (0, 2, 1, 3))      # → (B, Tq, H, D)


def ring_fold_choice(mesh, shape, axis_name: str = SEQ_AXIS,
                     block_k: int | None = None,
                     pallas_fold: bool = False,
                     pallas_block_q: int | None = None):
    """Resolve which fold the ring will actually run for a GLOBAL
    (B, T, H, Dh) shape: ``("pallas", bq, bk)`` when the kernel fold
    is requested AND the per-shard geometry is kernel-legal, else
    ``("scan", None, block_k)``.  One place for the unit gate, the
    entry below and the dryrun attestation to agree on."""
    from znicz_tpu.ops import pallas_attention as pa
    from znicz_tpu.parallel.mesh import kernel_shard_spec, \
        shard_shape, spec_divides

    spec, _ = kernel_shard_spec(mesh, 4, model_shard_dim=1,
                                model_axis=axis_name)
    if not pallas_fold or not spec_divides(mesh, shape, spec):
        return "scan", None, block_k
    _, t_local, h, dh = shard_shape(mesh, shape, spec)
    bq = min(pallas_block_q or pa.BLOCK_Q, t_local)
    bk = min(block_k or pa.BLOCK_K, t_local)
    pack = pa.head_pack_for(h, dh)
    if not pa.kernel_legal(t_local, t_local, dh * pack, bq, bk):
        return "scan", None, block_k
    return "pallas", bq, bk


def sequence_sharded_attention(mesh, q, k, v, causal: bool = False,
                               axis_name: str = SEQ_AXIS,
                               dot_dtype=None,
                               block_k: int | None = None,
                               pallas_fold: bool = False,
                               pallas_interpret: bool = False,
                               pallas_block_q: int | None = None):
    """Shard the time axis of q/k/v over ``mesh[axis_name]`` and run
    ring attention; returns output with the same sharding as q.

    ``pallas_fold=True`` requests the round-6 kernel fold (each hop a
    fused flash pass at its global offset); shapes the kernel's tiling
    cannot cover fall back to the scan fold silently — the same
    fallback philosophy as the unit gates.  ``pallas_interpret`` is
    the virtual-CPU-mesh lever (the REAL kernels, emulated).

    When the mesh also has a ``data`` axis, the BATCH dim shards over
    it — the ring runs per batch shard (the batch dim never enters the
    ring collectives), so data parallelism composes with sequence
    parallelism instead of being silently all-gathered away at the
    shard_map boundary."""
    from znicz_tpu.parallel.mesh import kernel_shard_spec

    # one spec convention for the ring and the mesh-native Pallas
    # kernels: batch rides the data axis, time (dim 1) rides the
    # named sequence/model axis
    spec, _ = kernel_shard_spec(mesh, 4, model_shard_dim=1,
                                model_axis=axis_name)
    fold, bq, bk = ring_fold_choice(
        mesh, q.shape, axis_name=axis_name, block_k=block_k,
        pallas_fold=pallas_fold, pallas_block_q=pallas_block_q)
    body = functools.partial(ring_attention_block,
                             axis_name=axis_name, causal=causal,
                             dot_dtype=dot_dtype, block_k=bk,
                             pallas_fold=(fold == "pallas"),
                             pallas_interpret=pallas_interpret,
                             pallas_block_q=bq)
    if fold == "pallas":
        from jax.sharding import PartitionSpec as P

        # per-device global row offsets as a SEQ-SHARDED operand (each
        # shard sees its own (1, 1) scalar) — axis_index would leave a
        # partition-id in the custom_vjp residuals, which the GSPMD
        # partitioner rejects at the shard_map boundary
        n_seq = mesh.shape[axis_name]
        t_local = q.shape[1] // n_seq
        offs = (jnp.arange(n_seq, dtype=jnp.int32)
                * t_local).reshape(n_seq, 1)
        # the opaque pallas_call (and its custom_vjp) has no
        # replication rule — check_vma off, as on the batch-sharded
        # flash path
        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(spec, spec, spec, P(axis_name, None)),
            out_specs=spec, check_vma=False)
        return fn(q, k, v, offs)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec)
    return fn(q, k, v)


def make_seq_mesh(n_devices: int | None = None):
    """A 1-D ``seq`` mesh over the local devices (tests use the
    virtual 8-CPU mesh)."""
    from jax.sharding import Mesh
    devices = jax.devices()
    n = n_devices or len(devices)
    return Mesh(np.array(devices[:n]), (SEQ_AXIS,))

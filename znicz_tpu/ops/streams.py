"""A residual path of n streams: manifold-constrained hyper-connections
(mHC, arXiv:2512.24880, over hyper-connections, arXiv:2409.19606; the
residual path of Xing4.0-29B-A4B, ``model_type`` xing4_0, PR 46).

The skip carries X ∈ R^{n×D} per token instead of one row of D.  Around
EACH sublayer F (a mixer, an MLP, an expert layer) three learned maps
of the stream itself decide what F reads and how its output is written
back:

.. code-block:: text

    x̃ = vec(X) / √(mean(vec(X)²) + norm_eps)        over all n·D, no gain
    z = x̃ φ                                          ``weights`` (n·D, 2n + n²):
                                                      [φ_pre | φ_post | φ_res]
    H_pre  = σ(α_pre · z_pre + b_pre)                (n,)
    H_post = 2 σ(α_post · z_post + b_post)           (n,)
    M⁰ = exp(clamp(α_res · mat(z_res) + b_res, ∓clamp))    (n, n)
    ``sinkhorn_iters`` times: M ← M / (rowsum M + ε), M ← M / (colsum M + ε)
    H_res = M                                        doubly stochastic to the
                                                      iteration's accuracy
    h   = Σ_j H_pre,j X_j                            READ  → (B, T, D)
    f   = F(h)                                       the EXISTING unit, with
                                                      ``pre_norm`` and
                                                      ``residual: false``
    X′_i = Σ_j H_res,ij X_j + H_post,i · f           WRITE → (B, n·D, T)

``maps_bias`` is [b_pre | b_post | b_res] (2n + n²,), ``maps_alpha``
(α_pre, α_post, α_res).  All of it in f32, the product x̃ φ at the
highest matmul precision (as the expert layer's router).

The stream is STORED POSITION-MINOR: a stream unit's n·D-wide array
is (B, n·D, T), not (B, T, n·D), and the maps it keeps are (B, n, T) and
(B, n, n, T).  The maps are a handful of numbers a token — n, n, n² —
and everything done to them (two sigmoids, exp, Sinkhorn's forty
normalisations) wants the TOKENS on the vector lanes: as (T, n, n) the
n² = 16 numbers of a token lie in a tile of 8 × 128, 1.6% of it.  The
compiler knows (compiled for a described v5e, it turned every (T, n·D)
stream into a position-minor COPY, 224 MB a unit at T 4,096, and the
step did not fit the chip); stored so, the mixes are sums of row blocks
scaled by a row of per-token weights, the statistics are sums down the
sublanes, x~ phi is phiᵀ X with T as the matmul's wide side — and the
one transpose a sublayer costs is of h and of f, D wide.  ``output``
of the READ and of the close, and ``input`` of the WRITE and of the
open, are (B, T, D) as every other unit's.

Four units, ONE family (``FAMILY`` = ``Streams`` in
``observe.op_scopes()``'s map), so that the graph stays a chain of
(B, T, ·) units and the sublayer's own code does not change:

- ``stream_open``  (:class:`StreamOpen`): (B, T, D) → (B, n·D, T), n copies;
- ``stream_read``  (:class:`StreamRead`): X → h; it also keeps H_post and
  H_res (``h_post``, ``h_res``) for its WRITE unit;
- ``stream_write`` (:class:`StreamWrite`): f → X′, reading X, H_post and
  H_res from ITS read unit — the nearest ``stream_read`` before it
  (``StandardWorkflow.link_forwards`` pairs them and refuses a table
  that does not, by index);
- ``stream_close`` (:class:`StreamClose`): (B, n·D, T) → (B, T, D), the sum.

The WRITE needs the stream from BEFORE the sublayer: one skip edge per
sublayer forward, and its cotangent joined on the way back.  Backward,
what is n·D wide is handed from one stream unit's GD to the one before
it as ``err_stream`` — an attribute that lives for one trace (or one
eager step), NOT a Vector: every Vector is a leaf of the step program
and holds its buffer for the whole step, and ten (B, n·D, T) f32
cotangents are 2.3 GB at T 4,096 × 14,336 that nothing reads after the
step.  The WRITE's GD gives its READ unit the skip edge's share
(``Hᵀ_res dX′`` and the two maps' cotangents, :meth:`StreamRead.take_skip`);
the READ's GD adds it to what returns through h and the maps.  What is
D wide (the sublayer's cotangents) stays in ``err_input`` Vectors, so
the sublayer's GD is linked as ever.

Both forwards are ``jax.checkpoint``-ed: their backward keeps X (a
leaf already) and f, and makes x̃ and the maps again — a (B, n·D, T)
residual per unit would be another 2.3 GB.

What Sinkhorn's iteration reached is kept ON THE DEVICE
(``stream_stats`` of the OPEN unit, which every READ folds into: the
worst |row sum − 1| and |column sum − 1| of H_res, the entries the
clamp touched, the applications) and read once per epoch
(:meth:`StreamOpen.on_epoch_ended`) into ``znicz_stream_maps``.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from znicz_tpu.memory import Vector
from znicz_tpu.ops.delta_net import GDGatedDeltaNet
from znicz_tpu.ops.nn_units import Forward, WeightlessGradientUnit

FAMILY = "Streams"

#: slots of ``stream_stats``
_ROW, _COL, _CLAMPED, _APPLIED = range(4)
#: b_res off its diagonal when a READ is drawn (its diagonal is 0):
#: H_res starts near the identity
RES_BIAS_OFF = -4.0


def _sigmoid(xp, a):
    return 1.0 / (1.0 + xp.exp(-a))


def sinkhorn(xp, m, iters: int, eps: float):
    """(B, n, n, T) positive → rows then columns normalised, ``iters``
    times (a loop in the program, not ``iters`` copies of its body);
    entry [i, j] of a token's matrix is ``m[:, i, j, t]``."""
    def once(m):
        m = m / (m.sum(axis=2, keepdims=True) + eps)
        return m / (m.sum(axis=1, keepdims=True) + eps)

    if xp is np:
        for _ in range(iters):
            m = once(m)
        return m
    return jax.lax.fori_loop(0, iters, lambda _, m: once(m), m)


def stream_maps(xp, x, phi, bias, alpha, n: int, norm_eps: float,
                iters: int, sink_eps: float, clamp: float):
    """(B, n·D, T) f32 → ``(H_pre (B, n, T), H_post (B, n, T), H_res
    (B, n, n, T), the raw logits of M⁰ (B, n, n, T))`` (module
    docstring)."""
    b, _, t = x.shape
    r = 1.0 / xp.sqrt((x * x).mean(axis=1, keepdims=True) + norm_eps)
    if xp is np:
        z = np.einsum("bkt,kc->bct", x, phi)
    else:
        z = jnp.einsum("bkt,kc->bct", x, phi,
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
    z = z * r                       # x̃ φ = r · (x φ): x̃ is never written
    bias = bias[:, None]
    h_pre = _sigmoid(xp, alpha[0] * z[:, :n] + bias[:n])
    h_post = 2.0 * _sigmoid(xp, alpha[1] * z[:, n:2 * n] + bias[n:2 * n])
    raw = (alpha[2] * z[:, 2 * n:] + bias[2 * n:]).reshape(b, n, n, t)
    h_res = sinkhorn(xp, xp.exp(xp.clip(raw, -clamp, clamp)), iters,
                     sink_eps)
    return h_pre, h_post, h_res, raw


def mix(h, x):
    """Σ_j h[:, …, j, :] · X_j over the n row blocks X_j of x
    (B, n·D, T): h (B, n, T) → (B, D, T); h (B, n, n, T) → (B, n·D, T),
    block i being Σ_j h[:, i, j] · X_j — n multiply-adds an element on
    the vector unit, each block scaled by a row of per-token weights."""
    n = h.shape[-2]
    d = x.shape[1] // n
    blocks = [x[:, j * d:(j + 1) * d] for j in range(n)]
    if h.ndim == 3:
        return sum(h[:, j, None] * blocks[j] for j in range(n))
    xp = np if isinstance(x, np.ndarray) else jnp
    return xp.concatenate(
        [sum(h[:, i, j, None] * blocks[j] for j in range(n))
         for i in range(n)], axis=1)


def rows_of(a):
    """(B, T, D) ↔ (B, D, T): the one transpose between the streams
    and a sublayer, D wide."""
    return a.swapaxes(1, 2)


class _Stream(Forward):
    """What the four units share: ``n_streams`` and no bias."""

    FAMILY = FAMILY
    EXPORT_PARAMS: tuple = ()

    def __init__(self, workflow, n_streams: int = 4, name=None,
                 **kwargs) -> None:
        kwargs["include_bias"] = False
        super().__init__(workflow, name=name, **kwargs)
        self.n_streams = int(n_streams)
        if self.n_streams < 1:
            raise ValueError(f"{self}: n_streams {n_streams}: a residual "
                             f"path has 1 stream or more")

    def unserved(self) -> str | None:
        return super().unserved() or (
            f"is a unit of a residual path of {self.n_streams} streams "
            f"(stream_open / stream_read / stream_write / stream_close); "
            f"serving carries one residual row a token — the n-stream "
            f"state, its maps and Sinkhorn's iterations exist on the "
            f"training path only (ROADMAP R1, serving half)")

    def _input_shape(self, wide: bool) -> tuple:
        """(B, T, D) of the model from this unit's input: the streams
        ((B, n·D, T), ``wide``) or a sublayer's rows (B, T, D)."""
        if self.input is None or not self.input:
            raise AttributeError(f"{self}: input not linked yet")
        if len(self.input.shape) != 3:
            raise ValueError(f"{self}: expected a 3-d input, got "
                             f"{self.input.shape}")
        if not wide:
            return tuple(self.input.shape)
        b, width, t = self.input.shape
        if width % self.n_streams:
            raise ValueError(
                f"{self}: an input of {width} rows is not "
                f"{self.n_streams} streams of one width")
        return b, t, width // self.n_streams

    def _allocate(self, shape: tuple, *more: Vector) -> None:
        self.output.reset(np.zeros(shape, dtype=self.output_store_dtype))
        self.init_vectors(self.input, self.output, *more)


class StreamOpen(_Stream):
    """(B, T, D) → (B, n·D, T): the row copied into the n streams.  It
    holds the family's ``stream_stats`` (module docstring)."""

    def __init__(self, workflow, **kwargs) -> None:
        super().__init__(workflow, **kwargs)
        self.stream_stats = Vector(name=f"{self.name}.stream_stats")
        #: the READ units that fold into ``stream_stats``
        self.reads = 0

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device=device, **kwargs)
        b, t, d = self._input_shape(wide=False)
        if not self.stream_stats:
            self.stream_stats.reset(np.zeros(4, np.float32))
        self._allocate((b, self.n_streams * d, t), self.stream_stats)

    def numpy_run(self) -> None:
        self.input.map_read()
        self.output.map_invalidate()
        self.output.mem[...] = np.tile(
            rows_of(self.input.mem.astype(np.float32)),
            (1, self.n_streams, 1))

    def xla_run(self) -> None:
        self.output.devmem = jnp.tile(
            rows_of(self.input.devmem.astype(jnp.float32)),
            (1, self.n_streams, 1))

    def on_epoch_ended(self) -> None:
        """Read the device totals once, publish them, start over."""
        from znicz_tpu.observe import metrics as obs_metrics
        stats = self.stream_stats
        if not stats or not self.reads:
            return
        stats.map_read()
        seen = np.asarray(stats.mem, np.float64)
        if obs_metrics.enabled() and seen[_APPLIED]:
            steps = seen[_APPLIED] / self.reads
            for stat, value in (("row_gap", seen[_ROW]),
                                ("col_gap", seen[_COL]),
                                ("clamped", seen[_CLAMPED] / steps),
                                ("sublayers", self.reads),
                                ("streams", self.n_streams)):
                obs_metrics.stream_maps(self.name, stat).set(value)
        stats.map_invalidate()
        stats.mem[...] = 0.0      # uploaded on the next region fire


class StreamClose(_Stream):
    """(B, n·D, T) → (B, T, D): the streams summed into one."""

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device=device, **kwargs)
        self._allocate(self._input_shape(wide=True))

    def _sum(self, x):
        b, t, d = self.output.shape
        return rows_of(x.reshape(b, self.n_streams, d, t).sum(axis=1))

    def numpy_run(self) -> None:
        self.input.map_read()
        self.output.map_invalidate()
        self.output.mem[...] = self._sum(self.input.mem.astype(np.float32))

    def xla_run(self) -> None:
        self.output.devmem = self._sum(
            self.input.devmem.astype(jnp.float32))


class StreamRead(_Stream):
    """X → h = Σ_j H_pre,j X_j, and the two maps its WRITE unit takes
    (module docstring)."""

    EXPORT_PARAMS = ("weights", "maps_bias", "maps_alpha")

    def __init__(self, workflow, sinkhorn_iters: int = 20,
                 sinkhorn_eps: float = 1e-6, clamp: float = 30.0,
                 norm_eps: float = 1e-6, alpha_init: float = 0.01,
                 **kwargs) -> None:
        kwargs.setdefault("weights_filling", "xavier")
        super().__init__(workflow, **kwargs)
        self.sinkhorn_iters = int(sinkhorn_iters)
        self.sinkhorn_eps = float(sinkhorn_eps)
        self.clamp = float(clamp)
        self.norm_eps = float(norm_eps)
        #: the three α's first value
        self.alpha_init = float(alpha_init)
        self.maps_bias = Vector(name=f"{self.name}.maps_bias")
        self.maps_alpha = Vector(name=f"{self.name}.maps_alpha")
        self.h_post = Vector(name=f"{self.name}.h_post", batch_major=True)
        self.h_res = Vector(name=f"{self.name}.h_res", batch_major=True)
        #: the OPEN unit's totals (linked by ``link_forwards``)
        self.stream_stats: Vector | None = None
        #: its WRITE unit (``link_forwards``); a READ without one is
        #: refused there
        self.write_unit = None
        self._traced_vjp = None
        #: the skip edge's cotangents, from the WRITE's GD to this
        #: unit's (:meth:`take_skip`): one trace or one eager step
        self._skip = None

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device=device, **kwargs)
        b, t, d = self._input_shape(wide=True)
        n = self.n_streams
        if not self.weights:
            self.weights.reset(self.fill_array(
                (n * d, 2 * n + n * n), self.weights_filling,
                self.weights_stddev, fan_in=n * d))
        if not self.maps_bias:
            # H_pre sums to 1 over the streams, H_post is 1, H_res is
            # near the identity: the block starts near x + F(norm(x̄))
            pre = np.full(n, -np.log(max(n - 1, 1)), np.float32)
            res = np.where(np.eye(n, dtype=bool), 0.0, RES_BIAS_OFF)
            self.maps_bias.reset(np.concatenate(
                [pre, np.zeros(n), res.reshape(-1)]).astype(np.float32))
        if not self.maps_alpha:
            self.maps_alpha.reset(np.full(3, self.alpha_init, np.float32))
        for vec, shape in ((self.h_post, (b, n, t)),
                           (self.h_res, (b, n, n, t))):
            vec.reset(np.zeros(shape, np.float32))
        self._allocate((b, t, d), self.weights, self.maps_bias,
                       self.maps_alpha, self.h_post, self.h_res)

    # -- pure forward ---------------------------------------------------
    def _maps(self, xp, rows, phi, bias, alpha):
        return stream_maps(xp, rows, phi, bias, alpha, self.n_streams,
                           self.norm_eps, self.sinkhorn_iters,
                           self.sinkhorn_eps, self.clamp)

    def forward_args(self) -> tuple:
        return (self.input.devmem,) + tuple(
            getattr(self, attr).devmem for attr in self.EXPORT_PARAMS)

    def xla_forward(self, x, phi, bias, alpha):
        """``((h, H_post, H_res), what Sinkhorn reached)``."""
        @jax.checkpoint
        def read(x, phi, bias, alpha):
            x = x.astype(jnp.float32)
            h_pre, h_post, h_res, raw = self._maps(jnp, x, phi, bias,
                                                   alpha)
            return (rows_of(mix(h_pre, x)), h_post, h_res), raw

        outs, raw = read(x, phi, bias, alpha)
        h_res = jax.lax.stop_gradient(outs[2])
        stats = jnp.stack([
            jnp.abs(h_res.sum(axis=2) - 1.0).max(),
            jnp.abs(h_res.sum(axis=1) - 1.0).max(),
            (jnp.abs(jax.lax.stop_gradient(raw)) > self.clamp).sum()
            .astype(jnp.float32)])
        return outs, stats

    def xla_run(self) -> None:
        args = self.forward_args()
        if not self.output._tracing:
            self._traced_vjp = None
            outs, stats = self.xla_forward(*args)
        else:
            outs, self._traced_vjp, stats = jax.vjp(
                self.xla_forward, *args, has_aux=True)
        self.output.devmem, self.h_post.devmem, self.h_res.devmem = outs
        if self.stream_stats:
            seen = self.stream_stats.devmem
            self.stream_stats.devmem = jnp.stack([
                jnp.maximum(seen[_ROW], stats[0]),
                jnp.maximum(seen[_COL], stats[1]),
                seen[_CLAMPED] + stats[2], seen[_APPLIED] + 1.0])

    def take_skip(self):
        """The skip edge's cotangents ``(dX, dH_post, dH_res)`` that
        the WRITE's GD left here."""
        skip, self._skip = self._skip, None
        if skip is None:
            raise RuntimeError(
                f"{self}: no skip cotangent from {self.write_unit}'s GD: "
                f"the stream units' backward runs last unit first, in "
                f"one trace or one eager step")
        return skip

    def forget_trace(self) -> None:
        self._traced_vjp = self._skip = None

    # -- numpy oracle ---------------------------------------------------
    def numpy_run(self) -> None:
        for attr in ("input",) + self.EXPORT_PARAMS:
            getattr(self, attr).map_read()
        x = self.input.mem.astype(np.float32)
        h_pre, h_post, h_res, raw = self._maps(
            np, x, self.weights.mem, self.maps_bias.mem,
            self.maps_alpha.mem)
        for vec, value in ((self.output, rows_of(mix(h_pre, x))),
                           (self.h_post, h_post), (self.h_res, h_res)):
            vec.map_invalidate()
            vec.mem[...] = value
        if self.stream_stats:
            self.stream_stats.map_write()
            seen = self.stream_stats.mem
            seen[_ROW] = max(seen[_ROW], np.abs(h_res.sum(2) - 1).max())
            seen[_COL] = max(seen[_COL], np.abs(h_res.sum(1) - 1).max())
            seen[_CLAMPED] += (np.abs(raw) > self.clamp).sum()
            seen[_APPLIED] += 1.0


class StreamWrite(_Stream):
    """f → X′_i = Σ_j H_res,ij X_j + H_post,i f, with X, H_post and
    H_res its READ unit's (module docstring)."""

    def __init__(self, workflow, **kwargs) -> None:
        super().__init__(workflow, **kwargs)
        #: the READ unit before the sublayer (``link_forwards``)
        self.read_unit: StreamRead | None = None
        self._traced_vjp = None

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device=device, **kwargs)
        read = self.read_unit
        if read is None:
            raise ValueError(f"{self}: no stream_read unit before it")
        b, t, d = self._input_shape(wide=False)
        n = self.n_streams
        if read.n_streams != n or tuple(read.input.shape) != (b, n * d, t):
            raise ValueError(
                f"{self}: writes {n} streams of {d} into {read}'s "
                f"{read.n_streams} streams, input {read.input.shape}")
        self._allocate((b, n * d, t))

    def region_vectors(self):
        vecs = super().region_vectors()
        read = self.read_unit
        return vecs + [v for v in (read.input, read.h_post, read.h_res)
                       if all(v is not seen for seen in vecs)]

    def forward_args(self) -> tuple:
        read = self.read_unit
        return (self.input.devmem, read.input.devmem, read.h_post.devmem,
                read.h_res.devmem)

    @staticmethod
    def write(xp, f, x, h_post, h_res):
        """f (B, T, D), x (B, n·D, T) and the READ's maps → X′
        (B, n·D, T)."""
        f = rows_of(f)
        return mix(h_res, x) + xp.concatenate(
            [h_post[:, i, None] * f for i in range(h_post.shape[1])],
            axis=1)

    @staticmethod
    @jax.checkpoint
    def xla_forward(f, x, h_post, h_res):
        return StreamWrite.write(jnp, f.astype(jnp.float32),
                                 x.astype(jnp.float32), h_post, h_res)

    def xla_run(self) -> None:
        args = self.forward_args()
        if not self.output._tracing:
            self._traced_vjp = None
            self.output.devmem = self.xla_forward(*args)
            return
        self.output.devmem, self._traced_vjp = jax.vjp(
            self.xla_forward, *args)

    def forget_trace(self) -> None:
        self._traced_vjp = None

    def numpy_run(self) -> None:
        read = self.read_unit
        for vec in (self.input, read.input, read.h_post, read.h_res):
            vec.map_read()
        out = self.write(np, self.input.mem.astype(np.float32),
                         read.input.mem.astype(np.float32),
                         read.h_post.mem, read.h_res.mem)
        self.output.map_invalidate()
        self.output.mem[...] = out.reshape(self.output.shape)


# ----------------------------------------------------------------------
# the backward units
# ----------------------------------------------------------------------
class _WideCotangent:
    """A stream GD whose forward's INPUT is the stream: its input's
    cotangent is ``err_stream`` (module docstring), so the base
    allocates no ``err_input`` — and does not take a weightless unit
    without one for a unit without effect."""

    #: hands ``err_stream`` to the stream GD before it
    STREAM_OUT = True

    def initialize(self, device=None, **kwargs) -> None:
        gate, wanted = self.gate_skip, self.need_err_input
        self.need_err_input = False
        try:
            super().initialize(device=device, **kwargs)
        finally:
            self.need_err_input, self.gate_skip = wanted, gate


class _StreamGD:
    FAMILY = FAMILY
    #: the (B, n·D, T) cotangent of this unit's input, for one trace
    #: or one eager step (a jax array, a tracer or an ndarray)
    err_stream = None
    #: the stream GD after this one, whose ``err_stream`` is this
    #: unit's output's cotangent (``StandardWorkflow.link_gds``)
    stream_gd = None

    def forget_trace(self) -> None:
        self.err_stream = None

    def _stream_in(self):
        """The cotangent of this unit's (B, n·D, T) output."""
        source = self.stream_gd
        if source is None or source.err_stream is None:
            raise RuntimeError(
                f"{self}: no stream cotangent from {source}: the stream "
                f"units' backward runs last unit first, in one trace or "
                f"one eager step")
        return source.err_stream


class GDStreamClose(_WideCotangent, _StreamGD, WeightlessGradientUnit):
    """The sum's backward: the cotangent copied to every stream."""

    MATCHES = (StreamClose,)

    def numpy_run(self) -> None:
        self.err_output.map_read()
        self.err_stream = np.tile(
            rows_of(self.err_output.mem.astype(np.float32)),
            (1, self.forward_unit.n_streams, 1))

    def xla_run(self) -> None:
        self.err_stream = jnp.tile(
            rows_of(self.err_output.devmem.astype(jnp.float32)),
            (1, self.forward_unit.n_streams, 1))


class GDStreamOpen(_StreamGD, WeightlessGradientUnit):
    """The copies' backward: the streams' cotangents summed."""

    MATCHES = (StreamOpen,)
    #: takes ``err_stream`` from the stream GD after it
    STREAM_IN = True

    def _sum(self, err):
        b, t, d = self.input.shape
        return rows_of(err.reshape(b, -1, d, t).sum(axis=1))

    def numpy_run(self) -> None:
        if self.need_err_input:
            self.err_input.map_invalidate()
            self.err_input.mem[...] = self._sum(self._stream_in())

    def xla_run(self) -> None:
        if self.need_err_input:
            self.err_input.devmem = self._sum(self._stream_in())


class GDStreamWrite(_StreamGD, WeightlessGradientUnit):
    """The WRITE's backward: df to the sublayer's GD (``err_input``),
    the skip edge's share to its READ unit."""

    MATCHES = (StreamWrite,)
    STREAM_IN = True

    def numpy_run(self) -> None:
        fwd = self.forward_unit
        read = fwd.read_unit
        for vec in (self.input, read.input, read.h_post, read.h_res):
            vec.map_read()
        b, t, d = self.input.shape
        n = fwd.n_streams
        err = np.asarray(self._stream_in(), np.float32)
        by_stream = err.reshape(b, n, d, t)
        f = rows_of(self.input.mem.astype(np.float32))
        read._skip = (
            mix(read.h_res.mem.swapaxes(1, 2), err),
            (by_stream * f[:, None]).sum(axis=2),
            np.einsum("bidt,bjdt->bijt", by_stream,
                      read.input.mem.astype(np.float32).reshape(
                          b, n, d, t)))
        self.err_input.map_invalidate()
        self.err_input.mem[...] = rows_of(mix(read.h_post.mem, err))

    def xla_run(self) -> None:
        fwd = self.forward_unit
        vjp = fwd._traced_vjp if fwd.output._tracing else None
        fwd._traced_vjp = None
        if vjp is None:
            vjp = jax.vjp(fwd.xla_forward, *fwd.forward_args())[1]
        df, *skip = vjp(self._stream_in().astype(jnp.float32))
        fwd.read_unit._skip = tuple(skip)
        self.err_input.devmem = df


class GDStreamRead(_WideCotangent, _StreamGD, GDGatedDeltaNet):
    """The READ's backward: the forward's pullback with (dh, dH_post,
    dH_res) — the last two from its WRITE's GD — plus the skip edge's
    dX; φ through the base's update rule, b and α beside it."""

    MATCHES = (StreamRead,)
    EXTRA = StreamRead.EXPORT_PARAMS[1:]
    HAS_AUX = True

    def _grads(self, xp, pullback):
        fwd = self.forward_unit
        skip, d_post, d_res = fwd.take_skip()
        err = self.err_output.mem if xp is np else self.err_output.devmem
        gx, *grads = pullback((xp.asarray(err, xp.float32),
                               xp.asarray(d_post, xp.float32),
                               xp.asarray(d_res, xp.float32)))
        self.err_stream = gx + skip
        return grads

    def xla_run(self) -> None:
        g_own, *g_extra = self._grads(jnp, self._pullback())
        self._apply_weights_xla(g_own)
        grads = dict(zip(self.EXTRA, g_extra))
        for attr, param, acc in self._extra_pairs():
            self._apply_weights_xla(grads[attr], vec=param, acc_vec=acc)

    def numpy_run(self) -> None:
        fwd = self.forward_unit
        for vec in (self.err_output, self.input):
            vec.map_read()
        self.weights.map_write()
        for _, param, _ in self._extra_pairs():
            param.map_write()
        args = (self.input.mem.astype(np.float32),) + tuple(
            getattr(fwd, attr).mem for attr in fwd.EXPORT_PARAMS)
        if self._host_pullback is None:   # one host program, built once
            self._host_pullback = jax.jit(
                lambda cts, *args: jax.vjp(
                    fwd.xla_forward, *args, has_aux=True)[1](cts))
        with jax.default_matmul_precision("highest"):
            g_own, *g_extra = (np.asarray(g) for g in self._grads(
                np, lambda cts: self._host_pullback(
                    tuple(jnp.asarray(c) for c in cts), *args)))
        self.err_stream = np.asarray(self.err_stream)
        self._apply_weights_np(g_own)
        grads = dict(zip(self.EXTRA, g_extra))
        for attr, param, acc in self._extra_pairs():
            self._apply_weights_np(grads[attr], vec=param, acc_vec=acc)

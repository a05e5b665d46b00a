"""Plain reference of the ``xing4_0_29b_a4b`` configuration
(XingChen-AGI Xing4.0-29B-A4B, ``model_type`` xing4_0): token embedding
→ the row copied into n residual streams → N × (READ → pre-norm latent
attention → WRITE, READ → pre-norm feed-forward → WRITE) → the streams
summed → final RMSNorm → untied head, softmax at every position — in
float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
no kernels: Sinkhorn's iteration as a Python loop, the latent attention
with K assembled in full one block of query rows at a time, a loop over
the experts with a mask.  Independent of the code under test: it reads
only the layer table and the parameters, keyed as a bundle is
(``layer<i>_weights``, ``_maps_bias``, ``_maps_alpha``, ``_weights_q_up``,
``_gain_q_latent``, ``_weights_kv_up``, ``_gain_latent``, ``_weights_out``,
``_gain_norm``, ``_weights_gate``, ``_up``, ``_down``, ``_weights_shared_*``).
Run it on the host's CPU device when the chip is full
(``jax.default_device``).

The layer equations (a token's stream X of n rows of D; eps 1e-6):

.. code-block:: text

    stream_open    X_j = e for j < n                       (the embedding's row)
    stream_read    (manifold-constrained hyper-connections, arXiv:2512.24880,
                   over arXiv:2409.19606)
      x~ = vec(X) / sqrt(mean(vec(X)^2) + eps)             over all n D, no gain
      z = x~ phi                     ``weights`` (n D, 2n + n^2) = [pre|post|res]
      H_pre  = sigmoid(a_pre z_pre + b_pre)                (n,)
      H_post = 2 sigmoid(a_post z_post + b_post)           (n,)
      M = exp(clamp(a_res mat(z_res) + b_res, -30, 30))    (n, n)
      20 times: M = M / (rowsum M + 1e-6); M = M / (colsum M + 1e-6)
      H_res = M ;  h = sum_j H_pre,j X_j                   the unit's output
    the sublayer   f = F(RMSNorm_gain(h))                  no skip of its own
    stream_write   X'_i = sum_j H_res,ij X_j + H_post,i f  with its READ's maps
    stream_close   y = sum_j X_j

    latent_attention (DeepSeek-V2's MLA, arXiv:2405.04434 section 2.1, WITH
    the query latent)
      [c_q | c | k_r] = m W ; c_q = RMSNorm(c_q) ; c = RMSNorm(c)
      [q_nope | q_rope] = c_q W_uq          all heads' q_nope, then q_rope
      [k_nope | v] = c W_up                 all heads' k_nope, then all v
      q_rope and the ONE k_r rotated with YaRN's blended frequencies,
      cos and sin NOT scaled (mscale = mscale_all_dim)
      s_h = (q_nope,h . k_nope,h + q_rope,h . k_r) * score_scale, causal
            score_scale = (nope + rope)^-1/2 (0.1 ln factor + 1)^2
      y = concat_h(softmax(s_h) v_h) W_o

    moe (DeepSeek-V3's router, arXiv:2412.19437 section 2.1.2, no groups)
      s = sigmoid(m W_r) ; top = the top_k largest of s + b
      w_e = routed_scale * s_e / sum_{top} s          from s, never from s + b
      y = Shared(m) + sum_{e in top and held} w_e Expert_e(m)

Departures from the published description, each a choice of LAYOUT or
of notation, none of arithmetic:

- the program stores the n-stream state position-minor, (B, n D, T);
  this file computes it as (B, T, n D) and hands the outputs of
  ``stream_open`` and ``stream_write`` over with the last two axes
  swapped, so that the comparison is entry by entry;
- the columns of W_uq (all heads' q_nope, then all heads' q_rope) and
  of W_up (all k_nope, then all v) stand side by side by PART, the
  published ones by head, and the two down-projections are ONE matrix
  [W_dq | W_dkv]: a fixed permutation of columns;
- the published rotation is interleaved: pairs (2i, 2i + 1) rotate by
  angle i.  :func:`rope_interleaved` does exactly that, on the rotary
  columns taken in the order ``pairs`` = (0, r/2, 1, r/2 + 1, …) — the
  fixed permutation by which the half-split convention of the code
  under test differs; it is applied to q_rope and to k_r alike, so
  every score q . k is the one the published order gives
  (``tests/test_xing_reference.py`` shows both);
- depth, experts held, the vocabulary slice, momentum SGD, random
  weights, no multi-token-prediction module: the configuration's file.

``held`` is the set of experts whose slabs exist here (one chip's share
of an expert-parallel deployment); the shares' routed parts plus the
shared expert once add up to the uncut layer.  The choice of experts is
piecewise constant: ``routing`` — the reference's own, or handed in —
is a constant of the differentiated function, and so is the bias b.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512

#: ``None``: plain float32.  A dtype: every matmul's two inputs are
#: rounded to it first (products and sums stay float32) — how a limit
#: of the comparison is shown to have teeth
_INPUTS = None


class matmul_inputs:
    """``with matmul_inputs(jnp.float8_e4m3fn): forward(...)``."""

    def __init__(self, dtype) -> None:
        self.dtype = dtype

    def __enter__(self):
        global _INPUTS
        self.old, _INPUTS = _INPUTS, self.dtype

    def __exit__(self, *exc):
        global _INPUTS
        _INPUTS = self.old


def _r(a):
    return a if _INPUTS is None else a.astype(_INPUTS).astype(jnp.float32)


def mm(a, b):
    return _r(a) @ _r(b)


def rms_norm(x, gain, eps: float):
    return gain * x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _param(p: dict, i: int, name: str):
    return jnp.asarray(p[f"layer{i}_{name}"], jnp.float32)


def _eps(spec: dict) -> float:
    return float(spec.get("norm_eps", 1e-5))


def _normed(x, p: dict, i: int, spec: dict):
    return rms_norm(x, _param(p, i, "gain_norm"), _eps(spec)) \
        if spec.get("pre_norm") else x


# ----------------------------------------------------------------------
# the residual streams
# ----------------------------------------------------------------------
def stream_norm(rows, eps: float):
    """x~: (N, n D) over all n D, no gain."""
    return rows * jax.lax.rsqrt(
        jnp.mean(rows * rows, axis=-1, keepdims=True) + eps)


def sinkhorn(m, iters: int, eps: float):
    for _ in range(iters):
        m = m / (m.sum(axis=-1, keepdims=True) + eps)      # rows
        m = m / (m.sum(axis=-2, keepdims=True) + eps)      # columns
    return m


def post_map(logits):
    return 2.0 * jax.nn.sigmoid(logits)


def stream_maps(x, p: dict, i: int, spec: dict):
    """(B, T, n D) → H_pre (B, T, n), H_post (B, T, n), H_res (B, T, n, n);
    f32 at the highest precision whatever ``matmul_inputs`` says (as the
    router)."""
    n = int(spec["n_streams"])
    z = stream_norm(x, float(spec.get("norm_eps", 1e-6))) \
        @ _param(p, i, "weights")
    bias, alpha = _param(p, i, "maps_bias"), _param(p, i, "maps_alpha")
    clamp = float(spec.get("clamp", 30.0))
    h_pre = jax.nn.sigmoid(alpha[0] * z[..., :n] + bias[:n])
    h_post = post_map(alpha[1] * z[..., n:2 * n] + bias[n:2 * n])
    logits = (alpha[2] * z[..., 2 * n:] + bias[2 * n:]).reshape(
        z.shape[:-1] + (n, n))
    h_res = sinkhorn(jnp.exp(jnp.clip(logits, -clamp, clamp)),
                     int(spec.get("sinkhorn_iters", 20)),
                     float(spec.get("sinkhorn_eps", 1e-6)))
    return h_pre, h_post, h_res


def streams_of(x, n: int):
    return x.reshape(x.shape[:-1] + (n, x.shape[-1] // n))


# ----------------------------------------------------------------------
# latent attention with a query latent
# ----------------------------------------------------------------------
def pairs(rot: int) -> np.ndarray:
    """The rotary columns in the order whose neighbours (2i, 2i + 1)
    are the half-split convention's partners (i, i + rot/2)."""
    return np.arange(rot).reshape(2, rot // 2).T.reshape(rot)


def yarn_inv_freq(rot: int, theta: float, yarn: dict | None) -> np.ndarray:
    """rot/2 inverse frequencies; with ``yarn`` (arXiv:2309.00071) the
    plain ones and the same / factor, blended over a linear ramp
    between the dims that turn beta_fast and beta_slow times over the
    original context."""
    plain = np.asarray([theta ** (-2.0 * i / rot)
                        for i in range(rot // 2)], np.float64)
    if not yarn:
        return plain
    factor = float(yarn["factor"])
    original = float(yarn["original_max_position_embeddings"])

    def dim_of(turns: float) -> float:
        return rot * np.log(original / (turns * 2 * np.pi)) \
            / (2 * np.log(theta))

    low = max(np.floor(dim_of(float(yarn.get("beta_fast", 32)))), 0)
    high = min(np.ceil(dim_of(float(yarn.get("beta_slow", 1)))), rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rot // 2) - low) / (high - low), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def rope_interleaved(x, theta: float, yarn: dict | None = None):
    """(B, T, H, r) rotated as published: columns (2i, 2i + 1) are a
    pair, turned by pos · inv_freq_i; cos and sin are not scaled."""
    t, rot = x.shape[1], x.shape[-1]
    angle = np.arange(t, dtype=np.float64)[:, None] \
        * yarn_inv_freq(rot, theta, yarn)[None, :]
    cos = jnp.asarray(np.cos(angle), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(angle), jnp.float32)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def query_latent(c_q, gain, eps: float):
    return rms_norm(c_q, gain, eps)


def score_scale(spec: dict) -> float:
    """The table's, which for this family is (nope + rope)^-1/2 times
    the square of YaRN's 0.1 ln(factor) + 1."""
    given = spec.get("score_scale")
    return float(given) if given is not None else \
        (int(spec["qk_nope"]) + int(spec["qk_rope"])) ** -0.5


def latent_mixer(m, p: dict, i: int, spec: dict):
    b, t, _ = m.shape
    h = int(spec["n_heads"])
    latent, nope, rope, dv = (int(spec[key]) for key in (
        "kv_latent", "qk_nope", "qk_rope", "v_head_dim"))
    if not spec.get("causal") or spec.get("head_gate"):
        raise ValueError("reference/xing: attention is causal, no gate")
    theta, yarn = float(spec["rope"]["theta"]), spec["rope"].get("yarn")
    if yarn and float(yarn.get("attention_factor", 0)) != 1.0:
        raise ValueError("reference/xing: cos and sin are not scaled "
                         "(attention_factor 1); the scores are")
    proj = mm(m, _param(p, i, "weights"))
    at, wide = h * nope, h * (nope + rope)
    if spec.get("q_latent"):
        below = int(spec["q_latent"])
        q = mm(query_latent(proj[..., :below],
                            _param(p, i, "gain_q_latent"), _eps(spec)),
               _param(p, i, "weights_q_up"))
    else:
        below, q = wide, proj[..., :wide]
    q_nope = q[..., :at].reshape(b, t, h, nope)
    q_rope = q[..., at:wide].reshape(b, t, h, rope)
    c = rms_norm(proj[..., below:below + latent],
                 _param(p, i, "gain_latent"), _eps(spec))
    k_r = proj[..., None, -rope:]                       # (B, T, 1, r)
    up = mm(c, _param(p, i, "weights_kv_up"))
    k_nope = up[..., :at].reshape(b, t, h, nope)
    v = up[..., at:].reshape(b, t, h, dv)
    order = pairs(rope)                  # module docstring: departures
    q_rope = rope_interleaved(q_rope[..., order], theta, yarn)
    k_r = rope_interleaved(k_r[..., order], theta, yarn)
    # K assembled in full: the shared rotary key repeated per head
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_r, (b, t, h, rope))],
                        axis=-1)
    block = min(QUERY_BLOCK, t)
    cols = np.arange(t)[None, :]
    scale, out = score_scale(spec), []
    for lo in range(0, t, block):
        hi = min(lo + block, t)
        keep = jnp.asarray(np.arange(lo, hi)[:, None] >= cols)
        s = jnp.einsum("bqhd,bkhd->bhqk", _r(q[:, lo:hi]), _r(k)) * scale
        s = jnp.where(keep, s, -jnp.inf)
        out.append(jnp.einsum("bhqk,bkhd->bqhd",
                              _r(jax.nn.softmax(s, axis=-1)), _r(v)))
    o = jnp.concatenate(out, axis=1)
    return mm(o.reshape(b, t, h * dv), _param(p, i, "weights_out"))


# ----------------------------------------------------------------------
# the feed-forward blocks
# ----------------------------------------------------------------------
def gated(m, w_gate, w_up, w_down):
    return mm(jax.nn.silu(mm(m, w_gate)) * mm(m, w_up), w_down)


def route(m, p: dict, i: int):
    """Router logits and scores of (N, D) rows (float32 in every
    configuration: ``matmul_inputs`` does not reach it)."""
    logits = m @ _param(p, i, "weights")
    return logits, jax.nn.sigmoid(logits)


def top_k(scores, k: int) -> np.ndarray:
    """(N, k) indices, the largest first, ties to the lower index."""
    return np.argsort(-np.asarray(scores), axis=-1, kind="stable")[:, :k]


def choose(scores, bias, spec: dict) -> np.ndarray:
    """The experts chosen, (N, top_k): the largest s + b, no groups."""
    if spec.get("groups"):
        raise ValueError("reference/xing: the router has no group limit")
    return top_k(np.asarray(scores) + (0.0 if bias is None
                                       else np.asarray(bias)),
                 int(spec["top_k"]))


def routed_scale(spec: dict) -> float:
    return float(spec.get("routed_scale", 1.0))


def moe_block(m3, p: dict, i: int, spec: dict, chosen=None, held=None,
              bias=None):
    """``(f, logits, chosen)`` of the NORMED rows (B, T, D); ``chosen``
    (N, k) names the experts to use (the reference's own choice when
    ``None``); ``held`` the experts whose slabs ``p`` holds, in the
    slabs' order; ``bias`` the selection bias."""
    b, t, d = m3.shape
    n_tok, experts = b * t, int(spec["n_experts"])
    k = int(spec["top_k"])
    if spec.get("score") != "sigmoid" or not spec.get("norm_topk"):
        raise ValueError("reference/xing: experts are scored by a "
                         "sigmoid, normalised over the chosen")
    if held is None:
        held = spec.get("held")
    held = list(range(experts)) if held is None else sorted(held)
    m = m3.reshape(n_tok, d)
    logits, scores = route(m, p, i)
    if chosen is None:
        chosen = choose(scores, bias if spec.get("select_bias") else None,
                        spec)
    chosen = np.asarray(chosen).reshape(n_tok, k)
    # the weights from s, never from s + b
    weight = jnp.take_along_axis(scores, jnp.asarray(chosen), axis=-1)
    weight = weight / weight.sum(axis=-1, keepdims=True) \
        * routed_scale(spec)
    w_gate, w_up, w_down = (_param(p, i, f"weights_{name}")
                            for name in ("gate", "up", "down"))
    rows_per_expert = [(chosen == e).sum() for e in held]
    cap = -(-int(max(rows_per_expert + [1])) // 128) * 128  # one shape
    f = jnp.zeros((n_tok, d), jnp.float32)
    for slot, e in enumerate(held):        # the mask: chosen == e
        rows, slots = np.nonzero(chosen == e)
        real = np.arange(cap) < len(rows)
        rows = np.concatenate([rows, np.zeros(cap - len(rows), np.int64)])
        slots = np.concatenate([slots, np.zeros(cap - len(slots),
                                                np.int64)])
        w = weight[rows, slots] * jnp.asarray(real, jnp.float32)
        f = f.at[rows].add(
            gated(m[rows], w_gate[slot], w_up[slot], w_down[slot])
            * w[:, None])
    if spec.get("shared_width"):
        f = f + gated(m, *(_param(p, i, f"weights_shared_{name}")
                           for name in ("gate", "up", "down")))
    return f.reshape(b, t, d), logits, chosen


#: looked up when a layer runs, so that a test or a control can put a
#: term out of action by replacing one function of this module
MIXERS = {"latent_attention": lambda *a: latent_mixer(*a),
          "gated_mlp": lambda m, p, i, spec: gated(
              m, _param(p, i, "weights"), _param(p, i, "weights_up"),
              _param(p, i, "weights_down"))}


# ----------------------------------------------------------------------
# the model
# ----------------------------------------------------------------------
def run(params: dict, layers: list, tokens, routing: dict | None = None,
        held: dict | None = None, bias: dict | None = None) -> tuple:
    """Every unit's output for ``tokens`` (B, T) — the opened streams,
    every h, every f, every X', the sum, the norm, the softmax over the
    vocabulary at every position; with them, per expert layer (keyed by
    its index): the router's logits and the experts used.  ``bias``
    (layer index → (E,)) is the selection bias where the reference
    chooses for itself."""
    outs, logits, chosen = [], {}, {}
    reads = []                 # (X, H_post, H_res) awaiting their WRITE
    with jax.default_matmul_precision("highest"):
        h = None
        for i, layer in enumerate(layers):
            kind, spec = layer["type"], layer.get("->", {})
            if kind == "embedding":
                ids = np.asarray(np.round(np.asarray(tokens)), np.int64)
                h = _param(params, i, "weights")[ids]
            elif kind == "stream_open":
                h = jnp.tile(h, (1, 1, int(spec["n_streams"])))
            elif kind == "stream_read":
                h_pre, h_post, h_res = stream_maps(h, params, i, spec)
                reads.append((h, h_post, h_res))
                h = jnp.einsum("btj,btjd->btd", h_pre,
                               streams_of(h, int(spec["n_streams"])))
            elif kind == "stream_write":
                x, h_post, h_res = reads.pop()
                x = streams_of(x, int(spec["n_streams"]))
                h = (jnp.einsum("btij,btjd->btid", h_res, x)
                     + h_post[..., None] * h[:, :, None, :]).reshape(
                    x.shape[:2] + (-1,))
            elif kind == "stream_close":
                h = streams_of(h, int(spec["n_streams"])).sum(axis=2)
            elif kind in MIXERS or kind == "moe":
                if spec.get("residual"):
                    raise ValueError(
                        f"reference/xing: layer {i} ({kind}) keeps a "
                        f"skip of its own; the streams are the skip")
                m = _normed(h, params, i, spec)
                if kind == "moe":
                    h, logits[i], chosen[i] = moe_block(
                        m, params, i, spec, (routing or {}).get(i),
                        (held or {}).get(i), (bias or {}).get(i))
                else:
                    h = MIXERS[kind](m, params, i, spec)
            elif kind == "rms_norm":
                h = rms_norm(h, _param(params, i, "weights"),
                             float(spec.get("eps", 1e-5)))
            elif kind == "softmax" and spec.get("per_position"):
                h = jax.nn.softmax(mm(h, _param(params, i, "weights")),
                                   axis=-1)
            else:
                raise ValueError(f"reference/xing: no layer {kind!r}")
            # the program STORES the streams position-minor,
            # (B, n D, T): the same numbers, the last two axes swapped
            outs.append(jnp.swapaxes(h, 1, 2) if kind in (
                "stream_open", "stream_write") else h)
    return outs, {"logits": logits, "chosen": chosen}


def forward(params: dict, layers: list, tokens,
            routing: dict | None = None, held: dict | None = None,
            bias: dict | None = None) -> list:
    return [np.asarray(o) for o in run(params, layers, tokens, routing,
                                       held, bias)[0]]


def loss(params: dict, layers: list, tokens, labels,
         routing: dict | None = None, held: dict | None = None,
         bias: dict | None = None):
    """Next-token cross-entropy, mean over every position (the config
    names no auxiliary loss)."""
    outs, _ = run(params, layers, tokens, routing, held, bias)
    labels = jnp.asarray(np.asarray(labels), jnp.int32)
    p_true = jnp.take_along_axis(outs[-1], labels[..., None], axis=-1)
    return -jnp.mean(jnp.log(p_true))


def loss_and_grads(params: dict, layers: list, tokens, labels,
                   bias: dict | None = None) -> tuple:
    """``jax.value_and_grad`` of :func:`loss` in every parameter, at
    the reference's own choice of experts (made in a pass of its own:
    the choice and the bias are constants of the differentiated
    function)."""
    routing = run(params, layers, tokens, bias=bias)[1]["chosen"]
    as_arrays = {name: jnp.asarray(value, jnp.float32)
                 for name, value in params.items()}
    value, grads = jax.jit(jax.value_and_grad(
        lambda p: loss(p, layers, tokens, labels, routing)))(as_arrays)
    return float(value), {k: np.asarray(g) for k, g in grads.items()}

"""Plain reference of the ``kanana_2_30b_a3b`` configuration (Kakao
kanana-2-30b-a3b-instruct-2601, ``model_type`` deepseek_v3): token
embedding → one block of latent attention + a dense SwiGLU → N blocks of
latent attention + sparse experts beside a shared expert → final RMSNorm
→ untied head, softmax at every position — in float32 ``jax.numpy``
under ``jax.default_matmul_precision("highest")``, no kernels: every
head's key ASSEMBLED from the latent's up-projection and the one shared
rotary key, the scores of one head's block of query rows at a time
against the keys that block can see, a loop over the experts with a mask.
Independent of the code under test: it reads only the layer table and
the parameters, keyed as a bundle is (``layer<i>_weights``,
``_weights_kv_up``, ``_gain_latent``, ``_weights_out``, ``_gain_norm``,
``_weights_up``, ``_weights_down``, ``_weights_gate``,
``_weights_shared_gate`` / ``_up`` / ``_down``).  Run it on the host's
CPU device when the chip is full (``jax.default_device``).

The equations (block l with input x of T × D, eps 1e-6; the table holds
a block as two layers, the mixer and the feed-forward):

.. code-block:: text

    n = RMSNorm(x; g1)
    [q | c' | k_r'] = n W          H (nope + rope) + L + rope columns
    c = RMSNorm(c'; g_c)           the latent's own norm
    [k_nope | v] = c W_up          H (nope + v) columns
    q_rope,h and the ONE k_r rotated by position (theta 1e6, pairs
        (2i, 2i + 1) turn together); q_nope, k_nope carry no position
    k_h = [k_nope,h | k_r]         the rotary key shared by all heads
    s_h = q_h . k_h / sqrt(nope + rope), causal ;  o_h = softmax(s_h) v_h
    a = x + [o_1 ... o_H] W_o

    m = RMSNorm(a; g2)
    layer 0:    y = a + W_d (silu(W_g m) * W_u m)             width 6,144
    layers 1-:  s = sigmoid(m W_r)                             E scores
                S = the top_k largest of s + b                 b: the bias
                w_e = routed_scale s_e / sum_{S} s   from s, never s + b
                y = a + sum_{e in S and held} w_e E_e(m) + E_shared(m)

    loss = mean_t CE(head(RMSNorm(y_last)), next token)

Departures from the published modelling code (DeepSeek-V3's, which
``model_type`` names), each a choice of LAYOUT or of notation, none of
arithmetic:

- the TWO shared experts (``n_shared_experts`` 2) are ONE SwiGLU of
  width 2 × 768: the published code itself builds them as one MLP of
  ``moe_intermediate_size × n_shared_experts`` — an identity;
- the query and the K/V down-projection are ONE matrix (one product
  over the normed input), its columns by PART (all heads' q_nope, all
  heads' q_rope, the latent, k_r), and W_up's likewise (all k_nope,
  all v); the published ones stand by head: a fixed permutation;
- the published rotation is interleaved (``rope_interleave``): pairs
  (2i, 2i + 1) turn by angle i.  :func:`rope_interleaved` does exactly
  that, on the rotary columns taken in the order :func:`pairs` =
  (0, r/2, 1, r/2 + 1, …) — the one fixed permutation by which the
  half-split convention of the code under test differs; it is applied
  to q_rope and to k_r alike, so every score is the one the published
  order gives (``tests/test_kanana_reference.py`` shows both);
- depth, experts held, the vocabulary slice, momentum SGD, random
  weights: the configuration's file.

``held`` (the layer table's, or the argument of :func:`run`) is the set
of experts whose slabs exist here — one chip's share of a deployment in
which 8 chips share each layer: the router, its bias and its top k are
over all E, the sum is over the chosen experts that are held, nothing
stands in for the others; the slabs are indexed by an expert's place in
``held``.  The shares' routed parts plus the shared expert ONCE add up
to the uncut layer (``tests/test_kanana_reference.py``).  The choice of
experts is piecewise constant: ``routing`` — the reference's own, or
handed in — is a constant of the differentiated function, and so is b.

The small functions a block is made of (:func:`latent_norm`,
:func:`rotate`, :func:`nope_parts`, :func:`head_keys`,
:func:`score_scale`, :func:`scores_of`, :func:`gate_weights`,
:func:`shared_expert`) are looked up when a layer runs, so that a test
or a control (``benchmarks/kanana_controls.py``) can make ONE term
wrong by replacing one of them.
"""

from __future__ import annotations

import functools
import os
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512
#: a query block meets the keys up to its own last row, rounded up to
#: this many (few distinct shapes: each new one compiles)
KEY_STEP = 2048

#: ``None``: plain float32.  A dtype: every matmul's two inputs are
#: rounded to it first (products and sums stay float32) — how a limit
#: of the comparison is shown to have teeth (``matmul_inputs``)
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
# latent attention without a query latent
# ----------------------------------------------------------------------
def pairs(rot: int) -> np.ndarray:
    """The rotary columns in the order whose neighbours (2i, 2i + 1)
    are the half-split convention's partners (i, i + rot/2)."""
    return np.arange(rot).reshape(2, rot // 2).T.reshape(rot)


def rope_interleaved(x, theta: float):
    """(B, T, H, r) rotated as published: columns (2i, 2i + 1) are a
    pair, turned by pos · theta^(−2i/r); the angles in float64."""
    t, rot = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-2.0 * np.arange(rot // 2, dtype=np.float64)
                         / rot)
    angle = np.arange(t, dtype=np.float64)[:, None] * inv_freq[None, :]
    cos = jnp.asarray(np.cos(angle), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(angle), jnp.float32)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def latent_norm(c, gain, eps: float):
    """c = RMSNorm(c'; g_c)."""
    return rms_norm(c, gain, eps)


def rotate(q_rope, k_r, theta: float):
    """(B, T, H, r) and (B, T, 1, r), both turned by position."""
    order = pairs(q_rope.shape[-1])      # module docstring: departures
    return (rope_interleaved(q_rope[..., order], theta),
            rope_interleaved(k_r[..., order], theta))


def nope_parts(q_nope, k_nope, theta: float):
    """The per-head parts carry no position."""
    return q_nope, k_nope


def head_keys(k_nope, k_r):
    """(B, T, H, nope) and the ONE rotary key (B, T, 1, r) → every
    head's key (B, T, H, nope + r): the same k_r behind each."""
    b, t, h, _ = k_nope.shape
    return jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r, (b, t, h, k_r.shape[-1]))], axis=-1)


def score_scale(spec: dict) -> float:
    """(nope + rope)^-1/2: the width of a whole key."""
    given = spec.get("score_scale")
    return float(given) if given is not None else \
        (int(spec["qk_nope"]) + int(spec["qk_rope"])) ** -0.5


@functools.partial(jax.jit, static_argnums=(4, 5))
def _rows_attend(q, k, v, keep, scale, inputs):
    """softmax(q kᵀ · scale + mask) v for ONE head and one block of
    query rows, (rows, d) against (keys, d) — one program a shape, so
    that the mask and the softmax are one pass over the scores;
    ``inputs``: the dtype the matmuls' inputs are rounded to
    (``matmul_inputs``), static."""
    with matmul_inputs(inputs), jax.default_matmul_precision("highest"):
        s = jnp.where(keep, (_r(q) @ _r(k).T) * scale, -jnp.inf)
        return _r(jax.nn.softmax(s, axis=-1)) @ _r(v)


def attention_core(q, k, v, scale: float):
    """(B, T, H, dk) × (B, T, H, dk) × (B, T, H, dv) → (B, T, H, dv),
    causal: one sequence, one head and one block of query rows at a
    time against the keys up to that block's last row; the mask cuts
    the rest.  A block's heads go to a pool of host threads — the CPU
    runs one program's softmax on one core, and at T 16,384 that pass,
    not the products, is most of the reference's time (all heads in one
    batched program: 35 s a layer on eight cores, so: 14) —, each on
    the device the arrays lie on (``jax.default_device`` is a thread's
    own: a worker would fall back to the process's, the chip); a trace,
    as under :func:`loss_and_grads`, stays on its own thread."""
    b, t, h = q.shape[:3]
    block = min(QUERY_BLOCK, t)
    inputs = _INPUTS
    if isinstance(q, jax.core.Tracer):
        pool, device = None, None
    else:
        pool = ThreadPoolExecutor(len(os.sched_getaffinity(0)))
        device = next(iter(q.devices()))

    def head(at, lo, hi, k1, keep):
        n, j = divmod(at, h)
        with jax.default_device(device):
            return _rows_attend(q[n, lo:hi, j], k[n, :k1, j], v[n, :k1, j],
                                keep, scale, inputs)

    out = []
    for lo in range(0, t, block):
        hi = min(lo + block, t)
        k1 = min(-(-hi // KEY_STEP) * KEY_STEP, t)
        keep = jnp.asarray(np.arange(lo, hi)[:, None]
                           >= np.arange(k1)[None, :])
        one = functools.partial(head, lo=lo, hi=hi, k1=k1, keep=keep)
        heads = list((pool.map if pool else map)(one, range(b * h)))
        out.append(jnp.stack(heads).reshape(b, h, hi - lo, -1))
    if pool:
        pool.shutdown()
    return jnp.concatenate(out, axis=2).transpose(0, 2, 1, 3)


def latent_mixer(m, p: dict, i: int, spec: dict):
    """The mixer's f of the NORMED rows (B, T, D)."""
    b, t, _ = m.shape
    h = int(spec["n_heads"])
    latent, nope, rope, dv = (int(spec[key]) for key in (
        "kv_latent", "qk_nope", "qk_rope", "v_head_dim"))
    if not spec.get("causal") or spec.get("head_gate") \
            or spec.get("q_latent"):
        raise ValueError("reference/kanana: attention is causal, with "
                         "neither a head gate nor a query latent")
    if set(spec["rope"]) - {"theta"}:
        raise ValueError(f"reference/kanana: a plain rotation at one "
                         f"theta (rope_scaling null), got {spec['rope']}")
    theta = float(spec["rope"]["theta"])
    proj = mm(m, _param(p, i, "weights"))
    at, wide = h * nope, h * (nope + rope)
    q_nope = proj[..., :at].reshape(b, t, h, nope)
    q_rope = proj[..., at:wide].reshape(b, t, h, rope)
    c = latent_norm(proj[..., wide:wide + latent],
                    _param(p, i, "gain_latent"), _eps(spec))
    k_r = proj[..., None, wide + latent:]               # (B, T, 1, r)
    up = mm(c, _param(p, i, "weights_kv_up"))
    k_nope = up[..., :at].reshape(b, t, h, nope)
    v = up[..., at:].reshape(b, t, h, dv)
    q_rope, k_r = rotate(q_rope, k_r, theta)
    q_nope, k_nope = nope_parts(q_nope, k_nope, theta)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    o = attention_core(q, head_keys(k_nope, k_r), v, score_scale(spec))
    return mm(o.reshape(b, t, h * dv), _param(p, i, "weights_out"))


# ----------------------------------------------------------------------
# the feed-forward blocks
# ----------------------------------------------------------------------
def gated(m, w_gate, w_up, w_down):
    return mm(jax.nn.silu(mm(m, w_gate)) * mm(m, w_up), w_down)


def dense_mlp(m, p: dict, i: int, spec: dict):
    return gated(m, _param(p, i, "weights"), _param(p, i, "weights_up"),
                 _param(p, i, "weights_down"))


def scores_of(logits):
    """An expert's score: the sigmoid of its own logit."""
    return jax.nn.sigmoid(logits)


def route(m, p: dict, i: int):
    """Router logits and scores of (N, D) rows (float32 in every
    configuration: ``matmul_inputs`` does not reach it)."""
    logits = m @ _param(p, i, "weights")
    return logits, scores_of(logits)


def top_k(scores, k: int) -> np.ndarray:
    """(N, k) indices, the largest first, ties to the lower index."""
    return np.argsort(-np.asarray(scores), axis=-1, kind="stable")[:, :k]


def choose(scores, bias, spec: dict) -> np.ndarray:
    """The experts chosen, (N, top_k): the largest s + b among all E
    (``n_group`` = ``topk_group`` = 1: no group limit)."""
    if spec.get("groups"):
        raise ValueError("reference/kanana: the router has no group limit")
    return top_k(np.asarray(scores) + (0.0 if bias is None
                                       else np.asarray(bias)),
                 int(spec["top_k"]))


def gate_weights(scores, chosen, bias, spec: dict):
    """(N, k) weights of the chosen experts: from s — never s + b —,
    normalised over the chosen (``norm_topk``), times ``routed_scale``."""
    weight = jnp.take_along_axis(scores, jnp.asarray(chosen), axis=-1)
    if spec.get("norm_topk"):
        weight = weight / weight.sum(axis=-1, keepdims=True)
    return weight * float(spec.get("routed_scale", 1.0))


def shared_expert(m, p: dict, i: int, spec: dict):
    """E_shared(m): ONE SwiGLU over the whole stored width (the
    model's two shared experts side by side)."""
    return gated(m, *(_param(p, i, f"weights_shared_{name}")
                      for name in ("gate", "up", "down")))


def moe_block(m3, p: dict, i: int, spec: dict, chosen=None, held=None,
              bias=None):
    """``(f, logits, chosen)`` of the NORMED rows (B, T, D); ``chosen``
    (N, k) names the experts to use (the reference's own choice when
    ``None``); ``held`` the experts whose slabs ``p`` holds, in the
    slabs' order (the table's, else all); ``bias`` the selection
    bias."""
    b, t, d = m3.shape
    n_tok, experts = b * t, int(spec["n_experts"])
    k = int(spec["top_k"])
    if spec.get("score") != "sigmoid" or spec.get("aux_loss_weight") \
            or spec.get("z_loss_weight"):
        raise ValueError("reference/kanana: experts are scored by a "
                         "sigmoid and balanced by the bias alone")
    if held is None:
        held = spec.get("held")
    held = list(range(experts)) if held is None else sorted(held)
    m = m3.reshape(n_tok, d)
    logits, scores = route(m, p, i)
    if not spec.get("select_bias"):
        bias = None
    if chosen is None:
        chosen = choose(scores, bias, spec)
    chosen = np.asarray(chosen).reshape(n_tok, k)
    weight = gate_weights(scores, chosen, bias, spec)
    w_gate, w_up, w_down = (_param(p, i, f"weights_{name}")
                            for name in ("gate", "up", "down"))
    # every expert's rows padded to one length (the pad: row 0 at
    # weight 0), so that the loop runs ONE shape
    most = max([int((chosen == e).sum()) for e in held] + [1])
    cap = -(-most // 128) * 128
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
        f = f + shared_expert(m, p, i, spec)
    return f.reshape(b, t, d), logits, chosen


# ----------------------------------------------------------------------
# the model
# ----------------------------------------------------------------------
def run(params: dict, layers: list, tokens, routing: dict | None = None,
        held: dict | None = None, bias: dict | None = None) -> tuple:
    """Every layer's output for ``tokens`` (B, T) — the last is the
    softmax over the vocabulary at every position; with them, per
    expert layer (keyed by its index): the router's logits and the
    experts used.  ``bias`` (layer index → (E,)) is the selection bias
    where the reference chooses for itself."""
    outs, logits, chosen = [], {}, {}
    with jax.default_matmul_precision("highest"):
        h = None
        for i, layer in enumerate(layers):
            kind, spec = layer["type"], layer.get("->", {})
            if kind == "embedding":
                ids = np.asarray(np.round(np.asarray(tokens)), np.int64)
                h = _param(params, i, "weights")[ids]
            elif kind in ("latent_attention", "gated_mlp", "moe"):
                m = _normed(h, params, i, spec)
                if kind == "moe":
                    f, logits[i], chosen[i] = moe_block(
                        m, params, i, spec, (routing or {}).get(i),
                        (held or {}).get(i), (bias or {}).get(i))
                elif kind == "gated_mlp":
                    f = dense_mlp(m, params, i, spec)
                else:
                    f = latent_mixer(m, params, i, spec)
                h = h + f if spec.get("residual") else f
            elif kind == "rms_norm":
                h = rms_norm(h, _param(params, i, "weights"),
                             float(spec.get("eps", 1e-5)))
            elif kind == "softmax" and spec.get("per_position"):
                h = jax.nn.softmax(mm(h, _param(params, i, "weights")),
                                   axis=-1)
            else:
                raise ValueError(f"reference/kanana: no layer {kind!r}")
            outs.append(h)
    return outs, {"logits": logits, "chosen": chosen}


def forward(params: dict, layers: list, tokens,
            routing: dict | None = None, held: dict | None = None,
            bias: dict | None = None) -> list:
    return [np.asarray(o) for o in run(params, layers, tokens, routing,
                                       held, bias)[0]]


def loss(params: dict, layers: list, tokens, labels,
         routing: dict | None = None, held: dict | None = None,
         bias: dict | None = None):
    """Next-token cross-entropy, mean over every position (no
    auxiliary loss: ``noaux_tc`` balances by the bias)."""
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
        lambda p: loss(p, layers, tokens, labels, routing, bias=bias)))(
            as_arrays)
    return float(value), {k: np.asarray(g) for k, g in grads.items()}

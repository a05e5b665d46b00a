"""Plain reference of the ``laguna_s_2_1`` configuration (poolside
Laguna-S-2.1, ``model_type`` laguna): token embedding → N × (pre-norm
attention block → pre-norm feed-forward block) → final RMSNorm → untied
head, softmax at every position — in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: attention one K/V head and
one block of query rows at a time, a loop over the experts with a mask,
no kernels, no sort, no grouped matmul.  Independent of the code under
test: it reads only the layer table and the parameters, keyed as a
bundle is (``layer<i>_weights``, ``_weights_out``, ``_weights_head_gate``,
``_gain_norm``, ``_weights_gate``, ``_up``, ``_down``,
``_weights_shared_gate``, ``_up``, ``_down``).  Run it on the host's CPU
device when the chip is full (``jax.default_device``).

The layer equations (block l, x of T × D, RMSNorm with a gain):

.. code-block:: text

    n = RMSNorm(x)
    q = n W_q   (H_l heads of dh)     k, v = n W_k, n W_v  (H_kv heads)
        query head h reads K/V head h // (H_l / H_kv); no biases
    q, k = RoPE(q), RoPE(k)      half-split over the first ``rotary_dim``
                                 of a head, the rest passes; with
                                 ``yarn`` the inverse frequencies blend
                                 theta^(-2i/r) and theta^(-2i/r)/factor
                                 over the ramp between the two
                                 correction dims, and cos, sin are
                                 multiplied by ``attention_factor``
    o_h = softmax(q_h k_g^T / sqrt(dh) + mask) v_g
                                 mask: causal, and with a ``window``
                                 also column > row - window
    g = sigmoid(n W_g)           W_g (D, H_l): one gate per head
    h = x + concat_h(g_h o_h) W_o

    m = RMSNorm(h)
    gated_mlp:  y = h + W_down (silu(W_gate m) * W_up m)
    moe:        s = sigmoid(W_r m)             all E outputs
                top = the top_k largest s_e;  w_e = scale s_e / sum_top s
                y = h + Shared(m) + sum_{e in top and held} w_e Expert_e(m)
                Shared, Expert_e: gated MLPs as above

    loss = mean_t CE(head(RMSNorm(y_last)), next token)
         + aux_loss_weight * sum_layers E * sum_e (rows_e / N) * mean_n
           (s_ne / sum_e s_ne)        rows_e over ALL experts

``held`` (the layer table's, or the argument of :func:`run`) is the set
of experts whose weights exist here — one chip's share of an
expert-parallel deployment: the router and its top k are over all E,
the sum is over the chosen experts that are held, nothing stands in for
the others; the slabs are indexed by an expert's place in ``held``.
:func:`run` with another ``held`` and the matching slabs gives another
chip's share; the shares' routed parts add up to the uncut layer's
(``tests/test_laguna_reference.py``).

Departures from the published model, all in the configuration's file:
depth, experts held, the vocabulary slice, momentum SGD, random weights.
The choice of experts is piecewise constant, so its gradient is nil and
``routing`` — the experts chosen, by the reference itself or handed in
by the caller — is a constant of the differentiated function.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512

#: ``None``: plain float32.  A dtype: every matmul's two inputs are
#: rounded to it first (the products and sums stay float32) — how a
#: limit of the comparison is shown to have teeth (``matmul_inputs``)
_INPUTS = None


class matmul_inputs:
    """``with matmul_inputs(jnp.float8_e4m3fn): run(...)``."""

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


# ----------------------------------------------------------------------
# rotary positions
# ----------------------------------------------------------------------
def inv_frequencies(rot: int, theta: float, yarn: dict | None
                    ) -> tuple[np.ndarray, float]:
    """``rot/2`` inverse frequencies in float64 and the factor on cos
    and sin: plain, or YaRN's (Peng et al. 2023, arXiv:2309.00071; the
    arithmetic of ``transformers``' ``_compute_yarn_parameters`` with
    ``truncate`` on)."""
    plain = np.asarray([theta ** (-2.0 * i / rot)
                        for i in range(rot // 2)], np.float64)
    if not yarn:
        return plain, 1.0
    factor = float(yarn["factor"])
    original = float(yarn["original_max_position_embeddings"])

    def correction_dim(rotations: float) -> float:
        return rot * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(float(yarn["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(yarn["beta_slow"]))),
               rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rot // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    # ramp 0: the frequency turns often within the original context and
    # is kept (extrapolation); ramp 1: it is divided by the factor
    blended = plain / factor * ramp + plain * (1.0 - ramp)
    scale = yarn.get("attention_factor")
    if scale is None:
        scale = 0.1 * math.log(factor) + 1.0
    return blended, float(scale)


def rope(x, spec: dict):
    """(B, T, H, dh) rotated by position over the first ``rotary_dim``
    of a head: with x1, x2 the two halves of that part,
    (x1 cos - x2 sin, x2 cos + x1 sin); the rest passes."""
    t, dh = x.shape[1], x.shape[3]
    rot = int(spec.get("rotary_dim") or dh)
    inv_freq, scale = inv_frequencies(rot, float(spec["theta"]),
                                      spec.get("yarn"))
    angle = np.arange(t, dtype=np.float64)[:, None] * inv_freq[None, :]
    cos = jnp.asarray(np.cos(angle) * scale,
                      jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(angle) * scale,
                      jnp.float32)[None, :, None, :]
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            rest], axis=-1)


# ----------------------------------------------------------------------
# the attention block
# ----------------------------------------------------------------------
def attention_core(q, k, v, window):
    """(B, T, H, dh) × (B, T, H_kv, dh) → (B, T, H, dh): causal softmax
    attention, with a ``window`` over columns > row - window; one K/V
    head and one block of query rows at a time, a windowed layer
    against the slab of keys its block can see (one shape for the whole
    loop: eager jax.numpy compiles each new shape)."""
    b, t, h, dh = q.shape
    h_kv = k.shape[2]
    group = h // h_kv
    block = min(QUERY_BLOCK, t)
    slab = t if window is None else min(t, block + int(window))
    out = []
    for lo in range(0, t, block):
        hi = min(lo + block, t)
        k0 = min(max(hi - slab, 0), t - slab)
        rows = np.arange(lo, hi)[:, None]
        cols = np.arange(k0, k0 + slab)[None, :]
        keep = rows >= cols
        if window is not None:
            keep &= cols > rows - int(window)
        keep = jnp.asarray(keep)
        heads = []
        for g in range(h_kv):
            qg = q[:, lo:hi, g * group:(g + 1) * group]
            kg, vg = k[:, k0:k0 + slab, g], v[:, k0:k0 + slab, g]
            s = jnp.einsum("bqgd,bkd->bgqk", _r(qg), _r(kg)) \
                / np.sqrt(dh)
            s = jnp.where(keep, s, -jnp.inf)
            heads.append(jnp.einsum(
                "bgqk,bkd->bqgd", _r(jax.nn.softmax(s, axis=-1)),
                _r(vg)))
        out.append(jnp.concatenate(heads, axis=2))
    return jnp.concatenate(out, axis=1)


def attention_block(x, p: dict, i: int, spec: dict):
    b, t, d = x.shape
    heads = int(spec["n_heads"])
    kv_heads = int(spec.get("n_kv_heads") or heads)
    dh = int(spec.get("head_dim") or d // heads)
    eps = float(spec.get("norm_eps", 1e-5))
    if not spec.get("causal"):
        raise ValueError("reference/laguna: attention is causal")
    n = rms_norm(x, _param(p, i, "gain_norm"), eps) \
        if spec.get("pre_norm") else x
    qkv = mm(n, _param(p, i, "weights"))
    qw, kw = heads * dh, kv_heads * dh
    q = qkv[..., :qw].reshape(b, t, heads, dh)
    k = qkv[..., qw:qw + kw].reshape(b, t, kv_heads, dh)
    v = qkv[..., qw + kw:].reshape(b, t, kv_heads, dh)
    if spec.get("rope"):
        q, k = rope(q, spec["rope"]), rope(k, spec["rope"])
    o = attention_core(q, k, v, spec.get("window"))
    if spec.get("head_gate"):
        gate = jax.nn.sigmoid(mm(n, _param(p, i, "weights_head_gate")))
        o = o * gate[..., None]
    y = mm(o.reshape(b, t, qw), _param(p, i, "weights_out"))
    return x + y if spec.get("residual") else y


# ----------------------------------------------------------------------
# the feed-forward blocks
# ----------------------------------------------------------------------
def gated(m, w_gate, w_up, w_down):
    return mm(jax.nn.silu(mm(m, w_gate)) * mm(m, w_up), w_down)


def _normed(x, p: dict, i: int, spec: dict):
    return rms_norm(x, _param(p, i, "gain_norm"),
                    float(spec.get("norm_eps", 1e-5))) \
        if spec.get("pre_norm") else x


def gated_mlp_block(x, p: dict, i: int, spec: dict):
    y = gated(_normed(x, p, i, spec), _param(p, i, "weights"),
              _param(p, i, "weights_up"), _param(p, i, "weights_down"))
    return x + y if spec.get("residual") else y


def route(m, p: dict, i: int):
    """Router logits and scores of (N, D) rows (float32 in every
    configuration: ``matmul_inputs`` does not reach it)."""
    logits = m @ _param(p, i, "weights")
    return logits, jax.nn.sigmoid(logits)


def top_k(scores, k: int) -> np.ndarray:
    """(N, k) experts, the largest score first, ties to the lower
    index."""
    return np.argsort(-np.asarray(scores), axis=-1, kind="stable")[:, :k]


def moe_block(x, p: dict, i: int, spec: dict, chosen=None, held=None):
    """``(y, logits, lb, chosen)``; ``chosen`` (N, k) names the experts
    to use (the reference's own top-k when ``None``); ``held`` the
    experts whose slabs ``p`` holds, in the slabs' order (the layer
    table's, else all)."""
    b, t, d = x.shape
    n_tok, experts = b * t, int(spec["n_experts"])
    k = int(spec["top_k"])
    if spec.get("score") != "sigmoid":
        raise ValueError("reference/laguna: experts are scored by a "
                         "sigmoid")
    if held is None:
        held = spec.get("held")
    held = list(range(experts)) if held is None else sorted(held)
    m = _normed(x, p, i, spec).reshape(n_tok, d)
    logits, scores = route(m, p, i)
    if chosen is None:
        chosen = top_k(scores, k)
    chosen = np.asarray(chosen).reshape(n_tok, k)
    weight = jnp.take_along_axis(scores, jnp.asarray(chosen), axis=-1)
    if spec.get("norm_topk"):
        weight = weight / weight.sum(axis=-1, keepdims=True)
    weight = weight * float(spec.get("routed_scale", 1.0))
    w_gate, w_up, w_down = (_param(p, i, f"weights_{name}")
                            for name in ("gate", "up", "down"))
    rows_per_expert = np.asarray(
        [(chosen == e).sum() for e in range(experts)], np.float32)
    f = jnp.zeros((n_tok, d), jnp.float32)
    # every expert's rows padded to one length (the pad: row 0 at
    # weight 0), so that the loop runs ONE shape
    most = max([rows_per_expert[e] for e in held] + [1])
    cap = -(-int(most) // 128) * 128
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
    y = f.reshape(b, t, d)
    if spec.get("residual"):
        y = x + y
    unit = scores / scores.sum(axis=-1, keepdims=True)
    lb = experts * jnp.sum(jnp.asarray(rows_per_expert / n_tok)
                           * unit.mean(axis=0))
    return y, logits, lb, chosen


# ----------------------------------------------------------------------
# the model
# ----------------------------------------------------------------------
def run(params: dict, layers: list, tokens, routing: dict | None = None,
        held: dict | None = None) -> tuple:
    """Every layer's output for ``tokens`` (B, T), as the training
    step's forward computes them, the last being the softmax over the
    vocabulary at every position; with them, per expert layer (keyed by
    its index): the router's logits, the experts used, the
    load-balancing loss.  ``held`` (layer index → experts) overrides
    the layer table's share."""
    outs, logits, chosen, aux = [], {}, {}, {}
    with jax.default_matmul_precision("highest"):
        h = None
        for i, layer in enumerate(layers):
            kind, spec = layer["type"], layer.get("->", {})
            if kind == "embedding":
                ids = np.asarray(np.round(np.asarray(tokens)), np.int64)
                h = _param(params, i, "weights")[ids]
            elif kind == "attention":
                h = attention_block(h, params, i, spec)
            elif kind == "gated_mlp":
                h = gated_mlp_block(h, params, i, spec)
            elif kind == "moe":
                h, logits[i], aux[i], chosen[i] = moe_block(
                    h, params, i, spec, (routing or {}).get(i),
                    (held or {}).get(i))
            elif kind == "rms_norm":
                h = rms_norm(h, _param(params, i, "weights"),
                             float(spec.get("eps", 1e-5)))
            elif kind == "softmax" and spec.get("per_position"):
                h = jax.nn.softmax(mm(h, _param(params, i, "weights")),
                                   axis=-1)
            else:
                raise ValueError(f"reference/laguna: no layer {kind!r}")
            outs.append(h)
    return outs, {"logits": logits, "chosen": chosen, "aux": aux}


def forward(params: dict, layers: list, tokens,
            routing: dict | None = None, held: dict | None = None
            ) -> list:
    return [np.asarray(o) for o in run(params, layers, tokens, routing,
                                       held)[0]]


def loss(params: dict, layers: list, tokens, labels,
         routing: dict | None = None, held: dict | None = None):
    """Next-token cross-entropy, mean over every position, plus each
    expert layer's weighted load-balancing loss."""
    outs, router = run(params, layers, tokens, routing, held)
    probs = outs[-1]
    labels = jnp.asarray(np.asarray(labels), jnp.int32)
    p_true = jnp.take_along_axis(probs, labels[..., None], axis=-1)
    total = -jnp.mean(jnp.log(p_true))
    for i, lb in router["aux"].items():
        total = total + float(
            layers[i]["->"].get("aux_loss_weight", 0.0)) * lb
    return total


def loss_and_grads(params: dict, layers: list, tokens, labels) -> tuple:
    """``jax.value_and_grad`` of :func:`loss` in every parameter, at
    the reference's own choice of experts (made in a pass of its own:
    the choice is a constant of the differentiated function)."""
    routing = run(params, layers, tokens)[1]["chosen"]
    as_arrays = {name: jnp.asarray(value, jnp.float32)
                 for name, value in params.items()}
    # one program: op by op, the backward is some 500 small compiles
    value, grads = jax.jit(jax.value_and_grad(
        lambda p: loss(p, layers, tokens, labels, routing)))(as_arrays)
    return float(value), {k: np.asarray(g) for k, g in grads.items()}

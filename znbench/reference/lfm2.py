"""Plain reference of the ``lfm2_8b_a1b`` configuration (LiquidAI
LFM2-8B-A1B, ``model_type`` lfm2_moe): token embedding → N × (pre-norm
mixer block → pre-norm feed-forward block) → final RMSNorm → untied
head, softmax at every position — in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: the convolution as three
shifted products, attention one K/V head and one block of query rows at
a time, a loop over the experts with a mask; no kernels, no sort, no
grouped matmul.  Independent of the code under test: it reads only the
layer table and the parameters, keyed as a bundle is
(``layer<i>_weights``, ``_weights_conv``, ``_weights_out``, ``_gain_q``,
``_gain_k``, ``_gain_norm``, ``_weights_gate``, ``_up``, ``_down``).
Run it on the host's CPU device when the chip is full
(``jax.default_device``).

The layer equations (block l, x of T × D, RMSNorm with a gain, eps 1e-5):

.. code-block:: text

    n = RMSNorm(x)
    short_conv: [B | C | z] = n W_in        three D-column blocks
                u_t = B_t * z_t
                c_t = sum_{j<J} taps[:, j] * u_{t-J+1+j}   zeros before
                                            the sequence; NO activation
                h = x + (C * c) W_out
    attention:  q = n W_q (H heads of dh)   k, v = n W_k, n W_v (H_kv)
                q_h = g_q * q_h / rms(q_h)  k_g = g_k * k_g / rms(k_g)
                            per HEAD over its dh dims, one gain of dh
                            shared by the heads (``qk_norm`` rms_head)
                q, k = RoPE(q), RoPE(k)     half-split over the whole head
                o_h = softmax(q_h k_g^T / sqrt(dh) + causal) v_g
                            query head h reads K/V head h // (H / H_kv)
                h = x + concat_h(o_h) W_o

    m = RMSNorm(h)
    gated_mlp:  y = h + W_down (silu(W_gate m) * W_up m)
    moe:        s = sigmoid(W_r m)          all E outputs, float32
                top = the top_k largest of s + b   (b: the selection
                            bias; it chooses and weighs nothing)
                w_e = scale * s_e / (sum_top s + 1e-6)
                y = h + sum_{e in top and held} w_e Expert_e(m)

    loss = mean_t CE(head(RMSNorm(y_last)), next token)
         + aux_loss_weight * sum_layers E * sum_e (rows_e / N) * mean_n
           (s_ne / sum_e s_ne)        rows_e over ALL experts

``held`` (the layer table's, or the argument of :func:`run`) is the set
of experts whose weights exist here — one chip's share of an
expert-parallel deployment: the router and its top k are over all E,
the sum is over the chosen experts that are held, nothing stands in for
the others; the slabs are indexed by an expert's place in ``held``.
The shares' routed parts add up to the uncut layer's
(``tests/test_lfm2_reference.py``).

Departures from the published model, all in the configuration's file:
depth, experts held, the vocabulary slice, momentum SGD, random
weights, embedding and head untied.  One departure of the SYSTEM from
this file: ``ops/moe.py`` divides the chosen scores by their plain sum,
this file by the sum + 1e-6 as published (5e-7 of a weight at sums near
2; far below every limit).  The choice of experts is piecewise
constant, so its gradient is nil and ``routing`` — the experts chosen,
by the reference itself or handed in by the caller — is a constant of
the differentiated function.
"""

from __future__ import annotations

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


def _eps(spec: dict) -> float:
    return float(spec.get("norm_eps", 1e-5))


def _normed(x, p: dict, i: int, spec: dict):
    return rms_norm(x, _param(p, i, "gain_norm"), _eps(spec)) \
        if spec.get("pre_norm") else x


# ----------------------------------------------------------------------
# the mixers
# ----------------------------------------------------------------------
def conv_taps(u, taps):
    """(B, T, D) convolved causally over time, each channel its own
    ``taps`` (D, J): c_t = sum_j taps[:, j] * u_{t-J+1+j}, zeros before
    the sequence.  No activation."""
    t, width = u.shape[1], taps.shape[1]
    padded = jnp.pad(u, ((0, 0), (width - 1, 0), (0, 0)))
    c = jnp.zeros_like(u)
    for j in range(width):
        c = c + padded[:, j:j + t] * taps[:, j]
    return c


def in_gate(gate, z):
    """u = B * z: the gate before the taps."""
    return gate * z


def out_gate(gate, c):
    """C * c: the gate after the taps."""
    return gate * c


def short_conv_block(x, p: dict, i: int, spec: dict):
    d = x.shape[-1]
    n = _normed(x, p, i, spec)
    projected = mm(n, _param(p, i, "weights"))
    gate_in, gate_out, z = (projected[..., :d], projected[..., d:2 * d],
                            projected[..., 2 * d:])
    taps = _param(p, i, "weights_conv")
    if taps.shape != (d, int(spec.get("conv_kernel", 3))):
        raise ValueError(f"reference/lfm2: taps {taps.shape}")
    y = mm(out_gate(gate_out, conv_taps(in_gate(gate_in, z), taps)),
           _param(p, i, "weights_out"))
    return x + y if spec.get("residual") else y


def rope(x, theta: float):
    """(B, T, H, dh) rotated by position over the whole head: with x1,
    x2 its two halves, (x1 cos - x2 sin, x2 cos + x1 sin)."""
    t, dh = x.shape[1], x.shape[3]
    inv_freq = np.asarray([theta ** (-2.0 * i / dh)
                           for i in range(dh // 2)], np.float64)
    angle = np.arange(t, dtype=np.float64)[:, None] * inv_freq[None, :]
    cos = jnp.asarray(np.cos(angle), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(angle), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def attention_core(q, k, v):
    """(B, T, H, dh) × (B, T, H_kv, dh) → (B, T, H, dh): causal softmax
    attention, one K/V head and one block of query rows at a time."""
    b, t, h, dh = q.shape
    h_kv = k.shape[2]
    group = h // h_kv
    block = min(QUERY_BLOCK, t)
    out = []
    for lo in range(0, t, block):
        hi = min(lo + block, t)
        keep = jnp.asarray(np.arange(lo, hi)[:, None]
                           >= np.arange(t)[None, :])
        heads = []
        for g in range(h_kv):
            qg = q[:, lo:hi, g * group:(g + 1) * group]
            s = jnp.einsum("bqgd,bkd->bgqk", _r(qg), _r(k[:, :, g])) \
                / np.sqrt(dh)
            s = jnp.where(keep, s, -jnp.inf)
            heads.append(jnp.einsum(
                "bgqk,bkd->bqgd", _r(jax.nn.softmax(s, axis=-1)),
                _r(v[:, :, g])))
        out.append(jnp.concatenate(heads, axis=2))
    return jnp.concatenate(out, axis=1)


def head_norm(x, gain, eps: float):
    """(B, T, H, dh) normed over each head's dh dims, one gain (dh,)."""
    return rms_norm(x, gain, eps)


def attention_block(x, p: dict, i: int, spec: dict):
    b, t, d = x.shape
    heads = int(spec["n_heads"])
    kv_heads = int(spec.get("n_kv_heads") or heads)
    dh = int(spec.get("head_dim") or d // heads)
    if not spec.get("causal") or spec.get("qk_norm") != "rms_head":
        raise ValueError("reference/lfm2: attention is causal with a "
                         "q/k norm per head")
    n = _normed(x, p, i, spec)
    qkv = mm(n, _param(p, i, "weights"))
    qw, kw = heads * dh, kv_heads * dh
    q = qkv[..., :qw].reshape(b, t, heads, dh)
    k = qkv[..., qw:qw + kw].reshape(b, t, kv_heads, dh)
    v = qkv[..., qw + kw:].reshape(b, t, kv_heads, dh)
    q = head_norm(q, _param(p, i, "gain_q"), _eps(spec))
    k = head_norm(k, _param(p, i, "gain_k"), _eps(spec))
    theta = float(spec["rope"]["theta"])
    o = attention_core(rope(q, theta), rope(k, theta), v)
    y = mm(o.reshape(b, t, qw), _param(p, i, "weights_out"))
    return x + y if spec.get("residual") else y


# ----------------------------------------------------------------------
# the feed-forward blocks
# ----------------------------------------------------------------------
def gated(m, w_gate, w_up, w_down):
    return mm(jax.nn.silu(mm(m, w_gate)) * mm(m, w_up), w_down)


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
    """(N, k) indices, the largest first, ties to the lower index."""
    return np.argsort(-np.asarray(scores), axis=-1, kind="stable")[:, :k]


def choose(scores, bias, spec: dict) -> np.ndarray:
    """The experts chosen, (N, top_k): the largest of score + bias."""
    biased = np.asarray(scores) + (0.0 if bias is None
                                   else np.asarray(bias))
    return top_k(biased, int(spec["top_k"]))


def weights_of(scores, chosen, spec: dict):
    """The chosen experts' weights in the routed sum: from the scores,
    never from score + bias; over their sum + 1e-6 (as published)."""
    weight = jnp.take_along_axis(scores, jnp.asarray(chosen), axis=-1)
    if spec.get("norm_topk"):
        weight = weight / (weight.sum(axis=-1, keepdims=True) + 1e-6)
    return weight * float(spec.get("routed_scale", 1.0))


def moe_block(x, p: dict, i: int, spec: dict, chosen=None, held=None,
              bias=None):
    """``(y, logits, lb, chosen)``; ``chosen`` (N, k) names the experts
    to use (the reference's own choice when ``None``); ``held`` the
    experts whose slabs ``p`` holds, in the slabs' order (the layer
    table's, else all); ``bias`` the selection bias (zeros when the
    table sets ``select_bias`` and none is given)."""
    b, t, d = x.shape
    n_tok, experts = b * t, int(spec["n_experts"])
    if spec.get("score") != "sigmoid" or spec.get("groups") \
            or spec.get("shared_width"):
        raise ValueError("reference/lfm2: experts are scored by a "
                         "sigmoid, chosen without groups, and none is "
                         "shared")
    if held is None:
        held = spec.get("held")
    held = list(range(experts)) if held is None else sorted(held)
    m = _normed(x, p, i, spec).reshape(n_tok, d)
    logits, scores = route(m, p, i)
    if chosen is None:
        chosen = choose(scores, bias if spec.get("select_bias") else None,
                        spec)
    chosen = np.asarray(chosen).reshape(n_tok, int(spec["top_k"]))
    weight = weights_of(scores, chosen, spec)
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
BLOCKS = {"short_conv": short_conv_block, "attention": attention_block,
          "gated_mlp": gated_mlp_block}


def run(params: dict, layers: list, tokens, routing: dict | None = None,
        held: dict | None = None, bias: dict | None = None) -> tuple:
    """Every layer's output for ``tokens`` (B, T), the last being the
    softmax over the vocabulary at every position; with them, per
    expert layer (keyed by its index): the router's logits, the experts
    used, the load-balancing loss.  ``bias`` (layer index → (E,)) is
    the selection bias where the reference chooses for itself."""
    outs, logits, chosen, aux = [], {}, {}, {}
    with jax.default_matmul_precision("highest"):
        h = None
        for i, layer in enumerate(layers):
            kind, spec = layer["type"], layer.get("->", {})
            if kind == "embedding":
                ids = np.asarray(np.round(np.asarray(tokens)), np.int64)
                h = _param(params, i, "weights")[ids]
            elif kind in BLOCKS:
                h = BLOCKS[kind](h, params, i, spec)
            elif kind == "moe":
                h, logits[i], aux[i], chosen[i] = moe_block(
                    h, params, i, spec, (routing or {}).get(i),
                    (held or {}).get(i), (bias or {}).get(i))
            elif kind == "rms_norm":
                h = rms_norm(h, _param(params, i, "weights"),
                             float(spec.get("eps", 1e-5)))
            elif kind == "softmax" and spec.get("per_position"):
                h = jax.nn.softmax(mm(h, _param(params, i, "weights")),
                                   axis=-1)
            else:
                raise ValueError(f"reference/lfm2: no layer {kind!r}")
            outs.append(h)
    return outs, {"logits": logits, "chosen": chosen, "aux": aux}


def forward(params: dict, layers: list, tokens,
            routing: dict | None = None, held: dict | None = None,
            bias: dict | None = None) -> list:
    return [np.asarray(o) for o in run(params, layers, tokens, routing,
                                       held, bias)[0]]


def loss(params: dict, layers: list, tokens, labels,
         routing: dict | None = None, held: dict | None = None,
         bias: dict | None = None):
    """Next-token cross-entropy, mean over every position, plus each
    expert layer's weighted load-balancing loss."""
    outs, router = run(params, layers, tokens, routing, held, bias)
    labels = jnp.asarray(np.asarray(labels), jnp.int32)
    p_true = jnp.take_along_axis(outs[-1], labels[..., None], axis=-1)
    total = -jnp.mean(jnp.log(p_true))
    for i, lb in router["aux"].items():
        total = total + float(
            layers[i]["->"].get("aux_loss_weight", 0.0)) * lb
    return total


def loss_and_grads(params: dict, layers: list, tokens, labels,
                   bias: dict | None = None) -> tuple:
    """``jax.value_and_grad`` of :func:`loss` in every parameter, at
    the reference's own choice of experts (made in a pass of its own:
    the choice and the bias are constants of the differentiated
    function)."""
    routing = run(params, layers, tokens, bias=bias)[1]["chosen"]
    as_arrays = {name: jnp.asarray(value, jnp.float32)
                 for name, value in params.items()}
    # one program: op by op, the backward is some hundreds of compiles
    value, grads = jax.jit(jax.value_and_grad(
        lambda p: loss(p, layers, tokens, labels, routing)))(as_arrays)
    return float(value), {k: np.asarray(g) for k, g in grads.items()}

"""Plain reference of the ``olmoe_1b_7b`` configuration (OLMoE-1B-7B,
arXiv:2409.02060; ``model_type`` olmoe): token embedding → N × (pre-norm
attention block → pre-norm expert block) → final RMSNorm → untied head,
softmax at every position — in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: a loop over the experts
with a mask, a full softmax over the experts, attention in query
blocks, no kernels, no sort, no grouped matmul.  Independent of the
code under test: it reads only the layer table and the parameters,
keyed as a bundle is (``layer<i>_weights``, ``_weights_out``,
``_gain_norm``, ``_gain_q``, ``_gain_k``, ``_weights_gate``, ``_up``,
``_down``).  Run it on the host's CPU device when the chip is full
(``jax.default_device``).

The layer equations:

.. code-block:: text

    n = RMSNorm(x)                           gain over the hidden size
    q, k, v = W_q n, W_k n, W_v n            no biases
    q, k = RMSNorm_q(q), RMSNorm_k(k)        over the WHOLE projection,
                                             not per head; own gains
    q, k = RoPE(q), RoPE(k)                  full head, half-split
                                             ("rotate_half"), theta 10000
    h = x + W_o · softmax(q kᵀ/√dh, causal) v
    m = RMSNorm(h);  p = softmax_E(W_r m)
    y = h + Σ_{e ∈ top_k(p)} p_e · W_down,e (silu(W_gate,e m) ⊙ W_up,e m)
                                             p_e NOT renormalised

    loss = mean_t CE(head(RMSNorm(y_last)), next token)
         + aux_loss_weight · Σ_layers E · Σ_e (rows_e/N) · mean_n p_ne
         + z_loss_weight  · Σ_layers mean_n logsumexp(W_r m_n)²

Departures from the published model, all in the configuration's file:
depth, momentum SGD for AdamW, random weights.  The choice of experts
is piecewise constant, so its gradient is nil and ``routing`` — the
experts chosen, by the reference itself or handed in by the caller —
is a constant of the differentiated function.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512

#: ``None``: plain float32.  A dtype: every matmul's two inputs are
#: rounded to it first (the products and sums stay float32) — how a
#: limit of the comparison is shown to have teeth: the reference with
#: bf16 inputs reads what the system's bf16 matmuls leave, with
#: float8 inputs what the next precision down would (``matmul_inputs``)
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


def rope(x, theta: float):
    """(B, T, H, dh) rotated by position: with x₁, x₂ the two halves
    of a head, (x₁ cos − x₂ sin, x₂ cos + x₁ sin), angle
    pos · theta^(−2i/dh)."""
    t, dh = x.shape[1], x.shape[3]
    inv_freq = 1.0 / theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh)
    angle = np.arange(t, dtype=np.float64)[:, None] * inv_freq[None, :]
    cos = jnp.asarray(np.cos(angle), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(angle), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _param(p: dict, i: int, name: str):
    return jnp.asarray(p[f"layer{i}_{name}"], jnp.float32)


def attention_block(x, p: dict, i: int, spec: dict):
    b, t, d = x.shape
    heads = int(spec["n_heads"])
    dh = d // heads
    eps = float(spec.get("norm_eps", 1e-5))
    n = rms_norm(x, _param(p, i, "gain_norm"), eps) \
        if spec.get("pre_norm") else x
    qkv = mm(n, _param(p, i, "weights"))
    q, k, v = (qkv[..., j * d:(j + 1) * d] for j in range(3))
    if spec.get("qk_norm"):
        q = rms_norm(q, _param(p, i, "gain_q"), eps)
        k = rms_norm(k, _param(p, i, "gain_k"), eps)
    q, k, v = (a.reshape(b, t, heads, dh) for a in (q, k, v))
    if spec.get("rope"):
        q = rope(q, float(spec["rope"]["theta"]))
        k = rope(k, float(spec["rope"]["theta"]))
    blocks = []
    for lo in range(0, t, QUERY_BLOCK):    # never a T × T tensor
        hi = min(lo + QUERY_BLOCK, t)
        # every block against ALL keys under the mask: one shape for
        # the whole loop (eager jax.numpy compiles each new shape)
        s = jnp.einsum("bqhd,bkhd->bhqk", _r(q[:, lo:hi]), _r(k)) \
            / np.sqrt(dh)
        if spec.get("causal"):
            keep = (np.arange(lo, hi)[:, None] >= np.arange(t)[None, :])
            s = jnp.where(jnp.asarray(keep), s, -jnp.inf)
        blocks.append(jnp.einsum("bhqk,bkhd->bqhd",
                                 _r(jax.nn.softmax(s, axis=-1)), _r(v)))
    o = jnp.concatenate(blocks, axis=1).reshape(b, t, d)
    y = mm(o, _param(p, i, "weights_out"))
    return x + y if spec.get("residual") else y


def route(m, p: dict, i: int):
    """Router logits and probabilities of (N, D) rows (float32 in
    every configuration: ``matmul_inputs`` does not reach it)."""
    logits = m @ _param(p, i, "weights")
    return logits, jax.nn.softmax(logits, axis=-1)


def top_k(probs, k: int) -> np.ndarray:
    """(N, k) experts, the largest probability first, ties to the
    lower index."""
    return np.argsort(-np.asarray(probs), axis=-1, kind="stable")[:, :k]


def moe_block(x, p: dict, i: int, spec: dict, chosen=None):
    """``(y, logits, (lb, z), chosen)``; ``chosen`` (N, k) names the
    experts to use (the reference's own top-k when ``None``)."""
    b, t, d = x.shape
    n_tok, experts = b * t, int(spec["n_experts"])
    k = int(spec["top_k"])
    m = (rms_norm(x, _param(p, i, "gain_norm"),
                  float(spec.get("norm_eps", 1e-5)))
         if spec.get("pre_norm") else x).reshape(n_tok, d)
    logits, probs = route(m, p, i)
    if chosen is None:
        chosen = top_k(probs, k)
    chosen = np.asarray(chosen).reshape(n_tok, k)
    top_p = jnp.take_along_axis(probs, jnp.asarray(chosen), axis=-1)
    if spec.get("norm_topk"):
        top_p = top_p / top_p.sum(axis=-1, keepdims=True)
    w_gate, w_up, w_down = (_param(p, i, f"weights_{name}")
                            for name in ("gate", "up", "down"))
    f = jnp.zeros((n_tok, d), jnp.float32)
    rows_per_expert = np.asarray(
        [(chosen == e).sum() for e in range(experts)], np.float32)
    # every expert's rows padded to one length (the pad: row 0 at
    # weight 0), so that the loop runs ONE shape: eager jax.numpy
    # compiles each new shape, and 64 experts of 64 row counts cost
    # minutes of compiling on the host
    cap = -(-int(rows_per_expert.max()) // 128) * 128
    for e in range(experts):               # the mask: chosen == e
        rows, slots = np.nonzero(chosen == e)
        real = np.arange(cap) < len(rows)
        rows = np.concatenate([rows, np.zeros(cap - len(rows), np.int64)])
        slots = np.concatenate([slots, np.zeros(cap - len(slots),
                                                np.int64)])
        weight = top_p[rows, slots] * jnp.asarray(real, jnp.float32)
        me = m[rows]
        hidden = jax.nn.silu(mm(me, w_gate[e])) * mm(me, w_up[e])
        f = f.at[rows].add(mm(hidden, w_down[e]) * weight[:, None])
    y = f.reshape(b, t, d)
    if spec.get("residual"):
        y = x + y
    lb = experts * jnp.sum(jnp.asarray(rows_per_expert / n_tok)
                           * probs.mean(axis=0))
    lse = jax.nn.logsumexp(logits, axis=-1)
    return y, logits, (lb, jnp.mean(lse * lse)), chosen


def run(params: dict, layers: list, tokens, routing: dict | None = None
        ) -> tuple:
    """Every layer's output for ``tokens`` (B, T), as the training
    step's forward computes them, the last being the softmax over the
    vocabulary at every position; with them, per expert layer (keyed
    by its index): the router's logits, the experts used, the two
    auxiliary losses."""
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
            elif kind == "moe":
                h, logits[i], aux[i], chosen[i] = moe_block(
                    h, params, i, spec, (routing or {}).get(i))
            elif kind == "rms_norm":
                h = rms_norm(h, _param(params, i, "weights"),
                             float(spec.get("eps", 1e-5)))
            elif kind == "softmax" and spec.get("per_position"):
                h = jax.nn.softmax(mm(h, _param(params, i, "weights")),
                                   axis=-1)
            else:
                raise ValueError(f"reference/olmoe: no layer {kind!r}")
            outs.append(h)
    return outs, {"logits": logits, "chosen": chosen, "aux": aux}


def forward(params: dict, layers: list, tokens,
            routing: dict | None = None) -> list:
    return [np.asarray(o) for o in run(params, layers, tokens,
                                       routing)[0]]


def loss(params: dict, layers: list, tokens, labels,
         routing: dict | None = None):
    """Next-token cross-entropy, mean over every position, plus each
    expert layer's weighted auxiliary losses."""
    outs, router = run(params, layers, tokens, routing)
    probs = outs[-1]
    labels = jnp.asarray(np.asarray(labels), jnp.int32)
    p_true = jnp.take_along_axis(probs, labels[..., None], axis=-1)
    total = -jnp.mean(jnp.log(p_true))
    for i, (lb, z) in router["aux"].items():
        spec = layers[i]["->"]
        total = total + float(spec.get("aux_loss_weight", 0.0)) * lb \
            + float(spec.get("z_loss_weight", 0.0)) * z
    return total


def loss_and_grads(params: dict, layers: list, tokens, labels) -> tuple:
    """``jax.value_and_grad`` of :func:`loss` in every parameter, at
    the reference's own choice of experts (made in a pass of its own:
    the choice is a constant of the differentiated function)."""
    routing = run(params, layers, tokens)[1]["chosen"]
    as_arrays = {name: jnp.asarray(value, jnp.float32)
                 for name, value in params.items()}
    value, grads = jax.value_and_grad(loss)(as_arrays, layers, tokens,
                                            labels, routing)
    return float(value), {k: np.asarray(g) for k, g in grads.items()}

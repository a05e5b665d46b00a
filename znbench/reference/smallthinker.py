"""Plain reference of the ``smallthinker_21b_a3b`` configuration
(PowerInfer SmallThinker-21BA3B-Instruct, arXiv:2507.20984): token
embedding → N × (pre-norm attention block → pre-norm sparse-expert
block whose ROUTER reads the attention block's input) → final RMSNorm →
untied head, softmax at every position — in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: attention one K/V head and
one block of query rows at a time as two plain matmuls with the band as
a mask, a loop over the experts with a mask, no kernels, no sort, no
grouped matmul.
Independent of the code under test: it reads only the layer table and
the parameters, keyed as a bundle is (``layer<i>_weights``,
``_weights_out``, ``_gain_norm``, ``_weights_gate``, ``_up``,
``_down``).  Run it on the host's CPU device when the chip is full
(``jax.default_device``).

The layer equations (block l with input x of T × D, RMSNorm with a
gain; the table holds the block as two layers, ``attention`` then
``moe``):

.. code-block:: text

    r = x W_r                    E logits, from the block's INPUT: before
                                 the input norm and before attention
    S = the top_k largest of r;  p = softmax(r[S])
                                 (softmax over all E, the top k of it,
                                 renormalised to sum 1: the same numbers)

    n = RMSNorm(x; g1)
    q = n W_q   (H heads of dh)     k, v = n W_k, n W_v   (H_kv heads)
        query head h reads K/V head h // (H / H_kv); no biases, no
        q/k norm
    with ``rope`` (rope_layout[l] = 1): q, k = RoPE(q), RoPE(k) over the
        whole head, half-split (x1 cos − x2 sin, x2 cos + x1 sin), and
        with ``window`` W row r sees columns in (r − W, r]
    without (rope_layout[l] = 0): NO positional signal, every column ≤ r
    o_h = softmax(q_h k_g^T / sqrt(dh) + mask) v_g
    a = x + concat_h(o_h) W_o

    m = RMSNorm(a; g2)
    y = a + sum_{e in S and held} p_e W_down,e (relu(W_gate,e m) * W_up,e m)

    loss = mean_t CE(head(RMSNorm(y_last)), next token)

``held`` (the layer table's, or the argument of :func:`run`) is the set
of experts whose weights exist here — one chip's share of an
expert-parallel deployment: the router and its top k are over all E,
the sum is over the chosen experts that are held, nothing stands in for
the others; the slabs are indexed by an expert's place in ``held``.
:func:`run` with another ``held`` and the matching slabs gives another
chip's share; the shares' routed parts add up to the uncut layer's
(``tests/test_smallthinker_reference.py``).

Departures from the published model, all in the configuration's file:
depth (one period of four blocks), the experts held, the vocabulary
slice, momentum SGD, random weights.  What the published config does
not settle and this file assumes (the configuration's ``assumed``): the
router's input is the RAW stream x; the window's edge is (r − W, r];
the rotation is half-split; no auxiliary loss.  The choice of experts
is piecewise constant, so its gradient is nil and ``routing`` — the
experts chosen, by the reference itself or handed in by the caller — is
a constant of the differentiated function.

A layer table may state what the published model does NOT do, so that
a comparison can be shown to have teeth
(``benchmarks/smallthinker_controls.py``, the tests): ``route_from``
None on an expert layer (the router reads m, the usual place),
``route_normed`` (it reads RMSNorm(x; g1)), ``act`` "silu",
``norm_topk`` false, a ``rope`` on layer 0, no ``rope`` or no ``window``
on a window layer.
"""

from __future__ import annotations

import functools

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


def _normed(x, p: dict, i: int, spec: dict):
    return rms_norm(x, _param(p, i, "gain_norm"),
                    float(spec.get("norm_eps", 1e-5))) \
        if spec.get("pre_norm") else x


# ----------------------------------------------------------------------
# the attention block
# ----------------------------------------------------------------------
def rope(x, theta: float):
    """(B, T, H, dh) rotated by position over the whole head: with x1,
    x2 its two halves, (x1 cos − x2 sin, x2 cos + x1 sin); the angles
    in float64."""
    t, dh = x.shape[1], x.shape[3]
    inv_freq = np.asarray([theta ** (-2.0 * i / dh)
                           for i in range(dh // 2)], np.float64)
    angle = np.arange(t, dtype=np.float64)[:, None] * inv_freq[None, :]
    cos = jnp.asarray(np.cos(angle), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(angle), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


@functools.partial(jax.jit, static_argnums=4)
def _rows_attend(qg, kg, vg, keep, inputs):
    """softmax(q kᵀ / √dh + mask) v of (rows, dh) queries over one K/V
    head's (keys, dh) slab — one program a shape, so that the mask and
    the softmax are one pass over the scores; ``inputs``: the dtype the
    matmuls' inputs are rounded to (``matmul_inputs``), static."""
    with matmul_inputs(inputs), jax.default_matmul_precision("highest"):
        s = mm(qg, kg.T) / np.sqrt(qg.shape[-1])
        s = jnp.where(keep, s, -jnp.inf)
        return mm(jax.nn.softmax(s, axis=-1), vg)


#: a full layer's query block meets the keys up to its own last row,
#: rounded up to this many (few distinct shapes: eager jax.numpy
#: compiles each new one)
KEY_STEP = 2048


def attention_core(q, k, v, window):
    """(B, T, H, dh) × (B, T, H_kv, dh) → (B, T, H, dh): causal softmax
    attention, with a ``window`` over columns > row − window.  One
    sequence, one K/V head and one block of query rows at a time, the
    group's query heads stacked under one another so that the scores
    and the values are plain 2-D matmuls; a block meets the slab of
    keys it can see — the window's, or the keys up to its last row —
    and the mask cuts the rest."""
    b, t, h, dh = q.shape
    h_kv = k.shape[2]
    group = h // h_kv
    block = min(QUERY_BLOCK, t)
    out = []
    for lo in range(0, t, block):
        hi = min(lo + block, t)
        if window is None:
            k0, k1 = 0, min(-(-hi // KEY_STEP) * KEY_STEP, t)
        else:
            slab = min(t, block + int(window))
            k0 = min(max(hi - slab, 0), t - slab)
            k1 = k0 + slab
        rows = np.arange(lo, hi)[:, None]
        cols = np.arange(k0, k1)[None, :]
        keep = rows >= cols
        if window is not None:
            keep &= cols > rows - int(window)
        keep = jnp.asarray(np.tile(keep, (group, 1)))   # head-major rows
        heads = []
        for n in range(b):
            per_kv = []
            for g in range(h_kv):
                # (group · rows, dh): query head by query head
                qg = q[n, lo:hi, g * group:(g + 1) * group].transpose(
                    1, 0, 2).reshape(group * (hi - lo), dh)
                kg, vg = k[n, k0:k1, g], v[n, k0:k1, g]
                o = _rows_attend(qg, kg, vg, keep, _INPUTS)
                per_kv.append(o.reshape(group, hi - lo, dh).transpose(
                    1, 0, 2))
            heads.append(jnp.concatenate(per_kv, axis=1))
        out.append(jnp.stack(heads))
    return jnp.concatenate(out, axis=1)


def attention_block(x, p: dict, i: int, spec: dict):
    b, t, d = x.shape
    heads = int(spec["n_heads"])
    kv_heads = int(spec.get("n_kv_heads") or heads)
    dh = int(spec.get("head_dim") or d // heads)
    if not spec.get("causal"):
        raise ValueError("reference/smallthinker: attention is causal")
    n = _normed(x, p, i, spec)
    qkv = mm(n, _param(p, i, "weights"))
    qw, kw = heads * dh, kv_heads * dh
    q = qkv[..., :qw].reshape(b, t, heads, dh)
    k = qkv[..., qw:qw + kw].reshape(b, t, kv_heads, dh)
    v = qkv[..., qw + kw:].reshape(b, t, kv_heads, dh)
    if spec.get("rope"):
        if set(spec["rope"]) - {"theta"}:
            raise ValueError(f"reference/smallthinker: a rotation over "
                             f"the whole head at one theta, got "
                             f"{spec['rope']}")
        theta = float(spec["rope"]["theta"])
        q, k = rope(q, theta), rope(k, theta)
    o = attention_core(q, k, v, spec.get("window"))
    y = mm(o.reshape(b, t, qw), _param(p, i, "weights_out"))
    return x + y if spec.get("residual") else y


# ----------------------------------------------------------------------
# the expert block
# ----------------------------------------------------------------------
def gate_fn(name: str):
    return {"relu": lambda a: jnp.maximum(a, 0.0),
            "silu": jax.nn.silu}[name]


def gated(m, w_gate, w_up, w_down, act: str = "relu"):
    return mm(gate_fn(act)(mm(m, w_gate)) * mm(m, w_up), w_down)


def route(m, p: dict, i: int):
    """Router logits and scores — the softmax over ALL the experts — of
    (N, D) rows (float32 in every configuration: ``matmul_inputs`` does
    not reach it)."""
    logits = m @ _param(p, i, "weights")
    return logits, jax.nn.softmax(logits, axis=-1)


def top_k(scores, k: int) -> np.ndarray:
    """(N, k) experts, the largest score first, ties to the lower
    index."""
    return np.argsort(-np.asarray(scores), axis=-1, kind="stable")[:, :k]


def router_rows(x, block_input, p: dict, i: int, spec: dict,
                before: tuple | None):
    """The (N, D) rows layer ``i``'s router reads: the block's input as
    it is (``route_from="block_input"``, the published model) — or,
    stated otherwise in the table, that input under the attention
    block's norm (``route_normed``; ``before`` = that layer's index and
    spec), or the expert block's own normed input (no ``route_from``)."""
    d = x.shape[-1]
    if spec.get("route_from") is None:
        return _normed(x, p, i, spec).reshape(-1, d)
    if spec["route_from"] != "block_input" or block_input is None:
        raise ValueError(f"reference/smallthinker: layer {i} routes from "
                         f"{spec['route_from']!r} and the layer before "
                         f"it is no sublayer")
    if spec.get("route_normed"):
        block_input = _normed(block_input, p, *before)
    return block_input.reshape(-1, d)


def moe_block(x, p: dict, i: int, spec: dict, chosen=None, held=None,
              block_input=None, before=None):
    """``(y, logits, chosen)``; ``chosen`` (N, k) names the experts to
    use (the reference's own top-k when ``None``); ``held`` the experts
    whose slabs ``p`` holds, in the slabs' order (the layer table's,
    else all); ``block_input`` what the attention block before this one
    read."""
    b, t, d = x.shape
    n_tok, experts = b * t, int(spec["n_experts"])
    k = int(spec["top_k"])
    if spec.get("score", "softmax") != "softmax":
        raise ValueError("reference/smallthinker: experts are scored by "
                         "the softmax over all of them")
    if spec.get("aux_loss_weight") or spec.get("z_loss_weight") \
            or spec.get("shared_width"):
        raise ValueError("reference/smallthinker: no auxiliary loss and "
                         "no shared expert")
    if held is None:
        held = spec.get("held")
    held = list(range(experts)) if held is None else sorted(held)
    m = _normed(x, p, i, spec).reshape(n_tok, d)
    logits, scores = route(
        router_rows(x, block_input, p, i, spec, before), p, i)
    if chosen is None:
        chosen = top_k(scores, k)
    chosen = np.asarray(chosen).reshape(n_tok, k)
    weight = jnp.take_along_axis(scores, jnp.asarray(chosen), axis=-1)
    if spec.get("norm_topk"):
        weight = weight / weight.sum(axis=-1, keepdims=True)
    act = spec.get("act", "silu")
    w_gate, w_up, w_down = (_param(p, i, f"weights_{name}")
                            for name in ("gate", "up", "down"))
    f = jnp.zeros((n_tok, d), jnp.float32)
    # every expert's rows padded to one length (the pad: row 0 at
    # weight 0), so that the loop runs ONE shape
    most = max([int((chosen == e).sum()) for e in held] + [1])
    cap = -(-most // 128) * 128
    for slot, e in enumerate(held):        # the mask: chosen == e
        rows, slots = np.nonzero(chosen == e)
        real = np.arange(cap) < len(rows)
        rows = np.concatenate([rows, np.zeros(cap - len(rows), np.int64)])
        slots = np.concatenate([slots, np.zeros(cap - len(slots),
                                                np.int64)])
        w = weight[rows, slots] * jnp.asarray(real, jnp.float32)
        f = f.at[rows].add(
            gated(m[rows], w_gate[slot], w_up[slot], w_down[slot], act)
            * w[:, None])
    y = f.reshape(b, t, d)
    if spec.get("residual"):
        y = x + y
    return y, logits, chosen


# ----------------------------------------------------------------------
# the model
# ----------------------------------------------------------------------
def run(params: dict, layers: list, tokens, routing: dict | None = None,
        held: dict | None = None) -> tuple:
    """Every layer's output for ``tokens`` (B, T), as the training
    step's forward computes them, the last being the softmax over the
    vocabulary at every position; with them, per expert layer (keyed by
    its index): the router's logits and the experts used.  ``held``
    (layer index → experts) overrides the layer table's share."""
    outs, logits, chosen = [], {}, {}
    with jax.default_matmul_precision("highest"):
        h = came_in = None
        for i, layer in enumerate(layers):
            kind, spec = layer["type"], layer.get("->", {})
            # what the layer before this one read, where that layer is
            # a residual sublayer: the block's input
            sublayer = i > 0 and layers[i - 1].get("->", {}).get("residual")
            block_input, came_in = (came_in if sublayer else None), h
            if kind == "embedding":
                ids = np.asarray(np.round(np.asarray(tokens)), np.int64)
                h = _param(params, i, "weights")[ids]
            elif kind == "attention":
                h = attention_block(h, params, i, spec)
            elif kind == "moe":
                h, logits[i], chosen[i] = moe_block(
                    h, params, i, spec, (routing or {}).get(i),
                    (held or {}).get(i), block_input,
                    (i - 1, layers[i - 1].get("->", {})))
            elif kind == "rms_norm":
                h = rms_norm(h, _param(params, i, "weights"),
                             float(spec.get("eps", 1e-5)))
            elif kind == "softmax" and spec.get("per_position"):
                h = jax.nn.softmax(mm(h, _param(params, i, "weights")),
                                   axis=-1)
            else:
                raise ValueError(
                    f"reference/smallthinker: no layer {kind!r}")
            outs.append(h)
    return outs, {"logits": logits, "chosen": chosen}


def forward(params: dict, layers: list, tokens,
            routing: dict | None = None, held: dict | None = None
            ) -> list:
    return [np.asarray(o) for o in run(params, layers, tokens, routing,
                                       held)[0]]


def loss(params: dict, layers: list, tokens, labels,
         routing: dict | None = None, held: dict | None = None):
    """Next-token cross-entropy, mean over every position."""
    probs = run(params, layers, tokens, routing, held)[0][-1]
    labels = jnp.asarray(np.asarray(labels), jnp.int32)
    p_true = jnp.take_along_axis(probs, labels[..., None], axis=-1)
    return -jnp.mean(jnp.log(p_true))


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

"""Plain reference of the ``ouro_2_6b`` configuration (ByteDance
Ouro-2.6B, a looped language model: arXiv:2510.25741): token embedding
→ R passes over ONE stack of N layers with shared weights, the final
RMSNorm after every pass → the same head and the same exit gate on
every pass's state → the exit distribution and the expected loss — in
float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
no kernels, the passes a Python loop, attention one block of query
rows at a time.  It reads only the layer table (a looped span is the
entries with ``"passes": R``) and the parameters, keyed as a bundle is
(``layer<i>_<attr>``).  Run it on the host's CPU device when the chip
is full (``jax.default_device``).

The equations (x of T × D; RMSNorm with a gain each, eps from the
table):

.. code-block:: text

    h⁰ = E[x]
    for r = 1 … R:                u = h^(r−1)
      for every layer l of the span:
        attention:  u ← u + RMSNorm_post(Attn(RMSNorm_pre(u)))
        gated_mlp:  u ← u + RMSNorm_post(W_down(silu(W_gate n) ⊙ W_up n)),
                                                      n = RMSNorm_pre(u)
      h^r = RMSNorm_f(u)              the span's last entry (rms_norm):
                                      what the NEXT pass takes up
      p^r = softmax(h^r W)            λ^r = σ(h^r w_exit + b_exit)
    Attn: q, k, v = n W_q, n W_k, n W_v (``weights`` = W_q | W_k | W_v),
          H heads of dh, rotary position over the whole head (half-split,
          theta from the table), causal softmax(q kᵀ/√dh) v, then W_o
    q_1 = λ¹;  q_r = λ^r ∏_{j<r}(1 − λ^j);  q_R = ∏_{j<R}(1 − λ^j)
    ℓ = Σ_r q_r · CE(p^r, y) − β · H(q);     loss = mean over positions

Departures from the published description, all in the configuration's
file under ``assumed``: the final norm applied after EVERY pass with
its output carried into the next one, the exit gate on that normed
state, β = 0.1, momentum SGD, depth and the vocabulary slice, random
weights.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512

#: ``None``: plain float32.  A name → dtype map: that part of the model
#: is rounded to the dtype first — how the comparison's limits are
#: shown to have teeth (``lowered``): ``carry`` the state h^r handed
#: from pass to pass, ``gate`` the exit gate's inputs, ``embedding``
#: the table, ``matmul`` every product's two inputs
_LOWER: dict = {}


class lowered:
    """``with lowered(carry=jnp.bfloat16): forward(...)``."""

    def __init__(self, **parts) -> None:
        self.parts = parts

    def __enter__(self):
        global _LOWER
        self.old, _LOWER = _LOWER, {**_LOWER, **self.parts}

    def __exit__(self, *exc):
        global _LOWER
        _LOWER = self.old


def _r(a, part: str):
    dtype = _LOWER.get(part)
    return a if dtype is None else a.astype(dtype).astype(jnp.float32)


def mm(a, b):
    return _r(a, "matmul") @ _r(b, "matmul")


def rms_norm(x, gain, eps: float):
    return gain * x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _param(p: dict, i: int, name: str):
    return jnp.asarray(p[f"layer{i}_{name}"], jnp.float32)


def rope(x, theta: float):
    """(B, T, H, dh) rotated by position over the whole head, the two
    halves of a head being the pairs (transformers' ``rotate_half``)."""
    t, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh)
    angle = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(angle), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(angle), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def attention(n, p: dict, i: int, spec: dict):
    b, t, d = n.shape
    heads = int(spec["n_heads"])
    dh = d // heads
    qkv = mm(n, _param(p, i, "weights")).reshape(b, t, 3, heads, dh)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    if spec.get("rope"):
        q = rope(q, float(spec["rope"]["theta"]))
        k = rope(k, float(spec["rope"]["theta"]))
    cols = np.arange(t)[None, :]
    out = []
    for lo in range(0, t, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, t)
        keep = jnp.asarray(np.arange(lo, hi)[:, None] >= cols)
        s = jnp.einsum("bqhd,bkhd->bhqk", _r(q[:, lo:hi], "matmul"),
                       _r(k, "matmul")) / np.sqrt(dh)
        s = jnp.where(keep[None, None], s, -jnp.inf)
        out.append(jnp.einsum(
            "bhqk,bkhd->bqhd", _r(jax.nn.softmax(s, axis=-1), "matmul"),
            _r(v, "matmul")))
    o = jnp.concatenate(out, axis=1).reshape(b, t, d)
    return mm(o, _param(p, i, "weights_out"))


def gated_mlp(n, p: dict, i: int, spec: dict):
    return mm(jax.nn.silu(mm(n, _param(p, i, "weights")))
              * mm(n, _param(p, i, "weights_up")),
              _param(p, i, "weights_down"))


#: looked up when a layer runs, so that a test can put a term out of
#: action by replacing one function of this module
MIXERS = {"attention": lambda *a: attention(*a),
          "gated_mlp": lambda *a: gated_mlp(*a)}


def carry(h):
    """What pass r + 1 takes up of pass r's normed state: all of it.
    (A test replaces this to show that a cotangent not joined fails.)"""
    return _r(h, "carry")


#: the state the exit gate reads: ``normed`` (h^r, as assumed) or, for a
#: test of that assumption's teeth, ``raw`` (u, before the final norm)
GATE_READS = "normed"


def sublayer(u, p: dict, i: int, kind: str, spec: dict):
    """u + RMSNorm_post(f(RMSNorm_pre(u))): ``gain_norm`` the gain
    before f, ``gain_post`` the one on its output."""
    eps = float(spec.get("norm_eps", 1e-5))
    n = rms_norm(u, _param(p, i, "gain_norm"), eps)
    return u + rms_norm(MIXERS[kind](n, p, i, spec),
                        _param(p, i, "gain_post"), eps)


def exit_distribution(lam):
    """q over axis 1 of λ (B, R, T), by the product written out."""
    r = lam.shape[1]
    q, left = [], jnp.ones_like(lam[:, 0])
    for j in range(r - 1):
        q.append(lam[:, j] * left)
        left = left * (1.0 - lam[:, j])
    return jnp.stack(q + [left], axis=1)


def gate(h, p: dict, i: int):
    """λ (B, R, T) of the normed states h (B, R, T, D)."""
    z = _r(h, "gate") @ _r(_param(p, i, "weights_exit"), "gate") \
        + _param(p, i, "bias_exit")[0]
    return jax.nn.sigmoid(z)


# ----------------------------------------------------------------------
# the model
# ----------------------------------------------------------------------
def span_of(layers: list) -> tuple[int, int, int]:
    """(first, one past the last, R) of the table's looped span."""
    member = [i for i, layer in enumerate(layers) if "passes" in layer]
    if not member:          # a plain chain: one pass of what lies between
        return 1, len(layers) - 1, 1
    return member[0], member[-1] + 1, int(layers[member[0]]["passes"])


def run(params: dict, layers: list, tokens) -> tuple[list, object]:
    """``(every table entry's output, the exit distribution)`` for
    ``tokens`` (B, T), as the training step's forward computes them: a
    looped member's output is its LAST pass's, the head's is every
    pass's softmax (B, R, T, V)."""
    first, stop, passes = span_of(layers)
    if layers[0]["type"] != "embedding" or first != 1 \
            or stop != len(layers) - 1:
        raise ValueError("reference/ouro: the table is embedding, one "
                         "looped span, loop_exits")
    outs: list = [None] * len(layers)
    q = None
    with jax.default_matmul_precision("highest"):
        ids = np.asarray(np.round(np.asarray(tokens)), np.int64)
        h = _r(_param(params, 0, "weights"), "embedding")[ids]
        outs[0] = h
        states, raw = [], []
        for _ in range(passes):
            for i in range(first, stop):
                kind, spec = layers[i]["type"], layers[i].get("->", {})
                if kind in MIXERS:
                    h = sublayer(h, params, i, kind, spec)
                elif kind == "rms_norm":
                    raw.append(h)
                    h = rms_norm(h, _param(params, i, "weights"),
                                 float(spec.get("eps", 1e-5)))
                else:
                    raise ValueError(f"reference/ouro: no layer {kind!r}")
                outs[i] = h
            states.append(h)
            h = carry(h)                # what the next pass takes up
        for i in range(stop, len(layers)):
            kind = layers[i]["type"]
            if kind != "loop_exits":
                raise ValueError(f"reference/ouro: no layer {kind!r}")
            h = jnp.stack(states, axis=1)               # (B, R, T, D)
            q = exit_distribution(gate(
                h if GATE_READS == "normed" else jnp.stack(raw, axis=1),
                params, i))
            outs[i] = jax.nn.softmax(
                mm(h, _param(params, i, "weights")), axis=-1)
    return outs, q


def forward(params: dict, layers: list, tokens, routing=None) -> list:
    """One expected output per table entry.  ``routing`` is the
    driver's (an expert layer's choice); this model has none."""
    if routing:
        raise ValueError("reference/ouro: no expert layer")
    return [np.asarray(o) for o in run(params, layers, tokens)[0]]


def exits(params: dict, layers: list, tokens) -> np.ndarray:
    """The exit distribution q (B, R, T)."""
    return np.asarray(run(params, layers, tokens)[1])


def loss(params: dict, layers: list, tokens, labels):
    """Σ_r q_r CE_r − β H(q), mean over every position."""
    outs, q = run(params, layers, tokens)
    beta = float(layers[-1].get("->", {}).get("entropy_weight", 0.1))
    labels = jnp.asarray(np.asarray(labels), jnp.int32)
    p_true = jnp.take_along_axis(
        outs[-1], jnp.broadcast_to(labels[:, None, :, None],
                                   outs[-1].shape[:3] + (1,)), axis=-1)
    ce = -jnp.log(p_true[..., 0])                          # (B, R, T)
    entropy = -jnp.sum(q * jnp.log(q), axis=1)
    return jnp.mean(jnp.sum(q * ce, axis=1) - beta * entropy)


def loss_and_grads(params: dict, layers: list, tokens, labels) -> tuple:
    """``jax.value_and_grad`` of :func:`loss` in every parameter (a
    looped member's is the sum over its passes, by construction)."""
    as_arrays = {name: jnp.asarray(value, jnp.float32)
                 for name, value in params.items()}
    value, grads = jax.jit(jax.value_and_grad(
        lambda p: loss(p, layers, tokens, labels)))(as_arrays)
    return float(value), {k: np.asarray(g) for k, g in grads.items()}

"""Plain reference of the ``olmo_hybrid_7b`` configuration (allenai
Olmo-Hybrid-7B, ``model_type`` olmo_hybrid): token embedding → N ×
(mixer block → gated-MLP block) → final RMSNorm → untied head, softmax
at every position; the mixer a gated-delta-rule linear-attention layer
three times in four and a QK-normed full-attention layer the fourth —
in float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``.
The delta rule is run as what it is, a recurrence over the positions
ONE TOKEN AT A TIME (``lax.scan`` over t, the state a d_k × d_v matrix
per head): no chunks, no triangular inverse, no kernels — nothing of
the algebra the program under test runs.  Attention is one head and
one block of query rows at a time.  It reads only the layer table and
the parameters, keyed as a bundle is (``layer<i>_<attr>``).  Run it on
the host's CPU device when the chip is full (``jax.default_device``).

The equations (x of T × D; RMSNorm with a gain, eps from the table;
every sublayer f is applied as x ← x + RMSNorm(f(x)) where the table
says ``post_norm``, x ← x + f(RMSNorm(x)) where it says ``pre_norm``):

.. code-block:: text

    gated_delta_net (H heads, d_k, d_v, J taps), per head h, position t:
      q~, k~, v~ = x W_q, x W_k, x W_v      ``weights`` = W_q | W_k | W_v
      u_t[c] = silu(sum_{j<J} taps[c, j] u~_{t-J+1+j}[c])   u~ = 0 before
                                            the sequence; over q~, k~, v~
      q_t = q_t / sqrt(|q_t|^2 + eps) / sqrt(d_k)
      k_t = k_t / sqrt(|k_t|^2 + eps)
      beta_t  = 2 sigmoid(x W_b)_h          (2: allow_neg_eigval)
      alpha_t = exp(-exp(A_h) softplus((x W_a)_h + b_h))
                                            ``weights_ba`` = W_b | W_a
      S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
      o_t = S_t^T q_t                       S_0 = 0, S of d_k x d_v
      y_t = concat_h(RMSNorm(o_t,h) g_o * silu((x W_g)_h)) W_o

    attention (H heads of dh, causal, no rotary position here):
      q = RMSNorm(x W_q), k = RMSNorm(x W_k)  over the WHOLE projection
      o_h = softmax(q_h k_h^T / sqrt(dh) + causal) v_h;   y = o W_o

    gated_mlp:  y = W_down (silu(W_gate x) * W_up x)

    loss = mean_t CE(head(RMSNorm(x_last)), next token)

Departures from the published model, all in the configuration's file:
depth, the vocabulary slice, momentum SGD, random weights.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512

#: ``None``: the state of the recurrence is float32.  A dtype: the state
#: is rounded to it after every token — how the comparison's limit is
#: shown to have teeth (``state_dtype``)
_STATE = None


class state_dtype:
    """``with state_dtype(jnp.bfloat16): forward(...)``."""

    def __init__(self, dtype) -> None:
        self.dtype = dtype

    def __enter__(self):
        global _STATE
        self.old, _STATE = _STATE, self.dtype

    def __exit__(self, *exc):
        global _STATE
        _STATE = self.old


#: ``None``: plain float32.  A dtype: every matmul's two inputs are
#: rounded to it first (the products and sums stay float32)
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


def _kept(state):
    return state if _STATE is None \
        else state.astype(_STATE).astype(jnp.float32)


def rms_norm(x, gain, eps: float):
    return gain * x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _param(p: dict, i: int, name: str):
    return jnp.asarray(p[f"layer{i}_{name}"], jnp.float32)


def _eps(spec: dict) -> float:
    return float(spec.get("norm_eps", 1e-5))


def sublayer(x, f, p: dict, i: int, spec: dict):
    """``f`` under the table's norm placement and skip."""
    if spec.get("pre_norm") and spec.get("post_norm"):
        raise ValueError("reference/olmo_hybrid: one norm placement")
    if spec.get("pre_norm"):
        y = f(rms_norm(x, _param(p, i, "gain_norm"), _eps(spec)))
    else:
        y = f(x)
    if spec.get("post_norm"):
        y = rms_norm(y, _param(p, i, "gain_norm"), _eps(spec))
    return x + y if spec.get("residual") else y


# ----------------------------------------------------------------------
# the gated delta rule, token by token
# ----------------------------------------------------------------------
def short_conv(u, taps):
    """(B, T, C) → (B, T, C): channel c's J taps over the J latest
    positions, zeros before the sequence."""
    t, width = u.shape[1], taps.shape[1]
    out = jnp.zeros_like(u)
    for j in range(width):
        back = width - 1 - j                # taps[:, j] meets u_{t-back}
        shifted = jnp.pad(u, ((0, 0), (back, 0), (0, 0)))[:, :t]
        out = out + shifted * taps[:, j]
    return out


def delta_rule(q, k, v, alpha, beta):
    """(B, T, H, d_k) × … → (B, T, H, d_v), the recurrence itself."""
    b, _, h, dk = q.shape

    def token(state, row):
        q_t, k_t, v_t, a_t, b_t = row
        state = state * a_t[..., None, None]
        seen = jnp.einsum("bhkv,bhk->bhv", state, k_t)
        state = _kept(state + b_t[..., None, None] * jnp.einsum(
            "bhk,bhv->bhkv", k_t, v_t - seen))
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    rows = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, alpha, beta))
    start = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    return jnp.moveaxis(jax.lax.scan(token, start, rows)[1], 0, 1)


def l2_norm(x, eps: float):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def gated_norm(o, gain, gate, eps: float):
    """Per head: RMSNorm over d_v with one gain, times silu(gate)."""
    return rms_norm(o, gain, eps) * jax.nn.silu(gate)


def gates(x, p: dict, i: int, spec: dict):
    """β (write strength) and α (decay), (B, T, H) each."""
    h = int(spec["n_heads"])
    ba = mm(x, _param(p, i, "weights_ba"))
    beta = jax.nn.sigmoid(ba[..., :h])
    if spec.get("allow_neg_eigval"):
        beta = 2.0 * beta
    alpha = jnp.exp(-jnp.exp(_param(p, i, "decay_log"))
                    * jax.nn.softplus(ba[..., h:]
                                      + _param(p, i, "decay_bias")))
    return beta, alpha


def delta_net_mixer(x, p: dict, i: int, spec: dict):
    b, t, _ = x.shape
    h, dk, dv = (int(spec[key]) for key in ("n_heads", "key_dim",
                                            "value_dim"))
    eps = _eps(spec)
    mixed = jax.nn.silu(short_conv(mm(x, _param(p, i, "weights")),
                                   _param(p, i, "weights_conv")))
    q = mixed[..., :h * dk].reshape(b, t, h, dk)
    k = mixed[..., h * dk:2 * h * dk].reshape(b, t, h, dk)
    v = mixed[..., 2 * h * dk:].reshape(b, t, h, dv)
    q, k = l2_norm(q, eps) / np.sqrt(dk), l2_norm(k, eps)
    beta, alpha = gates(x, p, i, spec)
    o = delta_rule(q, k, v, alpha, beta)
    gate = mm(x, _param(p, i, "weights_gate")).reshape(b, t, h, dv)
    o = gated_norm(o, _param(p, i, "gain_out"), gate, eps)
    return mm(o.reshape(b, t, h * dv), _param(p, i, "weights_out"))


# ----------------------------------------------------------------------
# full attention
# ----------------------------------------------------------------------
def attention_mixer(x, p: dict, i: int, spec: dict):
    b, t, d = x.shape
    heads = int(spec["n_heads"])
    dh = int(spec.get("head_dim") or d // heads)
    wide = heads * dh
    for option in ("rope", "window", "head_gate"):
        if spec.get(option):
            raise ValueError(f"reference/olmo_hybrid: attention has no "
                             f"{option}")
    if int(spec.get("n_kv_heads") or heads) != heads:
        raise ValueError("reference/olmo_hybrid: attention is multi-head")
    if not spec.get("causal"):
        raise ValueError("reference/olmo_hybrid: attention is causal")
    qkv = mm(x, _param(p, i, "weights"))
    q, k, v = qkv[..., :wide], qkv[..., wide:2 * wide], qkv[..., 2 * wide:]
    if spec.get("qk_norm"):
        q = rms_norm(q, _param(p, i, "gain_q"), _eps(spec))
        k = rms_norm(k, _param(p, i, "gain_k"), _eps(spec))
    q, k, v = (a.reshape(b, t, heads, dh) for a in (q, k, v))
    block = min(QUERY_BLOCK, t)
    cols = np.arange(t)[None, :]
    out = []
    for lo in range(0, t, block):
        hi = min(lo + block, t)
        keep = jnp.asarray(np.arange(lo, hi)[:, None] >= cols)
        per_head = []
        for g in range(heads):
            s = jnp.einsum("bqd,bkd->bqk", _r(q[:, lo:hi, g]),
                           _r(k[:, :, g])) / np.sqrt(dh)
            s = jnp.where(keep, s, -jnp.inf)
            per_head.append(jnp.einsum(
                "bqk,bkd->bqd", _r(jax.nn.softmax(s, axis=-1)),
                _r(v[:, :, g])))
        out.append(jnp.stack(per_head, axis=2))
    o = jnp.concatenate(out, axis=1).reshape(b, t, wide)
    return mm(o, _param(p, i, "weights_out"))


def gated_mlp(x, p: dict, i: int, spec: dict):
    return mm(jax.nn.silu(mm(x, _param(p, i, "weights")))
              * mm(x, _param(p, i, "weights_up")),
              _param(p, i, "weights_down"))


#: looked up when a layer runs, so that a test can put a term out of
#: action by replacing one function of this module
MIXERS = {"gated_delta_net":
          lambda *a: delta_net_mixer(*a),
          "attention": lambda *a: attention_mixer(*a),
          "gated_mlp": lambda *a: gated_mlp(*a)}


# ----------------------------------------------------------------------
# the model
# ----------------------------------------------------------------------
def run(params: dict, layers: list, tokens) -> list:
    """Every layer's output for ``tokens`` (B, T), as the training
    step's forward computes them, the last being the softmax over the
    vocabulary at every position."""
    outs = []
    with jax.default_matmul_precision("highest"):
        h = None
        for i, layer in enumerate(layers):
            kind, spec = layer["type"], layer.get("->", {})
            if kind == "embedding":
                ids = np.asarray(np.round(np.asarray(tokens)), np.int64)
                h = _param(params, i, "weights")[ids]
            elif kind in MIXERS:
                h = sublayer(
                    h, lambda m: MIXERS[kind](m, params, i, spec),
                    params, i, spec)
            elif kind == "rms_norm":
                h = rms_norm(h, _param(params, i, "weights"),
                             float(spec.get("eps", 1e-5)))
            elif kind == "softmax" and spec.get("per_position"):
                h = jax.nn.softmax(mm(h, _param(params, i, "weights")),
                                   axis=-1)
            else:
                raise ValueError(
                    f"reference/olmo_hybrid: no layer {kind!r}")
            outs.append(h)
    return outs


def forward(params: dict, layers: list, tokens, routing=None) -> list:
    """``routing`` is the driver's (the choice of experts of an expert
    layer); this model has none."""
    if routing:
        raise ValueError("reference/olmo_hybrid: no expert layer")
    return [np.asarray(o) for o in run(params, layers, tokens)]


def loss(params: dict, layers: list, tokens, labels):
    """Next-token cross-entropy, mean over every position."""
    probs = run(params, layers, tokens)[-1]
    labels = jnp.asarray(np.asarray(labels), jnp.int32)
    p_true = jnp.take_along_axis(probs, labels[..., None], axis=-1)
    return -jnp.mean(jnp.log(p_true))


def loss_and_grads(params: dict, layers: list, tokens, labels) -> tuple:
    """``jax.value_and_grad`` of :func:`loss` in every parameter."""
    as_arrays = {name: jnp.asarray(value, jnp.float32)
                 for name, value in params.items()}
    value, grads = jax.jit(jax.value_and_grad(
        lambda p: loss(p, layers, tokens, labels)))(as_arrays)
    return float(value), {k: np.asarray(g) for k, g in grads.items()}

"""Plain reference of the ``ling_3_0_flash`` configuration (inclusionAI
Ling-3.0-flash, ``model_type`` bailing_hybrid): token embedding →
N × (pre-norm mixer block → pre-norm feed-forward block) → final
RMSNorm → untied head, softmax at every position — in float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``, no
kernels: the delta rule TOKEN BY TOKEN (a ``lax.scan`` over positions of
the recurrence itself, none of the program's chunked algebra), the
latent attention with K assembled in full, the router with a plain
top-k twice, a loop over the experts with a mask.  Independent of the
code under test: it reads only the layer table and the parameters,
keyed as a bundle is.  Run it on the host's CPU device when the chip is
full (``jax.default_device``).

The layer equations (m = RMSNorm(x) with a gain, eps 1e-6):

.. code-block:: text

    gated_delta_net (decay "channel": Kimi Delta Attention, arXiv:2510.26692)
      q~, k~, v~ = m W_q, m W_k, m W_v  (``weights`` = W_q | W_k | W_v)
      u_t[c] = silu(sum_j taps[c, j] u~_{t-3+j}[c])   over q~, k~, v~ alike
      q = q / |q| / sqrt(d_k), k = k / |k|            per head
      beta = sigmoid(m W_b)                            one per head
      log alpha = lower_bound * sigmoid(exp(A_h) (m W_f + b))
                                        one per KEY CHANNEL, in (-5, 0)
                                        (``weights_ba`` = W_b | W_f)
      S_t = Diag(alpha_t) S_{t-1} + beta_t k_t (v_t - (Diag(alpha_t) S_{t-1})^T k_t)^T
      o_t = S_t^T q_t
      y = x + concat_h(RMSNorm_h(o_t) * sigmoid((m W_g)_h)) W_o

    attention with kv_latent (multi-head latent attention, arXiv:2405.04434
    section 2.1, no query latent)
      [q_nope | q_rope | c | k_r] = m W ; c = RMSNorm(c)
      [k_nope | v] = c W_up             all heads' k_nope, then all heads' v
      q_rope, k_r rotated; k_r is ONE key for all heads
      s_h = (q_nope,h . k_nope,h + q_rope,h . k_r) / sqrt(nope + rope), causal
      y = x + concat_h(sigmoid((m W_gate)_h) softmax(s_h) v_h) W_o

    moe (DeepSeek-V3's router, arXiv:2412.19437 section 2.1.2)
      s = sigmoid(m W_r)
      g_G = sum of the 2 largest of (s + b) in group G; the best
        ``groups[1]`` groups are kept; top = the top_k largest of (s + b)
        among their experts
      w_e = routed_scale * s_e / sum_{top} s     from s, never from s + b
      y = x + Shared(m) + sum_{e in top and held} w_e Expert_e(m)

Departures from the published description, each a choice of LAYOUT or
of notation, none of arithmetic:

- the columns of W (q_nope of all heads, q_rope of all heads, c, k_r)
  and of W_up (all k_nope, then all v) stand side by side by PART, the
  published ones by head: a fixed permutation of columns;
- the config says ``rope_interleave``: pairs (2i, 2i + 1) rotate by
  angle i.  :func:`rope_interleaved` does exactly that, on the rotary
  columns taken in the order ``PAIRS`` = (0, r/2, 1, r/2 + 1, …) — the
  fixed permutation by which the half-split convention of the code
  under test differs; it is applied to q_rope and to k_r alike, so
  every score q . k is the one the published order gives
  (``tests/test_ling_reference.py`` shows both);
- depth, experts held, the vocabulary slice, momentum SGD, random
  weights: the configuration's file.

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
#: likewise for the delta rule's state between tokens, for the sums of
#: log alpha (here: log alpha itself, rounded), for the latent's norm
_STATE = None
_DECAY = None
_LATENT = None


class _setting:
    name = ""

    def __init__(self, dtype) -> None:
        self.dtype = dtype

    def __enter__(self):
        self.old = globals()[self.name]
        globals()[self.name] = self.dtype

    def __exit__(self, *exc):
        globals()[self.name] = self.old


class matmul_inputs(_setting):
    """``with matmul_inputs(jnp.float8_e4m3fn): forward(...)``."""
    name = "_INPUTS"


class state_dtype(_setting):
    name = "_STATE"


class decay_dtype(_setting):
    name = "_DECAY"


class latent_dtype(_setting):
    name = "_LATENT"


def _round(a, dtype):
    return a if dtype is None else a.astype(dtype).astype(jnp.float32)


def _r(a):
    return _round(a, _INPUTS)


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
# the delta rule with a decay per key channel, token by token
# ----------------------------------------------------------------------
def short_conv(u, taps):
    """(B, T, C) → (B, T, C): channel c's J taps over the J latest
    positions, zeros before the sequence."""
    t, width = u.shape[1], taps.shape[1]
    out = jnp.zeros_like(u)
    for j in range(width):
        back = width - 1 - j
        shifted = jnp.pad(u, ((0, 0), (back, 0), (0, 0)))[:, :t]
        out = out + shifted * taps[:, j]
    return out


def delta_rule(q, k, v, alpha, beta):
    """(B, T, H, d_k) × … → (B, T, H, d_v), the recurrence itself;
    ``alpha`` (B, T, H, d_k) decays S's rows."""
    b, _, h, dk = q.shape

    def token(state, row):
        q_t, k_t, v_t, a_t, b_t = row
        state = state * a_t[..., None]
        seen = jnp.einsum("bhkv,bhk->bhv", state, k_t)
        state = _round(state + b_t[..., None, None] * jnp.einsum(
            "bhk,bhv->bhkv", k_t, v_t - seen), _STATE)
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    rows = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, alpha, beta))
    start = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    return jnp.moveaxis(jax.lax.scan(token, start, rows)[1], 0, 1)


def l2_norm(x, eps: float):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def decays(m, p: dict, i: int, spec: dict):
    """β (B, T, H) and α (B, T, H, d_k) ∈ (e^lower_bound, 1)."""
    h, dk = int(spec["n_heads"]), int(spec["key_dim"])
    if spec.get("decay") != "channel" or spec.get("lower_bound") is None:
        raise ValueError("reference/ling: the linear layers decay per "
                         "key channel under a lower bound")
    ba = mm(m, _param(p, i, "weights_ba"))
    beta = jax.nn.sigmoid(ba[..., :h])
    if spec.get("allow_neg_eigval"):
        beta = 2.0 * beta
    logits = (ba[..., h:] + _param(p, i, "decay_bias")).reshape(
        m.shape[:2] + (h, dk))
    log_alpha = float(spec["lower_bound"]) * jax.nn.sigmoid(
        jnp.exp(_param(p, i, "decay_log"))[:, None] * logits)
    return beta, jnp.exp(_round(log_alpha, _DECAY))


def output_gate(gate):
    return jax.nn.sigmoid(gate)


def kda_mixer(m, p: dict, i: int, spec: dict):
    b, t, _ = m.shape
    h, dk, dv = (int(spec[key]) for key in ("n_heads", "key_dim",
                                            "value_dim"))
    eps = _eps(spec)
    mixed = jax.nn.silu(short_conv(mm(m, _param(p, i, "weights")),
                                   _param(p, i, "weights_conv")))
    q = mixed[..., :h * dk].reshape(b, t, h, dk)
    k = mixed[..., h * dk:2 * h * dk].reshape(b, t, h, dk)
    v = mixed[..., 2 * h * dk:].reshape(b, t, h, dv)
    q, k = l2_norm(q, eps) / np.sqrt(dk), l2_norm(k, eps)
    beta, alpha = decays(m, p, i, spec)
    o = delta_rule(q, k, v, alpha, beta)
    gate = mm(m, _param(p, i, "weights_gate")).reshape(b, t, h, dv)
    if spec.get("gate") != "sigmoid":
        raise ValueError("reference/ling: the output gate is a sigmoid")
    o = rms_norm(o, _param(p, i, "gain_out"), eps) * output_gate(gate)
    return mm(o.reshape(b, t, h * dv), _param(p, i, "weights_out"))


# ----------------------------------------------------------------------
# latent attention
# ----------------------------------------------------------------------
def pairs(rot: int) -> np.ndarray:
    """The rotary columns in the order whose neighbours (2i, 2i + 1)
    are the half-split convention's partners (i, i + rot/2)."""
    return np.arange(rot).reshape(2, rot // 2).T.reshape(rot)


def rope_interleaved(x, theta: float):
    """(B, T, H, r) rotated as published: columns (2i, 2i + 1) are a
    pair, turned by pos · theta^(-2i/r)."""
    t, rot = x.shape[1], x.shape[-1]
    inv_freq = np.asarray([theta ** (-2.0 * i / rot)
                           for i in range(rot // 2)], np.float64)
    angle = np.arange(t, dtype=np.float64)[:, None] * inv_freq[None, :]
    cos = jnp.asarray(np.cos(angle), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(angle), jnp.float32)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def latent_mixer(m, p: dict, i: int, spec: dict):
    b, t, _ = m.shape
    h = int(spec["n_heads"])
    latent, nope, rope, dv = (int(spec[key]) for key in (
        "kv_latent", "qk_nope", "qk_rope", "v_head_dim"))
    if not spec.get("causal"):
        raise ValueError("reference/ling: attention is causal")
    theta = float(spec["rope"]["theta"])
    proj = mm(m, _param(p, i, "weights"))
    at = h * nope
    q_nope = proj[..., :at].reshape(b, t, h, nope)
    q_rope = proj[..., at:at + h * rope].reshape(b, t, h, rope)
    c = proj[..., at + h * rope:at + h * rope + latent]
    k_r = proj[..., None, -rope:]                       # (B, T, 1, r)
    c = _round(rms_norm(c, _param(p, i, "gain_latent"), _eps(spec)),
               _LATENT)
    up = mm(c, _param(p, i, "weights_kv_up"))
    k_nope = up[..., :at].reshape(b, t, h, nope)
    v = up[..., at:].reshape(b, t, h, dv)
    order = pairs(rope)                  # module docstring: departures
    q_rope = rope_interleaved(q_rope[..., order], theta)
    k_r = rope_interleaved(k_r[..., order], theta)
    # K assembled in full: the shared rotary key repeated per head
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_r, (b, t, h, rope))],
                        axis=-1)
    block = min(QUERY_BLOCK, t)
    cols = np.arange(t)[None, :]
    out = []
    for lo in range(0, t, block):
        hi = min(lo + block, t)
        keep = jnp.asarray(np.arange(lo, hi)[:, None] >= cols)
        s = jnp.einsum("bqhd,bkhd->bhqk", _r(q[:, lo:hi]), _r(k)) \
            / np.sqrt(nope + rope)
        s = jnp.where(keep, s, -jnp.inf)
        out.append(jnp.einsum("bhqk,bkhd->bqhd",
                              _r(jax.nn.softmax(s, axis=-1)), _r(v)))
    o = jnp.concatenate(out, axis=1)
    if spec.get("head_gate"):
        gate = jax.nn.sigmoid(mm(m, _param(p, i, "weights_head_gate")))
        o = o * gate[..., None]
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
    """The experts chosen, (N, top_k): a plain top-k TWICE — the groups
    by the sum of their 2 largest biased scores, then the experts among
    the kept groups' by their biased scores."""
    biased = np.asarray(scores) + (0.0 if bias is None
                                   else np.asarray(bias))
    groups = spec.get("groups")
    if groups:
        n_group, kept = int(groups[0]), int(groups[1])
        grouped = biased.reshape(biased.shape[0], n_group, -1)
        group_score = np.sort(grouped, axis=-1)[..., -2:].sum(axis=-1)
        keep = np.zeros(group_score.shape, bool)
        np.put_along_axis(keep, top_k(group_score, kept), True, axis=-1)
        biased = np.where(keep[:, :, None], grouped, -np.inf).reshape(
            biased.shape)
    return top_k(biased, int(spec["top_k"]))


def moe_block(x, p: dict, i: int, spec: dict, chosen=None, held=None,
              bias=None):
    """``(y, logits, lb, chosen)``; ``chosen`` (N, k) names the experts
    to use (the reference's own choice when ``None``); ``held`` the
    experts whose slabs ``p`` holds, in the slabs' order; ``bias`` the
    selection bias (zeros when the table sets ``select_bias`` and none
    is given)."""
    b, t, d = x.shape
    n_tok, experts = b * t, int(spec["n_experts"])
    k = int(spec["top_k"])
    if spec.get("score") != "sigmoid" or not spec.get("norm_topk"):
        raise ValueError("reference/ling: experts are scored by a "
                         "sigmoid, normalised over the chosen")
    if held is None:
        held = spec.get("held")
    held = list(range(experts)) if held is None else sorted(held)
    m = _normed(x, p, i, spec).reshape(n_tok, d)
    logits, scores = route(m, p, i)
    if chosen is None:
        chosen = choose(scores, bias if spec.get("select_bias") else None,
                        spec)
    chosen = np.asarray(chosen).reshape(n_tok, k)
    # the weights from s, never from s + b
    weight = jnp.take_along_axis(scores, jnp.asarray(chosen), axis=-1)
    weight = weight / weight.sum(axis=-1, keepdims=True) \
        * float(spec.get("routed_scale", 1.0))
    w_gate, w_up, w_down = (_param(p, i, f"weights_{name}")
                            for name in ("gate", "up", "down"))
    rows_per_expert = np.asarray(
        [(chosen == e).sum() for e in range(experts)], np.float32)
    f = jnp.zeros((n_tok, d), jnp.float32)
    most = max([rows_per_expert[e] for e in held] + [1])
    cap = -(-int(most) // 128) * 128       # one shape for the loop
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


#: looked up when a layer runs, so that a test can put a term out of
#: action by replacing one function of this module
MIXERS = {"gated_delta_net": lambda *a: kda_mixer(*a),
          "latent_attention": lambda *a: latent_mixer(*a),
          "gated_mlp": lambda m, p, i, spec: gated(
              m, _param(p, i, "weights"), _param(p, i, "weights_up"),
              _param(p, i, "weights_down"))}


# ----------------------------------------------------------------------
# the model
# ----------------------------------------------------------------------
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
            elif kind == "latent_attention" and not spec.get("kv_latent"):
                raise ValueError("reference/ling: attention has a "
                                 "latent K/V")
            elif kind in MIXERS:
                y = MIXERS[kind](_normed(h, params, i, spec), params, i,
                                 spec)
                h = h + y if spec.get("residual") else y
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
                raise ValueError(f"reference/ling: no layer {kind!r}")
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
    value, grads = jax.jit(jax.value_and_grad(
        lambda p: loss(p, layers, tokens, labels, routing)))(as_arrays)
    return float(value), {k: np.asarray(g) for k, g in grads.items()}

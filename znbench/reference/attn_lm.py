"""Plain reference of the ``attn_lm_base`` configuration: token
embedding → sinusoidal positions → N causal multi-head attention
layers → the last position → linear head + softmax, in float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")`` — no
kernels, no cache, no pages, no buckets, no batching of requests.  The
N-layer generalisation of ``chip_smoke.lm_oracle_logits``, independent
of the code under test: it reads only the layer table and the
parameters (keyed as a bundle is: ``layer<i>_weights``, ``_bias``,
``_weights_out``, ``_bias_out``).

What the chain is NOT (see the configuration's ``reduced``): there are
no feed-forward sublayers, no residual connections and no layer norms
between the attention layers, and the training loss looks at the last
position only.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def sinusoid(t: int, d: int):
    """(T, D): even dims sin, odd dims cos, wavelengths
    10000^(2i/d) (Vaswani et al. 2017, section 3.5)."""
    pos = jnp.arange(t, dtype=jnp.float32)[:, None]
    i = jnp.arange(d, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10000.0, 2.0 * jnp.floor(i / 2) / d)
    return jnp.where(i % 2 == 0, jnp.sin(angle), jnp.cos(angle))


def _attention(h, p, i: int, heads: int, causal: bool):
    b, t, d = h.shape
    dh = d // heads
    w = jnp.asarray(p[f"layer{i}_weights"], jnp.float32)
    bias = jnp.asarray(p[f"layer{i}_bias"], jnp.float32)
    qkv = h @ w + bias
    q, k, v = (qkv[..., j * d:(j + 1) * d].reshape(b, t, heads, dh)
               for j in range(3))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(dh)
    if causal:
        keep = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(keep, s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(b, t, d) \
        @ jnp.asarray(p[f"layer{i}_weights_out"], jnp.float32) \
        + jnp.asarray(p[f"layer{i}_bias_out"], jnp.float32)


def hidden(params: dict, layers: list, tokens) -> tuple:
    """Outputs of the sequence phase for ``tokens`` (B, T): every
    layer's output up to ``last_token`` (exclusive), and the index of
    the head layer."""
    outs = []
    with jax.default_matmul_precision("highest"):
        h = None
        for i, layer in enumerate(layers):
            kind, spec = layer["type"], layer.get("->", {})
            if kind == "embedding":
                ids = jnp.asarray(np.round(np.asarray(tokens)),
                                  jnp.int32)
                h = jnp.asarray(params[f"layer{i}_weights"],
                                jnp.float32)[ids]
            elif kind == "pos_encoding":
                h = h + float(spec.get("scale", 1.0)) \
                    * sinusoid(h.shape[1], h.shape[2])
            elif kind == "attention":
                h = _attention(h, params, i, int(spec["n_heads"]),
                               bool(spec.get("causal", False)))
            elif kind == "last_token":
                return outs, i
            else:
                raise ValueError(f"reference/attn_lm: no layer {kind!r}")
            outs.append(h)
    raise ValueError("reference/attn_lm: the chain has no last_token")


def _head(params: dict, i: int, rows):
    with jax.default_matmul_precision("highest"):
        return rows @ jnp.asarray(params[f"layer{i}_weights"],
                                  jnp.float32) \
            + jnp.asarray(params[f"layer{i}_bias"], jnp.float32)


def forward(params: dict, layers: list, tokens,
            masks: dict | None = None) -> list:
    """Every layer's output for ``tokens`` (B, T), as the training
    step's forward computes them: the sequence phase, the last
    position, the softmax over the vocabulary."""
    outs, at = hidden(params, layers, tokens)
    last = outs[-1][:, -1]
    probs = jax.nn.softmax(_head(params, at + 1, last), axis=-1)
    return [np.asarray(o) for o in outs] + [np.asarray(last),
                                            np.asarray(probs)]


def next_token_logits(params: dict, layers: list, tokens,
                      positions) -> np.ndarray:
    """Logits for the token AFTER each of ``positions`` of ONE
    sequence ``tokens`` (T,), by a full forward over the whole
    sequence: causal attention makes position p's output depend on
    tokens 0..p only, so one pass scores every generated token."""
    outs, at = hidden(params, layers, np.asarray(tokens)[None, :])
    rows = outs[-1][0][jnp.asarray(positions, jnp.int32)]
    return np.asarray(_head(params, at + 1, rows))

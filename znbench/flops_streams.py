"""Model operations and bytes of a language model whose residual path
is n streams mixed by learned maps (manifold-constrained
hyper-connections) around latent-K/V attention WITH a query latent and
expert layers that hold one chip's share — the yardstick's own
arithmetic for the Xing4.0-29B-A4B cell, beside ``flops_latent.py``
(whose rule for a latent layer knows no query latent, and which this PR
may not edit) and ``flops_moe.py``.

Per token and forward pass (D the model width, n streams):

- ``stream_read``: x~ phi 2·n·D·(2n + n²) and the mix h 2·n·D;
  ``stream_write``: the mix by H_res 2·n²·D and H_post·f 2·n·D — the
  n² + 2n mixes of a sublayer.  The norm over n·D, the sigmoids, the
  clamp, exp and Sinkhorn's iterations are not counted; the open's
  copies and the close's sum are additions and are not counted;
- ``latent_attention`` (H heads, ``qk_nope`` n_k, ``qk_rope`` r,
  ``v_head_dim`` v, K/V latent L, query latent Q): the two
  down-projections 2·D·(Q + L + r), the queries' up-projection
  2·Q·H·(n_k + r), the K/V up-projection 2·L·H·(n_k + v), the
  out-projection 2·H·v·D, and over the causal half the scores' two
  products and the values' 2·(n_k + r) + 2·v a visible pair and head
  (without ``q_latent`` the queries are columns of the one projection,
  as ``flops_latent`` counts them);
- ``gated_mlp`` 6·D·F; ``moe``: the router 2·D·E, the shared expert
  6·D·F_s, the routed rows THIS chip computes 6·D·F a row
  (``routed_rows`` per token, k·held/E under uniform routing); the head
  2·D·V.  Norms, rotations, softmaxes, the top-k and the gather /
  scatter around the experts are not counted.

Training is 3 × the forward; recomputed work does not count.

``stream_train_cost`` is the LEAST any implementation of the stream
units must move through HBM in a training step, f32 as stored — the
sublayer stands between a READ and its WRITE, forward and backward, so
there are four passes a sublayer and none can share a read with
another:

- READ forward: X in (n·D), h out (D);
- WRITE forward: X and f in (n·D + D), X' out (n·D);
- WRITE backward: dX' and X in (2·n·D), f in (D), df out (D) — the
  skip edge's share H_resᵀ dX' need not be written: the READ's backward
  can make it again from dX';
- READ backward: dh in (D), X and dX' in (2·n·D), dX out (n·D);

8·n·D + 5·D elements a token and sublayer, plus the open's and the
close's D in / n·D out and back.  The maps themselves (n² + 2n numbers
a token) and φ are noise beside it.  Its operations are the 3 × forward
FLOPs of the maps above; memory bounds it by far.
"""

from __future__ import annotations

from znbench import flops_band, flops_latent


def stream_reads(layers: list) -> list:
    return [layer["->"] for layer in layers
            if layer["type"] == "stream_read"]


def maps_flops_per_token(layers: list) -> float:
    """Forward FLOPs of one token's stream maps and mixes."""
    d = flops_band._embedding_dim(layers)
    total = 0.0
    for spec in stream_reads(layers):
        n = int(spec["n_streams"])
        total += 2.0 * n * d * (2 * n + n * n) + 2.0 * d * (n * n + 2 * n)
    return total


def forward_flops_per_token(layers: list, t: int,
                            routed_rows: dict | None = None) -> dict:
    """Forward FLOPs of one token at context ``t``, by part: the MLPs,
    the expert layers and the head as ``flops_latent`` counts them (the
    table keeps its indices, ``routed_rows`` being keyed by them), the
    maps and the latent layers by this file's rules."""
    d = flops_band._embedding_dim(layers)
    others = flops_latent.forward_flops_per_token(
        [layer if layer["type"] != "latent_attention" else {"type": "-"}
         for layer in layers], t, routed_rows)
    parts = {"stream_maps": maps_flops_per_token(layers),
             "mla_projections": 0.0, "mla_scores": 0.0,
             **{part: others[part] for part in (
                 "dense", "shared", "routed", "router", "head")}}
    pairs = flops_band.visible_pairs(t)
    for spec in flops_latent.latent_layers(layers):
        h, latent, nope, rope, v = flops_latent.latent_shape(spec)
        below = int(spec.get("q_latent") or 0)
        queries = 2.0 * d * below + 2.0 * below * h * (nope + rope) \
            if below else 2.0 * d * h * (nope + rope)
        parts["mla_projections"] += queries \
            + 2.0 * d * (latent + rope) \
            + 2.0 * latent * h * (nope + v) + 2.0 * h * v * d
        parts["mla_scores"] += \
            (2.0 * (nope + rope) + 2.0 * v) * h * pairs / t
    return parts


def lm_train_flops(layers: list, t: int, batch: int,
                   routed_rows: dict | None = None) -> float:
    """Model FLOPs of one training step over ``batch`` sequences of
    ``t`` tokens."""
    return 3.0 * batch * t * sum(
        forward_flops_per_token(layers, t, routed_rows).values())


def stream_train_cost(layers: list, t: int, batch: int) -> dict:
    """What the stream units of one training step need at the least
    (module docstring): FLOPs of the maps, bytes of the four passes a
    sublayer and of the open and the close."""
    d = flops_band._embedding_dim(layers)
    elements = 0.0
    for spec in stream_reads(layers):
        n = int(spec["n_streams"])
        elements += 8.0 * n * d + 5.0 * d
    for layer in layers:
        if layer["type"] in ("stream_open", "stream_close"):
            n = int(layer["->"]["n_streams"])
            elements += 2.0 * (n * d + d)
    rows = float(batch) * t
    return {"flops": 3.0 * rows * maps_flops_per_token(layers),
            "bytes": 4.0 * rows * elements}

"""Model operations and bytes of a language model whose attention
layers differ in head count and window and whose expert layers hold one
chip's share — the yardstick's own arithmetic for the Laguna cell,
beside ``flops.py`` and ``flops_moe.py`` (whose rules count four D × D
projections, all T² / 2 pairs and N·k rows over all experts, and which
this PR may not edit).

Per token and forward pass: the q, k, v and out projections at their
own widths (2·D·(H + 2·H_kv)·dh + 2·H·dh·D), the per-head gate
(2·D·H), the score and value matmuls over the pairs a row can see
(4·dh·H pairs per visible pair: the causal half, or the band
Σ_r min(r + 1, W) of a windowed layer), the dense and the shared gated
MLPs (6·D·F), the router (2·D·E), the routed rows THIS chip computes
(6·D·F a row: ``routed_rows`` per token, k·held/E under uniform
routing), and the head (2·D·V); norms, rotary positions, softmaxes and
the gather / scatter around the experts are not counted.  Training is
3 × the forward; recomputed work does not count.
"""

from __future__ import annotations


def visible_pairs(t: int, window=None) -> float:
    """(row, column) pairs a causal layer attends over in a sequence of
    ``t``: Σ_r min(r + 1, window); the causal half where the window is
    unset or covers the sequence."""
    w = t if not window else min(int(window), t)
    return w * (w + 1) / 2.0 + (t - w) * float(w)


def _embedding_dim(layers: list) -> int:
    return next(int(layer["->"]["dim"]) for layer in layers
                if layer["type"] == "embedding")


def attention_shape(spec: dict, d: int) -> tuple:
    """(query heads, K/V heads, head size) of an attention layer."""
    heads = int(spec["n_heads"])
    return (heads, int(spec.get("n_kv_heads") or heads),
            int(spec.get("head_dim") or d // heads))


def forward_flops_per_token(layers: list, t: int,
                            routed_rows: dict | None = None) -> dict:
    """Forward FLOPs of one token at context ``t``, by part.
    ``routed_rows`` (layer index → (token, expert) pairs computed here
    per token) replaces the uniform expectation k·held/E."""
    d = _embedding_dim(layers)
    parts = {"projections": 0.0, "scores": 0.0, "gate": 0.0, "dense": 0.0,
             "shared": 0.0, "routed": 0.0, "router": 0.0, "head": 0.0}
    for i, layer in enumerate(layers):
        kind, spec = layer["type"], layer.get("->", {})
        if kind == "attention":
            heads, kv, dh = attention_shape(spec, d)
            parts["projections"] += 2.0 * d * (heads + 2 * kv) * dh \
                + 2.0 * heads * dh * d
            if spec.get("head_gate"):
                parts["gate"] += 2.0 * d * heads
            pairs = visible_pairs(t, spec.get("window")) \
                if spec.get("causal") else float(t) * t
            parts["scores"] += 4.0 * dh * heads * pairs / t
        elif kind == "gated_mlp":
            parts["dense"] += 6.0 * d * int(spec["width"])
        elif kind == "moe":
            experts = int(spec["n_experts"])
            held = len(spec["held"]) if spec.get("held") is not None \
                else experts
            rows = (routed_rows or {}).get(
                i, int(spec["top_k"]) * held / experts)
            parts["routed"] += 6.0 * d * int(spec["width"]) * rows
            parts["shared"] += 6.0 * d * int(spec.get("shared_width", 0))
            parts["router"] += 2.0 * d * experts
        elif kind == "softmax":
            parts["head"] += 2.0 * d * int(spec["output_sample_shape"])
    return parts


def lm_train_flops(layers: list, t: int, batch: int,
                   routed_rows: dict | None = None) -> float:
    """Model FLOPs of one training step over ``batch`` sequences of
    ``t`` tokens."""
    return 3.0 * batch * t * sum(
        forward_flops_per_token(layers, t, routed_rows).values())


def flash_win_train_cost(layers: list, t: int, batch: int,
                         bytes_per_element: int = 2) -> dict:
    """What the WINDOWED flash kernels of one training step need,
    summed over the attention layers whose window is shorter than the
    sequence: FLOPs of the band only, by ``flops.flash_train_cost``'s
    accounting (two matmuls of 2·dh a visible pair forward, five
    backward: 14·dh·H a pair); bytes at the group's sharing — q, o
    forward and q, o, do, dq backward are H·dh wide, k, v forward and
    k, v, dk, dv backward H_kv·dh."""
    d = _embedding_dim(layers)
    flops = bytes_ = 0.0
    for layer in layers:
        spec = layer.get("->", {})
        window = spec.get("window")
        if layer["type"] != "attention" or not window \
                or int(window) >= t:
            continue
        heads, kv, dh = attention_shape(spec, d)
        flops += 14.0 * dh * heads * batch * visible_pairs(t, window)
        bytes_ += 6.0 * batch * t * (heads + kv) * dh * bytes_per_element
    return {"flops": flops, "bytes": bytes_}

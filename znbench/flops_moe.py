"""Model operations and bytes of a sparse-expert language model from
its layer table — the yardstick's own arithmetic for the OLMoE cell,
beside ``flops.py`` (whose ``forward_flops`` has no rule for an expert
layer, a norm with a gain or a head at every position, and which this
PR may not edit).

Per token and forward pass: the four D × D attention projections
(8·D²), the causal half of the score and value matmuls (2·T·D), the
``top_k`` experts a token is computed by — three D × F matmuls each
(6·k·D·F) — the router (2·D·E) and the head (2·D·V); norms, rotary
positions, softmaxes and the gather / scatter around the experts are
not counted.  Training is 3 × the forward (forward, input gradient,
weight gradient); recomputed work does not count.
"""

from __future__ import annotations


def of_type(layers: list, kind: str) -> list:
    return [layer.get("->", {}) for layer in layers
            if layer["type"] == kind]


def forward_flops_per_token(layers: list, t: int) -> dict:
    """Forward FLOPs of one token at context ``t``, by part."""
    d = next(int(spec["dim"]) for spec in of_type(layers, "embedding"))
    parts = {"projections": 0.0, "scores": 0.0, "experts": 0.0,
             "router": 0.0, "head": 0.0}
    for spec in of_type(layers, "attention"):
        parts["projections"] += 8.0 * d * d
        parts["scores"] += (2.0 if spec.get("causal") else 4.0) * t * d
    for spec in of_type(layers, "moe"):
        parts["experts"] += 6.0 * int(spec["top_k"]) * d \
            * int(spec["width"])
        parts["router"] += 2.0 * d * int(spec["n_experts"])
    for spec in of_type(layers, "softmax"):
        parts["head"] += 2.0 * d * int(spec["output_sample_shape"])
    return parts


def lm_train_flops(layers: list, t: int, batch: int) -> float:
    """Model FLOPs of one training step over ``batch`` sequences of
    ``t`` tokens."""
    return 3.0 * batch * t * sum(
        forward_flops_per_token(layers, t).values())


def gmm_train_cost(layers: list, tokens: int, d: int,
                   bytes_per_element: int = 2) -> dict:
    """What the grouped matmuls of one training step need, summed over
    the expert layers.  A layer runs nine over its N·k rows — gate, up
    and down forward, and for each its row gradient and its weight
    gradient — of 2·N·k·D·F FLOPs each: 18·N·k·D·F.  Bytes: a forward
    or row-gradient call reads its rows and the E slabs at
    ``bytes_per_element`` and writes f32 rows; a weight-gradient call
    reads two sets of rows and writes E slabs in f32."""
    flops = bytes_ = 0.0
    for spec in of_type(layers, "moe"):
        rows = float(tokens) * int(spec["top_k"])
        e, f = int(spec["n_experts"]), int(spec["width"])
        flops += 18.0 * rows * d * f
        slabs = float(e) * d * f
        for k, n in ((d, f), (d, f), (f, d)):       # gate, up, down
            forward = rows * k * bytes_per_element \
                + slabs * bytes_per_element + rows * n * 4
            row_grad = rows * n * bytes_per_element \
                + slabs * bytes_per_element + rows * k * 4
            weight_grad = rows * (k + n) * bytes_per_element + slabs * 4
            bytes_ += forward + row_grad + weight_grad
    return {"flops": flops, "bytes": bytes_}

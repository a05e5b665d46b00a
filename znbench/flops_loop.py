"""Model operations of a language model whose layer stack runs R times
a step on shared weights (a looped span: table entries with
``"passes": R``) with an exit at every pass — the yardstick's own
arithmetic for the Ouro cell, beside ``flops.py`` / ``flops_moe.py``,
whose counts read a table entry once and know no passes.

Per token and forward pass, a table entry counted ``passes`` times
where it has the key and once where it has not: an attention layer's
four D × D projections (8·D²) and the causal half of its score and
value matmuls (2·T·D; 4·T·D where not causal), a gated MLP's three
D × F matmuls (6·D·F), and for the ``loop_exits`` head — applied to
the R states the span before it leaves — R × (the head 2·D·V + the
exit gate 2·D).  Norms, rotary positions, softmaxes, the exit
distribution and the sum of the passes' partial gradients (adds) are
not counted.  Training is 3 × the forward (forward, input gradient,
weight gradient: every application has all three, the weight
gradient's being one of the R parts of the sum); recomputed work does
not count.
"""

from __future__ import annotations


def forward_flops_per_token(layers: list, t: int) -> dict:
    """Forward FLOPs of one token at context ``t``, by part."""
    d = next(int(layer["->"]["dim"]) for layer in layers
             if layer["type"] == "embedding")
    parts = {"projections": 0.0, "scores": 0.0, "mlps": 0.0,
             "head": 0.0, "exit_gate": 0.0}
    exits = 1          # states the unit after a looped span is handed
    for layer in layers:
        kind, spec = layer["type"], layer.get("->", {})
        passes = int(layer.get("passes", 1))
        if kind == "attention":
            parts["projections"] += passes * 8.0 * d * d
            parts["scores"] += passes \
                * (2.0 if spec.get("causal") else 4.0) * t * d
        elif kind == "gated_mlp":
            parts["mlps"] += passes * 6.0 * d * int(spec["width"])
        elif kind == "loop_exits":
            parts["head"] += exits * 2.0 * d \
                * int(spec["output_sample_shape"])
            parts["exit_gate"] += exits * 2.0 * d
        elif kind == "softmax":            # a plain head: last pass only
            parts["head"] += 2.0 * d * int(spec["output_sample_shape"])
        exits = passes if "passes" in layer else 1
    return parts


def applications_per_step(layers: list) -> int:
    """Member applications of one step: Σ ``passes`` over the looped
    span's entries."""
    return sum(int(layer["passes"]) for layer in layers
               if "passes" in layer)


def lm_train_flops(layers: list, t: int, batch: int) -> float:
    """Model FLOPs of one training step over ``batch`` sequences of
    ``t`` tokens."""
    return 3.0 * batch * t * sum(
        forward_flops_per_token(layers, t).values())

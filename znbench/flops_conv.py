"""Model operations and bytes of a language model whose token mixers
are gated short convolutions among grouped-query attention layers and
whose expert layers hold a share — the yardstick's own arithmetic for
the LFM2 cell, beside ``flops_band.py`` (whose rules have no short
convolution and which this PR may not edit; its attention, dense-MLP,
router, routed-rows and head rules are used as they are).

Per token and forward pass, beside ``flops_band``'s parts: a short
convolution's two projections at their own widths (2·D·3D + 2·D·D) and
its chain (2 gate products + 2·J − 1 for the taps, per channel: not
matmul work, counted all the same because it is what the mixer does).
Training is 3 × the forward; recomputed work (the chain made again in
the backward) does not count.
"""

from __future__ import annotations

from znbench import flops_band


def conv_layers(layers: list) -> list:
    return [layer.get("->", {}) for layer in layers
            if layer["type"] == "short_conv"]


def forward_flops_per_token(layers: list, t: int,
                            routed_rows: dict | None = None) -> dict:
    """Forward FLOPs of one token at context ``t``, by part
    (``flops_band``'s parts + ``conv_projections`` + ``conv_chain``)."""
    parts = flops_band.forward_flops_per_token(layers, t, routed_rows)
    d = flops_band._embedding_dim(layers)
    parts["conv_projections"] = parts["conv_chain"] = 0.0
    for spec in conv_layers(layers):
        taps = int(spec.get("conv_kernel", 3))
        parts["conv_projections"] += 2.0 * d * 3 * d + 2.0 * d * d
        parts["conv_chain"] += d * (2.0 + 2.0 * taps - 1.0)
    return parts


def lm_train_flops(layers: list, t: int, batch: int,
                   routed_rows: dict | None = None) -> float:
    """Model FLOPs of one training step over ``batch`` sequences of
    ``t`` tokens."""
    return 3.0 * batch * t * sum(
        forward_flops_per_token(layers, t, routed_rows).values())


def short_conv_train_cost(layers: list, t: int, batch: int,
                          out_bytes: int = 2) -> dict:
    """What the chain B ⊙ x̃ → taps → C ⊙ of one training step must
    move and do, summed over the short-convolution layers, whatever
    implements it.  A tensor counts at the width the program STORES it
    at; what a kernel keeps in VMEM counts 0.  Forward: the three
    D-column blocks of the projection read (f32) and y written
    (``out_bytes``: bf16 where W_out's matmul takes bf16).  Backward:
    the projection read again (f32), y's cotangent read
    (``out_bytes``), the projection's cotangent written (f32).  The
    taps and their cotangent are D × J: nothing.  FLOPs: the chain's
    2·J + 1 per channel forward; backward the chain again is
    recomputation (not counted), dC, dc, du (2·J − 1), dB, dx̃ and the
    taps' sums (2·J): 4·J + 3."""
    d = flops_band._embedding_dim(layers)
    flops = bytes_ = 0.0
    for spec in conv_layers(layers):
        taps = int(spec.get("conv_kernel", 3))
        elements = float(batch) * t * d
        flops += elements * ((2.0 * taps + 1.0) + (4.0 * taps + 3.0))
        bytes_ += elements * (3 * 4 + out_bytes)           # forward
        bytes_ += elements * (3 * 4 + out_bytes + 3 * 4)   # backward
    return {"flops": flops, "bytes": bytes_}


def held_gmm_train_cost(layers: list, routed_rows: dict, tokens: int,
                        bytes_per_element: int = 2) -> dict:
    """What the grouped matmuls of one training step need under
    ``held``, summed over the expert layers: by
    ``flops_moe.gmm_train_cost``'s accounting (nine calls a layer of
    2·rows·D·F FLOPs; a forward or row-gradient call reads its rows and
    the slabs at ``bytes_per_element`` and writes f32 rows, a
    weight-gradient call reads two sets of rows and writes the slabs in
    f32), over the rows this chip COMPUTED (``routed_rows``: layer
    index → pairs per token, ``band_lm_train_mfu.routed_rows``) and the
    slabs it HOLDS — not N·k rows over all E experts."""
    d = flops_band._embedding_dim(layers)
    flops = bytes_ = 0.0
    for i, layer in enumerate(layers):
        spec = layer.get("->", {})
        if layer["type"] != "moe" or i not in routed_rows:
            continue
        rows = float(tokens) * routed_rows[i]
        held = len(spec["held"]) if spec.get("held") is not None \
            else int(spec["n_experts"])
        f = int(spec["width"])
        flops += 18.0 * rows * d * f
        slabs = float(held) * d * f
        for k, n in ((d, f), (d, f), (f, d)):       # gate, up, down
            forward = rows * k * bytes_per_element \
                + slabs * bytes_per_element + rows * n * 4
            row_grad = rows * n * bytes_per_element \
                + slabs * bytes_per_element + rows * k * 4
            weight_grad = rows * (k + n) * bytes_per_element + slabs * 4
            bytes_ += forward + row_grad + weight_grad
    return {"flops": flops, "bytes": bytes_}

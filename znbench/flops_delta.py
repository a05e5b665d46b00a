"""Model operations and bytes of a language model that mixes
gated-delta-rule linear-attention layers with full-attention layers —
the yardstick's own arithmetic for the Olmo-Hybrid cell.  Only the
recurrent layer's rules are here: the attention layers, the gated MLPs
and the head are counted by ``flops_band.py`` (which skips a layer type
it does not know, so it reads this layer table as it stands), and
``lm_train_flops`` is its count plus the linear layers'.

Per token and forward pass:

- ``gated_delta_net`` (H heads, d_k, d_v, J taps; D the model width):
  the five projections at their own widths — q ‖ k ‖ v
  2·D·H·(2 d_k + d_v), the output gate 2·D·H·d_v, the two gates'
  logits 2·D·2H, the out-projection 2·H·d_v·D; the convolution
  2·J·H·(2 d_k + d_v); and the chunked rule as the program's algebra
  runs it in chunks of C (``chunk_flops``): K Kᵀ and Q Kᵀ 2·C²·d_k
  each, the triangular inverse by halves 2·C·Σ_{s=2,4,…,C/2} s², W
  2·C²·d_k, U and P V′ 2·C²·d_v each, and the three d_k × d_v products
  with the state (W S, K̂ᵀ V′, Q S) 2·C·d_k·d_v each — per chunk and
  head, so ÷ C per token.  Rematerialised work (the backward recomputes
  what is local to a chunk) is not counted;
- ``attention``, ``gated_mlp``, the head: ``flops_band``'s rules (at
  equal head counts 8·D·H·dh of projections and 4·dh·H·(t + 1)/2 over
  the causal half; 6·D·F; 2·D·V); norms, gates' nonlinearities and the
  softmax are not counted.

Training is 3 × the forward.

``delta_train_cost`` is what the NAMED kernels (``znicz_delta_state_fwd``
and ``_bwd``) are given to do, for their roofline: per chunk and head
the forward's two d_k × d_v products (4·C·d_k·d_v FLOPs) and the
backward's four (8·C·d_k·d_v); the bytes they move at the width the
program stores them (f32): forward reads W, K̂ (C × d_k), U (C × d_v),
the decay's row (d_v) and writes V′ (C × d_v) and the chunk's state
(d_k × d_v); backward reads W, K̂, the decay, S, V′ and the two
cotangents and writes dW, dK̂, dU and the decay's.  The lanes Mosaic
pads (d_k 96 → 128, d_v 192 → 256) are NOT counted: ``delta_pad_overwork``
says how much they are.
"""

from __future__ import annotations

from znbench import flops_band

#: positions per chunk of the program's scan (ops/pallas_delta.CHUNK;
#: stated here because the yardstick does not import the program)
CHUNK = 64
#: bytes per element of what the kernels read and write
STORED_BYTES = 4


def delta_shape(spec: dict) -> tuple:
    """(heads, d_k, d_v, taps) of a gated-delta-rule layer."""
    return (int(spec["n_heads"]), int(spec["key_dim"]),
            int(spec["value_dim"]), int(spec.get("conv_kernel", 4)))


def inverse_flops(chunk: int) -> float:
    """(I + L)⁻¹ by halves from 2 × 2 blocks: at block size s there are
    C / 2s pairs, two s × s × s matmuls each."""
    total, size = 0.0, 2
    while size < chunk:
        total += (chunk // (2 * size)) * 2 * 2.0 * size ** 3
        size *= 2
    return total


def chunk_flops(dk: int, dv: int, chunk: int = CHUNK) -> dict:
    """Forward FLOPs of one chunk of one head, by part."""
    c = float(chunk)
    return {"k_kt": 2 * c * c * dk, "inverse": inverse_flops(chunk),
            "w": 2 * c * c * dk, "u": 2 * c * c * dv,
            "state": 3 * 2 * c * dk * dv,
            "q_kt": 2 * c * c * dk, "p_v": 2 * c * c * dv}


def delta_flops_per_token(layers: list) -> dict:
    """Forward FLOPs of one token in the gated-delta-rule layers, by
    part (no part depends on the context's length)."""
    d = flops_band._embedding_dim(layers)
    parts = {"delta_projections": 0.0, "delta_conv": 0.0,
             "delta_rule": 0.0}
    for layer in layers:
        if layer["type"] != "gated_delta_net":
            continue
        h, dk, dv, taps = delta_shape(layer["->"])
        wide = h * (2 * dk + dv)
        parts["delta_projections"] += 2.0 * d * wide \
            + 2.0 * d * h * dv + 2.0 * d * 2 * h + 2.0 * h * dv * d
        parts["delta_conv"] += 2.0 * taps * wide
        parts["delta_rule"] += h * sum(
            chunk_flops(dk, dv).values()) / CHUNK
    return parts


def lm_train_flops(layers: list, t: int, batch: int) -> float:
    """Model FLOPs of one training step over ``batch`` sequences of
    ``t`` tokens: what ``flops_band`` counts of the layers it knows,
    plus the linear layers'."""
    return flops_band.lm_train_flops(layers, t, batch) \
        + 3.0 * batch * t * sum(delta_flops_per_token(layers).values())


def delta_train_cost(layers: list, t: int, batch: int,
                     chunk: int = CHUNK,
                     bytes_per_element: int = STORED_BYTES) -> dict:
    """What the ``znicz_delta_state_*`` kernels of one training step
    are given to do, summed over the gated-delta-rule layers."""
    flops = bytes_ = 0.0
    chunks = -(-t // chunk)
    for layer in layers:
        if layer["type"] != "gated_delta_net":
            continue
        h, dk, dv, _ = delta_shape(layer["->"])
        faces = {"key": chunk * dk, "value": chunk * dv,
                 "state": dk * dv, "row": dv}
        forward = 2 * faces["key"] + 2 * faces["value"] \
            + faces["state"] + faces["row"]
        backward = (2 * faces["key"] + faces["row"] + faces["state"]
                    + 2 * faces["value"] + faces["state"]) \
            + (2 * faces["key"] + faces["value"] + faces["row"])
        per_chunk_head = batch * h * chunks
        flops += per_chunk_head * (4.0 + 8.0) * chunk * dk * dv
        bytes_ += per_chunk_head * (forward + backward) \
            * float(bytes_per_element)
    return {"flops": flops, "bytes": bytes_}

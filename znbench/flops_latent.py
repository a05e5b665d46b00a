"""Model operations and bytes of a language model that mixes linear
layers whose delta rule decays PER KEY CHANNEL (Kimi Delta Attention)
with latent-K/V attention layers (keys of two widths, one rotary key
shared by all heads) over expert layers that hold one chip's share —
the yardstick's own arithmetic for the Ling-3.0-flash cell, beside
``flops_band.py`` and ``flops_delta.py`` (whose rules know one head
width, a scalar decay and no latent, and which this PR may not edit).

Per token and forward pass (D the model width):

- ``gated_delta_net`` with ``decay: channel`` (H heads, d_k, d_v,
  J taps): q ‖ k ‖ v 2·D·H·(2 d_k + d_v), the output gate 2·D·H·d_v,
  the write gate's and the decay's logits 2·D·(H + H·d_k) — the decay
  is a full-rank projection here —, the out-projection 2·H·d_v·D, the
  convolution 2·J·H·(2 d_k + d_v), and the chunked rule in chunks of C
  (``flops_delta.chunk_flops``: the SAME products as under a scalar
  decay — M and P computed by sub-blocks are still C² · d_k
  multiply-adds each, Γ being inside the contraction changes no count;
  the inverse by its 2 × 2-blocks-up work), ÷ C per token;
- ``attention`` with ``kv_latent`` (H heads, ``qk_nope`` n, ``qk_rope``
  r, ``v_head_dim`` v, latent L): the fused down-projection
  2·D·(H·(n + r) + L + r), the up-projection 2·L·H·(n + v), the head
  gate 2·D·H, the out-projection 2·H·v·D, and over the causal half the
  scores' two products and the values' 2·(n + r) + 2·v a visible pair
  and head (the shared rotary key's product is counted for every head:
  every head multiplies by it);
- ``gated_mlp`` 6·D·F; ``moe``: the router 2·D·E, the shared expert
  6·D·F_s, the routed rows THIS chip computes 6·D·F a row
  (``routed_rows`` per token, k·held/E under uniform routing); the head
  2·D·V.  Norms, rotations, gates' nonlinearities, softmaxes, both
  top-k and the gather / scatter around the experts are not counted.

Training is 3 × the forward; recomputed work does not count.

``kda_train_cost`` is what the four NAMED kernels of a linear layer
(``znicz_kda_chunk_fwd`` / ``_bwd``, ``znicz_kda_state_fwd`` / ``_bwd``)
are given to do, for their roofline — the products counted as the
PROGRAM does them (``kda_kernel_flops``): M and P by sub-blocks (a full
C × C of each, the part above the diagonal masked after), the inverse's
whole-chunk products (two (C, C)·(C, C) products a level, log2 C − 1
levels: 5.2 MFLOP a chunk where the by-halves count is 0.17), W and U;
the backward's recomputed M and its eleven products; the state walk's
two products forward and four backward.  The 0/1-matrix products by
which the kernels form prefix sums of log α are additions and are NOT
counted.  Bytes at the STORED width, f32 but for what is kept or
written at bf16: what each kernel reads and writes per chunk and head
(``kda_kernel_bytes``).

``mla_flash_train_cost`` is what the ``znicz_flash_*_mla`` kernels are
given: over the causal half the forward's two products (2·(n + r) +
2·v FLOPs a pair and head) and the backward's five at those widths
(scores again 2·(n + r), dp 2·v, dv 2·v, dq 2·(n + r), dk 2·(n + r));
a second recomputation by a two-pass backward is the kernels' own
business.  Bytes: q, k, v, o at the bf16 the kernels take them, the
shared key once, the f32 statistics and cotangents.
"""

from __future__ import annotations

from znbench import flops_band, flops_delta

#: positions per chunk of the program's scan (ops/pallas_delta.CHUNK;
#: stated here because the yardstick does not import the program)
CHUNK = 64


def kda_layers(layers: list) -> list:
    return [layer["->"] for layer in layers
            if layer["type"] == "gated_delta_net"
            and layer["->"].get("decay") == "channel"]


def latent_layers(layers: list) -> list:
    return [layer["->"] for layer in layers
            if layer["type"] == "latent_attention"
            and layer["->"].get("kv_latent")]


def latent_shape(spec: dict) -> tuple:
    """(heads, latent, qk_nope, qk_rope, v) of a latent-K/V layer."""
    return tuple(int(spec[key]) for key in (
        "n_heads", "kv_latent", "qk_nope", "qk_rope", "v_head_dim"))


def forward_flops_per_token(layers: list, t: int,
                            routed_rows: dict | None = None) -> dict:
    """Forward FLOPs of one token at context ``t``, by part."""
    d = flops_band._embedding_dim(layers)
    parts = {"kda_projections": 0.0, "kda_conv": 0.0, "kda_rule": 0.0,
             "mla_projections": 0.0, "mla_scores": 0.0, "dense": 0.0,
             "shared": 0.0, "routed": 0.0, "router": 0.0, "head": 0.0}
    pairs = flops_band.visible_pairs(t)
    for i, layer in enumerate(layers):
        kind, spec = layer["type"], layer.get("->", {})
        if kind == "gated_delta_net":
            h, dk, dv, taps = flops_delta.delta_shape(spec)
            channels = dk if spec.get("decay") == "channel" else 1
            wide = h * (2 * dk + dv)
            parts["kda_projections"] += 2.0 * d * wide \
                + 2.0 * d * h * dv + 2.0 * d * h * (1 + channels) \
                + 2.0 * h * dv * d
            parts["kda_conv"] += 2.0 * taps * wide
            parts["kda_rule"] += h * sum(flops_delta.chunk_flops(
                dk, dv, CHUNK).values()) / CHUNK
        elif kind == "latent_attention":
            h, latent, nope, rope, v = latent_shape(spec)
            parts["mla_projections"] += \
                2.0 * d * (h * (nope + rope) + latent + rope) \
                + 2.0 * latent * h * (nope + v) + 2.0 * h * v * d \
                + (2.0 * d * h if spec.get("head_gate") else 0.0)
            parts["mla_scores"] += \
                (2.0 * (nope + rope) + 2.0 * v) * h * pairs / t
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


def kda_kernel_flops(dk: int, dv: int, chunk: int = CHUNK) -> dict:
    """FLOPs of one chunk of one head in each of the four kernels, the
    products as the program does them."""
    c = float(chunk)
    levels = max(chunk.bit_length() - 2, 0)      # sizes 2, 4, …, C/2
    key, value, square = 2 * c * c * dk, 2 * c * c * dv, 2 * c * c * c
    return {
        # M, the inverse's two whole-chunk products a level, W, U, P
        "chunk_fwd": key + levels * 2 * square + key + value + key,
        # M again; dA (two), dKg, dV, dL (two C³), the sub-blocks'
        # left and right factors' cotangents for M and for P (four)
        "chunk_bwd": key + (key + value) + key + value + 2 * square
        + 4 * key,
        "state_fwd": 2 * 2 * c * dk * dv,
        "state_bwd": 4 * 2 * c * dk * dv,
    }


def kda_kernel_bytes(dk: int, dv: int, chunk: int = CHUNK) -> dict:
    """Bytes one chunk of one head moves through each kernel, at the
    widths the arrays are STORED in: f32, but for what the backward
    keeps at the bf16 its products take it in (V in the chunk kernel;
    W, K̂, V′ in the reverse walk) and for the per-chunk states, which
    the walk writes at bf16, their cotangent with them."""
    c = chunk
    key, value, square, state = c * dk, c * dv, c * c, dk * dv
    f32, kept = {
        # q, k, v, log α, β → W, K̂, U, decay, Qc, P, (I + L)⁻¹
        "chunk_fwd": (3 * key + value + c)
        + (3 * key + value + dk + 2 * square),
        # q, k, log α, β, X and six cotangents → five
        "chunk_bwd": (3 * key + c + square
                      + 3 * key + value + dk + square)
        + (3 * key + value + c),
        # W, K̂, U, decay → V′
        "state_fwd": (2 * key + value + dk) + value,
        # decay, dV′ → dW, dK̂, dU, d decay
        "state_bwd": (dk + value) + (2 * key + value + dk),
    }, {
        "chunk_fwd": 0,
        "chunk_bwd": value,                      # V
        "state_fwd": state,                      # → S
        "state_bwd": 2 * key + value + 2 * state,    # W, K̂, V′, S, dS
    }
    return {name: 4.0 * f32[name] + 2.0 * kept[name] for name in f32}


def kda_train_cost(layers: list, t: int, batch: int,
                   chunk: int = CHUNK) -> dict:
    """What the ``znicz_kda_*`` kernels of one training step are given
    to do, summed over the per-channel linear layers."""
    flops = bytes_ = 0.0
    chunks = -(-t // chunk)
    for spec in kda_layers(layers):
        h, dk, dv, _ = flops_delta.delta_shape(spec)
        per = batch * h * chunks
        flops += per * sum(kda_kernel_flops(dk, dv, chunk).values())
        bytes_ += per * sum(kda_kernel_bytes(dk, dv, chunk).values())
    return {"flops": flops, "bytes": bytes_}


def mla_flash_train_cost(layers: list, t: int, batch: int) -> dict:
    """What the two-width flash kernels of one training step need,
    summed over the latent-K/V layers."""
    flops = bytes_ = 0.0
    pairs = flops_band.visible_pairs(t)
    for spec in latent_layers(layers):
        h, _, nope, rope, v = latent_shape(spec)
        key = nope + rope
        forward = 2.0 * key + 2.0 * v
        backward = 3 * 2.0 * key + 2 * 2.0 * v
        flops += batch * h * pairs * (forward + backward)
        rows = batch * float(t)
        wide_q, wide_k = h * key, h * nope + rope
        # forward: q, k, v in, o out (bf16), the statistics (f32);
        # backward: q, k, v, o, do in (bf16), the statistics, and dq,
        # dk (the shared key's once), dv out (f32)
        bytes_ += rows * (2.0 * (wide_q + wide_k + 2 * h * v)
                          + 4.0 * h)
        bytes_ += rows * (2.0 * (wide_q + wide_k + 3 * h * v)
                          + 4.0 * h
                          + 4.0 * (wide_q + wide_k + h * v))
    return {"flops": flops, "bytes": bytes_}

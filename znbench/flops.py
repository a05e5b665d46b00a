"""Model operations and bytes from shapes — the yardstick's own
arithmetic, so that no PR that claims a gain can change it.

Copied from ``bench.train_step_flops`` (2·MACs per conv / FC forward,
×3 for training: forward, input gradient and weight gradient are each
one GEMM of that volume; elementwise, pooling and LRN work is not
counted) and ``benchmarks/seq_bench.attn_train_flops`` (four D×D
projections per token, score and value matmuls with the causal half
counted as half), generalised to a layer table of any depth with an
embedding (a gather: no FLOPs) and a head.  The originals stay where
they are until ROADMAP D6 retires them.
"""

from __future__ import annotations

import math


def expand(layers: list) -> list:
    """A layer table with ``"repeat": n`` entries written out."""
    out = []
    for layer in layers:
        layer = dict(layer)
        for _ in range(int(layer.pop("repeat", 1))):
            out.append(dict(layer))
    return out


def _pad4(padding) -> tuple:
    if isinstance(padding, int):
        return (padding,) * 4
    return tuple(padding) if padding else (0, 0, 0, 0)


def _pool_out(size: int, k: int, s: int) -> int:
    return -(-(size - k) // s) + 1 if size > k else 1


def forward_flops(layers: list, sample_shape: tuple, batch: int
                  ) -> float:
    """FLOPs of one forward pass over ``batch`` samples of
    ``sample_shape`` ((H, W, C) images or (T,) token ids)."""
    shape = tuple(sample_shape)
    flops = 0.0
    for layer in expand(layers):
        kind, spec = layer["type"], layer.get("->", {})
        if kind.startswith("conv"):
            h, w, c = shape
            top, bottom, left, right = _pad4(spec.get("padding", 0))
            sy, sx = spec.get("sliding", (1, 1))
            oh = (h + top + bottom - spec["ky"]) // sy + 1
            ow = (w + left + right - spec["kx"]) // sx + 1
            shape = (oh, ow, spec["n_kernels"])
            flops += 2.0 * batch * math.prod(shape) \
                * spec["ky"] * spec["kx"] * c
        elif kind.endswith("pooling"):
            h, w, c = shape
            sy, sx = spec.get("sliding", (spec["ky"], spec["kx"]))
            shape = (_pool_out(h, spec["ky"], sy),
                     _pool_out(w, spec["kx"], sx), c)
        elif kind.startswith("all2all") or kind == "softmax":
            n_out = spec["output_sample_shape"]
            n_out = math.prod(n_out) if isinstance(
                n_out, (tuple, list)) else int(n_out)
            flops += 2.0 * batch * math.prod(shape) * n_out
            shape = (n_out,)
        elif kind == "embedding":
            shape = shape + (int(spec["dim"]),)
        elif kind == "attention":
            t, d = shape
            heads = int(spec["n_heads"])
            proj = 4 * 2.0 * batch * t * d * d
            scores = 2 * 2.0 * batch * heads * t * t * (d // heads)
            if spec.get("causal"):
                scores *= 0.5    # only the lower triangle is model work
            flops += proj + scores
        elif kind == "last_token":
            shape = shape[1:]
        elif kind in ("norm", "dropout", "pos_encoding", "layer_norm"):
            pass                 # elementwise: not counted
        else:
            raise ValueError(f"flops: no rule for layer {kind!r}")
    return flops


def train_step_flops(layers: list, sample_shape: tuple, batch: int
                     ) -> float:
    return 3.0 * forward_flops(layers, sample_shape, batch)


def attention_layers(layers: list) -> list:
    return [layer.get("->", {}) for layer in expand(layers)
            if layer["type"] == "attention"]


def flash_train_cost(layers: list, t: int, d: int, batch: int,
                     bytes_per_element: int = 2) -> dict:
    """What the flash-attention kernels of one training step need,
    summed over the attention layers, by FlashAttention-2's own
    accounting: the forward is two T×T×dh matmuls per head
    (4·B·H·T²·dh FLOPs), the backward 2.5 × that (five matmuls, the
    recomputed scores among them), a causal mask halves both; the
    bytes are Q, K, V read and O written forward, Q, K, V, O, dO read
    and dQ, dK, dV written backward (12 tensors of B·T·D)."""
    flops = bytes_ = 0.0
    for spec in attention_layers(layers):
        heads = int(spec["n_heads"])
        unit = batch * heads * float(t) * t * (d // heads)
        layer_flops = (4.0 + 10.0) * unit
        if spec.get("causal"):
            layer_flops *= 0.5
        flops += layer_flops
        bytes_ += 12.0 * batch * t * d * bytes_per_element
    return {"flops": flops, "bytes": bytes_}


def roofline_seconds(cost: dict, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    by_compute = cost["flops"] / peaks["bf16_flops_per_s"]
    by_memory = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return (by_compute, "compute") if by_compute >= by_memory \
        else (by_memory, "memory")

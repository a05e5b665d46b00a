"""Operations and bytes of the UN-windowed causal flash kernels under
grouped queries — the yardstick's own arithmetic for the layers of a
window / global model that see every earlier position, beside
``flops_band.py`` (whose ``flash_win_train_cost`` counts the layers
under a window shorter than the sequence, and which this PR may not
edit) and ``flops.py`` (whose ``flash_train_cost`` takes H = H_kv and
dh = D / H).

Per layer and training step, by FlashAttention-2's accounting as
``flops.flash_train_cost`` has it: two matmuls of 2·dh a visible pair
forward, five backward — 14·dh·H FLOPs a visible (row, column) pair,
over the causal half T·(T + 1) / 2, whichever of one pass
(``znicz_flash_bwd``) or two (``znicz_flash_dq`` + ``znicz_flash_dkv``,
which recompute the scores once more) the backward takes: recomputed
work does not count.  Bytes at the group's sharing: q, o forward and
q, o, do, dq backward are H·dh wide, k, v forward and k, v, dk, dv
backward H_kv·dh — six tensors of each width.
"""

from __future__ import annotations

from znbench import flops_band


def causal_layers(layers: list, t: int) -> list:
    """The attention layers whose kernels are the un-windowed causal
    ones at sequence length ``t``: causal, no latent K/V, and no window
    that cuts (``flops_band.flash_win_train_cost`` has the others)."""
    return [layer["->"] for layer in layers
            if layer["type"] == "attention" and layer["->"].get("causal")
            and layer["->"].get("kv_latent") is None
            and not (layer["->"].get("window")
                     and int(layer["->"]["window"]) < t)]


def flash_causal_train_cost(layers: list, t: int, batch: int,
                            bytes_per_element: int = 2) -> dict:
    """What the un-windowed causal flash kernels of one training step
    need, summed over :func:`causal_layers`."""
    d = flops_band._embedding_dim(layers)
    flops = bytes_ = 0.0
    for spec in causal_layers(layers, t):
        heads, kv, dh = flops_band.attention_shape(spec, d)
        flops += 14.0 * dh * heads * batch * flops_band.visible_pairs(t)
        bytes_ += 6.0 * batch * t * (heads + kv) * dh * bytes_per_element
    return {"flops": flops, "bytes": bytes_}

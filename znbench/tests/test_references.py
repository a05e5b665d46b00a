"""The plain references: the N-layer LM forward against
``chip_smoke.lm_oracle_logits`` at one layer, and one pass scoring
every position against a pass per position.  (The references against
the SYSTEM, layer by layer, are what ``correct`` means in every toy
run of ``test_cells_toy.py``.)"""

import numpy as np

from znbench.harness import discovery

LAYERS = [{"type": "embedding", "->": {"vocab_size": 50, "dim": 16}},
          {"type": "pos_encoding", "->": {}},
          {"type": "attention", "->": {"n_heads": 2, "causal": True}},
          {"type": "last_token", "->": {}},
          {"type": "softmax", "->": {"output_sample_shape": 50}}]


def params_for(layers, rng, vocab=50, d=16):
    out = {}
    for i, layer in enumerate(layers):
        if layer["type"] == "embedding":
            out[f"layer{i}_weights"] = rng.normal(size=(vocab, d))
        elif layer["type"] == "attention":
            out[f"layer{i}_weights"] = 0.3 * rng.normal(size=(d, 3 * d))
            out[f"layer{i}_bias"] = 0.1 * rng.normal(size=3 * d)
            out[f"layer{i}_weights_out"] = 0.3 * rng.normal(size=(d, d))
            out[f"layer{i}_bias_out"] = 0.1 * rng.normal(size=d)
        elif layer["type"] == "softmax":
            out[f"layer{i}_weights"] = 0.3 * rng.normal(size=(d, vocab))
            out[f"layer{i}_bias"] = 0.1 * rng.normal(size=vocab)
    return {k: v.astype(np.float32) for k, v in out.items()}


def test_one_layer_agrees_with_chip_smokes_oracle():
    import chip_smoke
    reference = discovery.load_module("reference", "attn_lm")
    rng = np.random.default_rng(0)
    params = params_for(LAYERS, rng)
    seq = rng.integers(0, 50, size=12)
    mine = reference.next_token_logits(params, LAYERS, seq, [11])[0]
    theirs = chip_smoke.lm_oracle_logits(params, 2, seq)
    np.testing.assert_allclose(mine, theirs, rtol=2e-5, atol=2e-5)


def test_one_pass_scores_every_position_like_a_pass_per_prefix():
    reference = discovery.load_module("reference", "attn_lm")
    deep = LAYERS[:2] + [LAYERS[2]] * 3 + LAYERS[3:]
    rng = np.random.default_rng(1)
    params = params_for(deep, rng)
    seq = rng.integers(0, 50, size=10)
    padded = np.concatenate([seq, np.zeros(6, np.int64)])
    at_once = reference.next_token_logits(params, deep, padded,
                                          [4, 7, 9])
    for row, pos in zip(at_once, (4, 7, 9)):
        alone = reference.next_token_logits(params, deep,
                                            seq[:pos + 1], [pos])[0]
        np.testing.assert_allclose(row, alone, rtol=2e-5, atol=2e-5)
    probs = reference.forward(params, deep, seq[None, :])[-1]
    np.testing.assert_allclose(probs.sum(), 1.0, rtol=1e-5)

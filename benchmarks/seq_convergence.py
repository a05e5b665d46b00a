"""bf16-vs-f32 convergence evidence for the SEQUENCE stack, on the
real TPU chip.

The conv stack has BF16_CONVERGENCE.json; this is the same moving-
error-curve methodology for the attention path (round-4 verdict item
7): pos_encoding → attention → layer_norm → softmax trained twice
with identical seeds — float32 vs the production bf16 mode — on a
learnable synthetic sequence-classification task (class-prototype
sequences + noise, classes overlapping so validation error floors
above zero).  On TPU the bf16 arm runs the fused Pallas
flash-attention kernel (the unit default), so the band also certifies
the kernel's training numerics end-to-end, not just its unit-test
equality.

Band (same one-sided rule as benchmarks/bf16_convergence.py): bf16
must recover ≥70% of the f32 loss/error drop and may trail the f32
final by ≤30% of that drop; ending better than f32 is a pass.

Artifacts: SEQ_CONVERGENCE.json (per-epoch train CE + train/valid
error counts for both precisions) + a pass/fail summary line.

Run: ``python benchmarks/seq_convergence.py`` (env: SEQC_EPOCHS,
SEQC_BATCH, SEQC_CLASSES, SEQC_LEN, SEQC_DIM).
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

EPOCHS = int(os.environ.get("SEQC_EPOCHS", "40"))
BATCH = int(os.environ.get("SEQC_BATCH", "32"))
N_CLASSES = int(os.environ.get("SEQC_CLASSES", "16"))
SEQ_LEN = int(os.environ.get("SEQC_LEN", "256"))
DIM = int(os.environ.get("SEQC_DIM", "64"))
HEADS = int(os.environ.get("SEQC_HEADS", "4"))
#: prototype-to-noise ratio tuned so validation starts near chance
#: and falls without saturating at zero (the non-degeneracy contract)
NOISE = float(os.environ.get("SEQC_NOISE", "4"))
STEPS_PER_EPOCH = 8
VALID_STEPS = 2


def make_data():
    rng = np.random.default_rng(77)
    protos = rng.normal(0, 1, (N_CLASSES, SEQ_LEN, DIM))
    n_tr, n_va = STEPS_PER_EPOCH * BATCH, VALID_STEPS * BATCH
    yt = rng.integers(0, N_CLASSES, n_tr).astype(np.int32)
    yv = rng.integers(0, N_CLASSES, n_va).astype(np.int32)
    xt = (protos[yt] + NOISE * rng.normal(size=(n_tr, SEQ_LEN, DIM))) \
        .astype(np.float32)
    xv = (protos[yv] + NOISE * rng.normal(size=(n_va, SEQ_LEN, DIM))) \
        .astype(np.float32)
    return xt, yt, xv, yv


def train_curve(precision: str) -> dict:
    from znicz_tpu.backends import XLADevice
    from znicz_tpu.loader.fullbatch import ArrayLoader
    from znicz_tpu.models.standard_workflow import StandardWorkflow
    from znicz_tpu.utils import prng
    from znicz_tpu.utils.config import reset_root, root

    reset_root()
    root.common.precision_type = precision
    prng.seed_all(4242)
    xt, yt, xv, yv = make_data()
    gd = {"learning_rate": 0.01, "gradient_moment": 0.9}
    wf = StandardWorkflow(
        name=f"seqconv_{precision}",
        loader_factory=lambda w: ArrayLoader(
            w, train_data=xt, train_labels=yt,
            valid_data=xv, valid_labels=yv, minibatch_size=BATCH),
        layers=[
            {"type": "pos_encoding", "->": {}},
            {"type": "attention", "->": {"n_heads": HEADS}, "<-": gd},
            {"type": "layer_norm", "->": {}, "<-": gd},
            {"type": "softmax",
             "->": {"output_sample_shape": N_CLASSES}, "<-": gd},
        ],
        decision_config={"max_epochs": EPOCHS})
    wf._max_fires = 10 ** 9
    wf.initialize(device=XLADevice())
    flash = next(u for u in wf.forwards
                 if type(u).__name__ == "MultiHeadAttention")._flash.runs

    losses, errors, valid_errors = [], [], []
    orig = wf.decision.on_epoch_ended

    def hooked():
        orig()
        losses.append(wf.decision.epoch_loss[2])        # TRAIN mean CE
        errors.append(wf.decision.last_epoch_n_err[2])
        valid_errors.append(wf.decision.last_epoch_n_err[1])

    wf.decision.on_epoch_ended = hooked
    wf.run_chunked(steps_per_dispatch=STEPS_PER_EPOCH)
    return {"precision": precision, "flash_pallas": flash,
            "loss": losses, "n_err": errors,
            "valid_n_err": valid_errors}


def main() -> None:
    f32 = train_curve("float32")
    initial, final_f32 = f32["loss"][0], f32["loss"][-1]
    drop = initial - final_f32
    if drop <= 0.05 * initial:
        print(json.dumps({"error": "f32 baseline did not learn "
                          f"(drop {drop:.4f} of {initial:.4f})"}),
              flush=True)
        sys.exit(2)
    n_valid = VALID_STEPS * BATCH
    err_initial = f32["valid_n_err"][0]
    err_final_f32 = min(f32["valid_n_err"])
    if err_final_f32 == 0 or err_initial < 0.5 * n_valid:
        print(json.dumps({"error": "validation curve degenerate "
                          f"(initial {err_initial}, best "
                          f"{err_final_f32} of {n_valid})"}),
              flush=True)
        sys.exit(2)
    bf16 = train_curve("bfloat16")
    from benchmarks.convergence_common import one_sided_band
    verdict = one_sided_band(initial, final_f32, err_initial,
                             err_final_f32, bf16)
    final_bf16, gap = verdict["loss_final"], verdict["gap"]
    loss_ok, err_ok = verdict["loss_band_ok"], verdict["err_band_ok"]
    err_final_bf16 = verdict["valid_err_best"]
    err_gap = verdict["valid_err_gap"]
    ok = verdict["band_ok"]
    artifact = {
        "model": "pos_encoding+attention+layer_norm+softmax",
        "seq_len": SEQ_LEN, "dim": DIM, "heads": HEADS,
        "batch": BATCH, "n_classes": N_CLASSES, "epochs": EPOCHS,
        "n_valid": n_valid,
        "bf16_flash_pallas": bf16["flash_pallas"],
        "loss_initial_f32": initial, "loss_final_f32": final_f32,
        "loss_final_bf16": final_bf16, "gap": gap,
        "loss_band_ok": bool(loss_ok),
        "valid_err_initial": err_initial,
        "valid_err_best_f32": err_final_f32,
        "valid_err_best_bf16": err_final_bf16,
        "valid_err_gap": err_gap, "err_band_ok": bool(err_ok),
        "band_ok": bool(ok),
        "curves": {"float32": f32, "bfloat16": bf16},
    }
    with open(os.path.join(REPO, "SEQ_CONVERGENCE.json"), "w") as fh:
        json.dump(artifact, fh, indent=1)
    print(json.dumps({k: artifact[k] for k in (
        "loss_initial_f32", "loss_final_f32", "loss_final_bf16",
        "gap", "loss_band_ok", "valid_err_initial",
        "valid_err_best_f32", "valid_err_best_bf16", "err_band_ok",
        "bf16_flash_pallas", "band_ok")}), flush=True)
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()

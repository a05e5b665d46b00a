"""Device-resident minibatch schedule (FullBatchLoader.device_schedule):
per-step indices come from an on-device cursor over the uploaded
permutation, so a training step issues NO host→device transfers — the
TPU-first replacement for per-step index uploads (each transfer
costs a round trip)."""

import numpy as np

from tests.conftest import make_blobs
from znicz_tpu.backends import XLADevice
from znicz_tpu.loader.fullbatch import ArrayLoader
from znicz_tpu.models.standard_workflow import StandardWorkflow
from znicz_tpu.utils import prng

N_CLASSES, DIM = 3, 10


def build(device_schedule, max_epochs=3, normalization_scale=None):
    data, labels = make_blobs(40, N_CLASSES, DIM)
    if normalization_scale is not None:
        # store as uint8 to exercise raw-dtype HBM + fused normalize
        data = np.clip((data * 20 + 128), 0, 255).astype(np.uint8)
    n_train = 90
    wf = StandardWorkflow(
        name="devsched",
        loader_factory=lambda w: ArrayLoader(
            w,
            train_data=data[:n_train], train_labels=labels[:n_train],
            valid_data=data[n_train:], valid_labels=labels[n_train:],
            minibatch_size=20, device_schedule=device_schedule,
            normalization_scale=normalization_scale),
        layers=[
            {"type": "all2all_tanh", "->": {"output_sample_shape": 16},
             "<-": {"learning_rate": 0.1, "gradient_moment": 0.9}},
            {"type": "softmax", "->": {"output_sample_shape": N_CLASSES},
             "<-": {"learning_rate": 0.1, "gradient_moment": 0.9}},
        ],
        decision_config={"max_epochs": max_epochs})
    wf._max_fires = 100_000
    return wf


def _run(device_schedule, normalization_scale=None):
    prng.seed_all(1234)
    wf = build(device_schedule,
               normalization_scale=normalization_scale)
    wf.initialize(device=XLADevice())
    wf.run()
    wf.forwards[0].weights.map_read()
    return (wf.forwards[0].weights.mem.copy(),
            int(wf.decision.min_validation_n_err), wf)


def test_device_schedule_matches_host_schedule():
    """Same seed ⇒ the device-computed index stream must reproduce the
    host-upload path bitwise (same permutation, same order)."""
    w_host, err_host, _ = _run(device_schedule=False)
    w_dev, err_dev, _ = _run(device_schedule=True)
    np.testing.assert_allclose(w_host, w_dev, rtol=1e-4, atol=1e-5)
    assert err_host == err_dev


def test_uint8_fused_normalization_matches():
    """Raw uint8 dataset + gather-fused normalize ≡ the same data
    normalized ahead of time."""
    w_host, err_host, _ = _run(device_schedule=False,
                               normalization_scale=2.0 / 255.0)
    w_dev, err_dev, wf = _run(device_schedule=True,
                              normalization_scale=2.0 / 255.0)
    np.testing.assert_allclose(w_host, w_dev, rtol=1e-4, atol=1e-5)
    assert err_host == err_dev
    # and the dataset really is resident in raw dtype
    wf.loader.original_data.map_read()
    assert wf.loader.original_data.mem.dtype == np.uint8


def test_no_per_step_uploads(monkeypatch):
    """Steady-state steps must not call device.put: only epoch-
    boundary schedule refreshes (and the decision's error-counter
    reset) may upload."""
    prng.seed_all(1234)
    wf = build(device_schedule=True, max_epochs=2)
    device = XLADevice()
    wf.initialize(device=device)

    puts = []
    orig_put = type(device).put

    def counting_put(self, arr, vector=None):
        puts.append(getattr(vector, "name", "?"))
        return orig_put(self, arr, vector)

    monkeypatch.setattr(type(device), "put", counting_put)
    wf.run()
    # 2 epochs × (9 minibatches): legacy mode uploads indices+valid
    # every step (≥36 puts).  Device mode: per EPOCH one perm+cursor
    # refresh + the evaluator counter reset — far fewer.
    assert len(puts) <= 10, puts
    for name in puts:
        assert "minibatch_indices" not in name, puts
        assert "minibatch_valid" not in name, puts


def test_resume_restores_device_cursor(tmp_path):
    """Snapshot mid-training, resume: the device-side cursor must
    continue the host cursor exactly (covered by trajectory equality
    with an uninterrupted run)."""
    prng.seed_all(99)
    wf = build(device_schedule=True, max_epochs=4)
    wf.initialize(device=XLADevice())
    wf.run()
    wf.forwards[0].weights.map_read()
    want = wf.forwards[0].weights.mem.copy()

    prng.seed_all(99)
    wf1 = build(device_schedule=True, max_epochs=2)
    wf1.initialize(device=XLADevice())
    wf1.run()
    state = wf1.state_dict()

    prng.seed_all(1)  # resume must not depend on ambient seed
    wf2 = build(device_schedule=True, max_epochs=4)
    wf2.initialize(device=XLADevice())
    wf2.load_state(state)
    wf2.run()
    wf2.forwards[0].weights.map_read()
    np.testing.assert_allclose(wf2.forwards[0].weights.mem, want,
                               rtol=1e-4, atol=1e-5)


def test_run_chunked_matches_per_step():
    """run_chunked (lax.scan over the region body, one dispatch per
    chunk) must reproduce the per-step scheduler run exactly: same
    index stream, same PRNG chain advance, same error bookkeeping."""
    w_step, err_step, wf_step = _run(device_schedule=True)
    prng.seed_all(1234)
    wf = build(device_schedule=True)
    wf.initialize(device=XLADevice())
    wf.run_chunked(steps_per_dispatch=4)
    wf.forwards[0].weights.map_read()
    np.testing.assert_allclose(wf.forwards[0].weights.mem, w_step,
                               rtol=1e-4, atol=1e-5)
    assert int(wf.decision.min_validation_n_err) == err_step
    assert wf.decision.complete  # ran to max_epochs like the scheduler


def test_run_chunked_with_dropout_prng():
    """Stochastic units must advance their device PRNG chain per
    scanned step (the chain is a carried leaf): a dropout workflow
    trains identically chunked vs per-step."""
    def build_do(max_epochs=2):
        data, labels = make_blobs(40, N_CLASSES, DIM)
        wf = StandardWorkflow(
            name="devsched_do",
            loader_factory=lambda w: ArrayLoader(
                w, train_data=data[:90], train_labels=labels[:90],
                valid_data=data[90:], valid_labels=labels[90:],
                minibatch_size=30, device_schedule=True),
            layers=[
                {"type": "all2all_tanh",
                 "->": {"output_sample_shape": 16},
                 "<-": {"learning_rate": 0.1}},
                {"type": "dropout", "->": {"dropout_ratio": 0.3}},
                {"type": "softmax",
                 "->": {"output_sample_shape": N_CLASSES},
                 "<-": {"learning_rate": 0.1}},
            ],
            decision_config={"max_epochs": max_epochs})
        wf._max_fires = 100_000
        return wf

    results = []
    for chunked in (False, True):
        prng.seed_all(777)
        wf = build_do()
        wf.initialize(device=XLADevice())
        if chunked:
            wf.run_chunked(steps_per_dispatch=3)
        else:
            wf.run()
        wf.forwards[0].weights.map_read()
        results.append(wf.forwards[0].weights.mem.copy())
    np.testing.assert_allclose(results[0], results[1],
                               rtol=1e-4, atol=1e-5)


def test_run_chunked_on_mesh():
    """Scanned chunks compose with GSPMD data parallelism: the same
    digits-scale workflow chunked over an 8-device mesh converges."""
    from znicz_tpu.parallel import make_mesh

    prng.seed_all(1234)
    wf = build(device_schedule=True)
    wf.initialize(device=XLADevice(mesh=make_mesh()))
    wf.run_chunked(steps_per_dispatch=4)
    assert wf.decision.complete
    assert int(wf.decision.min_validation_n_err) <= 3
    data_arr = wf.loader.minibatch_data.devmem
    assert len(data_arr.sharding.device_set) == 8  # actually sharded


def test_run_chunked_per_step_fallback():
    """Units flagged NEEDS_PER_STEP_MINIBATCHES (ImageSaver) force the
    per-step scheduler — chunking must not silently starve them."""
    prng.seed_all(1234)
    wf = build(device_schedule=True, max_epochs=1)
    wf.link_image_saver()
    wf.initialize(device=XLADevice())
    calls = {"n": 0}
    orig = wf._region_unit.region.run_chunk

    def counting(n):
        calls["n"] += 1
        return orig(n)

    wf._region_unit.region.run_chunk = counting
    wf.run_chunked(steps_per_dispatch=4)
    assert calls["n"] == 0  # fell back to run(); no chunks dispatched
    assert wf.decision.complete

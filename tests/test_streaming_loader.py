"""Streaming data plane (round 10): the StreamingLoader must be
*invisible* except for memory — same epoch order as the resident
loader bit-for-bit, same trained weights across a mid-epoch
snapshot/resume, zero new XLA compiles once warmed, per-process 1/N
shards partitioning the epoch exactly — while the input pipeline runs
in background threads and hides under the step."""

import time

import numpy as np
import pytest

from znicz_tpu.backends import NumpyDevice, XLADevice
from znicz_tpu.dummy import DummyWorkflow
from znicz_tpu.loader.base import TRAIN, VALID, epoch_permutation
from znicz_tpu.loader.fullbatch import ArrayLoader
from znicz_tpu.loader.streaming import (ShardReader, StreamingLoader,
                                        write_shards)
from znicz_tpu.models.standard_workflow import StandardWorkflow
from znicz_tpu.observe import metrics as obs_metrics
from znicz_tpu.utils import prng

N_CLASSES, DIM = 3, 12


def u8_blobs(n_per_class=60, seed=7):
    """Learnable gaussian blobs quantized to uint8 (the raw-dtype
    wire format the streaming plane is built for)."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(0, 1, (N_CLASSES, DIM))
    data = np.concatenate([
        c + 0.3 * rng.normal(size=(n_per_class, DIM)) for c in centers])
    data = np.clip((data + 4.0) * 32.0, 0, 255).astype(np.uint8)
    labels = np.repeat(np.arange(N_CLASSES),
                       n_per_class).astype(np.int32)
    order = rng.permutation(len(data))
    return data[order], labels[order]


@pytest.fixture
def shard_dir(tmp_path):
    data, labels = u8_blobs()
    d = str(tmp_path / "shards")
    write_shards(d, data[:144], labels[:144],
                 valid_data=data[144:], valid_labels=labels[144:],
                 rows_per_shard=50)
    return d, data, labels


def make_streaming(shard_dir, device=None, minibatch_size=24, seed=77,
                   **kwargs):
    prng.seed_all(seed)
    ld = StreamingLoader(DummyWorkflow(), shard_dir,
                         minibatch_size=minibatch_size, **kwargs)
    ld.initialize(device=device or NumpyDevice())
    return ld


# ----------------------------------------------------------------------
# on-disk format
# ----------------------------------------------------------------------
def test_shard_roundtrip(shard_dir):
    d, data, labels = shard_dir
    reader = ShardReader(d)
    assert reader.class_lengths == [0, 36, 144]
    assert reader.sample_shape == (DIM,)
    assert reader.dtype == np.uint8
    assert reader.nbytes == 180 * DIM
    # global order: valid block then train block
    glob = np.concatenate([data[144:], data[:144]])
    glob_lab = np.concatenate([labels[144:], labels[:144]])
    idx = np.asarray([0, 35, 36, 49, 50, 121, 179])  # spans shards
    out = np.empty((len(idx), DIM), dtype=np.uint8)
    reader.gather(idx, out)
    np.testing.assert_array_equal(out, glob[idx])
    np.testing.assert_array_equal(reader.labels(idx), glob_lab[idx])


def test_epoch_permutation_is_counter_based():
    a = epoch_permutation(123, 4, 50)
    b = epoch_permutation(123, 4, 50)
    np.testing.assert_array_equal(a, b)          # pure function
    assert not np.array_equal(a, epoch_permutation(123, 5, 50))
    assert not np.array_equal(a, epoch_permutation(124, 4, 50))
    assert sorted(a) == list(range(50))          # a permutation


# ----------------------------------------------------------------------
# determinism: streamed ≡ resident, bit for bit
# ----------------------------------------------------------------------
def consume_order(loader, n_steps):
    seq = []
    for _ in range(n_steps):
        loader.run()
        seq.append((loader.epoch_number, loader.minibatch_class,
                    tuple(int(i) for i in
                          loader._host_indices[:loader.minibatch_size])))
    return seq


def test_streamed_order_matches_fullbatch_bitwise(shard_dir):
    """The acceptance contract: a streamed epoch reproduces the
    FullBatchLoader shuffled order exactly for the same seed — across
    MULTIPLE epochs (different permutations each, crossing the
    boundary the prefetch runs through)."""
    d, data, labels = shard_dir
    prng.seed_all(77)
    ref = ArrayLoader(DummyWorkflow(),
                      train_data=data[:144], train_labels=labels[:144],
                      valid_data=data[144:], valid_labels=labels[144:],
                      minibatch_size=24)
    ref.initialize(device=NumpyDevice())
    steps = 3 * len(ref._schedule)
    want = consume_order(ref, steps)

    ld = make_streaming(d, seed=77)
    try:
        got = consume_order(ld, steps)
    finally:
        ld.stop()
    assert got == want
    # the orders genuinely differ between epochs (shuffle is live)
    train_by_epoch = {}
    for ep, cls, idx in got:
        if cls == TRAIN:
            train_by_epoch.setdefault(ep, []).extend(idx)
    assert train_by_epoch[0] != train_by_epoch[1]


def test_streamed_content_and_normalization(shard_dir):
    d, data, labels = shard_dir
    glob = np.concatenate([data[144:], data[:144]])
    glob_lab = np.concatenate([labels[144:], labels[:144]])
    ld = make_streaming(d, normalization_scale=1 / 127.5,
                        normalization_bias=-1.0)
    try:
        for _ in range(8):
            ld.run()
            idx = np.asarray(ld._host_indices)
            np.testing.assert_array_equal(ld.minibatch_raw.mem,
                                          glob[idx])
            np.testing.assert_array_equal(ld.minibatch_labels.mem,
                                          glob_lab[idx])
            ld.numpy_run()  # oracle normalize path
            np.testing.assert_allclose(
                ld.minibatch_data.mem,
                glob[idx].astype(np.float32) / 127.5 - 1.0, atol=1e-6)
    finally:
        ld.stop()


# ----------------------------------------------------------------------
# per-process 1/N sharded reads
# ----------------------------------------------------------------------
def test_two_process_split_partitions_epoch(shard_dir):
    """Simulated 2-process split: the union of both processes' local
    index slices over one epoch is EXACTLY the epoch — every sample
    once, none dropped, none read twice — and both derive the same
    global order from the shared seed."""
    d, _data, _labels = shard_dir
    loaders = [make_streaming(d, seed=5, process_index=p,
                              process_count=2) for p in range(2)]
    try:
        a, b = loaders
        assert a.local_batch == 12 and b.local_batch == 12
        n_sched = len(a._schedule)
        for epoch in (0, 1):
            per_proc = []
            for p, ld in enumerate((a, b)):
                rows = []
                for c in range(n_sched):
                    idx, _cls, count = ld.schedule_entry(epoch, c)
                    lo = p * ld.local_batch
                    hi = min(lo + ld.local_batch, count)
                    if lo < count:  # rows past count are pad (masked
                        #             by minibatch_valid, re-read of
                        #             the padded sample is by design)
                        rows.append(idx[lo:hi])
                per_proc.append(np.concatenate(rows))
            union = np.concatenate(per_proc)
            assert not set(per_proc[0]) & set(per_proc[1])  # disjoint
            assert sorted(union) == list(range(180))        # exact
            # identical global order on both processes
            np.testing.assert_array_equal(a.epoch_order(epoch),
                                          b.epoch_order(epoch))
    finally:
        for ld in loaders:
            ld.stop()


def test_process_split_must_divide_batch(shard_dir):
    d, _data, _labels = shard_dir
    prng.seed_all(1)
    ld = StreamingLoader(DummyWorkflow(), d, minibatch_size=25,
                         process_index=0, process_count=2)
    with pytest.raises(ValueError, match="not divisible"):
        ld.initialize(device=NumpyDevice())


# ----------------------------------------------------------------------
# prefetch behavior
# ----------------------------------------------------------------------
def test_prefetch_crosses_epoch_and_overlaps(shard_dir):
    """With a simulated compute window after each step, the pipeline
    must (a) serve nearly every step from prefetch including the
    first entry of later epochs (the recovered stall the old design
    always paid), and (b) keep the consumer's blocking wait a small
    fraction of the producer's staging work."""
    d, _data, _labels = shard_dir
    ld = make_streaming(d, prefetch_depth=2)
    n_sched = len(ld._schedule)
    steps = 3 * n_sched
    try:
        before_hit = obs_metrics.loader_prefetch(ld.name, "hit").value
        before_x = obs_metrics.loader_prefetch(
            ld.name, "epoch_cross").value
        for _ in range(steps):
            ld.run()
            # the "device" chews the batch: 20 ms, not 2 — beside five
            # other test processes the producer thread does not get a
            # core inside 2 ms every second run (PR 37 measured it)
            time.sleep(0.02)
        assert ld.prefetch_hits >= steps - 2, (
            ld.prefetch_hits, ld.prefetch_misses)
        assert ld.epoch_cross_prefetches >= 2  # both boundaries served
        # canonical series carry the same story
        assert obs_metrics.loader_prefetch(ld.name, "hit").value \
            - before_hit == ld.prefetch_hits
        assert obs_metrics.loader_prefetch(
            ld.name, "epoch_cross").value - before_x \
            == ld.epoch_cross_prefetches
        assert obs_metrics.REGISTRY.get(
            "znicz_input_wait_seconds") is not None
        assert obs_metrics.REGISTRY.get(
            "znicz_prefetch_depth") is not None
    finally:
        ld.stop()


def test_bounded_staging_memory(shard_dir):
    """The ring pins host staging at ring_slots × batch_bytes no
    matter the dataset size — the 'streams past the resident budget'
    guarantee in miniature."""
    d, _data, _labels = shard_dir
    ld = make_streaming(d, prefetch_depth=3, ring_slots=4)
    try:
        ld.run()
        ring = ld._pipe.ring
        assert ring.n_slots == 4
        assert ring.nbytes == 4 * 24 * DIM  # uint8 batches
        assert ring.nbytes < ld.dataset_nbytes
    finally:
        ld.stop()


# ----------------------------------------------------------------------
# snapshot / resume (mid-epoch)
# ----------------------------------------------------------------------
def test_mid_epoch_resume_consumes_identical_sequence(shard_dir):
    """Interrupt mid-epoch; the resumed loader must consume the exact
    remaining sample sequence of the uninterrupted run (the zero1
    resume-parity pattern applied to the input plane)."""
    d, _data, _labels = shard_dir
    ref = make_streaming(d, seed=5)
    n_sched = len(ref._schedule)
    cut = n_sched + 2            # two entries into epoch 1
    total = 3 * n_sched
    try:
        want = consume_order(ref, total)
    finally:
        ref.stop()

    a = make_streaming(d, seed=5)
    try:
        head = consume_order(a, cut)
        state = a.state_dict()
    finally:
        a.stop()
    assert head == want[:cut]
    prng.seed_all(999)  # resume must not depend on the ambient seed
    b = StreamingLoader(DummyWorkflow(), d, minibatch_size=24)
    b.initialize(device=NumpyDevice())
    b.load_state(state)
    try:
        tail = consume_order(b, total - cut)
    finally:
        b.stop()
    assert tail == want[cut:]


def build_stream_wf(shard_dir, max_epochs=2, minibatch_size=24):
    return StandardWorkflow(
        name="stream_resume",
        loader_factory=lambda w: StreamingLoader(
            w, shard_dir, minibatch_size=minibatch_size,
            prefetch_depth=2, normalization_scale=1 / 127.5,
            normalization_bias=-1.0),
        layers=[{"type": "all2all_tanh",
                 "->": {"output_sample_shape": 16},
                 "<-": {"learning_rate": 0.05,
                        "gradient_moment": 0.9}},
                {"type": "softmax", "->": {"output_sample_shape": 3},
                 "<-": {"learning_rate": 0.05,
                        "gradient_moment": 0.9}}],
        decision_config={"max_epochs": max_epochs})


def gather_params(wf):
    out = []
    for fwd in wf.forwards:
        for name in ("weights", "bias"):
            vec = getattr(fwd, name, None)
            if vec is not None and vec:
                vec.map_read()
                out.append(np.array(vec.mem, copy=True))
    return out


def test_streaming_resume_matches_uninterrupted_training(shard_dir):
    """Workflow-level: 1 epoch + snapshot + 1 more ≡ 2 straight
    epochs — trained weights match (the streamed input sequence after
    resume is the proof's substrate)."""
    d, _data, _labels = shard_dir
    prng.seed_all(3)
    straight = build_stream_wf(d, max_epochs=2)
    straight._max_fires = 100_000
    straight.initialize(device=XLADevice())
    straight.run()
    w_straight = gather_params(straight)
    straight.stop()

    prng.seed_all(3)
    wf1 = build_stream_wf(d, max_epochs=1)
    wf1._max_fires = 100_000
    wf1.initialize(device=XLADevice())
    wf1.run()
    state = wf1.state_dict()
    wf1.stop()
    prng.seed_all(999)
    wf2 = build_stream_wf(d, max_epochs=2)
    wf2._max_fires = 100_000
    wf2.initialize(device=XLADevice())
    wf2.load_state(state)
    wf2.run()
    w_resumed = gather_params(wf2)
    wf2.stop()
    for got, want in zip(w_resumed, w_straight):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


# ----------------------------------------------------------------------
# end to end on the XLA backend + the mesh
# ----------------------------------------------------------------------
def test_streaming_trains_xla(shard_dir):
    d, _data, _labels = shard_dir
    prng.seed_all(3)
    wf = build_stream_wf(d, max_epochs=8)
    wf._max_fires = 100_000
    wf.initialize(device=XLADevice())
    wf.run()
    try:
        assert wf.decision.min_validation_n_err_pt <= 15.0
        assert wf.loader.prefetch_hits > 0
    finally:
        wf.stop()


def test_streaming_on_mesh_shards_batch(shard_dir):
    from znicz_tpu.parallel import make_mesh
    d, _data, _labels = shard_dir
    prng.seed_all(3)
    wf = build_stream_wf(d, max_epochs=2)
    wf._max_fires = 100_000
    wf.initialize(device=XLADevice(mesh=make_mesh()))
    wf.run()
    try:
        assert wf.decision.min_validation_n_err is not None
        raw = wf.loader.minibatch_raw.devmem
        assert len(raw.sharding.device_set) == 8  # data-sharded upload
        assert not raw.sharding.is_fully_replicated
    finally:
        wf.stop()


def test_streamed_equals_resident_training(shard_dir):
    """The whole point: swapping the resident loader for the streamed
    one changes NOTHING about the trajectory — same seed, same trained
    weights (the gather and normalize run in the same jit region
    either way)."""
    d, data, labels = shard_dir
    prng.seed_all(11)
    res = StandardWorkflow(
        name="resident_arm",
        loader_factory=lambda w: ArrayLoader(
            w, train_data=data[:144], train_labels=labels[:144],
            valid_data=data[144:], valid_labels=labels[144:],
            minibatch_size=24, normalization_scale=1 / 127.5,
            normalization_bias=-1.0),
        layers=[{"type": "all2all_tanh",
                 "->": {"output_sample_shape": 16},
                 "<-": {"learning_rate": 0.05,
                        "gradient_moment": 0.9}},
                {"type": "softmax", "->": {"output_sample_shape": 3},
                 "<-": {"learning_rate": 0.05,
                        "gradient_moment": 0.9}}],
        decision_config={"max_epochs": 2})
    res._max_fires = 100_000
    res.initialize(device=XLADevice())
    res.run()
    w_res = gather_params(res)
    res.stop()

    prng.seed_all(11)
    stream = build_stream_wf(d, max_epochs=2)
    stream._max_fires = 100_000
    stream.initialize(device=XLADevice())
    stream.run()
    w_stream = gather_params(stream)
    stream.stop()
    for got, want in zip(w_stream, w_res):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_validation_schedule_streams_too(shard_dir):
    d, _data, _labels = shard_dir
    ld = make_streaming(d)
    try:
        classes = []
        for _ in range(len(ld._schedule)):
            ld.run()
            classes.append(ld.minibatch_class)
        assert VALID in classes and TRAIN in classes
    finally:
        ld.stop()


def test_unlabeled_shards(tmp_path):
    data = np.arange(40 * 4, dtype=np.float32).reshape(40, 4)
    d = str(tmp_path / "unlab")
    write_shards(d, data, rows_per_shard=16)
    prng.seed_all(1)
    ld = StreamingLoader(DummyWorkflow(), d, minibatch_size=8)
    ld.initialize(device=NumpyDevice())
    try:
        assert not ld.has_labels
        ld.run()
        assert ld.minibatch_raw.mem.shape == (8, 4)
    finally:
        ld.stop()

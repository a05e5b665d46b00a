"""Full-batch loaders: whole dataset resident in HBM, minibatch
assembly is an on-device gather (reference:
``veles/loader/fullbatch.py`` — ``FullBatchLoader`` with its
gather-by-index kernel; here the kernel is ``jnp.take`` fused into the
jit region so minibatch assembly costs no host↔device traffic).
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from znicz_tpu.loader.base import Loader, TEST, TRAIN, VALID
from znicz_tpu.memory import Vector


class FullBatchLoader(Loader):
    """Loader whose subclass provides the entire dataset as arrays.

    Subclasses implement :meth:`load_data` and fill
    ``original_data`` / ``original_labels`` plus ``class_lengths``.
    Samples must be ordered test, validation, train along axis 0.

    Two TPU-first bandwidth choices:

    - the dataset stays in its ORIGINAL dtype in HBM (uint8 images are
      4× smaller than f32) and normalization is fused into the
      per-step gather inside the jit region;
    - with ``device_schedule`` (default, jit-region path) the shuffled
      permutation and the minibatch schedule live ON DEVICE: per-step
      indices come from a device-resident cursor, so a training step
      issues NO host→device transfers (a permutation upload per epoch
      replaces two uploads per step, each of which costs a round
      trip).
    """

    # the dataset itself: large, immutable, rebuilt by load_data on
    # resume — never serialized into snapshots; sched_* are derived
    # from _shuffled/_schedule (snapshotted) and re-uploaded on resume
    SNAPSHOT_EXCLUDE = Loader.SNAPSHOT_EXCLUDE + (
        "original_data", "original_labels", "sched_perm",
        "sched_starts", "sched_counts", "sched_cursor")

    def __init__(self, workflow, name: str | None = None,
                 normalization_scale: float | None = None,
                 normalization_bias: float = 0.0,
                 device_schedule: bool = True,
                 **kwargs) -> None:
        super().__init__(workflow, name=name, **kwargs)
        self.original_data = Vector(name=f"{self.name}.original_data")
        self.original_labels = Vector(name=f"{self.name}.original_labels")
        #: optional affine normalization x*scale + bias, fused into the
        #: gather (device path) / applied per-minibatch (oracle path)
        self.normalization_scale = normalization_scale
        self.normalization_bias = normalization_bias
        self.device_schedule = bool(device_schedule)
        self.sched_perm = Vector(name=f"{self.name}.sched_perm")
        self.sched_starts = Vector(name=f"{self.name}.sched_starts")
        self.sched_counts = Vector(name=f"{self.name}.sched_counts")
        self.sched_cursor = Vector(name=f"{self.name}.sched_cursor")

    @property
    def has_labels(self) -> bool:
        return bool(self.original_labels)

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device=device, **kwargs)
        self.init_vectors(self.original_data, self.original_labels)
        if self.device is not None and not self.device.is_host_only:
            assert self._shuffled is not None
            self.sched_perm.reset(self._shuffled.copy())
            self.sched_starts.reset(np.asarray(
                [lo for _, lo, _ in self._schedule], dtype=np.int32))
            self.sched_counts.reset(np.asarray(
                [hi - lo for _, lo, hi in self._schedule],
                dtype=np.int32))
            self.sched_cursor.reset(np.zeros((), dtype=np.int32))
            self.init_vectors(self.sched_perm, self.sched_starts,
                              self.sched_counts, self.sched_cursor)
            self._sched_dirty = False  # just uploaded fresh

    # -- device-resident schedule (see class docstring) -----------------
    def _on_device_schedule(self) -> bool:
        return (self.device_schedule and self._in_region
                and self.device is not None
                and not self.device.is_host_only)

    def _sync_device_schedule(self) -> None:
        if not self._sched_dirty:
            return
        # dirty the HOST copies; the region's unmap sweep uploads them
        # (once per epoch shuffle / snapshot resume, not per step)
        self.sched_perm.map_invalidate()
        self.sched_perm.mem[...] = self._shuffled
        self.sched_cursor.map_invalidate()
        self.sched_cursor.mem[...] = self._cursor - 1  # entry just picked
        self._sched_dirty = False

    def load_state(self, state: dict) -> None:
        super().load_state(state)
        self._sched_dirty = True  # device copies are stale after resume

    def create_minibatch_data(self) -> None:
        sample_shape = self.original_data.shape[1:]
        self.minibatch_data.reset(np.zeros(
            (self.max_minibatch_size,) + tuple(sample_shape),
            dtype=self.act_store_dtype))
        if self.has_labels:
            # one label per sample, or (T,) labels per sample (a
            # next-token label at every position)
            self.minibatch_labels.reset(np.zeros(
                (self.max_minibatch_size,)
                + tuple(self.original_labels.shape[1:]),
                dtype=np.int32))

    # -- the gather -----------------------------------------------------
    def _normalize_np(self, batch: np.ndarray) -> np.ndarray:
        if self.normalization_scale is not None:
            batch = batch * np.float32(self.normalization_scale) \
                + np.float32(self.normalization_bias)
        return batch

    def numpy_run(self) -> None:
        self.original_data.map_read()
        self.minibatch_indices.map_read()
        idx = self.minibatch_indices.mem
        self.minibatch_data.map_invalidate()
        self.minibatch_data.mem[...] = self._normalize_np(
            self.original_data.mem[idx].astype(np.float32))
        if self.has_labels:
            self.original_labels.map_read()
            self.minibatch_labels.map_invalidate()
            self.minibatch_labels.mem[...] = self.original_labels.mem[idx]

    def xla_run(self) -> None:
        if self._on_device_schedule():
            cursor = self.sched_cursor.devmem
            start = jnp.take(self.sched_starts.devmem, cursor)
            count = jnp.take(self.sched_counts.devmem, cursor)
            offs = jnp.arange(self.max_minibatch_size, dtype=jnp.int32)
            # short tail pads by repeating the first sample (host
            # semantics); masking uses minibatch_valid as before
            pos = start + jnp.where(offs < count, offs, 0)
            idx = jnp.take(self.sched_perm.devmem, pos)
            self.minibatch_indices.devmem = idx
            self.minibatch_valid.devmem = count.astype(jnp.int32)
            self.sched_cursor.devmem = \
                (cursor + 1) % np.int32(len(self._schedule))
        else:
            idx = self.minibatch_indices.devmem
        batch = jnp.take(
            self.original_data.devmem, idx, axis=0).astype(jnp.float32)
        if self.normalization_scale is not None:
            # fused into the gather program: dataset stays in its raw
            # dtype in HBM (uint8 = 4× less gather traffic + memory)
            batch = batch * jnp.float32(self.normalization_scale) \
                + jnp.float32(self.normalization_bias)
        self.minibatch_data.devmem = batch
        if self.has_labels:
            self.minibatch_labels.devmem = jnp.take(
                self.original_labels.devmem, idx, axis=0)


class ArrayLoader(FullBatchLoader):
    """FullBatchLoader fed directly with numpy arrays per class — the
    workhorse for samples and tests (reference analogue: the ad-hoc
    per-sample loader subclasses in ``znicz/samples/*``)."""

    def __init__(self, workflow,
                 train_data: np.ndarray,
                 train_labels: np.ndarray | None = None,
                 valid_data: np.ndarray | None = None,
                 valid_labels: np.ndarray | None = None,
                 test_data: np.ndarray | None = None,
                 test_labels: np.ndarray | None = None,
                 **kwargs) -> None:
        # before super().__init__: bypass the linked-attr machinery
        object.__setattr__(self, "_arrays",
                           (test_data, test_labels, valid_data, valid_labels,
                            train_data, train_labels))
        super().__init__(workflow, **kwargs)

    def load_data(self) -> None:
        (test_d, test_l, valid_d, valid_l, train_d, train_l) = self._arrays
        datas, labels = [], []
        lengths = [0, 0, 0]
        for cls, (d, l) in zip((TEST, VALID, TRAIN),
                               ((test_d, test_l), (valid_d, valid_l),
                                (train_d, train_l))):
            if d is None:
                if l is not None:
                    raise ValueError(f"{self}: labels without data for "
                                     f"class {cls}")
                continue
            lengths[cls] = len(d)
            datas.append(np.asarray(d))
            labels.append(None if l is None
                          else np.asarray(l, dtype=np.int32))
        if any(l is not None for l in labels):
            # labels index by GLOBAL sample position — partial labels
            # would silently misalign the gather
            missing = [i for i, l in enumerate(labels) if l is None]
            if missing:
                raise ValueError(
                    f"{self}: labels given for some classes but not "
                    f"others — provide labels for every supplied split")
            self.original_labels.reset(np.concatenate(labels, axis=0))
        self.class_lengths = lengths
        self.original_data.reset(np.concatenate(datas, axis=0))

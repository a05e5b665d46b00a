"""Loader base: the minibatch engine.

Rebuilds the reference's ``veles/loader/base.py``:

- three sample classes ``TEST=0 / VALID=1 / TRAIN=2`` with
  ``class_lengths``; one *epoch* walks every non-empty class in order
  (test, validation, train), the reference's schedule that lets the
  Decision unit account errors per class;
- train indices reshuffled every epoch — **counter-based**: the
  permutation for epoch *e* is a pure function of ``(shuffle_seed,
  e)`` through a Philox CBRNG (:func:`epoch_permutation`), not a
  stateful stream.  Any component can therefore compute any epoch's
  order without replaying history: prefetchers legally look across
  epoch boundaries (:meth:`Loader.schedule_entry`), every process of a
  multi-host run derives the same global order from the shared seed
  and reads only its 1/N slice, and a resumed run reproduces the
  exact remaining sequence from the snapshotted seed;
- the last minibatch of a class is **padded** to the static minibatch
  size (static shapes for XLA) and ``minibatch_valid`` carries the
  true count as a device scalar so evaluators mask the tail —
  replacing the reference's dynamic short minibatches, which would
  force recompilation on TPU;
- flags consumed by Decision: ``minibatch_class``, ``last_minibatch``,
  ``epoch_ended``, ``epoch_number``.

The index-picking bookkeeping is ``host_run`` (control plane); the
data gather is the device path (see ``fullbatch.py``) so it fuses into
the jit region.
"""

from __future__ import annotations

import threading

import numpy as np

from znicz_tpu.accelerated_units import AcceleratedUnit
from znicz_tpu.memory import Vector
from znicz_tpu.mutable import Bool
from znicz_tpu.utils import prng

TEST, VALID, TRAIN = 0, 1, 2
CLASS_NAME = {TEST: "test", VALID: "validation", TRAIN: "train"}

_U64 = (1 << 64) - 1


def epoch_permutation(seed: int, epoch: int, n: int) -> np.ndarray:
    """The framework's one shuffle function: a permutation of ``n``
    as a pure function of ``(seed, epoch)`` via the Philox
    counter-based RNG.  Every loader family (full-batch, streaming,
    image) derives its train order here, so a streamed epoch
    reproduces the resident loader's shuffled order bit-for-bit for
    the same seed — the determinism contract the streaming data
    plane's cross-epoch prefetch and per-process sharding rest on."""
    gen = np.random.Generator(np.random.Philox(
        key=np.array([seed & _U64, epoch & _U64], dtype=np.uint64)))
    return gen.permutation(n).astype(np.int32)


class Loader(AcceleratedUnit):
    """Abstract minibatch provider.

    Subclasses implement :meth:`load_data` (set ``class_lengths`` and
    storage), :meth:`create_minibatch_data` (allocate the minibatch
    Vectors) and the gather (``numpy_run``/``xla_run``).
    """

    SNAPSHOT_ATTRS = ("epoch_number", "_cursor", "_shuffled",
                      "_shuffle_seed", "minibatch_class",
                      "minibatch_size", "minibatch_offset")
    # transient per-step buffers; resume regenerates them next step
    SNAPSHOT_EXCLUDE = ("minibatch_data", "minibatch_labels",
                        "minibatch_indices", "minibatch_valid")

    def __init__(self, workflow, name: str | None = None,
                 minibatch_size: int = 100,
                 shuffle_limit: int = np.iinfo(np.int64).max,
                 prng_name: str = "default",
                 **kwargs) -> None:
        super().__init__(workflow, name=name, **kwargs)
        self.max_minibatch_size = int(minibatch_size)
        self.shuffle_limit = shuffle_limit  # epochs to keep shuffling
        self._prng_name = prng_name
        # outputs
        self.minibatch_data = Vector(name=f"{self.name}.minibatch_data",
                                     batch_major=True)
        self.minibatch_labels = Vector(
            name=f"{self.name}.minibatch_labels", batch_major=True)
        self.minibatch_indices = Vector(
            name=f"{self.name}.minibatch_indices", batch_major=True)
        self.minibatch_valid = Vector(name=f"{self.name}.minibatch_valid")
        # schedule state
        self.class_lengths = [0, 0, 0]
        self.epoch_number = 0
        self.minibatch_class = TRAIN
        self.minibatch_size = 0          # true sample count this step
        self.minibatch_offset = 0
        self.last_minibatch = Bool(False)
        self.epoch_ended = Bool(False)
        self.train_ended = Bool(False)
        self._schedule: list[tuple[int, int, int]] = []  # (class, lo, hi)
        self._cursor = 0
        self._shuffled: np.ndarray | None = None
        #: root of the counter-based shuffle: (seed, epoch) → order.
        #: Drawn once from the loader PRNG at initialize (so the global
        #: seed still decides the trajectory) and snapshotted.
        self._shuffle_seed = 0
        self._order_cache: dict[tuple[int, int], np.ndarray] = {}
        #: producer threads (streaming prefetch, decode pools) call
        #: train_order concurrently with the control plane
        self._order_lock = threading.Lock()
        self._host_indices: np.ndarray | None = None
        #: device-resident schedule copies need (re)uploading
        self._sched_dirty = True

    # ------------------------------------------------------------------
    @property
    def total_samples(self) -> int:
        return int(sum(self.class_lengths))

    @property
    def class_offsets(self) -> list[int]:
        """Global index where each class's samples start."""
        off, out = 0, []
        for length in self.class_lengths:
            out.append(off)
            off += length
        return out

    def class_index_range(self, cls: int) -> tuple[int, int]:
        lo = self.class_offsets[cls]
        return lo, lo + self.class_lengths[cls]

    # ------------------------------------------------------------------
    # subclass API
    # ------------------------------------------------------------------
    def load_data(self) -> None:
        raise NotImplementedError

    def create_minibatch_data(self) -> None:
        raise NotImplementedError

    # ------------------------------------------------------------------
    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device=device, **kwargs)
        self.rnd = prng.get(self._prng_name)
        self.load_data()
        if self.total_samples == 0:
            raise ValueError(f"{self}: load_data produced no samples")
        self.max_minibatch_size = min(self.max_minibatch_size,
                                      max(self.class_lengths))
        shards = getattr(self.device, "n_data_shards", 1)
        if self.max_minibatch_size % shards:
            aligned = (self.max_minibatch_size // shards) * shards
            if aligned == 0:
                raise ValueError(
                    f"{self}: minibatch_size {self.max_minibatch_size} "
                    f"cannot be sharded over the mesh's {shards} data "
                    f"shards")
            self.warning(
                "minibatch_size %d not divisible by %d data shards — "
                "clamped to %d", self.max_minibatch_size, shards, aligned)
            self.max_minibatch_size = aligned
        self.minibatch_indices.reset(
            np.zeros(self.max_minibatch_size, dtype=np.int32))
        self.minibatch_valid.reset(np.zeros((), dtype=np.int32))
        self.create_minibatch_data()
        self.init_vectors(self.minibatch_data, self.minibatch_labels,
                          self.minibatch_indices, self.minibatch_valid)
        # one draw from the shared stream roots ALL epoch permutations
        # (snapshot resume restores the saved seed over this one)
        self._shuffle_seed = int(self.rnd.randint(0, 2 ** 63))
        self._order_cache.clear()
        self._build_schedule()
        if (self._shuffled is None
                or len(self._shuffled) != self.total_samples):
            # fresh start; on snapshot resume the restored permutation
            # and cursor are kept so the trajectory continues exactly
            self._shuffled = np.arange(self.total_samples, dtype=np.int32)
            self._cursor = 0
            self._shuffle_train()

    def _build_schedule(self) -> None:
        self._schedule = []
        for cls in (TEST, VALID, TRAIN):
            lo, hi = self.class_index_range(cls)
            for start in range(lo, hi, self.max_minibatch_size):
                self._schedule.append(
                    (cls, start, min(start + self.max_minibatch_size, hi)))

    # ------------------------------------------------------------------
    # deterministic counter-based epoch order
    # ------------------------------------------------------------------
    def train_order(self, epoch: int) -> np.ndarray:
        """Global indices of the TRAIN segment in the order epoch
        ``epoch`` visits them — a pure function of the snapshotted
        ``_shuffle_seed`` (any epoch, past or future, no state
        replay).  ``shuffle_limit`` freezes the order at the last
        shuffled epoch, matching the stateful semantics it replaces."""
        lo, hi = self.class_index_range(TRAIN)
        n = hi - lo
        if n <= 0 or self.shuffle_limit <= 0:
            return np.arange(lo, hi, dtype=np.int32)
        eff = min(int(epoch), int(self.shuffle_limit) - 1)
        key = (self._shuffle_seed, eff)
        with self._order_lock:
            perm = self._order_cache.get(key)
            if perm is None:
                if len(self._order_cache) >= 4:  # current + lookahead
                    self._order_cache.pop(next(iter(self._order_cache)))
                perm = self._order_cache[key] = epoch_permutation(
                    self._shuffle_seed, eff, n)
        return (lo + perm).astype(np.int32)

    def epoch_order(self, epoch: int) -> np.ndarray:
        """The full global sample order of epoch ``epoch`` (test and
        validation segments ride in natural order; train shuffled)."""
        order = np.arange(self.total_samples, dtype=np.int32)
        lo, hi = self.class_index_range(TRAIN)
        if hi > lo:
            order[lo:hi] = self.train_order(epoch)
        return order

    def schedule_entry(self, epoch: int, cursor: int
                       ) -> tuple[np.ndarray, int, int]:
        """Deterministic ``(padded indices, class, true count)`` for
        ANY schedule position — including future epochs.  This is what
        lets prefetchers (streaming producer threads, the image
        loader's decode pool) run ahead across epoch boundaries: the
        order there is already decided by the counter-based shuffle,
        no stale-order hazard."""
        cls, lo, hi = self._schedule[cursor]
        count = hi - lo
        order = self.epoch_order(epoch)
        idx = np.empty(self.max_minibatch_size, dtype=np.int32)
        idx[:count] = order[lo:hi]
        if count < self.max_minibatch_size:  # pad: repeat the first
            idx[count:] = idx[0]
        return idx, cls, count

    def _shuffle_train(self) -> None:
        if self.epoch_number >= self.shuffle_limit:
            return
        lo, hi = self.class_index_range(TRAIN)
        if hi > lo:
            assert self._shuffled is not None
            self._shuffled[lo:hi] = self.train_order(self.epoch_number)
            self._sched_dirty = True  # device-resident copy is stale

    # ------------------------------------------------------------------
    # per-step control plane
    # ------------------------------------------------------------------
    def host_run(self) -> None:
        if self._cursor >= len(self._schedule):
            # previous step ended the epoch; begin the next one
            self._cursor = 0
            self.epoch_number += 1
            self._shuffle_train()
        cls, lo, hi = self._schedule[self._cursor]
        self._cursor += 1
        count = hi - lo
        idx = np.empty(self.max_minibatch_size, dtype=np.int32)
        idx[:count] = self._shuffled[lo:hi]
        if count < self.max_minibatch_size:  # pad by repeating the first
            idx[count:] = idx[0]
        self.minibatch_class = cls
        self.minibatch_size = count
        self.minibatch_offset = lo
        self._host_indices = idx  # host copy (streaming loaders read
        #                           it back without a device round-trip)
        at_end = self._cursor >= len(self._schedule)
        self.last_minibatch.value = (
            at_end or self._schedule[self._cursor][0] != cls)
        self.epoch_ended.value = at_end
        self.train_ended.value = at_end and cls == TRAIN
        if self._on_device_schedule():
            # indices/valid are computed ON DEVICE from the resident
            # schedule (sched_* leaves) — no per-step host→device
            # uploads (each one a round trip the step would wait on)
            self._sync_device_schedule()
            return
        self.minibatch_indices.map_invalidate()
        self.minibatch_indices.mem[...] = idx
        self.minibatch_valid.map_invalidate()
        self.minibatch_valid.mem[...] = count
        # device path (gather) needs indices on device
        if self.device is not None and not self.device.is_host_only:
            self.minibatch_indices.unmap()
            self.minibatch_valid.unmap()

    # device-resident schedule hooks (implemented by FullBatchLoader;
    # streaming loaders stage data host-side anyway, so they keep the
    # host-upload path)
    def _on_device_schedule(self) -> bool:
        return False

    def _sync_device_schedule(self) -> None:  # pragma: no cover - hook
        raise NotImplementedError

    @property
    def forward_mode(self) -> str:
        """"train" on train minibatches, else "eval" — linked (one-way)
        into stochastic units (dropout, stochastic pooling) so their
        region variants track the current minibatch class."""
        return "train" if self.minibatch_class == TRAIN else "eval"

    # stats ------------------------------------------------------------
    def class_minibatch_count(self, cls: int) -> int:
        return sum(1 for c, _, _ in self._schedule if c == cls)

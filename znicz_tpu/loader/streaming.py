"""Streaming data plane: sharded async prefetch that hides the input
pipeline under the step.

The third way between the reference's synchronous per-minibatch reads
and this repo's device-resident :class:`FullBatchLoader` (which caps
every workload at device memory — BENCH r03's 13.4k img/s/chip came
precisely from making inputs resident):
:class:`StreamingLoader` reads per-host file shards through a
background pipeline into a bounded ring of host staging buffers
(:class:`znicz_tpu.memory.StagingRing`), uploads them ahead of the
consumer with ``device_put`` prefetch (``prefetch_depth`` batches in
flight), and delivers each step's batch as a pointer swap
(:meth:`Vector.accept_device`) — so a training step's input cost is
the *wait* for an already-issued transfer, ≈ 0 when the pipeline keeps
up.  Host memory is pinned at ``ring_slots × batch_bytes`` no matter
how large the dataset is.

Three design decisions carry the whole plane:

1. **Counter-based shuffling** (:func:`znicz_tpu.loader.base.
   epoch_permutation`): epoch *e*'s order is a pure function of
   ``(shuffle_seed, e)``, so (a) the producer legally prefetches
   ACROSS epoch boundaries (no stale-order hazard — the order of an
   epoch that has not started yet is already decided), (b) every
   process of a multi-host run derives the same global order from the
   shared seed and reads only its ``1/N`` row slice of every
   minibatch (:meth:`StreamingLoader.local_indices` — together the
   slices partition the epoch exactly), and (c) a streamed epoch
   reproduces the :class:`FullBatchLoader` shuffled order
   **bit-for-bit** for the same seed (both derive from the same
   function; ``tests/test_streaming_loader.py`` pins it).

2. **Pipelined, not batched**: reader pool (shard gather into a ring
   slot) → uploader thread (``device_put`` + release the slot) →
   bounded device queue (depth = ``prefetch_depth``) → consumer.  Each
   stage overlaps the others and the device step; the bounded queues
   are the backpressure.

3. **Static signatures**: the staged batch rides in the dataset's raw
   dtype (uint8 images upload 4× smaller) and the affine normalize
   runs on-device inside the jit region (:meth:`xla_run`); shapes,
   dtypes and shardings are identical every step, so a warmed train
   loop adds ZERO XLA compiles (``tests/test_retrace_guard.py``).

Telemetry (round-9 registry): ``znicz_input_wait_seconds`` (consumer
block — ≈ 0 when hidden), ``znicz_input_stage_seconds`` (producer
cost — the work being hidden), ``znicz_prefetch_depth``,
``znicz_loader_prefetch_total{event=hit|miss|epoch_cross}``, and
uploads count into ``znicz_device_transfer_bytes_total{h2d}`` like
every other transfer.  ``input_hidden = 1 − wait_sum/stage_sum`` is
the overlap attestation ``stream_bench`` and the multichip dryrun
report.

On-disk format (:func:`write_shards`): a directory of ``.npy`` shard
files plus ``manifest.json`` (class lengths, sample shape, dtype).
Samples are stored in global-index order (test, validation, train) —
the same convention as the full-batch loaders — and read back through
``numpy`` memory maps, so a "read" is page-cache traffic in a reader
thread, never a resident copy.
"""

from __future__ import annotations

import json
import os
import queue
import threading
import time
import zlib
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field

import numpy as np

from znicz_tpu.loader.base import Loader
from znicz_tpu.memory import StagingRing, Vector
from znicz_tpu.observe import metrics as _metrics
from znicz_tpu.observe import tracing as _tracing
from znicz_tpu.resilience import faults as _faults
from znicz_tpu.utils.config import root

MANIFEST_NAME = "manifest.json"


class ShardReadError(RuntimeError):
    """A shard read failed (CRC mismatch, IO error, injected fault).
    Carries the shard index so the retry path can quarantine a
    persistently bad shard and continue the epoch."""

    def __init__(self, shard: int | None, msg: str) -> None:
        super().__init__(msg)
        self.shard = shard


class PipelineDead(RuntimeError):
    """The streaming pipeline's producer or uploader thread died.
    Raised in the CONSUMER (propagated through the bounded device
    queue by a poison-pill sentinel — the consumer never hangs on a
    dead producer); the loader absorbs a bounded number of these by
    rebuilding the pipeline (``engine.reader_restarts``, default 2)."""


def _file_crc32(path: str, chunk: int = 1 << 20) -> int:
    crc = 0
    with open(path, "rb") as fh:
        while True:
            buf = fh.read(chunk)
            if not buf:
                return crc
            crc = zlib.crc32(buf, crc)


# ----------------------------------------------------------------------
# on-disk shard format
# ----------------------------------------------------------------------
def write_shards(out_dir: str,
                 train_data: np.ndarray,
                 train_labels: np.ndarray | None = None,
                 valid_data: np.ndarray | None = None,
                 valid_labels: np.ndarray | None = None,
                 test_data: np.ndarray | None = None,
                 test_labels: np.ndarray | None = None,
                 rows_per_shard: int = 4096) -> str:
    """Write arrays as a sharded streaming dataset (the inverse of
    :class:`ShardReader`).  Rows land in global-index order — test,
    validation, train — matching the full-batch loader convention, so
    index *i* means the same sample to every loader family."""
    os.makedirs(out_dir, exist_ok=True)
    datas: list[np.ndarray] = []
    labels: list[np.ndarray | None] = []
    lengths = [0, 0, 0]
    for cls, (d, lab) in enumerate(((test_data, test_labels),
                                    (valid_data, valid_labels),
                                    (train_data, train_labels))):
        if d is None:
            if lab is not None:
                raise ValueError(f"labels without data for class {cls}")
            continue
        lengths[cls] = len(d)
        datas.append(np.asarray(d))
        labels.append(None if lab is None
                      else np.asarray(lab, dtype=np.int32))
    if not datas:
        raise ValueError("write_shards: no data given")
    if any(lab is not None for lab in labels) \
            and any(lab is None for lab in labels):
        raise ValueError("labels given for some classes but not others")
    data = np.concatenate(datas, axis=0)
    labs = (np.concatenate([lab for lab in labels if lab is not None])
            if labels[0] is not None else None)
    shards = []
    for i, lo in enumerate(range(0, len(data), int(rows_per_shard))):
        chunk = np.ascontiguousarray(data[lo:lo + rows_per_shard])
        fn = f"data-{i:05d}.npy"
        np.save(os.path.join(out_dir, fn), chunk)
        # per-shard integrity digest (round 11): readers verify on
        # first open and quarantine-and-continue on mismatch
        entry: dict = {"data": fn, "rows": int(len(chunk)),
                       "crc32": _file_crc32(os.path.join(out_dir, fn))}
        if labs is not None:
            lfn = f"labels-{i:05d}.npy"
            np.save(os.path.join(out_dir, lfn),
                    labs[lo:lo + rows_per_shard])
            entry["labels"] = lfn
            entry["labels_crc32"] = _file_crc32(
                os.path.join(out_dir, lfn))
        shards.append(entry)
    manifest = {"version": 1,
                "class_lengths": [int(n) for n in lengths],
                "sample_shape": [int(s) for s in data.shape[1:]],
                "dtype": str(data.dtype),
                "shards": shards}
    with open(os.path.join(out_dir, MANIFEST_NAME), "w") as fh:
        json.dump(manifest, fh, indent=1)
    return out_dir


class ShardReader:
    """Memory-mapped random-access view over a shard directory.

    Shard files open as read-only ``numpy`` memory maps on first
    touch; :meth:`gather` fancy-indexes them into a caller buffer, so
    the actual disk IO happens as page faults inside whatever reader
    thread called — the streaming loader's pool parallelism.  Labels
    (tiny) load eagerly."""

    def __init__(self, directory: str) -> None:
        self.directory = directory
        #: observability label for the rows-quarantined counter; the
        #: owning StreamingLoader stamps its unit name here (fallback:
        #: the shard directory's basename)
        self.obs_label = os.path.basename(
            os.path.normpath(directory)) or directory
        path = os.path.join(directory, MANIFEST_NAME)
        with open(path) as fh:
            self.manifest = json.load(fh)
        self.class_lengths = [int(n)
                              for n in self.manifest["class_lengths"]]
        self.sample_shape = tuple(self.manifest["sample_shape"])
        self.dtype = np.dtype(self.manifest["dtype"])
        self._shards = self.manifest["shards"]
        rows = np.asarray([s["rows"] for s in self._shards],
                          dtype=np.int64)
        self._offsets = np.concatenate(([0], np.cumsum(rows)))
        self.n_samples = int(self._offsets[-1])
        if self.n_samples != sum(self.class_lengths):
            raise ValueError(
                f"{path}: shard rows {self.n_samples} != "
                f"class_lengths sum {sum(self.class_lengths)}")
        self._maps: list[np.ndarray | None] = [None] * len(self._shards)
        self._lock = threading.Lock()
        #: shards that exhausted their read retries: their rows
        #: deliver zeros for the rest of the run (quarantine-and-
        #: continue beats crashing the epoch on one bad file)
        self._quarantined: set[int] = set()
        self.has_labels = all("labels" in s for s in self._shards)
        self._labels: np.ndarray | None = None
        if self.has_labels:
            parts = []
            for i, s in enumerate(self._shards):
                lpath = os.path.join(directory, s["labels"])
                want = s.get("labels_crc32")
                if want is not None and self.verify_crc \
                        and _file_crc32(lpath) != int(want):
                    raise ShardReadError(
                        i, f"{lpath}: labels CRC mismatch — dataset "
                           f"corrupt on disk")
                parts.append(np.load(lpath))
            self._labels = np.concatenate(parts).astype(np.int32)

    @property
    def verify_crc(self) -> bool:
        """``root.common.engine.shard_crc`` (default on): verify each
        shard file's manifest digest on first open.  One sequential
        read per shard per process — page-cache warming the mmap would
        do anyway."""
        return bool(root.common.engine.get("shard_crc", True))

    @property
    def quarantined(self) -> frozenset:
        return frozenset(self._quarantined)

    def quarantine(self, shard: int) -> None:
        """Mark a shard permanently bad: drop its mmap, serve zeros."""
        with self._lock:
            self._quarantined.add(int(shard))
            self._maps[int(shard)] = None

    @property
    def nbytes(self) -> int:
        """Logical dataset size (what a resident loader would hold)."""
        return self.n_samples * self.dtype.itemsize \
            * int(np.prod(self.sample_shape, dtype=np.int64))

    def _mmap(self, shard: int) -> np.ndarray:
        arr = self._maps[shard]
        if arr is None:
            with self._lock:
                arr = self._maps[shard]
                if arr is None:
                    path = os.path.join(
                        self.directory, self._shards[shard]["data"])
                    want = self._shards[shard].get("crc32")
                    if want is not None and self.verify_crc \
                            and _file_crc32(path) != int(want):
                        raise ShardReadError(
                            shard, f"{path}: CRC mismatch (manifest "
                                   f"{int(want)}) — shard corrupt on "
                                   f"disk")
                    arr = np.load(path, mmap_mode="r")
                    self._maps[shard] = arr
        return arr

    def gather(self, idx: np.ndarray, out: np.ndarray) -> None:
        """``out[k] = dataset[idx[k]]`` across shard boundaries.
        Quarantined shards contribute zero rows; fault sites
        ``loader.corrupt_shard`` / ``loader.short_read`` raise here
        exactly like a real CRC/IO failure would."""
        idx = np.asarray(idx, dtype=np.int64)
        shard_of = np.searchsorted(self._offsets, idx, side="right") - 1
        for s in np.unique(shard_of):
            s = int(s)
            mask = shard_of == s
            if s in self._quarantined:
                # round-19 satellite: zero-filled rows are silent data
                # loss — count every one so /metrics (and /readyz,
                # report-only) make the loss loud
                out[mask] = 0
                _metrics.loader_rows_quarantined(self.obs_label).inc(
                    int(mask.sum()))
                continue
            if _faults.fire("loader.corrupt_shard", shard=s) is not None:
                raise ShardReadError(s, f"injected corrupt shard {s}")
            if _faults.fire("loader.short_read", shard=s) is not None:
                raise ShardReadError(s, f"injected short read on "
                                        f"shard {s}")
            rows = idx[mask] - self._offsets[s]
            out[mask] = self._mmap(s)[rows]

    def labels(self, idx: np.ndarray) -> np.ndarray:
        assert self._labels is not None
        return self._labels[np.asarray(idx, dtype=np.int64)]


# ----------------------------------------------------------------------
# the pipeline
# ----------------------------------------------------------------------
@dataclass
class _Item:
    """One staged minibatch travelling read → upload → consume."""
    key: tuple[int, int]                 # (epoch, cursor) it belongs to
    labels: np.ndarray | None
    slot: int | None = None              # ring slot (host-only delivery)
    devarr: object = None                # uploaded device array
    crossed_epoch: bool = field(default=False)
    #: poison pill: a producer/uploader thread died — wakes the
    #: consumer IMMEDIATELY instead of leaving it blocked on the
    #: bounded queue (round-11 satellite: the dead-reader hang fix)
    pill: bool = field(default=False)


class _StreamPipeline:
    """Producer (reader pool → ring slot) + uploader (``device_put`` →
    bounded device queue) threads for one contiguous run of schedule
    positions.  Restarts (snapshot resume, schedule jumps) tear the
    pipeline down and build a fresh one at the new position — rare by
    construction, so simplicity wins over reuse."""

    def __init__(self, loader: "StreamingLoader",
                 epoch: int, cursor: int) -> None:
        self.loader = loader
        self.start_key = (epoch, cursor)
        self.stop_flag = threading.Event()
        self.error: BaseException | None = None
        self.ring = StagingRing(
            loader.ring_slots,
            (loader.local_batch,) + loader.sample_shape,
            loader.dataset_dtype)
        self.read_q: "queue.Queue[_Item]" = queue.Queue(
            maxsize=loader.ring_slots)
        self.dev_q: "queue.Queue[_Item]" = queue.Queue(
            maxsize=loader.prefetch_depth)
        self._pool = (ThreadPoolExecutor(
            loader.n_reader_threads,
            thread_name_prefix=f"{loader.name}.reader")
            if loader.n_reader_threads > 1 else None)
        self._producer = threading.Thread(
            target=self._thread_body, args=(self._produce, epoch, cursor),
            name=f"{loader.name}.producer", daemon=True)
        self._uploader = threading.Thread(
            target=self._thread_body, args=(self._upload,),
            name=f"{loader.name}.uploader", daemon=True)
        self._producer.start()
        self._uploader.start()

    # -- death propagation ---------------------------------------------
    def _thread_body(self, fn, *args) -> None:
        """Run a pipeline stage; on ANY death record the cause and
        push a poison pill through the device queue so the consumer
        raises :class:`PipelineDead` immediately instead of hanging on
        (or slow-polling) the bounded queue."""
        try:
            fn(*args)
        except BaseException as exc:  # noqa: BLE001 — must not die silent
            if not self.stop_flag.is_set():
                if self.error is None:
                    self.error = exc
                try:
                    self.dev_q.put_nowait(
                        _Item((-1, -1), None, pill=True))
                except queue.Full:
                    pass  # consumer has items to drain; the error
                    #       check in take()'s poll loop catches it

    # -- stage 1: shard gather into a ring slot ------------------------
    def _produce(self, epoch: int, cursor: int) -> None:
        loader = self.loader
        n_sched = len(loader._schedule)
        start_epoch = epoch
        while not self.stop_flag.is_set():
            slot = self.ring.acquire(timeout=0.1)
            if slot is None:
                continue
            try:
                if _faults.fire("loader.reader_death") is not None:
                    raise _faults.FaultInjected(
                        f"{loader.name}: injected reader-thread death")
                t0 = time.perf_counter()
                idx, _cls, _count = loader.schedule_entry(epoch, cursor)
                local = loader._local_slice(idx)
                self._gather_retry(local, self.ring.buffer(slot))
                labels = (loader._reader.labels(local)
                          if loader.has_labels else None)
                if _metrics.enabled():
                    _metrics.input_stage_seconds(loader.name).observe(
                        time.perf_counter() - t0)
                item = _Item((epoch, cursor), labels, slot=slot,
                             crossed_epoch=epoch > start_epoch
                             and cursor == 0)
            except BaseException as exc:
                self.ring.release(slot)
                if self.stop_flag.is_set():
                    return
                self.error = exc  # surfaced by the consumer's take()
                raise
            if not self._put(self.read_q, item):
                self.ring.release(slot)
                return
            cursor += 1
            if cursor >= n_sched:
                cursor, epoch = 0, epoch + 1

    def _gather_retry(self, local_idx: np.ndarray,
                      buf: np.ndarray) -> None:
        """Shard gather with exponential-backoff retry and quarantine:
        a transient failure (IO hiccup, injected short read) retries
        up to ``engine.read_retries`` times; a shard still failing
        after that is quarantined (its rows deliver zeros) and the
        gather proceeds — a persistently corrupt shard costs data, not
        the run."""
        loader = self.loader
        reader = loader._reader
        retries = int(root.common.engine.get("read_retries", 2))
        backoff = float(root.common.engine.get("read_backoff_s", 0.05))
        attempts = 0
        while True:
            try:
                self._gather(local_idx, buf)
                if attempts:
                    _metrics.recoveries("shard_retry").inc()
                return
            except ShardReadError as exc:
                if self.stop_flag.is_set():
                    raise
                attempts += 1
                _metrics.loader_read_retries(loader.name).inc()
                if attempts <= retries:
                    loader.warning(
                        "shard read failed (%s) — retry %d/%d",
                        exc, attempts, retries)
                    time.sleep(backoff * (2 ** (attempts - 1)))
                    continue
                shard = exc.shard
                if shard is None or shard in reader.quarantined:
                    raise  # not shard-attributable: real death
                reader.quarantine(shard)
                _metrics.loader_shards_quarantined(loader.name).inc()
                _metrics.recoveries("shard_quarantine").inc()
                loader.warning(
                    "shard %d quarantined after %d failed reads (%s) "
                    "— its rows deliver zeros for the rest of the run",
                    shard, attempts, exc)
                attempts = 0  # fresh budget for the remaining shards

    def _gather(self, local_idx: np.ndarray, buf: np.ndarray) -> None:
        reader = self.loader._reader
        n = len(local_idx)
        pool = self._pool
        t = self.loader.n_reader_threads
        if pool is None or n < 2 * t:
            reader.gather(local_idx, buf)
            return
        step = -(-n // t)  # ceil: t contiguous row ranges
        futs = [pool.submit(reader.gather, local_idx[lo:lo + step],
                            buf[lo:lo + step])
                for lo in range(0, n, step)]
        for f in futs:
            f.result()

    # -- stage 2: device_put ahead of the consumer ---------------------
    def _upload(self) -> None:
        loader = self.loader
        device = loader.device
        on_device = device is not None and not device.is_host_only
        while not self.stop_flag.is_set():
            try:
                item = self.read_q.get(timeout=0.1)
            except queue.Empty:
                continue
            if on_device:
                try:
                    buf = self.ring.buffer(item.slot)
                    devarr = device.put_local_batch(
                        buf, vector=loader.minibatch_raw)
                    if hasattr(devarr, "block_until_ready"):
                        # fence BEFORE releasing the slot: the transfer
                        # may read the host buffer asynchronously, and
                        # the ring hands this slot back for reuse
                        devarr.block_until_ready()
                except BaseException as exc:
                    self.ring.release(item.slot)
                    if self.stop_flag.is_set():
                        return
                    self.error = exc
                    raise
                if _metrics.enabled():
                    _metrics.transfer_bytes("h2d").inc(buf.nbytes)
                self.ring.release(item.slot)
                item.slot = None
                item.devarr = devarr
            if not self._put(self.dev_q, item):
                if item.slot is not None:
                    self.ring.release(item.slot)
                return

    def _put(self, q: "queue.Queue[_Item]", item: _Item) -> bool:
        while not self.stop_flag.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    # -- consumer side -------------------------------------------------
    def take(self, timeout: float = 300.0) -> _Item:
        deadline = time.monotonic() + timeout
        while True:
            try:
                item = self.dev_q.get(timeout=0.1)
            except queue.Empty:
                if self.error is not None:
                    raise PipelineDead(
                        f"{self.loader}: streaming producer died"
                    ) from self.error
                if time.monotonic() > deadline:
                    raise PipelineDead(
                        f"{self.loader}: streaming pipeline produced "
                        f"nothing for {timeout:.0f}s — reader thread "
                        f"dead?") from None
                continue
            if item.pill:
                raise PipelineDead(
                    f"{self.loader}: streaming pipeline thread died"
                ) from self.error
            return item

    def take_nowait(self) -> _Item | None:
        try:
            item = self.dev_q.get_nowait()
        except queue.Empty:
            return None
        if item.pill:
            raise PipelineDead(
                f"{self.loader}: streaming pipeline thread died"
            ) from self.error
        return item

    @property
    def ready(self) -> int:
        """Uploaded batches waiting for the consumer (live gauge)."""
        return self.dev_q.qsize()

    def stop(self) -> None:
        self.stop_flag.set()
        self._producer.join(timeout=5.0)
        self._uploader.join(timeout=5.0)
        if self._pool is not None:
            self._pool.shutdown(wait=False)


# ----------------------------------------------------------------------
# the loader
# ----------------------------------------------------------------------
class StreamingLoader(Loader):
    """Minibatch loader over a sharded on-disk dataset with async
    prefetch (module docstring has the design).

    Parameters
    ----------
    shard_dir:
        directory written by :func:`write_shards` (``manifest.json``
        + ``.npy`` shards).
    prefetch_depth:
        device batches uploaded ahead of the consumer (≥ 1; 2 =
        double-buffered h2d, 3 = triple).  Raise it when the transfer
        is long-latency; host footprint grows by one
        staged batch per unit.
    ring_slots:
        host staging buffers feeding the uploader (default
        ``prefetch_depth + 2``: one being read, one being uploaded,
        plus slack).
    n_reader_threads:
        shard-gather parallelism within one minibatch.
    process_index / process_count:
        this host's slice of the data axis (defaults to the jax
        process topology).  Each process stages only rows
        ``[p·B/P, (p+1)·B/P)`` of every global minibatch — per-host
        1/N reads whose union partitions the epoch exactly.
    normalization_scale / normalization_bias:
        optional affine ``x·scale + bias`` fused on-device into the
        jit region (the dataset stays in its raw dtype on the wire).
    """

    SNAPSHOT_EXCLUDE = Loader.SNAPSHOT_EXCLUDE + ("minibatch_raw",)

    def __init__(self, workflow, shard_dir: str,
                 name: str | None = None,
                 normalization_scale: float | None = None,
                 normalization_bias: float = 0.0,
                 prefetch_depth: int = 2,
                 ring_slots: int | None = None,
                 n_reader_threads: int = 2,
                 process_index: int | None = None,
                 process_count: int | None = None,
                 **kwargs) -> None:
        super().__init__(workflow, name=name, **kwargs)
        self.shard_dir = shard_dir
        self.normalization_scale = normalization_scale
        self.normalization_bias = normalization_bias
        self.prefetch_depth = max(1, int(prefetch_depth))
        self.ring_slots = int(ring_slots) if ring_slots \
            else self.prefetch_depth + 2
        self.n_reader_threads = max(1, int(n_reader_threads))
        if (process_index is None) != (process_count is None):
            raise ValueError(f"{self}: give both process_index and "
                             f"process_count or neither")
        self._pidx_arg = process_index
        self._pcount_arg = process_count
        self._pidx, self._pcount = 0, 1
        #: raw staging Vector: the dataset dtype rides the wire, the
        #: affine normalize runs on-device (same policy as ImageLoader)
        self.minibatch_raw = Vector(name=f"{self.name}.minibatch_raw",
                                    batch_major=True)
        self._reader: ShardReader | None = None
        self._pipe: _StreamPipeline | None = None
        self._held: tuple[_StreamPipeline, int] | None = None
        # overlap telemetry mirrors (canonical series hold the truth;
        # these stay readable without the registry, bench-style)
        self.prefetch_hits = 0
        self.prefetch_misses = 0
        self.input_wait_s = 0.0
        self.epoch_cross_prefetches = 0
        #: pipeline rebuilds after a producer/uploader death this run
        self.pipeline_restarts = 0

    # -- dataset ---------------------------------------------------------
    def load_data(self) -> None:
        self._reader = ShardReader(self.shard_dir)
        # rows-quarantined attribution under THIS loader's name (the
        # canonical per-loader label every other loader series uses)
        self._reader.obs_label = self.name
        self.class_lengths = list(self._reader.class_lengths)

    @property
    def has_labels(self) -> bool:
        assert self._reader is not None
        return self._reader.has_labels

    @property
    def sample_shape(self) -> tuple[int, ...]:
        assert self._reader is not None
        return self._reader.sample_shape

    @property
    def dataset_dtype(self) -> np.dtype:
        assert self._reader is not None
        return self._reader.dtype

    @property
    def dataset_nbytes(self) -> int:
        assert self._reader is not None
        return self._reader.nbytes

    @property
    def local_batch(self) -> int:
        """Rows of each global minibatch THIS process stages."""
        return self.max_minibatch_size // self._pcount

    def _local_slice(self, idx: np.ndarray) -> np.ndarray:
        lb = self.local_batch
        return idx[self._pidx * lb:(self._pidx + 1) * lb]

    def local_indices(self, epoch: int, cursor: int) -> np.ndarray:
        """Global sample indices this process reads for schedule
        position ``(epoch, cursor)`` — the per-host 1/N contract the
        2-process-split test pins (union = partition, no dup/drop)."""
        idx, _cls, _count = self.schedule_entry(epoch, cursor)
        return self._local_slice(idx)

    def create_minibatch_data(self) -> None:
        self.minibatch_raw.reset(np.zeros(
            (self.local_batch,) + self.sample_shape,
            dtype=self.dataset_dtype))
        self.minibatch_data.reset(np.zeros(
            (self.max_minibatch_size,) + self.sample_shape,
            dtype=self.act_store_dtype))
        if self.has_labels:
            self.minibatch_labels.reset(np.zeros(
                self.max_minibatch_size, dtype=np.int32))

    def initialize(self, device=None, **kwargs) -> None:
        if self._pidx_arg is not None:
            self._pidx = int(self._pidx_arg)
            self._pcount = int(self._pcount_arg)
        else:
            from znicz_tpu.parallel.process_shard import process_info
            self._pidx, self._pcount = process_info()
        super().initialize(device=device, **kwargs)
        if self.max_minibatch_size % self._pcount:
            raise ValueError(
                f"{self}: minibatch_size {self.max_minibatch_size} not "
                f"divisible by {self._pcount} processes")
        self.init_vectors(self.minibatch_raw)
        self._stop_pipeline()
        self.prefetch_hits = 0
        self.prefetch_misses = 0
        self.input_wait_s = 0.0
        self.epoch_cross_prefetches = 0
        self.pipeline_restarts = 0
        if _metrics.enabled():
            _metrics.prefetch_depth(self.name).set(self.prefetch_depth)

    def stop(self) -> None:
        self._stop_pipeline()
        super().stop()

    def load_state(self, state: dict) -> None:
        super().load_state(state)
        # the in-flight prefetch belongs to the pre-restore trajectory;
        # the first post-resume take() restarts at the restored cursor
        self._stop_pipeline()
        # round 18 (elastic restart): the global schedule is counter-
        # based, so a snapshot written by an N-process gang restores
        # onto ANY surviving process count — the 1/N slice is re-derived
        # here from the LIVE topology, never from the snapshot.  The
        # operator-facing log is what a restart drill greps for.
        if self._pcount > 1 or os.environ.get("ZNICZ_HEARTBEAT_DIR"):
            lb = self.local_batch
            self.info(
                "resumed at epoch %d cursor %d — re-sliced to rows "
                "[%d, %d) of every %d-row global minibatch "
                "(process %d/%d)", int(self.epoch_number),
                int(self._cursor), self._pidx * lb,
                (self._pidx + 1) * lb, self.max_minibatch_size,
                self._pidx, self._pcount)

    def _stop_pipeline(self) -> None:
        self._held = None
        if self._pipe is not None:
            self._pipe.stop()
            self._pipe = None

    def warmup(self) -> None:
        """Start the background pipeline at the current schedule
        position BEFORE the first step, so even step 1 is served from
        an in-flight prefetch (otherwise the first take is the one
        unavoidable synchronous read).  Optional — the pipeline
        self-starts on the first ``host_run`` either way."""
        if self._pipe is not None:
            return
        if self._cursor >= len(self._schedule):
            key = (self.epoch_number + 1, 0)   # next host_run wraps
        else:
            key = (self.epoch_number, self._cursor)
        self._pipe = _StreamPipeline(self, *key)

    # -- the per-step handoff -------------------------------------------
    def _take(self, expected: tuple[int, int]) -> _Item:
        """The staged batch for ``expected``, absorbing a bounded
        number of pipeline deaths: a dead producer/uploader thread
        raises :class:`PipelineDead` in the consumer (poison pill —
        never a hang on the bounded queue), and the loader rebuilds
        the pipeline at the expected position up to
        ``engine.reader_restarts`` (default 2) times per run before
        letting the error propagate.  A restart re-reads the same
        deterministic indices, so a recovered run is bit-identical to
        an undisturbed one."""
        while True:
            try:
                return self._take_inner(expected)
            except PipelineDead as exc:
                self.pipeline_restarts += 1
                limit = int(root.common.engine.get("reader_restarts", 2))
                self._stop_pipeline()
                if self.pipeline_restarts > limit:
                    raise
                self.warning(
                    "streaming pipeline died (%s) — restart %d/%d at "
                    "epoch %d cursor %d", exc, self.pipeline_restarts,
                    limit, *expected)
                _metrics.loader_pipeline_restarts(self.name).inc()
                _metrics.recoveries("reader_restart").inc()

    def _take_inner(self, expected: tuple[int, int]) -> _Item:
        """The staged batch for schedule position ``expected`` —
        served from the prefetch queue (hit) or after a pipeline
        (re)start at that position (miss)."""
        restarted = False
        if self._pipe is None:
            self._pipe = _StreamPipeline(self, *expected)
            restarted = True
        item = self._pipe.take_nowait()
        if item is not None and item.key != expected:
            # resume / schedule jump: the stream in flight is for the
            # wrong trajectory — rebuild at the expected position
            self._release_item(item)
            self._stop_pipeline()
            self._pipe = _StreamPipeline(self, *expected)
            restarted = True
            item = None
        hit = item is not None
        if item is None:
            with _tracing.TRACER.span(f"input_wait:{self.name}",
                                      cat="loader"):
                t0 = time.perf_counter()
                item = self._pipe.take()
                waited = time.perf_counter() - t0
            if item.key != expected:  # only possible pre-restart
                assert not restarted, (item.key, expected)
                self._release_item(item)
                self._stop_pipeline()
                return self._take(expected)
        else:
            waited = 0.0
        self.input_wait_s += waited
        # a boundary entry only counts as a RECOVERED stall when the
        # pipeline actually got ahead across the epoch (a hit); a miss
        # there is just the ordinary stall being repaid
        crossed = item.crossed_epoch and hit
        if _metrics.enabled():
            _metrics.input_wait_seconds(self.name).observe(waited)
            _metrics.loader_prefetch(
                self.name, "hit" if hit else "miss").inc()
            if crossed:
                _metrics.loader_prefetch(self.name, "epoch_cross").inc()
        if hit:
            self.prefetch_hits += 1
        else:
            self.prefetch_misses += 1
        if crossed:
            self.epoch_cross_prefetches += 1
        return item

    def _release_item(self, item: _Item) -> None:
        if item.slot is not None and self._pipe is not None:
            self._pipe.ring.release(item.slot)

    def host_run(self) -> None:
        super().host_run()  # schedule bookkeeping + indices/valid
        expected = (self.epoch_number, self._cursor - 1)
        item = self._take(expected)
        on_device = self.device is not None \
            and not self.device.is_host_only
        # host-only delivery holds the ring slot until the NEXT step
        # (the consumer reads minibatch_raw.mem in numpy_run); device
        # delivery released it at upload time
        if self._held is not None:
            pipe, slot = self._held
            if pipe is self._pipe:
                pipe.ring.release(slot)
            self._held = None
        if on_device:
            self.minibatch_raw.accept_device(item.devarr)
        else:
            self.minibatch_raw.map_invalidate()
            self.minibatch_raw.mem[...] = \
                self._pipe.ring.buffer(item.slot)
            self._held = (self._pipe, item.slot)
        if self.has_labels:
            assert item.labels is not None
            if self._pcount > 1 and on_device:
                # multi-process: this host stages only its label rows;
                # assemble the global batch like the data upload
                self.minibatch_labels.accept_device(
                    self.device.put_local_batch(
                        np.ascontiguousarray(item.labels),
                        vector=self.minibatch_labels))
            else:
                self.minibatch_labels.map_invalidate()
                self.minibatch_labels.mem[...] = item.labels
                if on_device:
                    self.minibatch_labels.unmap()
        if _metrics.enabled() and self._pipe is not None:
            pipe = self._pipe
            _metrics.REGISTRY.gauge(
                "znicz_prefetch_ready_batches",
                "Uploaded batches waiting for the consumer",
                labels=("loader",)).labels(
                    loader=self.name).set(pipe.ready)

    # -- the on-device normalize (fused into the jit region) ------------
    def numpy_run(self) -> None:
        self.minibatch_raw.map_read()
        self.minibatch_data.map_invalidate()
        batch = self.minibatch_raw.mem.astype(np.float32)
        if self.normalization_scale is not None:
            batch = batch * np.float32(self.normalization_scale) \
                + np.float32(self.normalization_bias)
        self.minibatch_data.mem[...] = batch

    def xla_run(self) -> None:
        import jax.numpy as jnp
        batch = self.minibatch_raw.devmem.astype(jnp.float32)
        if self.normalization_scale is not None:
            batch = batch * jnp.float32(self.normalization_scale) \
                + jnp.float32(self.normalization_bias)
        self.minibatch_data.devmem = batch


__all__ = ["StreamingLoader", "ShardReader", "write_shards",
           "MANIFEST_NAME", "ShardReadError", "PipelineDead"]

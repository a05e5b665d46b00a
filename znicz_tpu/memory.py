"""Vector: the framework's buffer type.

Rebuilds the reference's host↔device buffer pair (reference:
``veles/memory.py`` — ``Vector`` with ``mem``/``devmem`` and the
``map_read`` / ``map_write`` / ``map_invalidate`` / ``unmap`` lazy-sync
protocol), re-based on ``jax.Array``:

- ``devmem`` is a ``jax.Array`` living in HBM (or a tracer while a jit
  region is being traced);
- ``mem`` is a lazily-materialized host ``numpy`` mirror;
- the map/unmap state machine is preserved because it is the
  reference's central correctness invariant (SURVEY.md §3.2) and it
  keeps host↔HBM traffic explicit: ``map_read`` = device→host fetch,
  ``unmap`` = host→device upload, ``map_invalidate`` = "host will
  overwrite everything, skip the fetch".

Invalid transitions raise — the reference enforced the same assertions
as its substitute for a race detector (SURVEY.md §5.2).
"""

from __future__ import annotations

import enum
from typing import TYPE_CHECKING

import numpy as np

from znicz_tpu.observe import metrics as _metrics
from znicz_tpu.observe import tracing as _tracing

if TYPE_CHECKING:  # pragma: no cover
    from znicz_tpu.backends import Device


def _count_transfer(direction: str, nbytes: int) -> None:
    """Telemetry: host<->device traffic through the map/unmap
    protocol (the explicit-transfer invariant makes this THE place
    transfer volume is knowable).  Gated — disabled telemetry costs
    one dict lookup on an already-transferring path."""
    if _metrics.enabled():
        _metrics.transfer_bytes(direction).inc(nbytes)


def _is_float_dtype(dt: np.dtype) -> bool:
    """True for any float dtype incl. the ml_dtypes ones (bfloat16
    reports numpy kind 'V' and ``np.finfo`` rejects it, so probe with
    ``ml_dtypes.finfo``, which covers the numpy floats too)."""
    if np.issubdtype(dt, np.floating):
        return True
    try:
        import ml_dtypes
        ml_dtypes.finfo(dt)
        return True
    except ValueError:
        return False


class _State(enum.Enum):
    EMPTY = 0     #: no storage yet
    HOST = 1      #: host copy authoritative; device copy stale/absent
    DEVICE = 2    #: device copy authoritative; host copy stale
    SYNCED = 3    #: both copies valid; host reads need no transfer


class Vector:
    """Host-mirrored device buffer with explicit sync points."""

    __slots__ = ("_mem", "_devmem", "_state", "_device", "_tracing", "name",
                 "batch_major", "model_shard_dim", "data_shard_dim",
                 "data_shard_pad", "member_axis", "model_shard_axis",
                 "_partition", "cast_copy", "_cast_made")

    def __init__(self, mem: np.ndarray | None = None,
                 name: str = "", batch_major: bool = False,
                 model_shard_dim: int | None = None,
                 data_shard_dim: int | None = None,
                 member_axis: bool = False) -> None:
        self._mem: np.ndarray | None = None
        self._devmem = None
        self._state = _State.EMPTY
        self._device: "Device | None" = None
        self._tracing = False
        self.name = name
        #: first dim is the minibatch — shard it over the mesh's data
        #: axis when the device carries one (SPMD data parallelism)
        self.batch_major = batch_major
        #: dim sharded over the mesh's MODEL axis (tensor parallelism:
        #: column/row-parallel weights and feature-sharded activations);
        #: None = replicated over model.  Set before ``initialize`` —
        #: the device reads it when placing the buffer
        self.model_shard_dim = model_shard_dim
        #: dim sharded over the mesh's DATA axis for NON-batch-major
        #: persistent state (ZeRO-1 optimizer sharding: each chip owns
        #: 1/N of the momentum accumulators).  Composes with
        #: ``model_shard_dim`` (a different dim) so bf16 optimizer
        #: state + TP weights + data-sharded momentum all stack.
        self.data_shard_dim = data_shard_dim
        #: True when dim 0 is a POPULATION axis (K stacked model
        #: replicas — the population engine's member-major buffers,
        #: one slice per member of a K-replica training run).  Member
        #: buffers shard dim 0 over the mesh's DATA axis, the same
        #: axis batch-major buffers ride in ordinary data-parallel
        #: training: in population mode the members *are* the data
        #: parallelism (small nets train K-per-chip; a K that does not
        #: divide the axis stays replicated and XLA time-slices).
        #: ``model_shard_dim`` composes (a member's TP dim, already
        #: shifted by the leading member axis).  Mutually exclusive
        #: with ``batch_major``/``data_shard_dim``.
        self.member_axis = member_axis
        #: rows of zero padding appended along ``data_shard_dim`` when
        #: the logical dim does not divide the data-axis size (jax
        #: shardings must divide evenly).  Snapshots slice the padding
        #: off on save and re-pad on load, so checkpoints stay
        #: layout-independent (``Unit.state_dict``/``load_state``).
        self.data_shard_pad = 0
        #: mesh axis ``model_shard_dim`` rides — MODEL by default; the
        #: ring sets SEQ on a 3-D (data × model × seq) mesh so DP × TP
        #: × SP compose without overloading the model axis
        self.model_shard_axis = "model"
        #: resolved placement from the workflow's declarative
        #: partition-rule table (parallel.partition) — when set,
        #: ``backends.sharding_for`` is a pure lookup and the slot
        #: attributes above are a compatibility layer populated FROM
        #: this resolution, not hand-set by units
        self._partition = None
        #: the Vector that holds THIS one's device value in another
        #: dtype (:meth:`keep_cast`), or None; what its owner wants
        #: called when it was made again
        self.cast_copy: "Vector | None" = None
        self._cast_made = None
        if mem is not None:
            self.reset(mem)

    # ------------------------------------------------------------------
    # allocation
    # ------------------------------------------------------------------
    def reset(self, mem: np.ndarray | None) -> None:
        """(Re)bind host contents; device copy becomes stale."""
        self._check_not_tracing("reset")
        if mem is None:
            self._mem = None
            self._devmem = None
            self._state = _State.EMPTY
            return
        arr = np.asarray(mem)
        if arr.ndim and not arr.flags.c_contiguous:
            arr = np.ascontiguousarray(arr)  # NB: would promote 0-d to 1-d
        self._mem = arr
        self._state = _State.HOST

    def initialize(self, device: "Device") -> None:
        """Attach to a device; upload if the host copy is authoritative.

        Reference: ``Vector.initialize`` in ``veles/memory.py`` — called
        from ``AcceleratedUnit.init_vectors``.
        """
        self._check_not_tracing("initialize")
        self._device = device
        if device.is_host_only:
            return
        if self._state == _State.HOST:
            self._upload()
            self._state = _State.SYNCED

    def _upload(self) -> None:
        """The host copy to the device: the ONE way a host write
        reaches it, whoever wrote (``reset``, ``map_write`` …
        ``unmap``) — so a cast kept of the device value
        (:meth:`keep_cast`) is made again here, and at no writer.
        The mirror of :meth:`_read_back`: a span
        (``upload:<name>``) and a phase of ``znicz_setup_seconds``.
        They hold the HOST's time in the call and no fence: the copy
        may land after ``put`` returns, and what is left of it is
        waited for by whoever next blocks on the device."""
        if not _metrics.enabled():
            self._devmem = self._device.put(self._mem, vector=self)
            self._recast_for_host()
            return
        with _tracing.TRACER.span(
                f"upload:{self.name}", cat="transfer",
                bytes=int(self._mem.nbytes)) as span:
            self._devmem = self._device.put(self._mem, vector=self)
            self._recast_for_host()
        _count_transfer("h2d", self._mem.nbytes)
        _metrics.setup_seconds("upload").inc(span.dur_us / 1e6)

    def keep_cast(self, copy: "Vector", dtype, made=None) -> None:
        """From now on ``copy`` holds this Vector's device value in
        ``dtype``: a leaf of its own, for a reader that cannot cast on
        its way in (a kernel's operand) and would pay a pass over the
        tensor for the cast every step.  It is the cast of this
        Vector, bit for bit, wherever a program reads it:

        - it is made here, on the device, from the device value (no
          host array of its size ever exists), placed as this one is;
        - it is made AGAIN wherever the host's copy of this Vector is
          uploaded or an uploaded array adopted (:meth:`_upload`,
          :meth:`accept_device`), and ``made()`` is called, for the
          owner's count;
        - who writes this Vector ON the device calls :meth:`recast`
          (``GradientDescentBase._update_param_xla``: one more result
          of the fusion that holds the new value)."""
        if self._devmem is None or self._state == _State.HOST:
            raise ValueError(f"Vector '{self.name}': keep_cast before "
                             f"the value is on a device")
        for attr in ("model_shard_dim", "model_shard_axis",
                     "data_shard_dim", "data_shard_pad", "member_axis"):
            setattr(copy, attr, getattr(self, attr))
        copy._device, copy._mem = self._device, None
        copy._devmem = self._devmem.astype(dtype)
        copy._state = _State.DEVICE
        self.cast_copy, self._cast_made = copy, made

    def recast(self) -> None:
        """Write the kept cast, if one is kept, from the device value
        as it stands (traced, inside a region, or not)."""
        copy = self.cast_copy
        if copy is not None:
            copy.devmem = self._devmem.astype(copy.dtype)

    def check_cast(self) -> None:
        """Raise if a cast is kept and is not the cast of the device
        value as it stands: someone wrote this Vector on the device
        and did not call :meth:`recast` (asked after every dispatch
        under ``engine.debug_checks``)."""
        copy = self.cast_copy
        if copy is None:
            return
        import jax.numpy as jnp
        if not bool(jnp.array_equal(
                copy._devmem, self._devmem.astype(copy.dtype),
                equal_nan=True)):
            raise AssertionError(
                f"Vector '{self.name}': the cast kept in '{copy.name}' "
                f"is stale — a device-side writer did not recast()")

    def _recast_for_host(self) -> None:
        if self.cast_copy is not None:
            self.recast()
            if self._cast_made is not None:
                self._cast_made()

    @property
    def needs_collective_read(self) -> bool:
        """True when reading this Vector back to the host requires a
        cross-process collective (multi-process SPMD, batch-sharded
        buffer).  Such reads are only safe in lockstep — master-only
        paths (snapshots) must skip these Vectors or they deadlock."""
        dev = self._devmem
        return (self._state == _State.DEVICE
                and hasattr(dev, "is_fully_addressable")
                and not dev.is_fully_addressable
                and not dev.sharding.is_fully_replicated)

    # ------------------------------------------------------------------
    # the map/unmap protocol
    # ------------------------------------------------------------------
    def map_read(self) -> None:
        """Make the host copy current for reading."""
        self._check_not_tracing("map_read")
        if self._state == _State.EMPTY:
            raise ValueError(f"Vector '{self.name}': map_read on empty buffer")
        if self._state == _State.DEVICE:
            assert self._device is not None
            self._mem = self._read_back()
            _count_transfer("d2h", self._mem.nbytes)
            self._state = _State.SYNCED

    def _read_back(self) -> np.ndarray:
        """The blocking device→host read: the host waits here for
        every step dispatched before it, so it is a span
        (``host_read:<name>``), a count and a sum of waited seconds —
        a 12-byte read costs a whole step of waiting and no bytes
        worth counting."""
        if not _metrics.enabled():
            return self._device.get(self._devmem)
        with _tracing.TRACER.span(
                f"host_read:{self.name}", cat="transfer",
                bytes=int(self._devmem.nbytes)) as span:
            mem = self._device.get(self._devmem)
        _metrics.host_reads().inc()
        _metrics.host_read_wait_seconds().inc(span.dur_us / 1e6)
        return mem

    def map_write(self) -> None:
        """Make the host copy current and mark it authoritative."""
        self.map_read()
        if self._mem is not None and not self._mem.flags.writeable:
            # device.get may hand back a zero-copy read-only view
            self._mem = np.array(self._mem, copy=True)
        self._state = _State.HOST

    def map_invalidate(self) -> None:
        """Host will fully overwrite; skip the device→host fetch."""
        self._check_not_tracing("map_invalidate")
        if self._state == _State.EMPTY:
            raise ValueError(
                f"Vector '{self.name}': map_invalidate on empty buffer")
        if self._mem is None:
            assert self._devmem is not None
            self._mem = np.empty(self._devmem.shape,
                                 dtype=np.dtype(self._devmem.dtype))
        elif not self._mem.flags.writeable:
            self._mem = np.empty_like(self._mem)
        self._state = _State.HOST

    def unmap(self) -> None:
        """Make the device copy current (upload if host was written)."""
        self._check_not_tracing("unmap")
        if self._state == _State.EMPTY:
            raise ValueError(f"Vector '{self.name}': unmap on empty buffer")
        if self._device is None or self._device.is_host_only:
            return
        if self._state == _State.HOST:
            self._upload()
        self._state = _State.DEVICE

    # ------------------------------------------------------------------
    # storage access
    # ------------------------------------------------------------------
    @property
    def mem(self) -> np.ndarray:
        """The host ndarray.  Caller must hold a map_read/map_write."""
        if self._state == _State.DEVICE:
            raise ValueError(
                f"Vector '{self.name}': host access while device copy is "
                f"authoritative — call map_read()/map_write() first")
        if self._mem is None:
            raise ValueError(f"Vector '{self.name}': no storage")
        return self._mem

    @mem.setter
    def mem(self, value: np.ndarray) -> None:
        self.reset(value)

    @property
    def devmem(self):
        """The device ``jax.Array`` (or tracer inside a jit region)."""
        if self._tracing:
            return self._devmem
        if self._device is None or self._device.is_host_only:
            # Host-only backend: the ndarray *is* the device buffer.
            return self.mem
        if self._state == _State.HOST:
            raise ValueError(
                f"Vector '{self.name}': device access while host copy is "
                f"authoritative — call unmap() first")
        if self._devmem is None:
            raise ValueError(f"Vector '{self.name}': not initialized "
                             f"on a device")
        return self._devmem

    @devmem.setter
    def devmem(self, value) -> None:
        """Functional update from device compute (eager xla_run or the
        region builder writing traced results back).

        FLOAT writes are cast to the DECLARED dtype (the host
        mirror's, set at allocation) when they disagree — the
        storage-precision contract: a bf16-declared activation vector
        stores bf16 no matter what precision the producing math ran
        in, and scan carries (``JitRegion.run_chunk``) stay
        dtype-stable across steps.  Matching writes are untouched, and
        non-float mismatches (e.g. an int64 write into an int32 index
        vector) are NOT silently coerced — those are unit bugs that
        should stay visible.
        """
        if (self._mem is not None and hasattr(value, "dtype")
                and value.dtype != self._mem.dtype
                and hasattr(value, "astype")
                and _is_float_dtype(np.dtype(value.dtype))
                and _is_float_dtype(self._mem.dtype)):
            value = value.astype(self._mem.dtype)
        self._devmem = value
        if not self._tracing:
            self._state = _State.DEVICE

    def accept_device(self, devarr) -> None:
        """Adopt an ALREADY-uploaded device array as the authoritative
        copy — the streaming data plane's delivery handoff: an uploader
        thread ``device_put`` the staged batch while the previous step
        computed, and delivery is this pointer swap (zero host work on
        the step's critical path).  Shape/dtype must match the declared
        storage so consumers (jit regions) never see a new signature —
        the zero-recompile contract.  (Multi-process arrays are
        globally shaped while the host mirror holds only the local
        shard; those skip the host-shape check.)"""
        self._check_not_tracing("accept_device")
        if self._state == _State.EMPTY:
            raise ValueError(
                f"Vector '{self.name}': accept_device on empty buffer")
        addressable = getattr(devarr, "is_fully_addressable", True)
        if self._mem is not None and addressable:
            if (tuple(devarr.shape) != tuple(self._mem.shape)
                    or np.dtype(devarr.dtype) != self._mem.dtype):
                raise ValueError(
                    f"Vector '{self.name}': accept_device "
                    f"{devarr.shape}/{devarr.dtype} does not match the "
                    f"declared {self._mem.shape}/{self._mem.dtype}")
        self._devmem = devarr
        self._state = _State.DEVICE
        self._recast_for_host()

    @property
    def state_name(self) -> str:
        return self._state.name

    # ------------------------------------------------------------------
    # conveniences
    # ------------------------------------------------------------------
    @property
    def shape(self) -> tuple[int, ...]:
        if self._mem is not None:
            return tuple(self._mem.shape)
        if self._devmem is not None:
            return tuple(self._devmem.shape)
        raise ValueError(f"Vector '{self.name}': no storage")

    @property
    def dtype(self) -> np.dtype:
        if self._mem is not None and self._state != _State.DEVICE:
            return self._mem.dtype
        if self._devmem is not None:
            return np.dtype(self._devmem.dtype)
        if self._mem is not None:
            return self._mem.dtype
        raise ValueError(f"Vector '{self.name}': no storage")

    @property
    def size(self) -> int:
        return int(np.prod(self.shape)) if self else 0

    @property
    def sample_size(self) -> int:
        """Elements per sample (all dims but the first — the reference's
        frequent ``size // shape[0]`` idiom)."""
        shape = self.shape
        return int(np.prod(shape[1:])) if len(shape) > 1 else 1

    # -- ZeRO-1 padding helpers (snapshot layout independence) ---------
    def strip_data_pad(self, arr: np.ndarray) -> np.ndarray:
        """Remove the ``data_shard_pad`` zero rows — the LOGICAL
        content a snapshot stores, independent of the mesh size the
        padding was computed for."""
        if not self.data_shard_pad or self.data_shard_dim is None:
            return arr
        dim = self.data_shard_dim
        idx = [slice(None)] * arr.ndim
        idx[dim] = slice(0, arr.shape[dim] - self.data_shard_pad)
        return arr[tuple(idx)]

    def apply_data_pad(self, arr: np.ndarray) -> np.ndarray:
        """Re-pad a logical (snapshot) array to THIS Vector's padded
        storage shape — the inverse of :meth:`strip_data_pad` under the
        CURRENT mesh (a restore may re-shard onto a different mesh size
        than the one that saved)."""
        if not self.data_shard_pad or self.data_shard_dim is None:
            return arr
        dim = self.data_shard_dim
        want = self.shape[dim]
        have = arr.shape[dim]
        if have == want:
            return arr
        widths = [(0, 0)] * arr.ndim
        widths[dim] = (0, want - have)
        return np.pad(arr, widths)

    def __bool__(self) -> bool:
        return self._state != _State.EMPTY

    def __len__(self) -> int:
        return self.shape[0] if self else 0

    def __array__(self, dtype=None, copy=None):
        self.map_read()
        arr = self.mem
        return arr.astype(dtype) if dtype is not None else arr

    def __getitem__(self, idx):
        return self.mem[idx]

    def __setitem__(self, idx, value) -> None:
        self.mem[idx] = value

    def __repr__(self) -> str:
        if not self:
            return f"Vector('{self.name}', empty)"
        return (f"Vector('{self.name}', {self.shape}, {self.dtype}, "
                f"{self._state.name})")

    # ------------------------------------------------------------------
    # internals
    # ------------------------------------------------------------------
    def _check_not_tracing(self, op: str) -> None:
        if self._tracing:
            raise RuntimeError(
                f"Vector '{self.name}': {op}() inside a jit region — "
                f"host sync is not allowed in traced code; move this "
                f"unit out of the region or use device-side state")


class StagingRing:
    """Bounded ring of reusable host staging buffers — the streaming
    data plane's slot pool.

    Producers :meth:`acquire` a free slot, fill it (shard reads /
    decode), and hand the index downstream; whoever finishes with the
    contents (the uploader after ``device_put``, or the consumer on
    host-only backends) :meth:`release`\\ s it.  The bound is the
    backpressure mechanism: a stalled consumer blocks the producers at
    ``acquire`` instead of growing host memory — total staging
    footprint is pinned at ``n_slots × batch_bytes`` no matter how
    large the dataset is.

    Thread-safe; allocation happens once, up front (steady state does
    zero allocations on the step path).
    """

    def __init__(self, n_slots: int, shape: tuple[int, ...],
                 dtype) -> None:
        import queue
        if n_slots < 1:
            raise ValueError(f"StagingRing needs >= 1 slot, got {n_slots}")
        self._bufs = [np.zeros(shape, dtype=dtype) for _ in range(n_slots)]
        self._free: "queue.Queue[int]" = queue.Queue()
        for i in range(n_slots):
            self._free.put(i)

    @property
    def n_slots(self) -> int:
        return len(self._bufs)

    @property
    def n_free(self) -> int:
        return self._free.qsize()

    @property
    def nbytes(self) -> int:
        """Total host bytes pinned by the ring."""
        return sum(b.nbytes for b in self._bufs)

    def buffer(self, slot: int) -> np.ndarray:
        return self._bufs[slot]

    def acquire(self, timeout: float | None = None) -> int | None:
        """Next free slot index; blocks (bounded by ``timeout``) when
        the ring is full downstream.  ``None`` on timeout so pipeline
        threads can re-check their stop flag instead of hanging."""
        import queue
        try:
            return self._free.get(timeout=timeout)
        except queue.Empty:
            return None

    def release(self, slot: int) -> None:
        self._free.put(slot)


class PageStager:
    """Pinned staging rings + ONE uploader thread for KV-page h2d
    traffic (round 22) — the :class:`StagingRing` machinery the
    streaming loader runs for training batches, specialized to the
    serving data plane's unit of transfer: one KV-cache *page* per
    pool array (a spill restore promoting a cold prefix block back to
    HBM, or a prefill→decode handoff landing a prompt's pages in the
    decode pool's cache).

    One ring per page-pool spec (K and V pools have the same page
    shape but int8-quantized caches add f32 scale pools with their
    own), so a staged page is a *set* of per-pool buffers travelling
    together under one slot index tuple.  :meth:`upload` is
    synchronous for the caller — stage (memcpy into the pinned slot)
    → enqueue → the uploader thread ``device_put``\\ s and fences —
    because the caller's very next dispatch consumes the arrays; the
    ring bound is still load-bearing: concurrent uploaders (several
    decode-pool replicas accepting handoffs) backpressure at
    ``acquire`` instead of growing host memory.
    """

    def __init__(self, shapes_dtypes: list[tuple[tuple, object]],
                 n_slots: int = 2) -> None:
        import queue
        import threading
        self._rings = [StagingRing(n_slots, tuple(shape), dtype)
                       for shape, dtype in shapes_dtypes]
        self._work: "queue.Queue" = queue.Queue()
        self._thread = threading.Thread(
            target=self._upload_loop, name="page-uploader", daemon=True)
        self._thread.start()

    @property
    def nbytes(self) -> int:
        return sum(r.nbytes for r in self._rings)

    def _upload_loop(self) -> None:
        import jax
        while True:
            item = self._work.get()
            if item is None:
                return
            slots, fut = item
            try:
                out = []
                for ring, slot in zip(self._rings, slots):
                    out.append(jax.device_put(ring.buffer(slot)))
                for arr in out:  # fence: the slot is reusable only
                    arr.block_until_ready()  # once the copy landed
                fut.set_result(out)
            except Exception as exc:  # noqa: BLE001 — caller's error
                fut.set_exception(exc)
            finally:
                for ring, slot in zip(self._rings, slots):
                    ring.release(slot)

    def upload(self, pages: list[np.ndarray],
               timeout: float = 30.0) -> list:
        """Stage one page set and return its device arrays (blocks
        until the uploader fenced the copies)."""
        from concurrent.futures import Future
        if len(pages) != len(self._rings):
            raise ValueError(f"page set has {len(pages)} arrays, "
                             f"stager expects {len(self._rings)}")
        slots: list[int] = []
        for ring, page in zip(self._rings, pages):
            slot = ring.acquire(timeout=timeout)
            if slot is None:
                for r, s in zip(self._rings, slots):
                    r.release(s)
                raise TimeoutError(
                    "page staging ring full — uploader stalled past "
                    f"{timeout}s")
            np.copyto(ring.buffer(slot), page)
            slots.append(slot)
        fut: Future = Future()
        self._work.put((slots, fut))
        out = fut.result(timeout=timeout)
        if _metrics.enabled():
            _metrics.transfer_bytes("h2d").inc(
                sum(int(p.nbytes) for p in pages))
        return out

    def shutdown(self) -> None:
        self._work.put(None)
        self._thread.join(timeout=5.0)


class HostPageTier:
    """Host-DRAM tier for cold KV pages (round 22) — the capacity
    layer under the HBM page pool that lets a prefix working set
    survive past ``pool_pages``.

    Frames are preallocated numpy buffers (one ``(capacity, ...page
    shape)`` block per pool spec, allocation-free steady state); the
    free list hands out frame ids with the same exactly-once
    discipline as :class:`~znicz_tpu.serving.decode.PagedKVCache`
    page ids — a frame id is held by AT MOST ONE trie node, and a
    spilled block lives in exactly one tier at a time (HBM page XOR
    host frame; the accounting invariant
    tests/test_disagg.py pins).  Restores travel through the
    :class:`PageStager` ring + uploader thread.
    """

    def __init__(self, shapes_dtypes: list[tuple[tuple, object]],
                 capacity_pages: int, stager: PageStager | None = None,
                 ring_slots: int = 2) -> None:
        self.capacity = int(capacity_pages)
        if self.capacity < 1:
            raise ValueError(
                f"host tier needs >= 1 page, got {capacity_pages}")
        self._frames = [np.zeros((self.capacity,) + tuple(shape),
                                 dtype)
                        for shape, dtype in shapes_dtypes]
        self._free = list(range(self.capacity - 1, -1, -1))
        self._own_stager = stager is None
        self.stager = (stager if stager is not None
                       else PageStager(shapes_dtypes,
                                       n_slots=ring_slots))

    @property
    def used(self) -> int:
        return self.capacity - len(self._free)

    @property
    def full(self) -> bool:
        return not self._free

    @property
    def nbytes(self) -> int:
        return sum(f.nbytes for f in self._frames)

    def store(self, pages: list[np.ndarray]) -> int | None:
        """Land one exported page set in a free frame; ``None`` when
        the tier is full (caller falls back to eviction)."""
        if not self._free:
            return None
        hid = self._free.pop()
        for frame, page in zip(self._frames, pages):
            np.copyto(frame[hid], page)
        if _metrics.enabled():
            _metrics.transfer_bytes("d2h").inc(
                sum(int(p.nbytes) for p in pages))
        return hid

    def read(self, hid: int) -> list[np.ndarray]:
        return [frame[hid] for frame in self._frames]

    def upload(self, hid: int) -> list:
        """Device arrays for one stored frame, via the staging ring +
        uploader thread (the restore h2d path)."""
        return self.stager.upload(self.read(hid))

    def free(self, hid: int) -> None:
        self._free.append(hid)

    def shutdown(self) -> None:
        if self._own_stager:
            self.stager.shutdown()

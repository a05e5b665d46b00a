"""Device backends.

Rebuilds the reference's backend abstraction (reference:
``veles/backends.py`` — ``Device``/``OpenCLDevice``/``CUDADevice``/
``NumpyDevice`` selected by ``root.common.engine.backend``) for TPU:

- :class:`XLADevice` is the accelerator backend: jax/XLA over PJRT.
  It works on any jax platform (``tpu`` in production, ``cpu`` in unit
  tests with a virtual multi-device mesh) because the compute path is
  pure jax — this mirrors how the reference's units ran unchanged on
  OpenCL *or* CUDA.
- :class:`TPUDevice` is the TPU-pinned convenience subclass.
- :class:`NumpyDevice` is the host oracle backend: every unit's
  ``numpy_run`` is the spec that ``xla_run`` is tested against
  (reference test strategy, SURVEY.md §4).

There is no kernel build/autotune machinery here on purpose: XLA owns
tiling and fusion; the reference's per-device BLOCK_SIZE autotuning
(``veles/backends.py``) has no TPU analogue.
"""

from __future__ import annotations

import os

import numpy as np

import jax
import jax.numpy as jnp

from znicz_tpu.observe import metrics as _metrics
from znicz_tpu.utils.config import root
from znicz_tpu.utils.logger import Logger


_PRECISION_BY_LEVEL = {0: "default", 1: "float32", 2: "highest"}

#: JAX's persistent compilation cache when nobody placed it from
#: outside: a fixed path in the checkout (the path is part of the
#: cache key, so a directory that moves never hits)
_JAX_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache; call before the first
    compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already
    reads it and nothing is changed; otherwise the cache goes to
    ``<checkout>/.jax_cache`` and every program is kept, so that a
    second cold run on a kept directory compiles nothing.  Returns the
    directory in effect.  (This is JAX's own cache — separate from the
    repo's content-addressed executable store, ``ZNICZ_AOT_CACHE``.)"""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", _JAX_CACHE_DIR)
    # the default keeps only programs that took over a second to
    # compile: which programs those are changes from run to run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return _JAX_CACHE_DIR


class Device(Logger):
    """Backend base class."""

    backend = "abstract"
    #: True when there is no separate device memory (numpy oracle).
    is_host_only = False

    def __init__(self, **kwargs) -> None:
        super().__init__(**kwargs)
        self.compute_dtype = np.dtype(
            root.common.get("precision_type", "float32"))

    @staticmethod
    def create(backend: str | None = None) -> "Device":
        """Factory honoring ``root.common.engine.backend``."""
        backend = backend or root.common.engine.backend
        if backend == "numpy":
            return NumpyDevice()
        if backend == "tpu":
            return TPUDevice()
        if backend == "xla":
            return XLADevice()
        raise ValueError(f"unknown backend '{backend}' "
                         f"(expected xla | tpu | numpy)")

    # transfer API used by Vector -------------------------------------
    def put(self, arr: np.ndarray, vector=None):
        raise NotImplementedError

    def put_local_batch(self, arr: np.ndarray, vector=None):
        """Place a host-staged batch-major buffer.  Single-process
        backends: identical to :meth:`put`.  Multi-process SPMD
        overrides assemble the GLOBAL batch from this process's 1/N of
        the rows — the placement half of the streaming data plane's
        per-host sharded reads."""
        return self.put(arr, vector=vector)

    def get(self, devarr) -> np.ndarray:
        raise NotImplementedError

    def sync(self) -> None:
        """Block until queued device work completes."""

    @property
    def supports_donation(self) -> bool:
        """True when XLA implements input-buffer donation on this
        platform (TPU/GPU).  The serving engine's AOT programs donate
        the request buffer when they can — CPU only warns."""
        return False


class NumpyDevice(Device):
    """Host-only oracle backend (reference: ``NumpyDevice``)."""

    backend = "numpy"
    is_host_only = True

    def __init__(self, **kwargs) -> None:
        super().__init__(**kwargs)
        if _metrics.enabled():
            _metrics.backend_info(self.backend, "host").set(1)

    def put(self, arr: np.ndarray, vector=None) -> np.ndarray:
        return arr

    def get(self, devarr) -> np.ndarray:
        return np.asarray(devarr)


class XLADevice(Device):
    """jax/XLA backend over PJRT — the ``xla_run`` target.

    ``precision_type``/``precision_level`` from the config tree map to
    the matmul input dtype and ``jax.lax.Precision``:

    - level 0 (fast): inputs in ``precision_type`` (bf16 recommended on
      TPU — native MXU dtype), default XLA precision;
    - level 1: f32 matmul precision (deterministic accumulation);
    - level 2: ``highest`` (f32 data passes through MXU in multiple
      passes).
    """

    backend = "xla"
    platform: str | None = None  # subclass pin; None = jax default

    def __init__(self, device: "jax.Device | None" = None,
                 mesh: "jax.sharding.Mesh | None" = None, **kwargs) -> None:
        super().__init__(**kwargs)
        #: when set, this device is SPMD over the mesh: batch-major
        #: Vectors are sharded over the 'data' axis, everything else is
        #: replicated, and XLA inserts the ICI collectives — the TPU
        #: replacement for the reference's master–slave cluster
        #: (reference: veles/server.py / veles/client.py; SURVEY.md §2.5)
        self.mesh = mesh
        if device is None:
            if mesh is not None:
                device = mesh.devices.flat[0]
            else:
                devices = (jax.devices(self.platform) if self.platform
                           else jax.devices())
                device = devices[0]
        self.jax_device = device
        self.compute_dtype = np.dtype(
            root.common.get("precision_type", "float32"))
        level = int(root.common.get("precision_level", 0))
        self.matmul_precision = _PRECISION_BY_LEVEL.get(level, "default")
        self.info("XLA device %s (platform=%s, kind=%s, dtype=%s, "
                  "precision=%s, mesh=%s)", device, device.platform,
                  device.device_kind, self.compute_dtype,
                  self.matmul_precision,
                  None if mesh is None else dict(mesh.shape))
        if _metrics.enabled():
            _metrics.backend_info(self.backend, device.platform).set(1)
            # round 19: build-identity gauge with the full label set
            try:
                _metrics.set_build_info(
                    platform=device.platform,
                    mesh=("-" if mesh is None else "x".join(
                        str(n) for n in mesh.devices.shape)),
                    processes=jax.process_count())
            except Exception:  # noqa: BLE001 — telemetry only
                pass

    @property
    def supports_donation(self) -> bool:
        return self.jax_device.platform in ("tpu", "gpu", "cuda", "rocm")

    @property
    def n_data_shards(self) -> int:
        from znicz_tpu.parallel.axis import DATA_AXIS
        return 1 if self.mesh is None else self.mesh.shape[DATA_AXIS]

    def sharding_for(self, vector) -> "jax.sharding.Sharding | None":
        """Placement for one Vector on this device's mesh.

        Table-bound Vectors (allocated through a workflow that owns a
        ``parallel.partition.PartitionTable``) are a pure LOOKUP: the
        spec was resolved once from the workflow's ordered rule table
        at bind time.  The attribute-derived branch below survives as
        the compatibility layer for bare Vectors (tests, serving
        staging buffers) and for the ``engine.partition_rules=False``
        A/B arm — the golden-table test pins the two paths
        bitwise-equal on the default tables.
        """
        if self.mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec
        from znicz_tpu.parallel import replicated_sharding
        from znicz_tpu.parallel.axis import DATA_AXIS, MODEL_AXIS
        if vector is None:
            return replicated_sharding(self.mesh)
        resolved = getattr(vector, "_partition", None)
        if resolved is not None:
            from znicz_tpu.parallel.partition import sharding_of
            return sharding_of(self.mesh, resolved)
        model_dim = getattr(vector, "model_shard_dim", None)
        model_axis = getattr(vector, "model_shard_axis", MODEL_AXIS)
        data_dim = getattr(vector, "data_shard_dim", None)
        member = getattr(vector, "member_axis", False)
        if member:
            # population-stacked buffer: dim 0 is the member axis and
            # rides the mesh's data axis — in population mode the K
            # model replicas ARE the data parallelism.  A member count
            # that does not divide the axis stays replicated (XLA
            # time-slices the members instead of sharding them).
            if vector.batch_major or data_dim is not None:
                raise ValueError(
                    f"Vector '{vector.name}': member_axis buffers "
                    f"cannot also be batch_major / ZeRO-1 data-sharded"
                    f" — the member axis owns the data axis")
            if model_dim == 0:
                raise ValueError(
                    f"Vector '{vector.name}': dim 0 is the member "
                    f"axis — it cannot also carry the model axis")
            ndim = len(vector.shape)
            spec = [None] * ndim
            if ndim and vector.shape[0] % self.n_data_shards == 0:
                spec[0] = DATA_AXIS
            if model_dim is not None:
                spec[model_dim] = model_axis
            return NamedSharding(self.mesh, PartitionSpec(*spec))
        if not vector.batch_major and model_dim is None \
                and data_dim is None:
            return replicated_sharding(self.mesh)
        ndim = len(vector.shape)
        spec: list = [None] * ndim
        if vector.batch_major and ndim:
            if data_dim is not None:
                raise ValueError(
                    f"Vector '{vector.name}': batch-major buffers "
                    f"already ride the data axis on dim 0 — "
                    f"data_shard_dim is for persistent (ZeRO-1) state")
            spec[0] = DATA_AXIS
        if data_dim is not None:
            # ZeRO-1 optimizer state: each chip stores 1/N of the
            # accumulator along this dim (nn_units pads the dim to a
            # multiple of the data-axis size at allocation)
            if data_dim == model_dim:
                raise ValueError(
                    f"Vector '{vector.name}': dim {data_dim} cannot "
                    f"carry both the data and the model axis")
            spec[data_dim] = DATA_AXIS
        if model_dim is not None:
            if model_dim == 0 and vector.batch_major:
                raise ValueError(
                    f"Vector '{vector.name}': dim 0 is the batch (data"
                    f"-sharded) — it cannot also carry the model axis")
            spec[model_dim] = model_axis
        return NamedSharding(self.mesh, PartitionSpec(*spec))

    def put(self, arr: np.ndarray, vector=None):
        if self.jax_device.platform == "cpu":
            # On the CPU backend device_put is ZERO-COPY for aligned
            # numpy arrays: the "device" buffer aliases the host array,
            # and a later host write (map_invalidate → mem[...] = …)
            # would corrupt async in-flight computation.  Detach.
            # (TPU/GPU transfers always copy; no cost there.)
            arr = np.array(arr, copy=True)
        sharding = self.sharding_for(vector)
        if sharding is None:
            return jax.device_put(arr, self.jax_device)
        if self.mesh is not None and jax.process_count() > 1 \
                and not sharding.is_fully_addressable:
            # Multi-process upload WITHOUT the hidden collective:
            # ``jax.device_put`` onto a non-addressable sharding runs
            # a host-side ``assert_equal`` broadcast, which executes
            # immediately on this thread while previously dispatched
            # step programs (and their in-program collectives) are
            # still in flight asynchronously — on the CPU/Gloo backend
            # the two interleave in different orders per process and
            # cross lanes (corrupt data or a gloo size-mismatch
            # abort).  Every host mirror is GLOBAL bookkeeping (the
            # per-host slice path is ``put_local_batch``), so each
            # addressable device's shard is a local slice of ``arr``
            # and no cross-process traffic is needed at all.
            return jax.make_array_from_callback(
                arr.shape, sharding, lambda idx: arr[idx])
        return jax.device_put(arr, sharding)

    def put_local_batch(self, arr: np.ndarray, vector=None):
        """Multi-process meshes: ``arr`` holds only THIS process's
        rows of the (batch-major) buffer; assemble the global sharded
        array without any cross-host gather.  Single-process falls
        through to :meth:`put` (arr already is the whole batch)."""
        if self.mesh is not None and jax.process_count() > 1:
            if self.jax_device.platform == "cpu":
                # same zero-copy hazard as :meth:`put`: on CPU the
                # local shards ALIAS the host array — a staging-ring
                # slot reused by the producer after upload would
                # silently rewrite the device batch (half the global
                # rows become the NEXT batch's data).  Detach.
                arr = np.array(arr, copy=True)
            sharding = self.sharding_for(vector)
            assert sharding is not None
            return jax.make_array_from_process_local_data(sharding, arr)
        return self.put(arr, vector=vector)

    def get(self, devarr) -> np.ndarray:
        if isinstance(devarr, jax.Array) and not devarr.is_fully_addressable:
            # Multi-process SPMD: this process holds only its shards.
            # Replicated arrays (params, scalars) read locally; sharded
            # ones all-gather — safe because every process runs the
            # same program and reaches this read in lockstep.
            if devarr.sharding.is_fully_replicated:
                return np.asarray(devarr.addressable_data(0))
            from jax.experimental import multihost_utils
            return np.asarray(
                multihost_utils.process_allgather(devarr, tiled=True))
        return np.asarray(jax.device_get(devarr))

    def sync(self) -> None:
        # Block on a trivial computation queued after outstanding work.
        jnp.zeros((), device=self.jax_device).block_until_ready()


class TPUDevice(XLADevice):
    """XLA backend pinned to the TPU platform (reference analogue:
    ``CUDADevice`` — the production accelerator backend)."""

    backend = "tpu"
    platform = "tpu"

"""Mesh construction and sharding helpers.

The recipe (scaling-book style): pick a mesh, annotate shardings on the
batch and (replicated) parameters, let XLA insert the collectives, and
keep collectives on ICI by making the ``data`` axis span the pod slice.

This module is also the one home of the **kernel shard-spec
derivation** (:func:`kernel_shard_spec`): an opaque ``pallas_call``
has no GSPMD sharding rule, so on a multi-device mesh it must run
per-shard under ``shard_map`` with an explicit PartitionSpec — the
flash-attention and fused layer-norm kernels and the ring-attention
entry all derive their specs here, one convention for all three.
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from znicz_tpu.parallel.axis import (DATA_AXIS, MODEL_AXIS, PIPE_AXIS,
                                     SEQ_AXIS)


def kernel_shard_spec(mesh: Mesh | None, ndim: int,
                      model_shard_dim: int | None = None,
                      model_axis: str = MODEL_AXIS,
                      ) -> tuple[P, tuple[str, ...]]:
    """Derive the PartitionSpec for running a per-row kernel (flash
    attention, fused layer norm, the ring body) under ``shard_map``.

    Convention (matches ``XLADevice.sharding_for``): dim 0 is the
    batch and rides the ``data`` axis; ``model_shard_dim`` (a Vector's
    annotation — e.g. the time axis after a ring-attention unit) rides
    ``model_axis``.  Feature axes are never sharded here — these
    kernels reduce over the last axis per row, so rows must stay
    whole.

    Returns ``(spec, reduce_axes)``: ``reduce_axes`` are the mesh axes
    that actually split rows (size > 1) — the axes a kernel's
    cross-row reductions (γ/β gradient sums) must ``psum`` over.
    Size-1 axes stay in the spec (harmless, keeps one code path) but
    out of ``reduce_axes``.
    """
    spec: list = [None] * ndim
    axes: list[str] = []
    if mesh is not None:
        if (model_shard_dim != 0 and model_axis != DATA_AXIS
                and DATA_AXIS in mesh.shape):
            spec[0] = DATA_AXIS
            if mesh.shape[DATA_AXIS] > 1:
                axes.append(DATA_AXIS)
        if model_shard_dim is not None and model_axis in mesh.shape:
            spec[model_shard_dim] = model_axis
            if mesh.shape[model_axis] > 1:
                axes.append(model_axis)
    return P(*spec), tuple(axes)


def spec_divides(mesh: Mesh, shape, spec) -> bool:
    """True when every sharded dim of ``shape`` splits evenly over its
    mesh axis — the shard_map shape-legality gate (an indivisible dim
    falls back to the XLA path instead of erroring at trace)."""
    for dim, axis in enumerate(spec):
        if axis is None or dim >= len(shape):
            continue
        for name in (axis,) if isinstance(axis, str) else tuple(axis):
            if shape[dim] % mesh.shape[name]:
                return False
    return True


def shard_shape(mesh: Mesh, shape, spec) -> tuple:
    """The per-device shard shape of ``shape`` under ``spec`` — the
    shapes a ``shard_map`` body actually sees.  Kernel-legality gates
    (the ring's Pallas fold, the unit gates) must reason about THESE,
    not the global shape: T=2048 over an 8-way seq axis hands each
    device 256 rows, and that 256 is what the tiling must divide.
    Assumes :func:`spec_divides` holds."""
    out = list(shape)
    for dim, axis in enumerate(spec):
        if axis is None or dim >= len(out):
            continue
        for name in (axis,) if isinstance(axis, str) else tuple(axis):
            out[dim] //= mesh.shape[name]
    return tuple(out)


# ----------------------------------------------------------------------
# ZeRO-1 data-axis optimizer sharding (Rajbhandari et al., 2020, stage 1)
# ----------------------------------------------------------------------
def zero1_choice(device) -> bool:
    """Resolve the ``root.common.engine.zero1`` gate against a device.

    Auto (the default) engages whenever the device's mesh has a data
    axis of size > 1 — the regime where the replicated update wastes
    both HBM (N identical momentum copies) and ICI (an all-reduce
    moving 2× the bytes of the reduce-scatter + all-gather pair).
    ``root.common.engine.zero1 = False`` is the conservative opt-out;
    host-only and single-data-shard devices always keep the replicated
    update (nothing to shard over).
    """
    from znicz_tpu.utils.config import root
    if device is None or device.is_host_only:
        return False
    mesh = getattr(device, "mesh", None)
    if mesh is None or DATA_AXIS not in mesh.shape \
            or mesh.shape[DATA_AXIS] < 2:
        return False
    gate = root.common.engine.get("zero1", "auto")
    return gate not in (False, 0, "off", "false")


def zero1_partition(shape, n_shards: int,
                    model_shard_dim: int | None = None,
                    ) -> tuple[int | None, int]:
    """Pick ``(dim, pad)`` for sharding a parameter-shaped tensor over
    the data axis in the ZeRO-1 update.

    Preference order: the largest dim that divides evenly over
    ``n_shards`` (pad 0); otherwise the largest dim overall, padded up
    to the next multiple (jax shardings must divide evenly — the pad
    rows are zeros, invisible to the update math, and snapshots slice
    them off).  ``model_shard_dim`` is excluded — that dim already
    rides the model axis and the two compose as a 2-D sharding.
    Returns ``(None, 0)`` when there is nothing to shard (0-d, or
    every dim is the model dim).
    """
    if n_shards < 2:
        return None, 0
    candidates = [(size, d) for d, size in enumerate(shape)
                  if d != model_shard_dim and size > 0]
    if not candidates:
        return None, 0
    even = [(size, d) for size, d in candidates if size % n_shards == 0]
    if even:
        size, dim = max(even, key=lambda t: (t[0], -t[1]))
        return dim, 0
    size, dim = max(candidates, key=lambda t: (t[0], -t[1]))
    return dim, (-size) % n_shards


def zero1_specs(mesh: Mesh, ndim: int, data_shard_dim: int,
                model_shard_dim: int | None = None) -> tuple[P, P]:
    """The (sharded, gathered) PartitionSpec pair for one ZeRO-1
    parameter update: ``sharded`` places ``data_shard_dim`` on the
    data axis (the reduce-scatter target and the stored layout of the
    momentum), ``gathered`` keeps only the model axis (the layout
    every forward expects back).  Constraining grad→sharded and
    updated-param→gathered inside the jit region is what lets GSPMD
    fuse the all-reduce into a reduce-scatter + all-gather pair at
    half the bytes."""
    sharded: list = [None] * ndim
    gathered: list = [None] * ndim
    sharded[data_shard_dim] = DATA_AXIS
    if model_shard_dim is not None and model_shard_dim != data_shard_dim:
        sharded[model_shard_dim] = MODEL_AXIS
        gathered[model_shard_dim] = MODEL_AXIS
    return P(*sharded), P(*gathered)


def make_mesh(n_data: int | None = None, n_model: int = 1,
              n_seq: int = 1, devices=None, n_pipe: int = 1) -> Mesh:
    """Build a ([pipe, ]data, model[, seq]) mesh over the available
    devices.

    ``n_data=None`` uses all remaining devices on the data axis — the
    DP layout matching the reference's capability (its only scale-out
    strategy was data parallelism, SURVEY.md §2.5).  ``devices``
    defaults to ``jax.devices()``, which under a multi-process runtime
    (``parallel.distributed``) is the GLOBAL device list — the same
    call that builds an 8-way virtual CPU mesh builds a pod slice.

    ``n_seq > 1`` adds a third ``seq`` axis for sequence parallelism
    (the ring rides it instead of doubling up on ``model``, so
    DP × TP × SP compose); ``n_seq=1`` keeps the historical 2-D mesh
    so existing sharding specs and tests are untouched.

    ``n_pipe > 1`` (round 20) prepends a LEADING ``pipe`` axis — the
    slowest-varying position, so each pipeline stage owns a contiguous
    block of devices and stage-boundary transfers cross the fewest
    links.  The pipeline executor assigns stage ``k`` the sub-mesh
    ``mesh_for_stage(mesh, k)``; DP/TP/SP placements inside a stage
    are untouched.
    """
    if devices is None:
        devices = jax.devices()
    if n_data is None:
        n_data = len(devices) // (n_model * n_seq * n_pipe)
    use = n_data * n_model * n_seq * n_pipe
    shape = [n_data, n_model] + ([n_seq] if n_seq > 1 else [])
    names = [DATA_AXIS, MODEL_AXIS] + ([SEQ_AXIS] if n_seq > 1 else [])
    if n_pipe > 1:
        shape = [n_pipe] + shape
        names = [PIPE_AXIS] + names
    grid = np.asarray(devices[:use]).reshape(shape)
    return Mesh(grid, axis_names=tuple(names))


def mesh_for_stage(mesh: Mesh, stage: int) -> Mesh:
    """The per-stage sub-mesh of a pipelined mesh: index the leading
    ``pipe`` axis at ``stage`` and return the remaining
    (data, model[, seq]) mesh over that stage's device block.  A mesh
    without a pipe axis is returned unchanged (single-stage layouts and
    the CPU temporal-MPMD executor, which time-multiplexes every stage
    over the same devices)."""
    if PIPE_AXIS not in mesh.axis_names:
        return mesh
    k = mesh.axis_names.index(PIPE_AXIS)
    grid = np.take(mesh.devices, stage, axis=k)
    names = tuple(n for n in mesh.axis_names if n != PIPE_AXIS)
    return Mesh(grid, axis_names=names)


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Shard the leading (batch) dim over the data axis."""
    return NamedSharding(mesh, P(DATA_AXIS))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Fully replicated (parameters, scalars)."""
    return NamedSharding(mesh, P())

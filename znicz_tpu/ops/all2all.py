"""Fully-connected forward units (reference: ``znicz/all2all.py``).

``y = act(x @ W + b)`` — the GEMM rides the MXU via
``jnp.dot``/``lax.dot_general`` (the reference hand-tiled this in
OpenCL/CUDA; on TPU XLA owns the tiling, SURVEY.md §2.3).  Activation
flavors are fused into the same jit region, so the elementwise tail
costs no extra HBM round-trip.

``All2AllSoftmax`` also produces ``max_idx`` (argmax per sample) like
the reference — used by the evaluator and image-saver units.
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from znicz_tpu.memory import Vector
from znicz_tpu.ops import activations_math
from znicz_tpu.ops.nn_units import Forward


class All2All(Forward):
    """Linear fully-connected layer.

    ``output_sample_shape`` is the per-sample output shape (an int or
    tuple), mirroring the reference's constructor.

    ``model_parallel`` (Megatron-style tensor parallelism over the
    mesh's MODEL axis — beyond the reference, which only scaled via
    data parallelism):

    - ``"column"``: weights shard (n_in, n_out/m); output features
      shard over model.  Bias shards with the features.
    - ``"row"``: weights shard (n_in/m, n_out); expects a feature-
      sharded input (a preceding column layer) and produces a
      replicated-over-model output — GSPMD inserts the psum.
    - ``None`` (default): replicated weights, pure data parallelism.

    ``per_position=True`` applies the layer to every position of a
    (batch, time, features) input — B·T rows through one (features,
    n_out) matrix, output (batch, time, n_out) — instead of flattening
    time into the features: the language-model head.

    Annotation-only: the GEMMs are unchanged, ``sharding_for`` places
    the buffers, and XLA's partitioner derives the collectives
    (all-gather/reduce-scatter over ICI).  On a mesh with model=1 or
    no mesh at all the annotations are no-ops.
    """

    ACTIVATION = "linear"

    def __init__(self, workflow, output_sample_shape, name=None,
                 model_parallel: str | None = None,
                 per_position: bool = False, **kwargs):
        super().__init__(workflow, name=name, **kwargs)
        self.per_position = bool(per_position)
        if isinstance(output_sample_shape, (int, np.integer)):
            output_sample_shape = (int(output_sample_shape),)
        self.output_sample_shape = tuple(output_sample_shape)
        self.activation = activations_math.get(self.ACTIVATION)
        if model_parallel not in (None, "column", "row"):
            raise ValueError(f"{self}: model_parallel must be None, "
                             f"'column' or 'row', got {model_parallel!r}")
        if model_parallel is not None \
                and len(self.output_sample_shape) != 1:
            # the column split partitions the FLATTENED n_out; a
            # multi-dim sample shape would shard the wrong physical dim
            raise ValueError(
                f"{self}: model_parallel requires a 1-D "
                f"output_sample_shape, got {self.output_sample_shape}")
        self.model_parallel = model_parallel

    @property
    def neurons(self) -> int:
        return int(np.prod(self.output_sample_shape))

    def lead_shape(self, x_shape) -> tuple:
        """The leading dims of an input that index rows of the GEMM:
        the batch, or (batch, time) when ``per_position``."""
        return tuple(x_shape[:-1] if self.per_position else x_shape[:1])

    def _apply_model_parallel(self, n_in: int, n_out: int) -> None:
        """Set model-axis sharding dims on weights/bias/output before
        the device places them.  No-op when ``model_parallel`` is
        unset or the device has no mesh; a mesh WITHOUT a model axis
        raises (a silent no-op there would hide a sharding request)."""
        if self.model_parallel is None:
            return
        n_model = 1
        mesh = getattr(self.device, "mesh", None)
        if mesh is not None:
            from znicz_tpu.parallel.axis import MODEL_AXIS
            if MODEL_AXIS not in mesh.shape:
                # a custom mesh without the model axis (e.g. a seq-only
                # mesh) would otherwise die later in sharding_for with
                # an opaque PartitionSpec error naming a missing axis
                raise ValueError(
                    f"{self}: model_parallel='{self.model_parallel}' "
                    f"needs a mesh with a '{MODEL_AXIS}' axis; this "
                    f"mesh has {dict(mesh.shape)} (framework "
                    f"make_mesh always provides one; custom meshes "
                    f"must too, or drop model_parallel)")
            n_model = mesh.shape[MODEL_AXIS]
        from jax.sharding import PartitionSpec as P
        from znicz_tpu.parallel.axis import DATA_AXIS, MODEL_AXIS
        if self.model_parallel == "column":
            if n_out % n_model:
                raise ValueError(
                    f"{self}: column-parallel n_out {n_out} not "
                    f"divisible by model axis size {n_model}")
            self.partition_leaf("weights", P(None, MODEL_AXIS))
            if self.include_bias:
                self.partition_leaf("bias", P(MODEL_AXIS))
            # output features ride the model axis: (batch, n_out/m)
            # (1-D sample shape enforced above)
            self.partition_leaf("output", P(DATA_AXIS, MODEL_AXIS))
        else:  # row
            if n_in % n_model:
                raise ValueError(
                    f"{self}: row-parallel n_in {n_in} not divisible "
                    f"by model axis size {n_model}")
            self.partition_leaf("weights", P(MODEL_AXIS))
            # bias replicated: added after the psum; output replicated

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device=device, **kwargs)
        if self.input is None or not self.input:
            raise AttributeError(f"{self}: input not linked/allocated yet")
        lead = self.lead_shape(self.input.shape)
        n_in = int(np.prod(self.input.shape[len(lead):]))
        n_out = self.neurons
        if not self.weights:
            self.weights.reset(self.fill_array(
                (n_in, n_out), self.weights_filling, self.weights_stddev,
                fan_in=n_in))
        if self.include_bias and not self.bias:
            self.bias.reset(self.fill_array(
                (n_out,), self.bias_filling, self.bias_stddev, fan_in=n_in))
        self.output.reset(np.zeros(lead + self.output_sample_shape,
                                   dtype=self.output_store_dtype))
        self._apply_model_parallel(n_in, n_out)
        self.init_vectors(self.input, self.output, self.weights, self.bias)

    # -- math (shared shape logic; xp-generic) --------------------------
    def _forward(self, xp, x, w, b):
        lead = self.lead_shape(x.shape)
        y = self.mxu_dot(xp, x.reshape(int(np.prod(lead)), -1), w)
        if b is not None:
            y = y + b
        y = self.activation.fwd(xp, y)
        return y.reshape(lead + self.output_sample_shape)

    def numpy_run(self) -> None:
        self.input.map_read()
        self.weights.map_read()
        x = self.input.mem.astype(np.float32)
        b = None
        if self.include_bias:
            self.bias.map_read()
            b = self.bias.mem
        self.output.map_invalidate()
        self.output.mem[...] = self._forward(np, x, self.weights.mem, b)

    def xla_run(self) -> None:
        x = self.input.devmem
        w = self.weights.devmem
        b = self.bias.devmem if self.include_bias else None
        self.output.devmem = self._forward(jnp, x, w, b)


class All2AllTanh(All2All):
    """Fused scaled-tanh flavor (reference: ``All2AllTanh``)."""
    ACTIVATION = "tanh"


class All2AllRELU(All2All):
    """Fused smooth-RELU (softplus) flavor (reference: ``All2AllRELU``)."""
    ACTIVATION = "relu"


class All2AllStrictRELU(All2All):
    """Fused max(x,0) flavor (reference: ``All2AllStrictRELU``)."""
    ACTIVATION = "strict_relu"


class All2AllSigmoid(All2All):
    """Fused sigmoid flavor (reference: ``All2AllSigmoid``)."""
    ACTIVATION = "sigmoid"


class All2AllSoftmax(All2All):
    """Softmax output layer; also computes per-sample argmax
    (reference: ``All2AllSoftmax`` with its ``max_idx`` kernel)."""

    ACTIVATION = "linear"  # softmax applied over the linear output

    #: probabilities stay f32 — they feed the evaluator's CE/log and
    #: are tiny (batch × n_classes) next to the conv activations
    output_store_dtype = np.dtype(np.float32)

    def __init__(self, workflow, output_sample_shape, name=None, **kwargs):
        super().__init__(workflow, output_sample_shape, name=name, **kwargs)
        self.max_idx = Vector(name=f"{self.name}.max_idx", batch_major=True)

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device=device, **kwargs)
        self.max_idx.reset(np.zeros(
            self.lead_shape(self.input.shape), dtype=np.int32))
        self.init_vectors(self.max_idx)

    def _softmax(self, xp, logits):
        m = logits.max(axis=1, keepdims=True)
        e = xp.exp(logits - m)
        return e / e.sum(axis=1, keepdims=True)

    def _logits(self, xp, x, w, b):
        """(rows, classes): a row per sample, or per position."""
        rows = int(np.prod(self.lead_shape(x.shape)))
        y = self.mxu_dot(xp, x.reshape(rows, -1), w)
        return y if b is None else y + b

    def numpy_run(self) -> None:
        self.input.map_read()
        self.weights.map_read()
        b = None
        if self.include_bias:
            self.bias.map_read()
            b = self.bias.mem
        x = self.input.mem.astype(np.float32)
        logits = self._logits(np, x, self.weights.mem, b)
        self.output.map_invalidate()
        self.max_idx.map_invalidate()
        self.output.mem[...] = self._softmax(np, logits).reshape(
            self.output.shape)
        self.max_idx.mem[...] = np.argmax(logits, axis=1).astype(
            np.int32).reshape(self.max_idx.shape)

    def xla_run(self) -> None:
        b = self.bias.devmem if self.include_bias else None
        logits = self._logits(jnp, self.input.devmem, self.weights.devmem, b)
        self.output.devmem = self._softmax(jnp, logits).reshape(
            self.output.shape)
        self.max_idx.devmem = jnp.argmax(logits, axis=1).astype(
            jnp.int32).reshape(self.max_idx.shape)

"""Dropout (reference: ``znicz/dropout.py`` — ``DropoutForward`` /
``DropoutBackward``).

Train mode: zero each activation with probability ``dropout_ratio``
and scale survivors by ``1/(1−ratio)`` (inverted dropout, so eval is
identity — documented divergence: the reference scaled at eval time;
final-accuracy semantics are identical).  The mask is stored and
reused by the backward unit, exactly like the reference.

``forward_mode`` ("train"/"eval") is a static region key, so the jit
region compiles a masked and an identity variant — this is the
per-minibatch-gate case SURVEY.md §7 calls out.  Device randomness
comes from the unit's own PRNG key chain (a region leaf).
"""

from __future__ import annotations

import numpy as np

import jax

from znicz_tpu.memory import Vector
from znicz_tpu.ops.nn_units import Forward, WeightlessGradientUnit


class DropoutForward(Forward):
    def __init__(self, workflow, dropout_ratio: float = 0.5, name=None,
                 **kwargs) -> None:
        super().__init__(workflow, name=name, **kwargs)
        if not 0.0 <= dropout_ratio < 1.0:
            raise ValueError(f"dropout_ratio {dropout_ratio} not in [0,1)")
        self.dropout_ratio = float(dropout_ratio)
        self.forward_mode = "train"
        self.mask = Vector(name=f"{self.name}.mask", batch_major=True)

    def region_key(self) -> tuple:
        return (self.forward_mode,)

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device=device, **kwargs)
        if self.input is None or not self.input:
            raise AttributeError(f"{self}: input not linked yet")
        self.output.reset(np.zeros(self.input.shape,
                                   dtype=self.output_store_dtype))
        self.mask.reset(np.ones(self.input.shape,
                                dtype=self.act_store_dtype))
        self.inherit_model_shard(self.output, self.mask)
        self.init_vectors(self.input, self.output, self.mask)
        self.init_rng()

    def numpy_run(self) -> None:
        from znicz_tpu.utils import prng
        self.input.map_read()
        self.output.map_invalidate()
        if self.forward_mode == "train":
            keep = 1.0 - self.dropout_ratio
            self.mask.map_invalidate()
            self.mask.mem[...] = (
                prng.get().numpy.uniform(size=self.input.shape) < keep
            ).astype(np.float32) / keep
            self.output.mem[...] = self.input.mem * self.mask.mem
        else:
            self.output.mem[...] = self.input.mem

    def xla_run(self) -> None:
        x = self.input.devmem
        if self.forward_mode != "train":
            self.output.devmem = x
            return
        key = self.take_key()
        keep = 1.0 - self.dropout_ratio
        mask = jax.random.bernoulli(key, keep, x.shape).astype(
            x.dtype) / keep
        self.mask.devmem = mask
        self.output.devmem = x * mask


class DropoutBackward(WeightlessGradientUnit):
    MATCHES = (DropoutForward,)

    def region_key(self) -> tuple:
        fwd = self.forward_unit
        return (fwd.forward_mode if fwd is not None else "train",)

    def numpy_run(self) -> None:
        fwd = self.forward_unit
        self.err_output.map_read()
        self.err_input.map_invalidate()
        if fwd.forward_mode == "train":
            fwd.mask.map_read()
            self.err_input.mem[...] = self.err_output.mem * fwd.mask.mem
        else:
            self.err_input.mem[...] = self.err_output.mem

    def xla_run(self) -> None:
        fwd = self.forward_unit
        err = self.err_output.devmem
        if fwd.forward_mode != "train":
            self.err_input.devmem = err
            return
        self.err_input.devmem = err * fwd.mask.devmem


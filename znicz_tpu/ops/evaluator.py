"""Evaluators: turn network output + ground truth into the backward
chain's seed error and host-readable quality metrics
(reference: ``znicz/evaluator.py``).

``EvaluatorSoftmax`` consumes the softmax output and emits

- labels may be (B, T) — a next-token label at every position — with
  a (B, T, C) output: the B·T rows are then what samples are below;
- ``err_output = (p − onehot(t)) / n_valid`` — the combined
  softmax+cross-entropy derivative w.r.t. the logits, masked over
  padded tail samples (static-shape minibatches, see loader);
- ``n_err`` — mispredictions among valid samples (device scalar the
  Decision unit reads per step);
- ``confusion_matrix`` — optional (n_classes², accumulated per epoch
  host-side by Decision).

``EvaluatorMSE`` serves regression / autoencoder targets:
``err_output = (y − target)·2/n_valid`` and per-step summed squared
error.
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from znicz_tpu.accelerated_units import AcceleratedUnit
from znicz_tpu.loader.base import TRAIN
from znicz_tpu.memory import Vector


class EvaluatorBase(AcceleratedUnit):
    def __init__(self, workflow, name: str | None = None, **kwargs) -> None:
        super().__init__(workflow, name=name, **kwargs)
        self.output: Vector | None = None        # link from last forward
        self.minibatch_valid: Vector | None = None  # link from loader
        self.err_output = Vector(name=f"{self.name}.err_output",
                                 batch_major=True)
        # anomaly guard hooks, linked by StandardWorkflow when the
        # guard is on (resilience.guard): step_flags is seeded here
        # ([running_ok, loss_ok] = isfinite(step loss)); fault_inject
        # is the chaos harness's [loss_add, grad_add] leaf (None
        # unless a fault plan configures a train site)
        self.step_flags: Vector | None = None
        self.fault_inject: Vector | None = None
        # round 19: the guard-hosted [param_fp, grad_fp] SDC
        # fingerprint — zero-seeded here on TRAIN steps only (the
        # static minibatch_class is already part of the region key),
        # so validation steps keep the last train step's fingerprint
        # for the sentinel's vote to read
        self.sdc_fingerprint: Vector | None = None

    def _valid_mask(self, xp, n_rows, per_sample: int = 1):
        """Mask of the valid rows and their number; a sample is
        ``per_sample`` rows (positions)."""
        valid = self.minibatch_valid.devmem if xp is jnp \
            else self.minibatch_valid.mem
        valid = valid * per_sample
        return (xp.arange(n_rows) < valid), valid

    def _inject(self, xp, idx: int):
        """The chaos leaf's additive term (0.0 normally, NaN on an
        injected step); 0.0 when no fault plan is configured."""
        inj = self.fault_inject
        if inj is None or not inj:
            return None
        return inj.devmem[idx] if xp is jnp else inj.mem[idx]

    def _seed_step_flags(self, xp, loss_ok) -> None:
        """Write [running_ok, loss_ok]; the backward chain ANDs its
        gradient-finiteness into slot 0 and the AnomalyGuard commits
        the verdict at the end of the step.

        Under gradient accumulation (round 20) the flags span ALL
        microbatches of one accumulated step: accumulation-phase
        bodies AND their loss verdict into the running flags instead
        of overwriting (the guard resets them to ones after each
        apply-phase commit, so the first microbatch starts from a
        clean [1, 1]) — one non-finite microbatch loss poisons the
        whole step's verdict, matching the fused-batch semantics."""
        flags = self.step_flags
        if flags is None or not flags:
            return
        from znicz_tpu.accelerated_units import current_accum_phase
        phase = current_accum_phase()
        if xp is jnp:
            f = loss_ok.astype(jnp.float32)
            if phase is not None:
                flags.devmem = flags.devmem * f
            else:
                flags.devmem = jnp.stack([f, f])
        else:
            f = np.float32(1.0 if loss_ok else 0.0)
            flags.mem[...] = [f, f]
        if phase is None or phase[0] == "apply":
            # the SDC per-step slots reset once per OPTIMIZER step —
            # accumulation microbatches fold no fingerprints
            self._seed_fingerprint(xp)

    def _seed_fingerprint(self, xp) -> None:
        """Zero the SDC fingerprint's per-step slots (claimed param
        fp, grad fp, pre-update refold) at the top of a TRAIN step so
        the GD units fold this step's checksums into a fresh slate;
        the sticky self-check count and the previous claimed fp (slots
        3/4) persist.  The branch is static: ``minibatch_class`` is in
        the region key."""
        fp = self.sdc_fingerprint
        if fp is None or not fp or int(self.minibatch_class) != TRAIN:
            return
        if xp is jnp:
            fp.devmem = fp.devmem.at[:3].set(0.0)
        else:
            fp.mem[:3] = 0.0


class EvaluatorSoftmax(EvaluatorBase):
    """Softmax cross-entropy evaluator (reference:
    ``EvaluatorSoftmax``)."""

    def __init__(self, workflow, name: str | None = None,
                 compute_confusion: bool = False, **kwargs) -> None:
        super().__init__(workflow, name=name, **kwargs)
        self.labels: Vector | None = None      # link from loader
        self.max_idx: Vector | None = None     # link from All2AllSoftmax
        self.minibatch_class = TRAIN           # usually linked from loader
        self.n_err = Vector(name=f"{self.name}.n_err")
        # per-class error counts for the WHOLE epoch, accumulated on
        # device so Decision syncs host-side once per epoch instead of
        # once per step (a TPU-first change: a per-step device→host
        # scalar fetch costs a round trip and stalls the dispatch
        # queue)
        self.epoch_n_err = Vector(name=f"{self.name}.epoch_n_err")
        # optional (3, C, C) confusion counts, same epoch-accumulation
        # scheme (reference: EvaluatorSoftmax confusion matrix)
        self.compute_confusion = compute_confusion
        self.confusion_matrix = Vector(name=f"{self.name}.confusion")
        # summed cross-entropy −log p(true) per class, accumulated on
        # device like epoch_n_err (read once per epoch; the loss curve
        # the bf16-vs-f32 convergence artifact tracks)
        self.epoch_loss = Vector(name=f"{self.name}.epoch_loss")

    def region_key(self) -> tuple:
        # minibatch_class indexes the on-device accumulator statically
        return (int(self.minibatch_class),)

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device=device, **kwargs)
        if self.output is None or not self.output:
            raise AttributeError(f"{self}: output not linked yet")
        self.err_output.reset(np.zeros(self.output.shape, dtype=np.float32))
        self.n_err.reset(np.zeros((), dtype=np.int32))
        if not self.epoch_n_err:
            self.epoch_n_err.reset(np.zeros(3, dtype=np.int32))
        if not self.epoch_loss:
            self.epoch_loss.reset(np.zeros(3, dtype=np.float32))
        if self.compute_confusion and not self.confusion_matrix:
            c = self.n_classes
            self.confusion_matrix.reset(np.zeros((3, c, c), dtype=np.int32))
        self.init_vectors(self.err_output, self.n_err, self.epoch_n_err,
                          self.epoch_loss, self.confusion_matrix,
                          self.output, self.labels, self.max_idx,
                          self.minibatch_valid)

    @property
    def n_classes(self) -> int:
        return self.output.shape[-1]

    @property
    def labels_per_sample(self) -> int:
        """1, or T where every position of a sample has a label."""
        return int(np.prod(self.labels.shape[1:]))

    def _rows(self, p, t, max_idx):
        """(rows, classes) probabilities, (rows,) labels and
        arg-maxes: a row per sample, or per position."""
        return (p.reshape(-1, p.shape[-1]), t.reshape(-1),
                max_idx.reshape(-1))

    def numpy_run(self) -> None:
        for vec in (self.output, self.labels, self.max_idx,
                    self.minibatch_valid):
            vec.map_read()
        p, t, max_idx = self._rows(self.output.mem, self.labels.mem,
                                   self.max_idx.mem)
        mask, valid = self._valid_mask(np, p.shape[0],
                                       self.labels_per_sample)
        onehot = np.zeros_like(p)
        onehot[np.arange(p.shape[0]), t] = 1.0
        err = mask[:, None] * (p - onehot) / max(int(valid), 1)
        grad_inj = self._inject(np, 1)
        if grad_inj is not None:
            err = err + grad_inj
        self.err_output.map_invalidate()
        self.err_output.mem[...] = err.reshape(self.err_output.shape)
        self.n_err.map_invalidate()
        n_err = int(np.sum((max_idx != t) & mask))
        self.n_err.mem[...] = n_err
        self.epoch_n_err.map_write()
        self.epoch_n_err.mem[int(self.minibatch_class)] += n_err
        self.epoch_loss.map_write()
        p_true = np.maximum(p[np.arange(p.shape[0]), t], 1e-30)
        loss_sum = np.float32(np.sum(mask * -np.log(p_true)))
        loss_inj = self._inject(np, 0)
        if loss_inj is not None:
            loss_sum = loss_sum + np.float32(loss_inj)
        loss_ok = bool(np.isfinite(loss_sum))
        # a non-finite step must not poison the epoch accumulator —
        # the guard skips its update; the accumulator skips its sample
        self.epoch_loss.mem[int(self.minibatch_class)] += float(
            loss_sum if loss_ok else 0.0)
        self._seed_step_flags(np, loss_ok)
        if self.compute_confusion:
            self.confusion_matrix.map_write()
            cm = self.confusion_matrix.mem[int(self.minibatch_class)]
            np.add.at(cm, (t[mask], max_idx[mask]), 1)

    def xla_run(self) -> None:
        p, t, max_idx = self._rows(self.output.devmem,
                                   self.labels.devmem,
                                   self.max_idx.devmem)
        mask, valid = self._valid_mask(jnp, p.shape[0],
                                       self.labels_per_sample)
        # p − onehot(t) as a select: no rows × classes one-hot exists,
        # not even in the trace (at 8,192 × 50,304 it would be 1.65 GB)
        hit = t[:, None] == jnp.arange(p.shape[1])[None, :]
        denom = jnp.maximum(valid, 1).astype(p.dtype)
        err = mask[:, None] * jnp.where(hit, p - 1.0, p) / denom
        grad_inj = self._inject(jnp, 1)
        if grad_inj is not None:
            err = err + grad_inj.astype(err.dtype)
        self.err_output.devmem = err.reshape(self.err_output.shape)
        n_err = jnp.sum((max_idx != t) & mask).astype(jnp.int32)
        self.n_err.devmem = n_err
        self.epoch_n_err.devmem = self.epoch_n_err.devmem.at[
            int(self.minibatch_class)].add(n_err)
        p_true = jnp.maximum(p[jnp.arange(p.shape[0]), t], 1e-30)
        loss_sum = jnp.sum(mask * -jnp.log(p_true)).astype(jnp.float32)
        loss_inj = self._inject(jnp, 0)
        if loss_inj is not None:
            loss_sum = loss_sum + loss_inj
        loss_ok = jnp.isfinite(loss_sum)
        # a non-finite step must not poison the epoch accumulator —
        # the guard skips its update; the accumulator skips its sample
        self.epoch_loss.devmem = self.epoch_loss.devmem.at[
            int(self.minibatch_class)].add(
                jnp.where(loss_ok, loss_sum, 0.0))
        self._seed_step_flags(jnp, loss_ok)
        if self.compute_confusion:
            # masked rows contribute 0; duplicate (t, pred) pairs
            # accumulate via scatter-add
            cls = int(self.minibatch_class)
            self.confusion_matrix.devmem = self.confusion_matrix.devmem.at[
                cls, t, max_idx].add(mask.astype(jnp.int32))


class EvaluatorMSE(EvaluatorBase):
    """Mean-squared-error evaluator for regression / autoencoders
    (reference: ``EvaluatorMSE``)."""

    def __init__(self, workflow, name: str | None = None,
                 root_metric: bool = True, **kwargs) -> None:
        super().__init__(workflow, name=name, **kwargs)
        self.target: Vector | None = None  # link from loader
        self.minibatch_class = TRAIN       # usually linked from loader
        self.metrics = Vector(name=f"{self.name}.metrics")  # summed sq err
        self.epoch_sse = Vector(name=f"{self.name}.epoch_sse")

    def region_key(self) -> tuple:
        return (int(self.minibatch_class),)

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device=device, **kwargs)
        if self.output is None or not self.output:
            raise AttributeError(f"{self}: output not linked yet")
        self.err_output.reset(np.zeros(self.output.shape, dtype=np.float32))
        self.metrics.reset(np.zeros((), dtype=np.float32))
        if not self.epoch_sse:
            self.epoch_sse.reset(np.zeros(3, dtype=np.float32))
        self.init_vectors(self.err_output, self.metrics, self.epoch_sse,
                          self.output, self.target, self.minibatch_valid)

    def numpy_run(self) -> None:
        for vec in (self.output, self.target, self.minibatch_valid):
            vec.map_read()
        y = self.output.mem
        batch = y.shape[0]
        t = self.target.mem.reshape(batch, -1).astype(np.float32)
        y2 = y.reshape(batch, -1)
        mask, valid = self._valid_mask(np, batch)
        diff = mask[:, None] * (y2 - t)
        err = (diff * (2.0 / max(int(valid), 1))).reshape(y.shape)
        grad_inj = self._inject(np, 1)
        if grad_inj is not None:
            err = err + grad_inj
        self.err_output.map_invalidate()
        self.err_output.mem[...] = err
        self.metrics.map_invalidate()
        sse = np.float32(np.sum(diff * diff))
        loss_inj = self._inject(np, 0)
        if loss_inj is not None:
            sse = sse + np.float32(loss_inj)
        self.metrics.mem[...] = sse
        loss_ok = bool(np.isfinite(sse))
        self.epoch_sse.map_write()
        self.epoch_sse.mem[int(self.minibatch_class)] += \
            sse if loss_ok else 0.0
        self._seed_step_flags(np, loss_ok)

    def xla_run(self) -> None:
        # f32 math regardless of the activation storage dtype: the SSE
        # reduction over the whole minibatch would swamp small terms in
        # bf16, and the decision unit selects models on this number
        y = self.output.devmem.astype(jnp.float32)
        batch = y.shape[0]
        t = self.target.devmem.reshape(batch, -1).astype(jnp.float32)
        y2 = y.reshape(batch, -1)
        mask, valid = self._valid_mask(jnp, batch)
        diff = mask[:, None] * (y2 - t)
        denom = jnp.maximum(valid, 1).astype(y.dtype)
        err = (diff * (2.0 / denom)).reshape(y.shape)
        grad_inj = self._inject(jnp, 1)
        if grad_inj is not None:
            err = err + grad_inj.astype(err.dtype)
        self.err_output.devmem = err
        sse = jnp.sum(diff * diff)
        loss_inj = self._inject(jnp, 0)
        if loss_inj is not None:
            sse = sse + loss_inj
        self.metrics.devmem = sse
        loss_ok = jnp.isfinite(sse)
        self.epoch_sse.devmem = self.epoch_sse.devmem.at[
            int(self.minibatch_class)].add(jnp.where(loss_ok, sse, 0.0))
        self._seed_step_flags(jnp, loss_ok)


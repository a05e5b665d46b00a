"""StandardWorkflow: declarative model assembly + training-loop wiring.

Rebuilds the reference's ``znicz/standard_workflow.py``: a complete
training loop from a declarative ``layers`` list.  Layer dicts use the
reference's convention — ``{"type": <name>, "->": {forward kwargs},
"<-": {gradient kwargs}}``.

Topology (both backends):

.. code-block:: text

    start → repeater → loader(host pick) → [hot chain] → decision ─→ repeater
                                                            └─(complete)→ end
    side chain on decision.improved: snapshotter

The hot chain is backend-dependent — the TPU-first core of the design:

- ``xla``: ONE :class:`~znicz_tpu.accelerated_units.RegionUnit`
  compiling loader-gather → forwards → evaluator → backwards into a
  single donated-buffer XLA program (two variants: train minibatches
  run the backward units, validation/test minibatches skip them via
  the region's static key);
- ``numpy``: the oracle path — each unit fires eagerly through the
  scheduler exactly like the reference's NumPy backend.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Sequence

import numpy as np

from znicz_tpu.accelerated_units import AcceleratedWorkflow, RegionUnit
from znicz_tpu.backends import NumpyDevice
from znicz_tpu.loader.base import TRAIN, Loader
from znicz_tpu.mutable import Bool
from znicz_tpu.ops import activation, all2all, conv, cutter, dropout, pooling
from znicz_tpu.ops import attention, deconv, depooling, lstm, normalization
from znicz_tpu.ops import delta_net, embedding, layer_norm, moe, pos_encoding
from znicz_tpu.ops import short_conv, streams
from znicz_tpu.ops import loop_exits, rms_norm
from znicz_tpu.ops import seq_reshape
from znicz_tpu.ops import gd, gd_conv, gd_pooling  # noqa: F401 (pairs)
from znicz_tpu.ops.decision import DecisionGD, DecisionMSE
from znicz_tpu.ops.lr_adjust import LearningRateAdjust
from znicz_tpu.ops.evaluator import EvaluatorMSE, EvaluatorSoftmax
from znicz_tpu.ops.nn_units import Forward, gd_for
from znicz_tpu.pass_span import TABLE_KEY as PASSES_KEY, PassSpan, spans_of
from znicz_tpu.units import Repeater
from znicz_tpu.utils.snapshotter import Snapshotter


#: layer-type registry: name → forward class (backward via gd_for)
_LAYER_TYPES: dict[str, type] = {}


def register_layer_type(name: str, forward_cls: type) -> None:
    _LAYER_TYPES[name] = forward_cls


def layer_type(name: str) -> type:
    try:
        return _LAYER_TYPES[name]
    except KeyError:
        raise ValueError(f"unknown layer type '{name}' "
                         f"(have {sorted(_LAYER_TYPES)})") from None


for _name, _cls in {
    "all2all": all2all.All2All,
    "all2all_tanh": all2all.All2AllTanh,
    "all2all_relu": all2all.All2AllRELU,
    "all2all_str": all2all.All2AllStrictRELU,
    "all2all_sigmoid": all2all.All2AllSigmoid,
    "softmax": all2all.All2AllSoftmax,
    "conv": conv.Conv,
    "conv_tanh": conv.ConvTanh,
    "conv_relu": conv.ConvRELU,
    "conv_str": conv.ConvStrictRELU,
    "conv_sigmoid": conv.ConvSigmoid,
    "max_pooling": pooling.MaxPooling,
    "maxabs_pooling": pooling.MaxAbsPooling,
    "avg_pooling": pooling.AvgPooling,
    "stochastic_pooling": pooling.StochasticPooling,
    "norm": normalization.LRNormalizerForward,
    "cutter": cutter.Cutter,
    "dropout": dropout.DropoutForward,
    "activation_tanh": activation.ForwardTanh,
    "activation_relu": activation.ForwardRELU,
    "activation_str": activation.ForwardStrictRELU,
    "activation_sigmoid": activation.ForwardSigmoid,
    "activation_log": activation.ForwardLog,
    "activation_mul": activation.ForwardMul,
    "deconv": deconv.Deconv,
    "deconv_tanh": deconv.DeconvTanh,
    "deconv_relu": deconv.DeconvRELU,
    "deconv_sigmoid": deconv.DeconvSigmoid,
    "depooling": depooling.Depooling,
    "lstm": lstm.LSTM,
    "attention": attention.MultiHeadAttention,
    # the same unit under the name of what ``kv_latent`` makes it, so
    # that a table says what it holds and a program without the
    # mechanism refuses it by its first unknown NAME (units take unknown
    # options silently: PR 37 found the parent training another model)
    "latent_attention": attention.MultiHeadAttention,
    "to_sequence": seq_reshape.ToSequence,
    "last_token": seq_reshape.LastToken,
    "pos_encoding": pos_encoding.PositionalEncoding,
    "layer_norm": layer_norm.LayerNorm,
    "embedding": embedding.Embedding,
    "rms_norm": rms_norm.RMSNorm,
    "moe": moe.MoE,
    "gated_mlp": moe.GatedMLP,
    "gated_delta_net": delta_net.GatedDeltaNet,
    "short_conv": short_conv.ShortConv,
    # a residual path of n streams (ops/streams.py): open, then a READ
    # and a WRITE around every sublayer, then close
    "stream_open": streams.StreamOpen,
    "stream_read": streams.StreamRead,
    "stream_write": streams.StreamWrite,
    "stream_close": streams.StreamClose,
    "loop_exits": loop_exits.All2AllExits,
}.items():
    register_layer_type(_name, _cls)


class StandardWorkflow(AcceleratedWorkflow):
    """Declarative training workflow.

    Parameters
    ----------
    loader_factory:
        ``callable(workflow) -> Loader`` building the dataset unit.
    layers:
        list of layer dicts (``{"type", "->", "<-"}``).  Adjacent
        entries with ``"passes": R`` form a looped span: that stretch
        of the chain runs R times a step on shared weights, one
        gradient summed over the passes and one update
        (:mod:`znicz_tpu.pass_span`; XLA path only).
        Two options of the feed-forward layers (``ops/moe.py``):
        ``act`` (``moe``, ``gated_mlp``) names the gate function,
        ``"silu"`` or ``"relu"`` — any other name is refused by the
        unit; ``route_from="block_input"`` (``moe``) takes the router's
        logits from the input of the layer BEFORE the expert layer, as
        it is — refused here, by index, unless that layer is a
        ``residual`` sublayer outside a looped span (the edge beside
        the chain: :meth:`_link_route`), and by ``run_pipelined`` (a
        stage boundary would cut it).  ``export_forward`` and the
        decode engine refuse both by name (``export.refuse_unserved``).
    loss:
        ``"softmax"`` (classification) or ``"mse"``.
    decision_config / snapshotter_config:
        kwargs for the Decision / Snapshotter units
        (``snapshotter_config=None`` disables snapshots).
    """

    def __init__(self, workflow=None, name: str | None = None,
                 loader_factory: Callable[["StandardWorkflow"], Loader]
                 | None = None,
                 layers: Sequence[dict] = (),
                 loss: str = "softmax",
                 evaluator_config: dict[str, Any] | None = None,
                 decision_config: dict[str, Any] | None = None,
                 snapshotter_config: dict[str, Any] | None = None,
                 lr_adjuster_config: dict[str, Any] | None = None,
                 anomaly_guard: bool | None = None,
                 **kwargs) -> None:
        super().__init__(workflow, name=name, **kwargs)
        if loader_factory is None:
            raise ValueError("loader_factory is required")
        self.layers_config = list(layers)
        self.loss = loss
        # kept for the SDC sentinel's shadow-oracle clone (round 19)
        self._loader_factory = loader_factory
        self._evaluator_config = dict(evaluator_config or {})
        self._decision_config = dict(decision_config or {})

        self.repeater = Repeater(self, name="repeater")
        self.loader = loader_factory(self)
        assert isinstance(self.loader, Loader)
        self.forwards: list[Forward] = []
        self.gds: list = []
        #: the looped spans of the table, one :class:`PassSpan` each
        self.pass_spans: list[PassSpan] = []
        self.anomaly_guard = None
        self.integrity = None  # the round-19 SDC sentinel
        self._pipeline = None  # round-20 pipeline executor (lazy)
        self.link_forwards()
        self.link_evaluator(**(evaluator_config or {}))
        self.link_decision(**(decision_config or {}))
        self.link_gds()
        from znicz_tpu.utils.config import root as _root
        guard_on = (anomaly_guard if anomaly_guard is not None
                    else bool(_root.common.engine.get("anomaly_guard",
                                                      True)))
        if guard_on:
            self.link_anomaly_guard()
        self.link_loop()
        self.snapshotter = None
        self.image_saver = None
        if snapshotter_config is not None:
            self.link_snapshotter(**snapshotter_config)
        self.lr_adjuster = None
        if lr_adjuster_config is None and any(
                "lr_policy" in spec.get("<-", {})
                or "bias_lr_policy" in spec.get("<-", {})
                for spec in self.layers_config):
            lr_adjuster_config = {}  # per-layer policies imply an adjuster
        if lr_adjuster_config is not None:
            self.link_lr_adjuster(**lr_adjuster_config)
        self._region_unit: RegionUnit | None = None

    # ------------------------------------------------------------------
    # builders (reference API surface: link_forwards / link_gds / ...)
    # ------------------------------------------------------------------
    def link_forwards(self) -> None:
        prev = None
        # table index → the looped span that ENDS before it / holds it
        ends, member_of = {}, {}
        for first, stop, passes in spans_of(self.layers_config):
            if stop == len(self.layers_config):
                raise ValueError(
                    f"layers {first}–{stop - 1}: a looped span "
                    f"('{PASSES_KEY}') cannot end the table — the unit "
                    f"after it takes its state (or, as loop_exits, "
                    f"every pass's)")
            span = PassSpan(self, passes,
                            name=f"pass_span_{len(self.pass_spans)}")
            self.pass_spans.append(span)
            ends[stop] = span
            member_of.update((i, span) for i in range(first, stop))
        for index, spec in enumerate(self.layers_config):
            cls = layer_type(spec["type"])
            cfg = dict(spec.get("->", {}))
            tied = spec.get("tied_to")  # autoencoder decoder layers
            #                             reference the encoder layer
            #                             they invert (MnistAE/
            #                             ImagenetAE topology)
            tied_unit = None
            if tied is not None:
                tied_unit = self.forwards[tied]
                if issubclass(cls, deconv.Deconv):
                    # geometry mirrors the tied conv layer
                    tied_cfg = self.layers_config[tied].get("->", {})
                    for key in ("n_kernels", "kx", "ky", "sliding",
                                "padding"):
                        if key in tied_cfg:
                            cfg.setdefault(key, tied_cfg[key])
            unit = cls(self, **cfg)
            if tied_unit is not None:
                if issubclass(cls, deconv.Deconv):
                    unit.output_shape_source = tied_unit.input
                    if spec.get("tied_weights"):
                        unit.link_attrs(tied_unit, "weights")
                elif issubclass(cls, depooling.Depooling):
                    unit.pooling_unit = tied_unit
                else:
                    raise ValueError(
                        f"layer type '{spec['type']}' does not "
                        f"support tied_to")
            span = ends.get(index)
            if prev is None:
                unit.link_attrs(self.loader, ("input", "minibatch_data"))
            elif span is not None and getattr(cls, "TAKES_PASSES", False):
                # every pass's state of the span before it, not the last
                span.takes_passes = True
                unit.link_attrs(span, ("input", "states"))
            else:
                unit.link_attrs(prev, ("input", "output"))
            if "forward_mode" in unit.__dict__:  # stochastic units track
                unit.link_attrs(self.loader, "forward_mode",
                                two_way=False)  # the minibatch class
            if index in member_of:
                member_of[index].forwards.append(unit)
                unit.pass_span = member_of[index]
            if isinstance(unit, streams._Stream):
                self._link_stream(index, unit, prev)
            if getattr(unit, "route_from", None):
                self._link_route(index, unit, prev)
            self.forwards.append(unit)
            prev = unit
        unwritten = [i for i, u in enumerate(self.forwards)
                     if isinstance(u, streams.StreamRead)
                     and u.write_unit is None]
        if unwritten:
            raise ValueError(
                f"layers {unwritten}: a stream_read with no stream_write "
                f"after its sublayer — the stream it read would end "
                f"there")

    def _link_stream(self, index: int, unit, prev) -> None:
        """The stream units' edges beside the chain's (ops/streams.py):
        a WRITE is given its READ — the nearest before it that no WRITE
        has yet — and every READ the OPEN unit's totals; a table that
        does not pair them is refused here, by index."""
        wide = (streams.StreamOpen, streams.StreamWrite)
        if isinstance(unit, (streams.StreamRead, streams.StreamClose)):
            if not isinstance(prev, wide):
                raise ValueError(
                    f"layer {index}: a {self.layers_config[index]['type']} "
                    f"reads the streams, which a stream_open or a "
                    f"stream_write hands it; layer {index - 1} is a "
                    f"{self.layers_config[index - 1]['type'] if index else 'loader'}")
        if isinstance(unit, streams.StreamRead):
            opened = next((u for u in reversed(self.forwards)
                           if isinstance(u, streams.StreamOpen)), None)
            opened.reads += 1
            unit.link_attrs(opened, "stream_stats")
        elif isinstance(unit, streams.StreamWrite):
            at = next((i for i in range(index - 1, -1, -1)
                       if isinstance(self.forwards[i], streams._Stream)),
                      None)
            read = None if at is None else self.forwards[at]
            if not isinstance(read, streams.StreamRead):
                raise ValueError(
                    f"layer {index}: a stream_write with no stream_read "
                    f"of its own: the stream unit before it is "
                    + ("none" if at is None else
                       f"layer {at}, a {self.layers_config[at]['type']}"))
            if at == index - 1:
                raise ValueError(
                    f"layer {index}: a stream_write with no sublayer "
                    f"between it and its stream_read, layer {at}")
            unit.read_unit, read.write_unit = read, unit

    def _link_route(self, index: int, unit, prev) -> None:
        """An expert layer's second forward edge (``ops/moe.py``,
        ``route_from="block_input"``): its router reads the INPUT of
        the residual sublayer before it — the block's input, the
        ``residual`` being inside that sublayer's unit.  The stream
        units' edge beside the chain (:meth:`_link_stream`) is of
        another kind: that one carries the n·D-wide stream past a
        sublayer from one stream unit to the next; this one carries a
        plain (B, T, D) Vector past a sublayer into a unit of the
        chain, and its cotangent back into that sublayer's GD
        (:meth:`link_gds`).  A table that has no such sublayer there is
        refused here, by index."""
        looped = any(getattr(u, "pass_span", None) is not None
                     for u in (prev, unit))
        if not getattr(prev, "residual", False) or looped:
            raise ValueError(
                f"layer {index}: route_from={unit.route_from!r} reads "
                f"the input of the residual sublayer before the expert "
                f"layer, outside a looped span; layer {index - 1} is "
                + ("the loader" if prev is None else
                   f"a {self.layers_config[index - 1]['type']} "
                   f"(residual: {getattr(prev, 'residual', False)}, "
                   f"looped: {looped})"))
        unit.link_attrs(prev, ("route_input", "input"))

    def link_evaluator(self, **config) -> None:
        last = self.forwards[-1]
        if self.loss == "softmax" and isinstance(last,
                                                 loop_exits.All2AllExits):
            # a head with an exit at every pass brings its own loss
            ev = loop_exits.EvaluatorLoopExits(self, name="evaluator",
                                               **config)
            ev.exits_unit = last
            ev.link_attrs(last, "output", "max_idx", "exit_q",
                          "exit_stats")
            ev.link_attrs(self.loader, ("labels", "minibatch_labels"),
                          "minibatch_valid", "minibatch_class")
        elif self.loss == "softmax":
            ev = EvaluatorSoftmax(self, name="evaluator", **config)
            ev.link_attrs(last, "output", "max_idx")
            ev.link_attrs(self.loader, ("labels", "minibatch_labels"),
                          "minibatch_valid", "minibatch_class")
        elif self.loss == "mse":
            ev = EvaluatorMSE(self, name="evaluator", **config)
            ev.link_attrs(last, "output")
            ev.link_attrs(self.loader, ("target", "minibatch_data"),
                          "minibatch_valid", "minibatch_class")
        else:
            raise ValueError(f"unknown loss '{self.loss}'")
        self.evaluator = ev

    def link_decision(self, **config) -> None:
        cls = DecisionGD if self.loss == "softmax" else DecisionMSE
        self.decision = cls(self, name="decision", **config)
        self.decision.loader = self.loader
        self.decision.evaluator = self.evaluator

    def link_gds(self) -> None:
        """Build the backward chain via the fwd↔bwd pairing registry
        (reference: MatchingObject-driven ``link_gds``)."""
        self.gds = []
        next_gd = None
        for i, fwd in enumerate(reversed(self.forwards)):
            spec = self.layers_config[len(self.forwards) - 1 - i]
            cls = gd_for(type(fwd))
            gd_kwargs = {k: v for k, v in spec.get("<-", {}).items()
                         if k not in ("lr_policy", "bias_lr_policy")}
            span = getattr(fwd, "pass_span", None)
            # a looped span's first member sends its input's cotangent
            # back to the pass before, whatever precedes the span
            unit = cls(self, need_err_input=(
                i != len(self.forwards) - 1
                or (span is not None and span.passes > 1)), **gd_kwargs)
            unit.forward_unit = fwd  # geometry/mask/activation source
            unit.link_attrs(fwd, "input", "output", "weights", "bias")
            if next_gd is None:
                unit.link_attrs(self.evaluator, "err_output")
                if isinstance(fwd, loop_exits.All2AllExits):
                    unit.link_attrs(self.evaluator, "err_exit_q")
            elif span is not None and fwd is span.forwards[-1]:
                # the span joins what reaches its end from ``next_gd``
                # with the later pass's own (PassSpan.trace_backward)
                span.consumer_gd = next_gd
                unit.link_attrs(span, ("err_output", "err_last"))
            else:
                unit.link_attrs(next_gd, ("err_output", "err_input"))
            if getattr(cls, "STREAM_IN", False):
                # what is n·D wide goes from stream GD to stream GD
                # (ops/streams.py), not through a Vector
                if not getattr(type(next_gd), "STREAM_OUT", False):
                    raise ValueError(
                        f"layer {len(self.forwards) - 1 - i}: a "
                        f"{spec['type']} hands the streams on, and the "
                        f"layer after it is "
                        f"{'none' if next_gd is None else 'no stream_read or stream_close'}")
                unit.stream_gd = next_gd
            if next_gd is not None and getattr(
                    next_gd.forward_unit, "route_from", None):
                # the expert layer after this sublayer read this
                # sublayer's input too: its GD parks that cotangent
                # here, and this unit joins it to its own
                # (GradientDescentBase.join_beside)
                next_gd.forward_unit.route_gd = unit
            if span is not None:
                span.gds.insert(0, unit)
            # train minibatches only (reference: decision.gd_skip)
            unit.gate_skip = Bool._derived(
                lambda: self.loader.minibatch_class != TRAIN)
            self.gds.append(unit)
            next_gd = unit
        self.gds.reverse()

    def link_anomaly_guard(self) -> None:
        """Attach the resilience anomaly guard (round 11): the
        evaluator seeds per-step finite flags, every weighted GD folds
        its gradient check in and gates its update, and the guard unit
        commits the streak/totals state the Decision unit reads (see
        :mod:`znicz_tpu.resilience.guard`).  Gate:
        ``root.common.engine.anomaly_guard`` (default on) or the
        ``anomaly_guard`` constructor argument."""
        from znicz_tpu.resilience.guard import AnomalyGuard
        guard = AnomalyGuard(self, name="anomaly_guard")
        self.anomaly_guard = guard
        self.evaluator.link_attrs(guard, "step_flags", "fault_inject",
                                  two_way=False)
        for gd_unit in self.gds:
            gd_unit.link_attrs(guard, ("anomaly_flag", "step_flags"),
                               two_way=False)
        if guard.sdc_fingerprint is not None:
            # round 19: the SDC fingerprint rides the same region —
            # evaluator zero-seeds it per train step, every weighted
            # GD folds its checksums in, the sentinel reads it at
            # vote/audit cadence (resilience.integrity)
            from znicz_tpu.resilience.integrity import IntegritySentinel
            self.evaluator.link_attrs(guard, "sdc_fingerprint",
                                      two_way=False)
            for gd_unit in self.gds:
                gd_unit.link_attrs(guard, "sdc_fingerprint",
                                   "sdc_inject", two_way=False)
            self.integrity = IntegritySentinel(self)

    def rollback_to_snapshot(self, streak: int) -> bool:
        """Anomaly-streak recovery (called by the Decision unit after
        K consecutive non-finite steps): reload the Snapshotter's last
        good checkpoint through the digest-verified load path and
        resume mid-epoch (the round-10 resume machinery restores the
        loader cursor, PRNG streams and optimizer state).  Returns
        True when a rollback happened.  Without a snapshot the guard
        has still prevented weight poisoning (anomalous updates were
        skipped), so the run continues with a warning."""
        import os as _os

        from znicz_tpu.observe import metrics as _metrics
        from znicz_tpu.utils.snapshotter import Snapshotter
        snap = self.snapshotter
        path = snap.destination if snap is not None else None
        if self.anomaly_guard is not None:
            self.anomaly_guard.reset_streak()
            self.anomaly_guard.reset_sdc_fingerprint()
        if not path or not _os.path.exists(path):
            self.warning(
                "anomaly streak %d with no snapshot to roll back to — "
                "anomalous updates were skipped, continuing as-is",
                streak)
            return False
        state = Snapshotter.load(path)
        self.load_state(state)
        _metrics.anomaly_rollbacks(self.name).inc()
        _metrics.recoveries("rollback").inc()
        self.warning("anomaly streak %d: rolled back to %s and "
                     "resumed", streak, path)
        return True

    def link_loop(self) -> None:
        """Wire the training loop's control flow."""
        self.repeater.link_from(self.start_point)
        self.loader.link_from(self.repeater)
        self.decision.link_from(self._link_hot_chain(self.loader))
        self.repeater.link_from(self.decision)
        self.repeater.gate_block = self.decision.complete
        self.end_point.link_from(self.decision)
        self.end_point.gate_block = ~self.decision.complete

    def _link_hot_chain(self, after):
        """Backend-independent wiring is impossible to decide before
        ``initialize`` (device unknown), so both paths are wired and
        gated: the RegionUnit disables itself on the numpy backend and
        the eager chain is skipped on the XLA backend."""
        # eager oracle chain
        prev = after
        for fwd in self.forwards:
            fwd.link_from(prev)
            prev = fwd
        self.evaluator.link_from(prev)
        prev = self.evaluator
        for gd_unit in reversed(self.gds):
            gd_unit.link_from(prev)
            prev = gd_unit
        if self.anomaly_guard is not None:
            # the guard commits the step's anomaly verdict AFTER the
            # last backward unit (same position it holds in the
            # region's trace order)
            self.anomaly_guard.link_from(prev)
            prev = self.anomaly_guard
        return prev

    def _relink_end_point_last(self) -> None:
        """Keep ``end_point`` the LAST successor of the decision so
        epoch side-chain units (snapshotter, plotters, image saver,
        lr adjuster) still fire on the final epoch before the workflow
        stops (the scheduler drains successors in link order)."""
        if self.decision in self.end_point.links_from:
            self.end_point.unlink_from(self.decision)
            self.end_point.link_from(self.decision)

    def link_lr_adjuster(self, lr_policy=None, bias_lr_policy=None) -> None:
        """Attach a :class:`LearningRateAdjust` over the weighted GD
        units (reference: ``link_lr_adjuster``).  Per-layer overrides
        ride in the layer spec's ``"<-"`` dict as ``lr_policy`` /
        ``bias_lr_policy``; the arguments here are the defaults."""
        from znicz_tpu.ops.nn_units import WeightlessGradientUnit
        adj = LearningRateAdjust(self, name="lr_adjuster")
        adj.loader = self.loader
        for i, gd_unit in enumerate(self.gds):
            if isinstance(gd_unit, WeightlessGradientUnit):
                continue  # no learning-rate state to schedule
            spec = self.layers_config[i].get("<-", {})
            adj.add_gd_unit(
                gd_unit,
                lr_policy=spec.get("lr_policy", lr_policy),
                bias_lr_policy=spec.get("bias_lr_policy", bias_lr_policy))
        adj.link_from(self.decision)
        self._relink_end_point_last()
        self.lr_adjuster = adj

    def link_snapshotter(self, **config) -> None:
        self.snapshotter = Snapshotter(self, name="snapshotter", **config)
        self.snapshotter.decision = self.decision
        self.snapshotter.link_from(self.decision)
        self._relink_end_point_last()
        self.snapshotter.gate_skip = ~self.decision.improved
        # snapshotter rides the loop edge; repeater waits for no one
        # extra (Repeater = any-gate), so no deadlock.

    # -- observability side chains (reference: link_image_saver and the
    # samples' plotter wiring) -----------------------------------------
    def _epoch_side_unit(self, unit) -> None:
        unit.link_from(self.decision)
        self._relink_end_point_last()
        unit.gate_skip = ~self.decision.epoch_ended

    def link_error_plotter(self, server=None):
        """Error-percentage curves per sample class, one point per
        epoch (reference: the AccumulatingPlotter triple every sample
        wired)."""
        from znicz_tpu.loader.base import CLASS_NAME
        from znicz_tpu.plotting_units import AccumulatingPlotter
        p = AccumulatingPlotter(self, name="error_plotter",
                                server=server, ylabel="error %")
        metric = ("epoch_n_err_pt" if self.loss == "softmax"
                  else "epoch_mse")
        for cls in range(3):
            p.add_series(
                CLASS_NAME[cls],
                lambda cls=cls: (getattr(self.decision, metric)[cls]
                                 if self.loader.class_lengths[cls] else None))
        self._epoch_side_unit(p)
        self.error_plotter = p
        return p

    def link_confusion_plotter(self, klass: int = 1, server=None):
        """Validation (or given class) confusion-matrix heatmap; turns
        on the evaluator's device-side confusion accumulation."""
        from znicz_tpu.plotting_units import MatrixPlotter
        if not getattr(self.evaluator, "compute_confusion", False):
            raise ValueError(
                "confusion plotter needs the evaluator built with "
                "compute_confusion=True (pass evaluator_config)")
        p = MatrixPlotter(
            self, name="confusion_matrix", server=server,
            fetch=lambda: self.decision.confusion_matrixes[klass])
        self._epoch_side_unit(p)
        self.confusion_plotter = p
        return p

    def link_weights_plotter(self, layer: int = 0, sample_shape=None,
                             server=None):
        """First-layer filters as a tiled image (reference:
        ``Weights2D``)."""
        from znicz_tpu.ops.nn_plotting_units import Weights2D
        p = Weights2D(self, name=f"weights2d_l{layer}", server=server,
                      sample_shape=sample_shape)
        p.link_attrs(self.forwards[layer], ("input", "weights"),
                     two_way=False)
        self._epoch_side_unit(p)
        self.weights_plotter = p
        return p

    def link_image_saver(self, **config):
        """Dump misclassified samples per epoch (reference:
        ``link_image_saver``); classification workflows only."""
        from znicz_tpu.ops.image_saver import ImageSaver
        if self.loss != "softmax":
            raise ValueError("image saver needs a classification loss")
        s = ImageSaver(self, name="image_saver", **config)
        s.link_attrs(self.loader, ("input", "minibatch_data"),
                     ("labels", "minibatch_labels"),
                     ("indices", "minibatch_indices"),
                     "minibatch_valid", "minibatch_class", "epoch_number",
                     two_way=False)
        s.link_attrs(self.forwards[-1], "max_idx", two_way=False)
        s.link_from(self.decision)  # after the step's compute
        self._relink_end_point_last()
        self.image_saver = s
        return s

    def link_shell(self, **config):
        """Interactive console once per epoch (reference: ``Shell``
        from ``veles/interaction.py``)."""
        from znicz_tpu.interaction import Shell
        s = Shell(self, name="shell", **config)
        self._epoch_side_unit(s)
        self.shell = s
        return s

    def link_weight_publisher(self, **config):
        """Publish the trained forward chain every N epochs into a
        serving handoff directory (round 13 — the training half of the
        continuous train-to-serve loop; a serving process's
        :class:`~znicz_tpu.resilience.publisher.PublicationWatcher`
        picks the bundles up for canary-gated hot swaps).  Config:
        ``directory``, ``prefix``, ``every_n_epochs``."""
        from znicz_tpu.resilience.publisher import WeightPublisher
        p = WeightPublisher(self, name="weight_publisher", **config)
        self._epoch_side_unit(p)
        self.weight_publisher = p
        return p

    def link_publisher(self, **config):
        """Post-training report generation (reference: ``Publisher``
        from ``veles/publishing/``): fires once, when the decision
        raises ``complete``."""
        from znicz_tpu.publishing import Publisher
        p = Publisher(self, name="publisher", **config)
        p.link_from(self.decision)
        self._relink_end_point_last()
        p.gate_skip = ~self.decision.complete
        self.publisher = p
        return p

    def run_chunked(self, steps_per_dispatch: int = 32) -> None:
        """Fast-path training driver: amortize dispatch cost by running
        up to ``steps_per_dispatch`` minibatch steps per device call
        (``JitRegion.run_chunk`` — one ``lax.scan`` program), the
        idiomatic JAX training loop.

        Semantics vs :meth:`run`: identical trajectory — the loader's
        device-resident schedule reproduces the per-step index stream
        bitwise, stochastic units advance their device PRNG chains per
        scanned step, and the evaluator's error counters accumulate on
        device exactly as in per-step mode.  Chunks never cross a
        class-segment or epoch boundary, so decision bookkeeping and
        the epoch side chain (snapshotter, plotters, LR adjuster) fire
        at the same points; an active LR-adjust policy is applied at
        chunk granularity (piecewise-constant within a chunk) rather
        than per step, and the anomaly guard's host hook arms its
        fault / SDC injection leaves once per dispatch, so under
        ``run_chunked(k)`` an armed fault holds for the dispatch's k
        steps.  Requires the XLA backend + a device-schedule loader;
        falls back to :meth:`run` otherwise.
        """
        region_unit = self._region_unit
        loader = self.loader
        if (region_unit is None or steps_per_dispatch <= 1
                or not loader._on_device_schedule()):
            return self.run()
        per_step = [u for u in self.units
                    if getattr(u, "NEEDS_PER_STEP_MINIBATCHES", False)]
        if per_step:
            # such units consume EVERY minibatch (e.g. ImageSaver's
            # worst-sample dumps); inside a scanned chunk only the
            # last step's data survives
            self.warning("run_chunked: %s need per-step minibatches — "
                         "falling back to per-step run()",
                         [u.name for u in per_step])
            return self.run()
        region = region_unit.region
        assert region is not None

        def advance() -> int:
            """The chunk's host bookkeeping; returns its step count."""
            loader.run()  # (+ schedule upload if stale)
            cls = loader.minibatch_class
            k = 1
            while (k < steps_per_dispatch and not loader.epoch_ended
                   and loader._cursor < len(loader._schedule)
                   and loader._schedule[loader._cursor][0] == cls):
                loader.run()
                k += 1
            return k

        def dispatch(k: int) -> None:
            region.run_chunk(k)
            if (self.lr_adjuster is not None
                    and loader.minibatch_class == TRAIN):
                # chunk-granular application of the per-step policy
                self.lr_adjuster._n_iterations += k - 1
                self.lr_adjuster.run()

        self._drive(advance, dispatch, "chunks")

    def _drive(self, advance, dispatch, noun: str) -> None:
        """The ONE step loop of the drivers that walk the hot loop
        themselves (``run_chunked``, ``run_accumulated``,
        ``run_pipelined``), recording the spans ``wf.run()`` records:
        the root, ONE fire of the loader per dispatch around
        ``advance()`` (a whole chunk's or optimizer step's
        bookkeeping), the decision's; the region's dispatch span is
        its own.  Per dispatch, in the graph's order: the loader, the
        anomaly guard's host hook (it arms the fault / SDC injection
        leaves — a no-op where no fault site is configured; an armed
        fault holds for every step of the dispatch, so for the k steps
        of a chunk), ``dispatch(what advance returned)`` — the
        driver's device call and its LR-adjuster application — then
        the decision and, at epoch end, its side chain.  ``noun``
        names the driver's unit of work in the ``max_fires`` error."""
        loader = self.loader
        decision = self.decision
        guard = self.anomaly_guard
        side_units = [u for u in decision.links_to
                      if u is not self.repeater and u is not self.end_point]
        self.run_started_at = time.time()
        self.stopped.value = False
        fires = 0
        with self._run_span():
            while not decision.complete and not self.stopped:
                advanced = loader._record_fire(advance)
                if guard is not None:
                    guard.host_run()
                dispatch(advanced)
                decision._fire()
                self._fire_epoch_side_units(side_units)
                fires += 1
                if (self._max_fires is not None
                        and fires > self._max_fires):
                    raise RuntimeError(
                        f"workflow '{self.name}' exceeded max_fires="
                        f"{self._max_fires} {noun} (runaway loop?)")

    def _fire_epoch_side_units(self, side_units) -> None:
        """The decision's epoch side chain, for :meth:`_drive`."""
        decision = self.decision
        if not (decision.epoch_ended or decision.complete):
            return
        for unit in side_units:
            if unit is self.lr_adjuster:
                continue  # the drivers apply it per optimizer step
            if not unit.gate_block and not unit.gate_skip:
                unit._fire()

    def _microbatches(self, microbatches: int | None) -> int:
        """M of an optimizer step split into microbatches: the
        argument, else ``engine.grad_accum``."""
        if microbatches is None:
            from znicz_tpu.utils.config import root
            microbatches = root.common.engine.get("grad_accum", 1) or 1
        return int(microbatches)

    def _require_microbatchable(self, n_micro: int, noun: str) -> None:
        """What ``run_accumulated`` and ``run_pipelined`` (``noun``:
        "accumulated" / "pipelined") both need before they build
        anything: an XLA region, a device-schedule loader, and a TRAIN
        set that divides into optimizer steps of ``n_micro`` FULL
        microbatches."""
        loader = self.loader
        if self._region_unit is None or not loader._on_device_schedule():
            raise RuntimeError(
                f"workflow '{self.name}': run_{noun} requires the XLA "
                f"region + a device-schedule loader (a {noun} step is "
                f"made of on-device programs; there is no meaningful "
                f"host fallback)")
        n_train = int(loader.class_lengths[TRAIN])
        if n_train % (loader.max_minibatch_size * n_micro) != 0:
            raise RuntimeError(
                f"workflow '{self.name}': TRAIN set of {n_train} does "
                f"not divide into {noun} steps of "
                f"{loader.max_minibatch_size} × {n_micro} microbatches — "
                f"a ragged tail microbatch would break the fixed "
                f"{noun} program")

    def _drive_microbatched(self, n_micro: int, train_step,
                            noun: str) -> None:
        """The step ``run_accumulated`` and ``run_pipelined`` share: a
        TRAIN step advances the index stream over all its ``n_micro``
        microbatches, calls ``train_step()`` and applies the LR
        adjuster once (ONE optimizer step happened, whatever M is);
        eval/validation minibatches run unaccumulated through the
        regular region program."""
        loader = self.loader
        region = self._region_unit.region
        assert region is not None

        def advance() -> int:
            """One optimizer step's host bookkeeping (+ schedule
            upload if stale); returns the minibatch class."""
            loader.run()
            if loader.minibatch_class == TRAIN:
                for _ in range(n_micro - 1):
                    loader.run()
            return loader.minibatch_class

        def dispatch(cls: int) -> None:
            if cls != TRAIN:
                return region.run()
            train_step()
            if self.lr_adjuster is not None:
                self.lr_adjuster.run()

        self._drive(advance, dispatch, f"{noun} steps")

    def run_accumulated(self, microbatches: int | None = None) -> None:
        """Gradient-accumulation training driver (round 20): every
        optimizer step consumes ``M = engine.grad_accum`` consecutive
        TRAIN minibatches through ONE device program
        (:meth:`JitRegion.run_accum` — a ``lax.scan`` of M−1
        accumulate-only bodies feeding one apply body), so the global
        batch is ``M × minibatch_size`` while per-step activation
        memory stays at one microbatch.

        Semantics: the applied update is bitwise-equal to a fused
        batch of ``M × minibatch_size`` whenever the arithmetic is
        exact (each microbatch gradient is normalized by its own
        minibatch size; the apply body divides the accumulated sum by
        M).  Anomaly verdicts AND across the M microbatches — one NaN
        anywhere skips the whole accumulated step — and the SDC
        fingerprints fold once, at apply.  Eval/validation minibatches
        run unaccumulated through the regular region program.
        """
        n_micro = self._microbatches(microbatches)
        if n_micro <= 1:
            return self.run()
        self._require_microbatchable(n_micro, "accumulated")
        from znicz_tpu.observe import metrics as _metrics
        _metrics.grad_accum_microbatches(self.name).set(n_micro)
        region = self._region_unit.region
        self._drive_microbatched(
            n_micro, lambda: region.run_accum(n_micro), "accumulated")

    def run_pipelined(self, n_stages: int,
                      microbatches: int | None = None,
                      schedule: str = "1f1b") -> None:
        """Pipeline-parallel training driver (round 20): split the
        forward/backward chain into ``n_stages`` contiguous stages and
        drive each TRAIN optimizer step through the
        :class:`~znicz_tpu.parallel.pipeline.PipelineExecutor`'s
        merged 1F1B (or GPipe) schedule over ``M = engine.grad_accum``
        microbatches.  Riding the accumulation phases keeps the
        trained trajectory identical to :meth:`run_accumulated` —
        each stage buffers M−1 microbatch gradients and applies once —
        while per-stage live activations stay at ONE microbatch.
        Eval/validation minibatches run through the unstaged region
        program unchanged.
        """
        from znicz_tpu.parallel.pipeline import PipelineExecutor
        if self.pass_spans:
            raise NotImplementedError(
                f"workflow '{self.name}': run_pipelined does not run a "
                f"looped span (table key '{PASSES_KEY}'): its stages "
                f"are built from one forward and one backward per "
                f"layer, and a state that returns to an earlier stage "
                f"has no place in their schedule")
        routed = [i for i, unit in enumerate(self.forwards)
                  if getattr(unit, "route_from", None)]
        if routed:
            raise NotImplementedError(
                f"workflow '{self.name}': run_pipelined does not run "
                f"layer {routed[0]} (moe, route_from="
                f"{self.forwards[routed[0]].route_from}): its router "
                f"reads the sublayer before it beside the chain, and "
                f"that cotangent is handed back inside ONE program — "
                f"a stage boundary between the two would cut the edge")
        n_micro = self._microbatches(microbatches)
        self._require_microbatchable(n_micro, "pipelined")
        executor = self._pipeline
        if (executor is None or executor.n_stages != int(n_stages)
                or executor.n_micro != n_micro
                or executor.schedule_kind != schedule):
            executor = self._pipeline = PipelineExecutor(
                self, n_stages, n_micro, schedule=schedule)
        self._drive_microbatched(n_micro, executor.run_step, "pipelined")

    def build_shadow(self) -> "StandardWorkflow":
        """A numpy-backend clone for the SDC sentinel's
        redundant-compute audit: same declarative config (identical
        construction order ⇒ identical unit/vector names, so
        ``load_state`` restores the clone leaf-for-leaf), no guard
        (the shadow IS the trusted oracle), no snapshots/side-chains.
        The audit drives it one minibatch at a time after a
        ``load_state`` of the live workflow's pre-step state."""
        from znicz_tpu.backends import NumpyDevice
        shadow = StandardWorkflow(
            name=f"{self.name}_shadow",
            loader_factory=self._loader_factory,
            layers=self.layers_config,
            loss=self.loss,
            evaluator_config=self._evaluator_config,
            decision_config={**self._decision_config,
                             "max_epochs": None,
                             "fail_iterations": 10 ** 9},
            snapshotter_config=None,
            anomaly_guard=False)
        shadow._max_fires = 10 ** 9
        shadow.initialize(device=NumpyDevice())
        return shadow

    def export_forward(self, path: str) -> str:
        """Serialize the trained forward chain for serving
        (reference: ``ForwardExporter``; see
        :mod:`znicz_tpu.export`)."""
        from znicz_tpu.export import export_forward
        return export_forward(self, path)

    def hot_chain_units(self) -> list:
        """The per-minibatch hot chain in trace order — the unit list
        a :class:`~znicz_tpu.accelerated_units.JitRegion` compiles and
        the population engine vmaps (loader gather → forwards →
        evaluator → backwards, anomaly guard last)."""
        members = [self.loader, *self.forwards, self.evaluator,
                   *reversed(self.gds)]
        if self.anomaly_guard is not None:
            members.append(self.anomaly_guard)
        return members

    def promote_lr_leaves(self) -> None:
        """Turn every weighted GD unit's learning rate into a device
        leaf (its ``lr_state`` Vector, the same slot a
        :class:`LearningRateAdjust` schedule uses) holding the
        configured ``[lr, lr_bias]``.  The population engine calls
        this so learning rates become *member-stacked* state — each of
        the K replicas trains (and mutates) its own rate without a
        recompile.  Idempotent; call after ``initialize``.  Finite
        steps are bitwise identical to the baked-constant path (same
        f32 value, same multiply)."""
        for gd_unit in self.gds:
            if gd_unit.weights is None or not gd_unit.weights:
                continue
            if gd_unit.lr_state:
                continue  # already scheduled / promoted
            gd_unit.lr_state.reset(np.asarray(
                [gd_unit.learning_rate, gd_unit.learning_rate_bias],
                dtype=np.float32))
            gd_unit.init_vectors(gd_unit.lr_state)

    # ------------------------------------------------------------------
    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device=device, **kwargs)
        if not isinstance(self.device, NumpyDevice) \
                and self._region_unit is None:
            self._compile_region()

    def _compile_region(self) -> None:
        """Swap the eager hot chain for one jit region (xla backend)."""
        members = self.hot_chain_units()
        guard = self.anomaly_guard
        region = RegionUnit(self, members, name="train_region",
                            pass_spans=self.pass_spans)
        region.initialize(device=self.device)
        region._initialized = True
        # rewire: loader → [guard host hook] → region → decision (drop
        # the eager chain).  Like the loader, the guard stays in the
        # control graph for its per-step host_run (the fault-inject
        # leaf) while its device compute runs inside the region.
        tail = guard if guard is not None \
            else (self.gds[0] if self.gds else self.evaluator)
        self.decision.unlink_from(tail)
        first_fwd = self.forwards[0]
        first_fwd.unlink_from(self.loader)
        if guard is not None:
            guard.unlink_from(self.gds[0] if self.gds
                              else self.evaluator)
            guard.link_from(self.loader)
            region.link_from(guard)
        else:
            region.link_from(self.loader)
        self.decision.link_from(region)
        self._region_unit = region

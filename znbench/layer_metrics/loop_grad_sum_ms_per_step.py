"""Device time per training step of summing a looped span's partial
gradients over its passes: the self time of the operations the
program's map (``znicz_tpu.observe.op_scopes()``) puts in phase
``pass_sum`` of any unit — the scope around the adds in
``GradientDescentBase._whole_gradient``: weight-sized f32 adds that
multiply nothing, (R − 1) × the span's parameters a step — ÷ steps.  An
operation counts only if EVERY scoped instruction fused into it lies in
that scope: where XLA folds an add into the weight-gradient product or
into the update's fusion, the time is that operation's (backward,
``update_ms_per_step``) and this reads less, down to 0 — which is the
finding then, not a gap.  Nothing where the program has no looped span
(no ``znicz_loop`` gauge: every other cell, the parent of PR 35) or
hands out no map."""

from znbench.harness import discovery


def read(obs):
    from znicz_tpu.observe import metrics
    if metrics.REGISTRY.get("znicz_loop") is None:
        return None
    shared = discovery.load_module("layer_metrics",
                                   "unit_attributed_share")
    join = shared.joined(obs)
    steps = obs.observations.get("steps")
    if not join or not steps:
        return None
    seconds, names = join

    def phases(entry) -> set:
        if not entry:
            return set()
        return set(entry.get("phases") or (entry.get("phase"),))

    return 1e3 * sum(value for name, value in seconds.items()
                     if phases(names.get(name)) == {"pass_sum"}) / steps

"""Member applications a looped span's step program ran per step: a
count the program keeps ON THE DEVICE (one add per traced application:
``PassSpan.applications``), read once per epoch with the other
epoch-end reads and published as the gauge ``znicz_loop{group,stat}``,
``applications_per_step``, over the window's last epoch; summed over
the spans.  R × the span's layers exactly — the guard that no later
change trains on fewer passes.  Nothing where the program has no such
gauge (the parent of PR 35) or no looped span."""


def read(obs):
    from znicz_tpu.observe import metrics
    family = metrics.REGISTRY.get("znicz_loop")
    if family is None:
        return None
    values = [gauge.value for (_group, stat), gauge in family.items()
              if stat == "applications_per_step"]
    return sum(values) if values else None

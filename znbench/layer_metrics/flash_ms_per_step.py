"""Device time of the flash-attention kernels (forward, dq, dk/dv) per
training step: the self time of the operations ``is_flash`` accepts,
mean over the chips."""

from znbench import trace_reduce


def is_flash(name: str, detail: str) -> bool:
    """The Mosaic custom calls of ``ops/pallas_attention.py``.  The
    ``pallas_call``s carry no ``name=``: the trace names them after
    the traced function (``jvp__.<n>``) and shows them as
    ``custom-call`` HLO lines — the only custom calls of a step whose
    layer table has attention and no layer norm."""
    return " custom-call(" in detail or "flash" in name.lower()


def read(obs):
    steps = obs.observations.get("steps")
    if not steps or not obs.trace.devices:
        return None
    seconds = trace_reduce.matching_seconds(obs.trace, is_flash,
                                            obs.trace_window)
    return 1e3 * seconds / steps

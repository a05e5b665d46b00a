"""Device time of the WINDOWED flash-attention kernels per training
step: the self time of the operations whose name carries both
``znicz_flash`` and ``_win`` (the program names a windowed layer's
three ``pallas_call``s ``znicz_flash_fwd_win``, ``znicz_flash_dq_win``,
``znicz_flash_dkv_win``; the ``flash_*_ms_per_step`` readers match
``znicz_flash_fwd`` … by substring and count them too), mean over the
chips.  Nothing where no operation has the name: a program without a
windowed layer (the parent of PR 29) or kernels run in interpret mode
(``--toy``)."""

from znbench import trace_reduce


def is_win(name: str, _detail: str) -> bool:
    return "znicz_flash" in name and "_win" in name


def read(obs):
    steps = obs.observations.get("steps")
    if not steps or not obs.trace.devices:
        return None
    seconds = trace_reduce.matching_seconds(obs.trace, is_win,
                                            obs.trace_window)
    return 1e3 * seconds / steps if seconds else None

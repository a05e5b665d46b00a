"""Device time of the UN-windowed causal flash-attention kernels per
training step: the self time of the operations named
``znicz_flash_fwd`` / ``_dq`` / ``_dkv`` / ``_bwd`` WITHOUT ``_win``
(a windowed layer's: ``flash_win_ms_per_step``) and without ``_mla``
(a latent layer's two-width kernels: ``mla_flash_ms_per_step``), mean
over the chips — the full-attention layers of a window / global model,
whichever of one or two passes their backward takes.  Nothing where no
operation has such a name: kernels run in interpret mode (``--toy``),
or a model all of whose layers are windowed or latent."""

from znbench import trace_reduce

KERNELS = ("znicz_flash_fwd", "znicz_flash_dq", "znicz_flash_dkv",
           "znicz_flash_bwd")


def is_causal(name: str, _detail: str) -> bool:
    return any(kernel in name for kernel in KERNELS) \
        and "_win" not in name and "_mla" not in name


def read(obs):
    steps = obs.observations.get("steps")
    if not steps or not obs.trace.devices:
        return None
    seconds = trace_reduce.matching_seconds(obs.trace, is_causal,
                                            obs.trace_window)
    return 1e3 * seconds / steps if seconds else None

"""Device time of the two-width flash kernels of the latent-K/V
attention layers per training step: the self time of the operations
whose name holds ``znicz_flash`` AND ``_mla`` (``znicz_flash_fwd_mla``,
``znicz_flash_bwd_mla_dq``, ``znicz_flash_bwd_mla_dkv``), mean over the
chips.  ``flash_fwd_`` / ``flash_bwd_ms_per_step`` count them too, by
substring.  Nothing where no operation has the name (a program without
such a layer, the plain core, interpret mode)."""

from znbench import trace_reduce


def read(obs):
    steps = obs.observations.get("steps")
    if not steps or not obs.trace.devices:
        return None
    seconds = trace_reduce.matching_seconds(
        obs.trace,
        lambda name, _detail: "znicz_flash" in name and "_mla" in name,
        obs.trace_window)
    return 1e3 * seconds / steps if seconds else None

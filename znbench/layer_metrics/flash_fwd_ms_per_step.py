"""Device time of the flash-attention FORWARD kernel per training
step: the self time of the operations named ``znicz_flash_fwd``, mean
over the chips.

The program names its three ``pallas_call``s (``znicz_flash_fwd``,
``znicz_flash_dq``, ``znicz_flash_dkv``); on the TPU the name becomes
the HLO instruction's (``%jvp_znicz_flash_fwd_.<n> = … custom-call``),
which is what the trace calls the operation.  Only the instruction's
name is looked at: the HLO line of an operation that CONSUMES a
kernel's result carries the kernel's name too.  Returns nothing where
no operation has the name: a program that does not name its kernels,
or kernels run in interpret mode (``--toy`` on the CPU), which leave
plain XLA operations and no kernel to time.
"""

from znbench import trace_reduce


def kernel_ms_per_step(obs, kernel: str):
    """Self time per step of the operations whose name has ``kernel``
    in it, ``None`` where there is none."""
    steps = obs.observations.get("steps")
    if not steps or not obs.trace.devices:
        return None
    seconds = trace_reduce.matching_seconds(
        obs.trace, lambda name, _detail: kernel in name,
        obs.trace_window)
    return 1e3 * seconds / steps if seconds else None


def read(obs):
    return kernel_ms_per_step(obs, "znicz_flash_fwd")

"""Device time of the flash-attention dQ backward kernel per training
step: the self time of the operations named ``znicz_flash_dq``, mean
over the chips (see ``flash_fwd_ms_per_step``).  Returns nothing
where the kernels run in interpret mode or carry no name."""

from znbench.harness import discovery


def read(obs):
    return discovery.load_module(
        "layer_metrics", "flash_fwd_ms_per_step").kernel_ms_per_step(
            obs, "znicz_flash_dq")

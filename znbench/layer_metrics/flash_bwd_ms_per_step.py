"""Device time of the ONE-PASS flash-attention backward kernel per
training step: the self time of the operations named
``znicz_flash_bwd`` — dq, dk and dv in one walk, where a causal call's
K side is one grid tile — mean over the chips (see
``flash_fwd_ms_per_step``).  Returns nothing where no backward takes
the one pass (a program from before it, a cell whose calls keep
``znicz_flash_dq`` + ``znicz_flash_dkv``), where the kernels run in
interpret mode or carry no name."""

from znbench.harness import discovery


def read(obs):
    return discovery.load_module(
        "layer_metrics", "flash_fwd_ms_per_step").kernel_ms_per_step(
            obs, "znicz_flash_bwd")

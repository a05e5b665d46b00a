"""Device time of the gated delta rule's state kernels per training
step: the self time of the operations whose name holds ``znicz_delta``
(``znicz_delta_state_fwd``, ``znicz_delta_state_bwd``: the walk from
chunk to chunk, forward and reverse), mean over the chips.  Only the
instruction's name is looked at (see ``flash_fwd_ms_per_step``).
Nothing where no operation has the name: a program without the unit
(the parent of PR 31), the plain scan, or kernels run in interpret mode
(``--toy``), which leave plain XLA operations and no kernel to time."""

from znbench.harness import discovery


def read(obs):
    return discovery.load_module(
        "layer_metrics", "flash_fwd_ms_per_step").kernel_ms_per_step(
            obs, "znicz_delta")

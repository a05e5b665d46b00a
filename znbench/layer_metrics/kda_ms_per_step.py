"""Device time of the per-channel delta rule's kernels per training
step: the self time of the operations whose name holds ``znicz_kda``
(``znicz_kda_chunk_fwd`` / ``_bwd``: what is local to a chunk under a
decay per key channel; ``znicz_kda_state_fwd`` / ``_bwd``: the walk
from chunk to chunk, S's rows scaled), mean over the chips.  Only the
instruction's name is looked at (see ``flash_fwd_ms_per_step``); the
scalar-decay kernels (``znicz_gdr_chunk``, ``znicz_delta_state``) hold
no such name, nor these theirs.  Nothing where no operation has the
name: a program without the unit (the parent of PR 37), the plain scan,
or kernels run in interpret mode (``--toy``)."""

from znbench.harness import discovery


def read(obs):
    return discovery.load_module(
        "layer_metrics", "flash_fwd_ms_per_step").kernel_ms_per_step(
            obs, "znicz_kda")

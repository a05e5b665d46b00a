"""Device time per training step of the kernels that compute what is
local to a chunk of the gated delta rule — Γ, K Kᵀ, the triangular
inverse, W, U, K̂, Qc and P in VMEM: the self time of the operations
whose name holds ``znicz_gdr_chunk`` (``znicz_gdr_chunk_fwd``,
``znicz_gdr_chunk_bwd``), mean over the chips.  The walk from chunk to
chunk is ``delta_ms_per_step``'s (``znicz_delta``): neither name holds
the other.  Only the instruction's name is looked at (see
``flash_fwd_ms_per_step``).  Nothing where no operation has the name: a
program that computes a chunk in ``jax.numpy`` (the parent of PR 32),
one without the unit, or kernels run in interpret mode (``--toy``)."""

from znbench.harness import discovery


def read(obs):
    return discovery.load_module(
        "layer_metrics", "flash_fwd_ms_per_step").kernel_ms_per_step(
            obs, "znicz_gdr_chunk")

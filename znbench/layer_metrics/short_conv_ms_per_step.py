"""Device time of the short convolution's chain kernels per training
step: the self time of the operations whose name holds
``znicz_short_conv`` (``znicz_short_conv_fwd``: both gates and the taps
from the projection where it lies to W_out's input;
``znicz_short_conv_bwd``: the projection's cotangent and the taps',
u and c made again in VMEM), mean over the chips.  Only the
instruction's name is looked at (see ``flash_fwd_ms_per_step``).
Nothing where no operation has the name: a program without the unit
(the parent of PR 43), the chain in ``jax.numpy``, or kernels run in
interpret mode (``--toy``)."""

from znbench.harness import discovery


def read(obs):
    return discovery.load_module(
        "layer_metrics", "flash_fwd_ms_per_step").kernel_ms_per_step(
            obs, "znicz_short_conv")

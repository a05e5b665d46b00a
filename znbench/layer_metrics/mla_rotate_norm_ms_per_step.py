"""Device time per training step of the ELEMENT-WISE PASSES at
activation size around the latent-K/V attention layers' kernels: the
self time of the operations the program's map puts wholly in phase
``rotate_norm`` of an attention layer (``ops/attention.py``
``_latent_forward``'s scope: the pre-norm, the latent's norm and the
query latent's, the rotation of the heads' rotary parts and of the one
shared key, the scores' scale, the casts to the kernels' dtype and
back; forward and pullback) ÷ steps.  At T 16,384 each is a pass over
134–440 MB: what a ``perf_opt`` that fuses them into the projections'
epilogues or the kernels' prologues would start from.  The matmuls are
``mla_project_ms_per_step`` — which holds what the compiler fused
INTO a product (the norm's last multiply, a cast): this row is the
passes that stand alone —, the kernels ``mla_flash_ms_per_step``.
Nothing where
the program hands out no map, or knows no such phase (the parent of
PR 52)."""

from znbench.harness import discovery


def read(obs):
    return discovery.load_module(
        "layer_metrics", "mla_project_ms_per_step").read(
            obs, "rotate_norm")

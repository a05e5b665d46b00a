"""Parallelism: device meshes, SPMD data parallelism, distributed init.

Replaces the reference's L3 cluster layer (reference:
``veles/server.py``, ``veles/client.py``, ``veles/distributable.py`` —
asynchronous ZeroMQ master–slave parameter server) with synchronous
SPMD over a ``jax.sharding.Mesh``: the gradient fold that the reference
performed host-side in ``apply_data_from_slave`` becomes an in-program
ICI all-reduce (``lax.pmean`` over the ``data`` axis), and multi-host
bootstrap is ``jax.distributed.initialize`` over DCN (SURVEY.md §2.5,
§5.8).
"""

from znicz_tpu.parallel.axis import (  # noqa: F401
    DATA_AXIS,
    MODEL_AXIS,
    PIPE_AXIS,
    SEQ_AXIS,
    current_data_axis,
    data_axis,
    maybe_pmean,
    maybe_psum,
)
from znicz_tpu.parallel.distributed import (  # noqa: F401
    ensure_initialized,
)
from znicz_tpu.parallel.mesh import (  # noqa: F401
    make_mesh,
    mesh_for_stage,
    batch_sharding,
    kernel_shard_spec,
    replicated_sharding,
    spec_divides,
    zero1_choice,
    zero1_partition,
    zero1_specs,
)

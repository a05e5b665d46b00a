"""Device time of the expert layers' grouped matmuls per training
step: the self time of the operations named after JAX's Pallas grouped
matmul kernels — ``gmm`` (forward and row gradient) and ``tgmm``
(weight gradient); the trace names a ``pallas_call`` after its kernel
(``%gmm.<n>``, ``%tgmm.<n>``; PERF.md §6, PR 25) — mean over the
chips.  Only the instruction's name is looked at.  Nothing where no
operation has the name: a program without an expert layer (the parent
of PR 25), the XLA grouped matmul (``ragged_dot``, off a TPU), or
kernels run in interpret mode (``--toy``)."""

from znbench import trace_reduce


def is_gmm(name: str, _detail: str) -> bool:
    return "gmm" in name.lower()


def read(obs):
    steps = obs.observations.get("steps")
    if not steps or not obs.trace.devices:
        return None
    seconds = trace_reduce.matching_seconds(obs.trace, is_gmm,
                                            obs.trace_window)
    return 1e3 * seconds / steps if seconds else None

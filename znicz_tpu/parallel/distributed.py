"""Multi-host bring-up: ``jax.distributed.initialize`` from env or
flags.

The reference's cluster bootstrap was an explicit Server/Client
handshake (``veles/server.py``); the TPU-native replacement is PJRT
multi-process SPMD — every host runs the same program over one global
mesh.  This module is the single home of that bootstrap so the
Launcher, ``bench.py`` and the dryrun all bring up a pod slice the
same way, **unmodified**: export three env vars and run the same
command on every host.

Environment contract (flags win over env; both optional):

- ``ZNICZ_COORDINATOR``  — ``host:port`` of process 0,
- ``ZNICZ_NUM_PROCESSES`` — total process count,
- ``ZNICZ_PROCESS_ID``   — this process's index (0 = master).

On TPU pods the PJRT plugin can discover all three; on CPU/GPU
clusters (and the two-process CI smoke) they must be given.
"""

from __future__ import annotations

import os

ENV_COORDINATOR = "ZNICZ_COORDINATOR"
ENV_NUM_PROCESSES = "ZNICZ_NUM_PROCESSES"
ENV_PROCESS_ID = "ZNICZ_PROCESS_ID"

_initialized = False


def env_spec() -> dict | None:
    """The env-var bring-up request, or None when unset."""
    coordinator = os.environ.get(ENV_COORDINATOR)
    if not coordinator:
        return None
    spec: dict = {"coordinator_address": coordinator}
    n = os.environ.get(ENV_NUM_PROCESSES)
    if n is not None:
        spec["num_processes"] = int(n)
    pid = os.environ.get(ENV_PROCESS_ID)
    if pid is not None:
        spec["process_id"] = int(pid)
    return spec


def ensure_initialized(coordinator: str | None = None,
                       num_processes: int | None = None,
                       process_id: int | None = None,
                       timeout_s: float | None = None) -> bool:
    """Idempotent ``jax.distributed.initialize`` with a bring-up
    deadline.

    Explicit arguments win; otherwise the env contract above is
    consulted.  Returns True when this process is part of an
    initialized multi-process runtime (including when a caller
    already initialized it), False when nothing requested distributed
    mode — callers can branch mesh construction on the result.

    Round 18 (elastic): bring-up is bounded instead of hanging
    forever on a wrong ``ZNICZ_COORDINATOR`` or a missing peer —
    ``engine.dist_init_timeout_s`` (default 300 s; the ``timeout_s``
    argument overrides) caps each attempt,
    ``engine.dist_init_retries`` (default 2) extra attempts run with
    ``engine.dist_init_backoff_s`` (default 2 s, doubling) between
    them, and final failure raises a RuntimeError naming the exact
    spec and the usual causes.  An elastic restart re-invokes this in
    the relaunched gang with the surviving host set (smaller
    ``ZNICZ_NUM_PROCESSES``, renumbered ids) — the partition table
    then re-resolves every placement onto the smaller mesh.
    """
    global _initialized
    import jax

    from znicz_tpu.utils.config import root

    if _initialized:
        return True
    spec = env_spec() or {}
    if coordinator is not None:
        spec["coordinator_address"] = coordinator
    if num_processes is not None:
        spec["num_processes"] = num_processes
    if process_id is not None:
        spec["process_id"] = process_id
    if not spec.get("coordinator_address"):
        return False
    # CPU backends need a collectives implementation for
    # cross-process computations (the default "none" fails every
    # multi-process program); harmless no-op on TPU pods
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    engine = root.common.engine
    timeout = float(timeout_s if timeout_s is not None
                    else engine.get("dist_init_timeout_s", 300.0))
    retries = int(engine.get("dist_init_retries", 2))
    backoff = float(engine.get("dist_init_backoff_s", 2.0))
    last_exc: Exception | None = None
    for attempt in range(retries + 1):
        try:
            jax.distributed.initialize(
                initialization_timeout=max(1, int(timeout)), **spec)
            _initialized = True
            return True
        except (TypeError, ValueError):
            raise  # a bad spec never fixes itself — fail loudly now
        except Exception as exc:  # timeout / connection refused / ...
            last_exc = exc
            try:  # release a half-bound coordinator before retrying
                jax.distributed.shutdown()
            except Exception:
                pass
            if attempt < retries:
                import time as _time
                wait = backoff * (2 ** attempt)
                _time.sleep(wait)
    raise RuntimeError(
        f"jax.distributed bring-up failed after {retries + 1} "
        f"attempt(s) of {timeout:.0f}s each: {last_exc}.  Spec: "
        f"coordinator={spec.get('coordinator_address')!r}, "
        f"num_processes={spec.get('num_processes')}, "
        f"process_id={spec.get('process_id')}.  Check that (a) every "
        f"process exports the SAME {ENV_COORDINATOR} (host:port of "
        f"process 0) and a distinct {ENV_PROCESS_ID} in "
        f"[0, {ENV_NUM_PROCESSES}), (b) process 0 is actually running "
        f"and its port is reachable from this host, and (c) no stale "
        f"process from a previous gang still holds the port.  Raise "
        f"engine.dist_init_timeout_s for slow pod bring-up."
        ) from last_exc


def shutdown() -> None:
    """Tear down the distributed runtime (best effort) so a fresh
    :func:`ensure_initialized` can bring up a new gang — the elastic
    supervisor's relaunched workers are new OS processes, but tests
    and notebook drivers re-enter in-process."""
    global _initialized
    import jax
    try:
        if _initialized:
            jax.distributed.shutdown()
    finally:
        _initialized = False


def process_info() -> tuple[int, int]:
    """(process_index, process_count) of the current runtime."""
    import jax
    return jax.process_index(), jax.process_count()

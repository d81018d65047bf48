"""Multi-host bootstrap and process-topology probes.

Replaces the reference's rendezvous stack:

- ``dist.init_process_group(backend, init_method='tcp://...', world_size,
  rank)`` (``/root/reference/multi_proc_single_gpu.py:167-168, 323-331``)
  becomes ``jax.distributed.initialize(coordinator_address, num_processes,
  process_id)`` — one process per *host* (SPMD), not per chip.
- ``distributed_is_initialized()`` (``:21-25``) becomes ``is_distributed()``.
- There is no backend flag: the mesh is the backend configuration; XLA routes
  collectives over ICI within a slice and DCN across slices.

All topology access goes through ``process_index()`` / ``process_count()``
so multi-host shard arithmetic is unit-testable with monkeypatched values
(SURVEY.md section 4, "multi-host logic").
"""

from __future__ import annotations

import os
from typing import Optional

import jax

_initialized = False
_init_info: dict = {}


def _multiprocess_env_detected() -> bool:
    """True when the environment indicates a multi-process launch.

    These are the variables JAX's own cluster detection consumes: an
    explicit coordinator (``JAX_COORDINATOR_ADDRESS``), a multi-worker TPU
    pod (``TPU_WORKER_HOSTNAMES`` listing >1 hosts, or megascale
    coordination), or a Slurm / Open MPI launcher. When any is present,
    ``jax.distributed.initialize()`` is called with NO arguments so JAX's
    autodetection fills in address/size/rank itself — this code never
    second-guesses it (a previous revision gated on a nonstandard
    ``TPU_WORKER_COUNT`` variable, which real pod runtimes do not set).
    """
    env = os.environ
    if env.get("JAX_COORDINATOR_ADDRESS") or env.get("MEGASCALE_COORDINATOR_ADDRESS"):
        return True
    hosts = [h for h in env.get("TPU_WORKER_HOSTNAMES", "").split(",") if h.strip()]
    if len(hosts) > 1:
        return True
    for var in ("SLURM_NTASKS", "OMPI_COMM_WORLD_SIZE", "PMI_SIZE"):
        try:
            if int(env.get(var, "0")) > 1:
                return True
        except ValueError:
            pass
    return False


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Initialize the multi-host runtime (idempotent).

    With no arguments, auto-detects from the environment the way TPU pods /
    cluster launchers configure it (the analog of ``torch.distributed.launch``
    injecting ``--local_rank``, reference ``:319-321``). Explicit arguments
    mirror the reference's ``--init-method`` / ``--world-size`` / ``--rank``
    flags. Single-process runs skip initialization entirely, like the
    reference's world-size-1 path still calling ``init_process_group`` —
    except here single-process needs no rendezvous at all.
    """
    global _initialized
    if _initialized:
        return
    import time

    explicit = coordinator_address is not None or (num_processes or 0) > 1
    if explicit:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
        _init_info["mode"] = "explicit"
        _init_info["coordinator"] = coordinator_address
    elif _multiprocess_env_detected():
        # Let JAX's cluster autodetection (TPU pod metadata, Slurm, OMPI)
        # work out coordinator/size/rank on its own.
        jax.distributed.initialize()
        _init_info["mode"] = "auto"
    else:
        _init_info["mode"] = "single"
    _init_info["initialized_at"] = time.time()
    _initialized = True


def is_distributed() -> bool:
    """True iff more than one host process participates (cf. reference ``:21-25``)."""
    return process_count() > 1


def process_index() -> int:
    """This host's rank among participating processes."""
    return jax.process_index()


def process_count() -> int:
    """Number of participating host processes."""
    return jax.process_count()


def runtime_info() -> dict:
    """Topology snapshot for supervision diagnostics (watchdog phase
    reports, failure events): how this world was bootstrapped, when, and
    this host's coordinates. Values are plain Python so the dict drops
    straight into a JSON summary."""
    info = dict(_init_info)
    info["process_index"] = process_index()
    info["process_count"] = process_count()
    return info

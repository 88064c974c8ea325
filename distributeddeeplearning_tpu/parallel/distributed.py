"""Multi-host initialization — the ``hvd.init()`` / mpirun-rendezvous equivalent.

The reference bootstraps its world with ``hvd.init()`` inside every
process that ``mpirun --hostfile $AZ_BATCHAI_MPI_HOST_FILE`` forks
(SURVEY.md §3.1; job command line in ``01_Train*.ipynb`` cell 15), with
env propagated by ``mpirun -x``. JAX replaces the whole stack with a
gRPC coordination service: every host process calls
``jax.distributed.initialize(coordinator, num_processes, process_id)``
and XLA handles device-level collectives over ICI/DCN from there — no
SSH, no hostfile, no NCCL env tuning (§2a).

Env contract (set by the launcher, ``launch.py``):
  ``DDL_COORDINATOR`` — ``host:port`` of process 0
  ``DDL_NUM_PROCESSES`` / ``DDL_PROCESS_ID``
On Cloud TPU VMs none are needed — ``jax.distributed.initialize()``
autodetects from TPU metadata; set ``DISTRIBUTED=True`` (the reference's
own flag) to request that path.
"""

from __future__ import annotations

import os
from typing import Optional

import jax

from distributeddeeplearning_tpu.utils.logging import get_logger

_initialized = False


def maybe_initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> bool:
    """Initialise multi-host JAX if configured; no-op single-host.

    Returns True if distributed init ran. Safe to call more than once
    (like ``hvd.init()``).
    """
    global _initialized
    if _initialized:
        return True
    log = get_logger()

    coordinator_address = coordinator_address or os.environ.get("DDL_COORDINATOR")
    if num_processes is None and "DDL_NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["DDL_NUM_PROCESSES"])
    if process_id is None and "DDL_PROCESS_ID" in os.environ:
        process_id = int(os.environ["DDL_PROCESS_ID"])

    explicit = coordinator_address is not None
    autodetect = (
        os.environ.get("DISTRIBUTED", "").strip().lower()
        in {"1", "true", "t", "yes"}
        and os.environ.get("TPU_WORKER_HOSTNAMES") not in (None, "localhost")
    )
    if not explicit and not autodetect:
        return False

    kwargs = {}
    if explicit:
        kwargs = dict(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    jax.distributed.initialize(**kwargs)
    _initialized = True
    log.info(
        "distributed initialized: process %d/%d, %d local / %d global devices",
        jax.process_index(),
        jax.process_count(),
        jax.local_device_count(),
        jax.device_count(),
    )
    return True


def shutdown() -> None:
    global _initialized
    if _initialized:
        jax.distributed.shutdown()
        _initialized = False

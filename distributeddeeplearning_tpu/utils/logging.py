"""Rank-aware logging + canonical throughput reporting.

Capability parity with the reference's per-trainer logging machinery,
which is duplicated three times there (``HorovodAdapter`` + ``_get_logger``
at ``HorovodTF/src/imagenet_estimator_tf_horovod.py:70-95``, Keras
``:69-94``, PyTorch ``:70-95``) and its ``_log_summary`` throughput block
(TF ``:397-410``, Keras ``:257-270``, PyTorch ``:242-255``). Here it is one
module: a ``LoggerAdapter`` that injects the JAX process index (the
Horovod-rank equivalent) and an optional epoch tag into every record, and
``log_summary`` printing the repo's canonical ``Total images/sec`` metric
block.

On TPU the "rank" is ``jax.process_index()`` — there is one process per
host rather than one per accelerator, so the adapter also logs the local
device count.
"""

from __future__ import annotations

import logging
import sys
from functools import lru_cache
from typing import Any, Mapping, MutableMapping, Optional


def _get_rank() -> int:
    """Process index, tolerating an uninitialized backend.

    Mirrors the reference's ``_get_rank`` which swallows pre-init Horovod
    errors (``imagenet_estimator_tf_horovod.py:60-67``). Crucially this
    must NOT initialise the backend itself: ``jax.process_index()`` before
    ``jax.distributed.initialize`` would permanently lock the process into
    a single-host world. Pre-init, fall back to the launcher's
    ``DDL_PROCESS_ID``.
    """
    import os

    import jax
    from jax._src import xla_bridge  # no public "is a backend up" probe

    if xla_bridge.backends_are_initialized():
        return jax.process_index()
    return int(os.environ.get("DDL_PROCESS_ID", 0))


class RankAdapter(logging.LoggerAdapter):
    """Injects ``[rank]`` and ``[Epoch n]`` into records.

    Reference ``HorovodAdapter`` injects ``gpurank`` + epoch the same way
    (``imagenet_estimator_tf_horovod.py:70-88``).
    """

    def __init__(self, logger: logging.Logger, rank: Optional[int] = None):
        # rank=None → resolve at log time: on the pod-autodetect path the
        # adapter is constructed before jax.distributed.initialize, when
        # the true process index isn't knowable yet.
        super().__init__(logger, {"rank": rank})

    def process(self, msg, kwargs: MutableMapping[str, Any]):
        extra = kwargs.pop("extra", {})
        epoch = extra.get("epoch")
        prefix = f"[Epoch {epoch}] " if epoch is not None else ""
        rank = self.extra["rank"]
        kwargs["extra"] = {"rank": _get_rank() if rank is None else rank}
        return f"{prefix}{msg}", kwargs


@lru_cache(maxsize=None)
def get_logger(name: str = "ddl_tpu", rank: Optional[int] = None) -> RankAdapter:
    """``lru_cache``'d rank-tagged logger singleton (reference ``_get_logger``)."""
    logger = logging.getLogger(name)
    if not logger.handlers:
        handler = logging.StreamHandler(sys.stdout)
        handler.setFormatter(
            logging.Formatter("%(asctime)s rank:%(rank)s [%(levelname)s] %(message)s")
        )
        logger.addHandler(handler)
        logger.setLevel(logging.INFO)
        logger.propagate = False
    return RankAdapter(logger, rank=rank)


def log_summary(
    *,
    data_length: int,
    duration_s: float,
    batch_size_per_device: int,
    num_devices: int,
    dataset_kind: str,
    logger: Optional[RankAdapter] = None,
    extra_fields: Optional[Mapping[str, Any]] = None,
) -> float:
    """Print the canonical throughput block; returns total images/sec.

    Field-for-field parity with the reference ``_log_summary``
    (``imagenet_estimator_tf_horovod.py:397-410``): data length, duration,
    ``Total images/sec`` (the repo's canonical metric, SURVEY.md §6),
    per-device and total batch size, device count, dataset kind. The
    reference's throughput math bug (§2c.8) is not reproduced: callers pass
    the *global* number of images actually processed.
    """
    log = logger or get_logger()
    images_per_sec = data_length / duration_s if duration_s > 0 else float("inf")
    log.info("Total duration: %.3f s", duration_s)
    log.info("Total images processed: %d", data_length)
    log.info("Batch size (per device): %d", batch_size_per_device)
    log.info("Batch size (total): %d", batch_size_per_device * num_devices)
    log.info("Devices: %d", num_devices)
    log.info("Dataset: %s", dataset_kind)
    log.info("Total images/sec: %.1f", images_per_sec)
    log.info("Images/sec per device: %.1f", images_per_sec / max(num_devices, 1))
    for k, v in (extra_fields or {}).items():
        log.info("%s: %s", k, v)
    return images_per_sec

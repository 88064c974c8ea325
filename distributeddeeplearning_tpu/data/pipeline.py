"""Host→device staging: global-array assembly + async prefetch.

The reference's input-pipeline performance tier is tf.data threads
(``parallel_interleave``/``map_and_batch``, prefetch 256 —
``imagenet_estimator_tf_horovod.py:249-259``) and Keras multiprocess
workers (``:332-342``). The TPU-native equivalent is (a) building *global*
jax.Arrays from per-host numpy shards so a jitted step sees one logical
batch regardless of process count, and (b) a background thread keeping
``prefetch_batches`` batches resident in HBM so the step never waits on
PCIe (HBM-bandwidth rule: overlap host transfer with compute).
"""

from __future__ import annotations

import collections
import queue
import threading
from typing import Any, Callable, Iterable, Iterator, Optional

import jax
from jax.sharding import Mesh, NamedSharding

from distributeddeeplearning_tpu import obs
from distributeddeeplearning_tpu.parallel.mesh import batch_sharding

PyTree = Any


def shard_batch(batch: PyTree, mesh: Mesh, sharding: Optional[PyTree] = None) -> PyTree:
    """Place a process-local numpy batch as a global, batch-sharded jax.Array.

    Single-process: a plain sharded ``device_put``. Multi-host: each process
    contributes its local shard and the result is a global array spanning
    the mesh (``make_array_from_process_local_data`` — the moment the
    reference's per-rank ``DistributedSampler`` shards become one logical
    batch).

    ``sharding`` may be a single ``NamedSharding`` (applied to every leaf)
    or a pytree of shardings matching ``batch`` — the SP engine shards
    2-D token arrays over ``(data, seq)`` but 1-D eval weights over
    ``data`` only.
    """
    sh = sharding if sharding is not None else batch_sharding(mesh)
    if jax.process_count() == 1:
        return jax.device_put(batch, sh)
    if isinstance(sh, jax.sharding.Sharding):
        return jax.tree.map(
            lambda x: jax.make_array_from_process_local_data(sh, x), batch
        )
    return jax.tree.map(
        lambda x, s: jax.make_array_from_process_local_data(s, x), batch, sh
    )


def prefetch_to_device(
    it: Iterable[PyTree],
    mesh: Mesh,
    *,
    size: int = 2,
    sharding: Optional[NamedSharding] = None,
    transform: Optional[Callable[[PyTree], PyTree]] = None,
) -> Iterator[PyTree]:
    """Asynchronously stage batches onto the mesh, ``size`` deep.

    A daemon thread pulls from ``it``, calls :func:`shard_batch` (device
    transfer starts immediately; JAX transfers are async), and the consumer
    pops fully-staged batches. Equivalent role to the reference's
    ``prefetch(256)`` (TF ``:258``) + pinned-memory DataLoader (PyTorch
    ``:313-316``).

    ``sharding`` may also be a callable ``batch -> sharding`` (single or
    pytree), resolved per batch — engines whose staging layout depends on
    the batch arity (SP: eval weights shard differently) use this.

    ``transform`` is a host transform of the job's objective (``data/
    noise.py``: block-diffusion noising), applied to each batch on the
    staging thread before it is placed; it emits its own span.

    Emits, a batch: span ``data.stage`` round the placement and counter
    ``data.h2d_bytes`` (bytes of the host leaves placed) on the staging
    thread, span ``data.stage_wait`` round the consumer's ``q.get()``.
    """
    def stage(batch):
        # On the thread that does the placement (the producer's, when
        # there is one): its time, and the bytes it hands to the device.
        if transform is not None:
            batch = transform(batch)
        with obs.span("data.stage"):
            staged = shard_batch(
                batch, mesh, sharding(batch) if callable(sharding) else sharding
            )
        obs.counter(
            "data.h2d_bytes",
            sum(int(getattr(x, "nbytes", 0)) for x in jax.tree.leaves(batch)),
        )
        return staged

    if size <= 0:
        for batch in it:
            yield stage(batch)
        return

    q: "queue.Queue" = queue.Queue(maxsize=size)
    _END = object()
    err: list = []
    cancelled = threading.Event()

    def _put(item) -> bool:
        while not cancelled.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def producer():
        try:
            for batch in it:
                if not _put(stage(batch)):
                    return  # consumer gone: stop staging, free HBM refs
        except Exception as e:  # surfaced on the consumer side
            err.append(e)
        finally:
            _put(_END)

    t = threading.Thread(target=producer, daemon=True)
    t.start()
    try:
        while True:
            # How long the step loop waited for a staged batch: the input
            # layer's starvation, measured where it is felt.
            with obs.span("data.stage_wait"):
                item = q.get()
            if item is _END:
                if err:
                    raise err[0]
                return
            yield item
    finally:
        # Consumer abandoned the generator (break / exception / close):
        # unblock and terminate the producer so staged device batches and
        # the thread are released rather than pinned for the process life.
        cancelled.set()


def normalize_staged_images(images):
    """Fold the host pipeline's normalization into the device program for
    raw-byte staging (``INPUT_STAGING=uint8``): uint8 inputs become
    torchvision-normalized f32 — XLA fuses the (x/255 − mean)/sd chain
    into the first pass that reads the batch, so the only cost of uint8
    staging is LESS transfer (half of bf16, a quarter of f32).

    Contract: a uint8 NHWC batch entering a vision engine means
    "un-normalized RGB bytes" (every dataset honors this —
    ``data/__init__.staging_dtype``). Anything else passes through
    untouched — other dtypes are already normalized host-side, and the
    rank-4 gate keeps uint8 TOKEN batches (rank 2 — byte-level LMs feed
    ``nn.Embed`` integer codes through these same engines) out of the
    image path.
    """
    import jax.numpy as jnp

    from distributeddeeplearning_tpu.config import (
        IMAGENET_RGB_MEAN,
        IMAGENET_RGB_SD,
    )

    if images.dtype != jnp.uint8 or images.ndim != 4:
        return images
    mean = jnp.asarray(IMAGENET_RGB_MEAN, jnp.float32)
    sd = jnp.asarray(IMAGENET_RGB_SD, jnp.float32)
    return (images.astype(jnp.float32) / 255.0 - mean) / sd

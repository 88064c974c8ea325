"""Real-data input-pipeline throughput (VERDICT r2 Missing #2 / Next #4).

Stages a real-JPEG dataset (synthetic images re-encoded to JPEG — it is
DECODE throughput that matters), then measures:

1. host-only decode+augment rate for each reader (ImageFolder threaded
   PIL, tf.data TFRecord, native TFRecord reader) — img/s and
   img/s/core;
2. end-to-end training img/s on the attached device with the real
   pipeline feeding the DP train step, vs the synthetic upper bound.

Usage::

    python scripts/real_data_bench.py prepare [--images 2048] [--root DIR]
    python scripts/real_data_bench.py host    [--root DIR] [--steps 8]
    python scripts/real_data_bench.py e2e     [--root DIR] [--batch 256]

Default root: ``.benchdata/`` (gitignored).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:  # `python scripts/real_data_bench.py` from anywhere
    sys.path.insert(0, _REPO)

DEFAULT_ROOT = os.path.join(_REPO, ".benchdata")


def prepare(root: str, n_images: int, image_size: int = 224, classes: int = 8):
    """ImageFolder tree of JPEGs (smooth low-frequency content — random
    noise would be unrealistically slow to decode) + TFRecord shards."""
    from PIL import Image

    from distributeddeeplearning_tpu.data.prepare import write_tfrecords

    folder = os.path.join(root, "imagefolder")
    rng = np.random.RandomState(42)
    for c in range(classes):
        os.makedirs(os.path.join(folder, f"class{c:03d}"), exist_ok=True)
    t0 = time.perf_counter()
    for i in range(n_images):
        c = i % classes
        # low-frequency pattern + mild noise ≈ natural-image entropy
        yy, xx = np.mgrid[0:image_size, 0:image_size].astype(np.float32)
        base = (
            127
            + 80 * np.sin(xx / (7 + c) + i)[..., None]
            * np.cos(yy / (11 + c))[..., None]
            + rng.normal(0, 12, (image_size, image_size, 3))
        )
        img = Image.fromarray(np.clip(base, 0, 255).astype(np.uint8))
        img.save(
            os.path.join(folder, f"class{c:03d}", f"img{i:06d}.jpg"),
            quality=85,
        )
    dt = time.perf_counter() - t0
    n, _ = write_tfrecords(folder, os.path.join(root, "tfrecords"), num_shards=8)
    sizes = []
    for dirpath, _, files in os.walk(folder):
        sizes += [os.path.getsize(os.path.join(dirpath, f)) for f in files]
    print(
        f"prepared {n} JPEGs ({np.mean(sizes) / 1024:.1f} KB avg) in {dt:.1f}s "
        f"+ 8 TFRecord shards under {root}"
    )


def _rate(name: str, it, steps: int, warmup: int = 2):
    n, t0 = 0, None
    for i, item in enumerate(it):
        if i == warmup:
            t0 = time.perf_counter()
            n = 0
        if i >= warmup:
            n += item[0].shape[0]
        if i >= warmup + steps:
            break
    if t0 is None or n == 0:
        raise SystemExit(
            f"{name}: dataset too small for warmup={warmup} + measurement "
            "— lower --batch or add --images"
        )
    dt = time.perf_counter() - t0
    cores = os.cpu_count() or 1
    print(
        f"{name:32s} {n / dt:8.1f} img/s host-only "
        f"({n / dt / cores:.1f} img/s/core, {cores} cores)"
    )
    return n / dt


def host(root: str, steps: int, batch: int, workers: int, worker_mode: str):
    from distributeddeeplearning_tpu.data.imagenet import (
        ImageFolderDataset,
        TFRecordImageNetDataset,
    )

    folder = os.path.join(root, "imagefolder")
    pattern = os.path.join(root, "tfrecords", "imagenet-*")
    results = {}
    ds = ImageFolderDataset(
        folder, global_batch_size=batch, train=True, num_workers=workers,
        worker_mode=worker_mode,
    )
    results["imagefolder"] = _rate(
        f"ImageFolder (PIL, {workers} {worker_mode}s)", ds.epoch(0), steps
    )
    try:
        tfds = TFRecordImageNetDataset(
            pattern, global_batch_size=batch, train=True
        )
        results["tfrecord-tfdata"] = _rate(
            "TFRecord (tf.data)", tfds.epoch(0), steps
        )
    except Exception as e:  # tensorflow optional
        print(f"TFRecord (tf.data) skipped: {e}")
    from distributeddeeplearning_tpu.data import make_dataset
    from distributeddeeplearning_tpu.config import TrainConfig

    cfg = TrainConfig(
        fake=False, data_dir=os.path.join(root, "tfrecords"),
        data_format="tfrecord-native", batch_size_per_device=batch,
        num_workers=workers, worker_mode=worker_mode,
    )
    try:
        nds = make_dataset(cfg, train=True)
        results["tfrecord-native"] = _rate(
            f"TFRecord (native reader, {workers} {worker_mode}s)",
            nds.epoch(0), steps,
        )
    except Exception as e:
        print(f"TFRecord (native) skipped: {e}")
    return results


def transfer(batch: int, image_size: int = 224, reps: int = 12):
    """Host→device transfer rate in isolation, per staging dtype — the
    middle leg of the e2e decomposition (host decode → transfer → step).
    Measures a sharded ``device_put`` of one global batch, fenced by a
    device readback."""
    import jax
    import jax.numpy as jnp
    import ml_dtypes

    from distributeddeeplearning_tpu.data.pipeline import shard_batch
    from distributeddeeplearning_tpu.parallel.mesh import data_parallel_mesh

    mesh = data_parallel_mesh(jax.device_count())
    rng = np.random.RandomState(0)
    base = rng.randint(0, 255, size=(batch, image_size, image_size, 3))
    touch = jax.jit(lambda x: jnp.sum(x[:, 0, 0, 0].astype(jnp.float32)))
    out = {}
    for name, arr in (
        ("float32", base.astype(np.float32)),
        ("bfloat16", base.astype(ml_dtypes.bfloat16)),
        ("uint8", base.astype(np.uint8)),
    ):
        labels = rng.randint(0, 1000, size=(batch,)).astype(np.int32)
        x, _ = shard_batch((arr, labels), mesh)
        float(touch(x))  # warm compile
        # (a) fenced: one put at a time — the latency-bound floor
        t0 = time.perf_counter()
        for _ in range(reps):
            x, _ = shard_batch((arr, labels), mesh)
            float(touch(x))
        fenced = (time.perf_counter() - t0) / reps
        # (b) streamed: enqueue every put, fence once — what the
        # prefetch pipeline actually achieves with transfers in flight
        t0 = time.perf_counter()
        xs = [shard_batch((arr, labels), mesh)[0] for _ in range(reps)]
        for x in xs:
            float(touch(x))
        streamed = (time.perf_counter() - t0) / reps
        mb = arr.nbytes / 1e6
        out[name] = batch / streamed
        print(
            f"transfer {name:8s}: {mb:6.1f} MB/batch  "
            f"fenced {fenced * 1e3:7.1f} ms ({batch / fenced:7.1f} img/s)  "
            f"streamed {streamed * 1e3:7.1f} ms "
            f"({mb / streamed / 1e3:5.2f} GB/s, {batch / streamed:7.1f} img/s)"
        )
    return out


def e2e(root: str, batch: int, steps: int):
    """Real pipeline → prefetch → compiled DP train step on the device.
    ``INPUT_STAGING=uint8`` stages raw bytes + on-device normalize."""
    import jax
    import jax.numpy as jnp

    from distributeddeeplearning_tpu.config import TrainConfig
    from distributeddeeplearning_tpu.data import make_dataset, staging_dtype
    from distributeddeeplearning_tpu.data.pipeline import prefetch_to_device
    from distributeddeeplearning_tpu.models.resnet import ResNet
    from distributeddeeplearning_tpu.parallel.mesh import data_parallel_mesh
    from distributeddeeplearning_tpu.training import (
        create_optimizer,
        create_train_state,
        make_train_step,
    )
    from distributeddeeplearning_tpu.training.train_step import replicate_state

    cfg = TrainConfig(
        fake=False,
        data_dir=os.path.join(root, "imagefolder"),
        batch_size_per_device=batch,
        num_workers=int(os.environ.get("NUM_WORKERS", "8")),
        input_staging=os.environ.get("INPUT_STAGING", "auto"),
    )
    data = make_dataset(cfg, train=True)
    model = ResNet(depth=50, num_classes=1000, dtype=jnp.bfloat16)
    mesh = data_parallel_mesh(jax.device_count())
    tx, _ = create_optimizer(cfg, steps_per_epoch=data.steps_per_epoch)
    state = replicate_state(create_train_state(model, cfg, tx), mesh)
    step = make_train_step(model, tx, mesh, cfg, donate_state=False)

    seen, t0 = 0, None
    warmup = 2
    metrics = None
    for i, batch_np in enumerate(
        prefetch_to_device(data.epoch(0), mesh, size=cfg.prefetch_batches)
    ):
        state, metrics = step(state, batch_np[:2])
        if i + 1 == warmup:
            float(metrics["loss"])  # fence: compile + pipeline spin-up done
            t0 = time.perf_counter()
            seen = 0
        elif i + 1 > warmup:
            seen += int(batch_np[0].shape[0])  # the GLOBAL batch delivered
        if i + 1 >= warmup + steps:
            break
    if t0 is None or metrics is None or seen == 0:
        raise SystemExit(
            "e2e: dataset too small for warmup + measurement — lower "
            "--batch or re-run `prepare` with more --images"
        )
    float(metrics["loss"])  # fence
    dt = time.perf_counter() - t0
    print(
        f"end-to-end real-data: {seen / dt:8.1f} img/s on "
        f"{jax.default_backend()} (batch {batch}, {seen} images)"
    )
    return seen / dt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("mode", choices=["prepare", "host", "transfer", "e2e"])
    ap.add_argument("--root", default=DEFAULT_ROOT)
    ap.add_argument("--images", type=int, default=2048)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--worker-mode", default="thread",
                    choices=["thread", "process"])
    args = ap.parse_args()
    if args.mode == "prepare":
        prepare(args.root, args.images)
    elif args.mode == "host":
        host(args.root, args.steps, args.batch, args.workers, args.worker_mode)
    elif args.mode == "transfer":
        transfer(args.batch)
    else:
        e2e(args.root, args.batch, args.steps)


if __name__ == "__main__":
    sys.exit(main())

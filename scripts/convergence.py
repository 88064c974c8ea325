"""Convergence-to-accuracy runs on REAL data (VERDICT r3 #2).

Every training test in the suite is a few-step loss-decrease or an
engine-equality oracle; nothing had ever been trained to a stated
target. These two runs close that: the full recipe — augmentation,
warmup + step/cosine decay, L2/decoupled weight decay, per-replica BN,
exact full-set eval — engaged end to end on the attached chip, on real
data available in-image (the environment has no network egress):

* ``vision`` — ResNet18 through the KERAS front-end (compile/fit/
  evaluate with the reference-style warmup + schedule callbacks) on an
  ImageFolder built from scikit-learn's bundled *handwritten digits*
  scans (1,797 real 8×8 images; the classic test-set half of NIST's
  UCI digits) — train 1,497 / held-out 300, JPEG files on disk through
  the real ``ImageFolderDataset`` decode+augment path.
  Stated target: ≥ 95 % top-1. (BASELINE.md records the result.)
* ``lm`` — byte-level ``lm_small`` on a real code corpus: the CPython
  standard library's own ``.py`` sources (~25 MB of text), 95/5
  train/held-out split, AdamW + warmup/cosine, exact full-coverage
  eval perplexity-per-byte. Stated target: eval ppl ≤ 3.0 (≈1.6
  bits/byte — compact for a from-scratch 512-wide model, far below the
  8.0 ppl of a byte-uniform... enormous gap to random ≈ 256).

Usage::

    python scripts/convergence.py vision [--epochs 40]
    python scripts/convergence.py lm [--steps 2000]

Each prints ONE JSON line with the final metric vs its target.
"""

from __future__ import annotations

import argparse
import glob
import io
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # `python scripts/convergence.py` from anywhere
    sys.path.insert(0, REPO)
DATA_ROOT = os.path.join(REPO, ".benchdata")

VISION_TARGET_TOP1 = 0.95
LM_TARGET_PPL = 3.0


def build_digits_imagefolder(root: str, image_size: int = 32):
    """scikit-learn digits → ImageFolder JPEGs (train/ + val/), built
    once. Real scanned handwriting, 10 classes, stratified 300-image
    holdout (every 6th image of each class)."""
    from PIL import Image
    from sklearn.datasets import load_digits

    root = f"{root}{image_size}"  # cache key: the built resolution
    train_dir, val_dir = os.path.join(root, "train"), os.path.join(root, "val")
    if os.path.exists(os.path.join(root, ".done")):
        return train_dir, val_dir
    digits = load_digits()
    counters = {}
    for img8, label in zip(digits.images, digits.target):
        idx = counters.get(int(label), 0)
        counters[int(label)] = idx + 1
        split = val_dir if idx % 6 == 5 else train_dir
        d = os.path.join(split, f"digit_{label}")
        os.makedirs(d, exist_ok=True)
        arr = (img8 / 16.0 * 255).astype(np.uint8)
        rgb = np.stack([arr] * 3, axis=-1)
        Image.fromarray(rgb).resize(
            (image_size, image_size), Image.BILINEAR
        ).save(os.path.join(d, f"img_{idx:04d}.jpeg"), quality=95)
    with open(os.path.join(root, ".done"), "w") as f:
        f.write("ok\n")
    return train_dir, val_dir


def run_vision(epochs: int = 40, batch: int = 128) -> dict:
    from distributeddeeplearning_tpu.config import TrainConfig
    from distributeddeeplearning_tpu.data.imagenet import ImageFolderDataset
    from distributeddeeplearning_tpu.frontends.keras_style import Model
    from distributeddeeplearning_tpu.training.callbacks import (
        LearningRateScheduleCallback,
        LearningRateWarmupCallback,
    )

    train_dir, val_dir = build_digits_imagefolder(
        os.path.join(DATA_ROOT, "digits")
    )
    cfg = TrainConfig(
        model="resnet18",
        num_classes=10,
        image_size=32,
        batch_size_per_device=batch,
        epochs=epochs,
        base_lr=0.02,
        weight_decay=5e-5,  # the reference Keras L2 surgery constant
        validation=True,
    )
    train = ImageFolderDataset(
        train_dir, global_batch_size=batch, image_size=32, train=True,
        num_workers=4,
    )
    val = ImageFolderDataset(
        val_dir, global_batch_size=batch, image_size=32, train=False,
        num_workers=4,
    )
    model = Model("resnet18", cfg).compile(optimizer="momentum")
    t0 = time.perf_counter()
    model.fit(
        train,
        epochs=epochs,
        callbacks=[
            # reference-style declarative schedule (Keras :211-224):
            # 3 warmup epochs, ×0.1 at 50 %, ×0.01 at 80 % of the run
            LearningRateWarmupCallback(warmup_epochs=3),
            LearningRateScheduleCallback(
                start_epoch=epochs // 2, multiplier=0.1
            ),
            LearningRateScheduleCallback(
                start_epoch=int(epochs * 0.8), multiplier=0.01
            ),
        ],
    )
    metrics = model.evaluate(val)  # exact full-set eval (pad + mask)
    return {
        "run": "vision_digits_resnet18",
        "top1": round(float(metrics["top1"]), 4),
        "target_top1": VISION_TARGET_TOP1,
        "met": bool(metrics["top1"] >= VISION_TARGET_TOP1),
        "val_samples": int(metrics["samples"]),
        "epochs": epochs,
        "minutes": round((time.perf_counter() - t0) / 60, 1),
    }


def load_stdlib_corpus(max_bytes: int = 48 * 2**20) -> bytes:
    """The CPython standard library's .py sources, concatenated in
    sorted-path order (deterministic)."""
    import sysconfig

    stdlib = sysconfig.get_paths()["stdlib"]
    chunks, total = [], 0
    for path in sorted(glob.glob(os.path.join(stdlib, "**", "*.py"),
                                 recursive=True)):
        if "site-packages" in path:
            continue
        try:
            data = open(path, "rb").read()
        except OSError:
            continue
        chunks.append(data)
        total += len(data)
        if total >= max_bytes:
            break
    return b"\n".join(chunks)[:max_bytes]


def run_lm(
    steps: int = 2000,
    batch: int = 16,
    seq_len: int = 512,
    model_name: str = "lm_small",
    target_ppl: float = LM_TARGET_PPL,
    max_mb: int = 48,
    **model_kw,
) -> dict:
    import jax
    import jax.numpy as jnp

    from distributeddeeplearning_tpu.config import TrainConfig
    from distributeddeeplearning_tpu.data.pipeline import shard_batch
    from distributeddeeplearning_tpu.models import get_model
    from distributeddeeplearning_tpu.parallel.mesh import data_parallel_mesh
    from distributeddeeplearning_tpu.training import (
        create_optimizer,
        create_train_state,
        make_train_step,
    )
    from distributeddeeplearning_tpu.training.train_step import (
        make_eval_step,
        replicate_state,
    )

    corpus = load_stdlib_corpus(max_bytes=max_mb * 2**20)
    data = np.frombuffer(corpus, np.uint8)
    n_rows = len(data) // (seq_len + 1)
    rows = data[: n_rows * (seq_len + 1)].reshape(n_rows, seq_len + 1)
    rng = np.random.RandomState(0)
    order = rng.permutation(n_rows)
    n_eval = max(n_rows // 20, batch)  # 5 % held out
    eval_rows = rows[order[:n_eval]].astype(np.int32)
    train_rows = rows[order[n_eval:]].astype(np.int32)

    # "epochs" for the schedule: warmup 10 %, cosine to 0 over the run.
    steps_per_epoch = max(steps // 10, 1)
    cfg = TrainConfig(
        model=model_name,
        num_classes=256,
        batch_size_per_device=batch,
        epochs=10,
        warmup_epochs=1,
        lr_schedule="cosine",
        optimizer="adamw",
        base_lr=3e-4,
        scale_lr_by_world_size=False,
        weight_decay=0.0,
        decoupled_weight_decay=0.1,
    )
    model = get_model(
        model_name, num_classes=256, max_seq_len=seq_len, attn_impl="fused"
        if jax.default_backend() == "tpu" else "xla", **model_kw,
    )
    mesh = data_parallel_mesh(jax.device_count())
    tx, _ = create_optimizer(cfg, steps_per_epoch)
    state = replicate_state(
        create_train_state(
            model, cfg, tx, input_shape=(1, seq_len), input_dtype=jnp.int32
        ),
        mesh,
    )
    step = make_train_step(model, tx, mesh, cfg)
    t0 = time.perf_counter()
    for i in range(steps):
        take = rng.randint(0, len(train_rows) - batch + 1)
        b = train_rows[take : take + batch]
        state, metrics = step(
            state, shard_batch((b[:, :-1], b[:, 1:]), mesh)
        )
        if i % 200 == 0:
            print(
                f"step {i}: loss {float(metrics['loss']):.3f}", flush=True
            )
    train_minutes = (time.perf_counter() - t0) / 60

    # exact full-coverage eval: every held-out row once, tail padded+masked
    eval_step = make_eval_step(model, mesh)
    sums = {"loss": 0.0, "count": 0.0}
    for start in range(0, len(eval_rows), batch):
        b = eval_rows[start : start + batch]
        weights = np.ones(len(b), np.float32)
        if len(b) < batch:
            pad = batch - len(b)
            b = np.concatenate([b, np.zeros((pad, seq_len + 1), np.int32)])
            weights = np.concatenate([weights, np.zeros(pad, np.float32)])
        m = eval_step(
            state, shard_batch((b[:, :-1], b[:, 1:], weights), mesh)
        )
        count = float(m["count"])
        sums["loss"] += float(m["loss"]) * count
        sums["count"] += count
    eval_loss = sums["loss"] / sums["count"]
    ppl = float(np.exp(eval_loss))
    return {
        "run": f"{model_name}_stdlib_bytes",
        "eval_ppl_per_byte": round(ppl, 3),
        "eval_bits_per_byte": round(eval_loss / np.log(2), 3),
        "target_ppl": target_ppl,
        "met": bool(ppl <= target_ppl),
        "steps": steps,
        "train_tokens": steps * batch * seq_len,
        "eval_rows": int(n_eval),
        "minutes": round(train_minutes, 1),
        **({"model_kw": model_kw} if model_kw else {}),
    }


MOE_TARGET_PPL = 2.85  # within ~4 % of the dense twin's 2.749 r4 result


def run_moe(
    steps: int = 2000,
    batch: int = 16,
    seq_len: int = 512,
    experts: int = 8,
    top_k: int = 2,
    capacity_factor: float = 1.25,
    with_dense: bool = True,
    max_mb: int = 48,
) -> dict:
    """Dense-vs-MoE QUALITY at equal step budget (VERDICT r4 #4).

    The EP tier has routing-equality oracles and an exact cost audit
    (``scripts/moe_audit.py``, PROFILE.md) but no evidence the routed
    model *learns* competitively. This trains ``lm_moe_small`` and its
    dense twin on the same stdlib byte corpus with the same optimizer,
    schedule, and step budget, and reports both eval perplexities. The
    stated target: MoE eval-ppl ≤ 2.85 per byte (within ~4 % of the
    dense twin's round-4 2.749 — routed capacity must not cost quality
    at this scale, where experts see ~1/8 of the gradient signal each).
    """
    moe = run_lm(
        steps, batch, seq_len,
        model_name="lm_moe_small",
        target_ppl=MOE_TARGET_PPL,
        max_mb=max_mb,
        moe_experts=experts,
        moe_top_k=top_k,
        moe_capacity_factor=capacity_factor,
    )
    out = {
        "run": "moe_vs_dense_stdlib_bytes",
        "moe": moe,
        "experts": experts,
        "top_k": top_k,
        "capacity_factor": capacity_factor,
        "met": moe["met"],
    }
    if with_dense:
        dense = run_lm(
            steps, batch, seq_len, model_name="lm_small", max_mb=max_mb
        )
        out["dense"] = dense
        out["ppl_gap_pct"] = round(
            100.0
            * (moe["eval_ppl_per_byte"] - dense["eval_ppl_per_byte"])
            / dense["eval_ppl_per_byte"],
            2,
        )
    return out


def run_cluster(epochs: int = 40, batch: int = 128) -> dict:
    """Convergence through the FLAGSHIP CLUSTER STACK (VERDICT r4 #3):
    ``prepare.py``-written TFRecord shards → ``TFRecordImageNetDataset``
    → ``ENGINE=pjit`` (GSPMD, batch-split per-replica BN,
    ``models/norm.py``) → ``INPUT_STAGING=uint8`` (on-device normalize)
    → exact full-set eval. This is the exact stack
    ``docs/ORCHESTRATION.md`` submits to a pod (reference anchor: the
    ``01_Train*.ipynb`` cell-15 command line is the reference's
    flagship path); the vision target is unchanged: ≥ 95 % top-1 on the
    held-out digits."""
    import jax

    from distributeddeeplearning_tpu.config import TrainConfig
    from distributeddeeplearning_tpu.data.imagenet import TFRecordImageNetDataset
    from distributeddeeplearning_tpu.data.prepare import write_tfrecords
    from distributeddeeplearning_tpu.models import get_model
    from distributeddeeplearning_tpu.training.callbacks import (
        LearningRateScheduleCallback,
        LearningRateWarmupCallback,
    )
    from distributeddeeplearning_tpu.training.loop import evaluate, fit

    train_dir, val_dir = build_digits_imagefolder(
        os.path.join(DATA_ROOT, "digits")
    )
    shard_root = os.path.join(DATA_ROOT, "digits32_tfrec")
    # Sentinel = the LAST artifact written: an interrupted first run must
    # not leave a half-built cache that every later run trusts.
    if not os.path.exists(os.path.join(shard_root, "val", "count.txt")):
        # Same shard writer `prepare.py ingest` ends in (native TFRecord
        # framing + first-party Example codec).
        write_tfrecords(
            train_dir, os.path.join(shard_root, "train"),
            num_shards=8, prefix="digits",
        )
        write_tfrecords(
            val_dir, os.path.join(shard_root, "val"),
            num_shards=2, prefix="digits",
        )
    cfg = TrainConfig(
        model="resnet18",
        engine="pjit",
        input_staging="uint8",
        num_classes=10,
        image_size=32,
        batch_size_per_device=batch,
        epochs=epochs,
        base_lr=0.02,
        weight_decay=5e-5,
        validation=True,
        fake=False,
    )
    train = TFRecordImageNetDataset(
        os.path.join(shard_root, "train", "digits-*"),
        global_batch_size=batch, image_size=32, train=True,
        image_dtype=np.uint8,
    )
    val = TFRecordImageNetDataset(
        os.path.join(shard_root, "val", "digits-*"),
        global_batch_size=batch, image_size=32, train=False,
        image_dtype=np.uint8,
    )
    model = get_model("resnet18", num_classes=10)
    t0 = time.perf_counter()
    result = fit(
        model, cfg, train,
        epochs=epochs,
        callbacks=[
            LearningRateWarmupCallback(warmup_epochs=3),
            LearningRateScheduleCallback(
                start_epoch=epochs // 2, multiplier=0.1
            ),
            LearningRateScheduleCallback(
                start_epoch=int(epochs * 0.8), multiplier=0.01
            ),
        ],
    )
    metrics = evaluate(
        model, cfg, val, state=result.state
    )  # exact full-set eval (record-sharded, pad + mask)
    return {
        "run": "cluster_digits_resnet18_pjit_uint8_tfrecord",
        "stack": "prepare.write_tfrecords + TFRecordImageNetDataset + "
                 "ENGINE=pjit(per-replica BN) + INPUT_STAGING=uint8",
        "top1": round(float(metrics["top1"]), 4),
        "target_top1": VISION_TARGET_TOP1,
        "met": bool(metrics["top1"] >= VISION_TARGET_TOP1),
        "val_samples": int(metrics["samples"]),
        "epochs": epochs,
        "minutes": round((time.perf_counter() - t0) / 60, 1),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    sub = p.add_subparsers(dest="cmd", required=True)
    v = sub.add_parser("vision")
    v.add_argument("--epochs", type=int, default=40)
    v.add_argument("--batch", type=int, default=128)
    l = sub.add_parser("lm")
    l.add_argument("--steps", type=int, default=2000)
    l.add_argument("--batch", type=int, default=16)
    l.add_argument("--seq-len", type=int, default=512)
    m = sub.add_parser("moe", help="dense-vs-MoE quality at equal budget")
    m.add_argument("--steps", type=int, default=2000)
    m.add_argument("--batch", type=int, default=16)
    m.add_argument("--seq-len", type=int, default=512)
    m.add_argument("--experts", type=int, default=8)
    m.add_argument("--top-k", type=int, default=2)
    m.add_argument("--cf", type=float, default=1.25)
    m.add_argument("--no-dense", action="store_true",
                   help="skip the paired dense run")
    m.add_argument("--max-mb", type=int, default=48,
                   help="corpus cap in MiB (small for CPU smoke)")
    c = sub.add_parser("cluster", help="flagship pjit+TFRecord+uint8 stack")
    c.add_argument("--epochs", type=int, default=40)
    c.add_argument("--batch", type=int, default=128)
    args = p.parse_args(argv)
    if args.cmd == "vision":
        out = run_vision(args.epochs, args.batch)
    elif args.cmd == "lm":
        out = run_lm(args.steps, args.batch, args.seq_len)
    elif args.cmd == "moe":
        out = run_moe(
            args.steps, args.batch, args.seq_len,
            experts=args.experts, top_k=args.top_k,
            capacity_factor=args.cf, with_dense=not args.no_dense,
            max_mb=args.max_mb,
        )
    else:
        out = run_cluster(args.epochs, args.batch)
    print(json.dumps(out))
    return 0 if out["met"] else 1


if __name__ == "__main__":
    sys.exit(main())
